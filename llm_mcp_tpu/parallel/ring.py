"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference's long-context story is routing policy only — prompts are
bucketed by estimated length and sent to bigger model tiers or the cloud
(`core/internal/routing/router.go:92-123,420-447`); no computation is ever
split across devices. Here long context is a real subsystem: when a prompt
exceeds one chip's HBM (KV + activations), prefill shards the *sequence*
axis over the mesh's `sp` axis and the attention collectives ride ICI.

Two interchangeable context-parallel schemes, both SPMD under `shard_map`:

  - **Ring attention** (`ring_attention_local`): K/V shards rotate around
    the `sp` ring via `lax.ppermute` while each device's Q shard accumulates
    online-softmax partials (flash-attention style m/l/acc carry). Compute
    for chunks entirely in the causal future is skipped with `lax.cond`, so
    the causal ring does ~half the FLOPs of the naive rotation. Peak memory
    per chip is O(S/sp · hd) for K/V — sequence length scales linearly with
    the number of chips.
  - **Ulysses all-to-all** (`ulysses_attention_local`): two `all_to_all`s
    trade the sequence sharding for a head sharding, run ordinary dense
    causal attention on full-length sequences with H/sp local heads, and
    trade back. Cheaper collectives on small meshes; requires
    sp | n_kv_heads.

`llama_prefill_sp` runs the whole Llama prefill under one `shard_map` with
Megatron-style tensor parallelism (vocab-parallel embedding + logits, psum
after wo/w2) composed with either context-parallel attention — tokens arrive
sharded [dp, sp], weights sharded on tp, and the returned KV shards land
directly in the engine cache's [.., tp, sp, ..] layout without any gather.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.configs import ModelConfig
from ..ops.rope import rope_tables, apply_rope

NEG_INF = float(-1e30)


def _shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with the replication check off (ppermute/cond carries
    confuse varying-manual-axes inference; correctness is asserted by tests
    against the single-device reference)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


# ---------------------------------------------------------------------------
# Ring attention (causal, GQA, length-masked)
# ---------------------------------------------------------------------------


def ring_attention_local(
    q: jnp.ndarray,  # [B, H, Sl, hd] — local query shard (S sharded on axis)
    k: jnp.ndarray,  # [B, Hkv, Sl, hd]
    v: jnp.ndarray,  # [B, Hkv, Sl, hd]
    lengths: jnp.ndarray,  # [B] int32 global valid lengths (replicated)
    *,
    axis_name: str = "sp",
    window: jnp.ndarray | int = 0,  # sliding window (0 = global); may be traced
    softcap: float = 0.0,  # Gemma2-style score capping (0 = off)
    scale: float = 0.0,  # query scale override (0 = head_dim**-0.5)
) -> jnp.ndarray:
    """Causal GQA attention with K/V rotating around the `axis_name` ring.

    Call inside `shard_map` with the sequence axis sharded over `axis_name`.
    Online softmax makes the P-step accumulation exact (not approximate);
    tests assert bitwise-tolerance agreement with dense attention. Sliding
    windows and score softcaps thread through so the windowed families
    (Mistral/Gemma2) long-context-prefill like plain Llama.
    """
    B, H, Sl, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    nshards = jax.lax.psum(1, axis_name)  # static: axis size
    idx = jax.lax.axis_index(axis_name)
    window = jnp.asarray(window, dtype=jnp.int32)

    qg = (q.astype(jnp.float32) * (scale or hd**-0.5)).reshape(B, Hkv, G, Sl, hd)
    q_pos = idx * Sl + jnp.arange(Sl, dtype=jnp.int32)  # [Sl] global positions

    acc = jnp.zeros((B, Hkv, G, Sl, hd), jnp.float32)
    m = jnp.full((B, Hkv, G, Sl, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, Hkv, G, Sl, 1), jnp.float32)
    perm = [(j, (j + 1) % nshards) for j in range(nshards)]

    def step(t, carry):
        acc, m, l, k, v = carry
        src = jnp.mod(idx - t, nshards)  # origin shard of the current chunk
        k_pos = src * Sl + jnp.arange(Sl, dtype=jnp.int32)  # [Sl]
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)

        def compute(acc, m, l):
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            causal = k_pos[None, :] <= q_pos[:, None]  # [Slq, Slk]
            causal &= (window == 0) | (q_pos[:, None] - k_pos[None, :] < window)
            valid = k_pos[None, :] < lengths[:, None]  # [B, Slk]
            mask = causal[None, None, None] & valid[:, None, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # Mask p explicitly: for a fully-masked row m_new stays NEG_INF
            # and exp(s - m_new) would be 1, silently averaging V.
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
            return acc_new, m_new, l_new

        # Chunks entirely in the causal future contribute nothing — skip the
        # matmuls (the ring still rotates so later steps see the data).
        acc, m, l = jax.lax.cond(
            src <= idx, compute, lambda a, mm, ll: (a, mm, ll), acc, m, l
        )

        def rotate(kv):
            k, v = kv
            return (
                jax.lax.ppermute(k, axis_name, perm),
                jax.lax.ppermute(v, axis_name, perm),
            )

        # The last rotation's result is discarded — skip the ICI transfer.
        k, v = jax.lax.cond(t < nshards - 1, rotate, lambda kv: kv, (k, v))
        return acc, m, l, k, v

    acc, m, l, _, _ = jax.lax.fori_loop(0, nshards, step, (acc, m, l, k, v))
    # Rows that saw no valid key (padding beyond `lengths`) emit 0, not NaN.
    out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
    return out.reshape(B, H, Sl, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) context parallelism
# ---------------------------------------------------------------------------


def _dense_causal_attention(
    qg, k, v, lengths, pos_offset=0, window=0, softcap=0.0, scale=0.0
):
    """Reference dense causal GQA attention.  qg [B, Hkv, G, S, hd]."""
    B, Hkv, G, S, hd = qg.shape
    window = jnp.asarray(window, dtype=jnp.int32)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk",
        qg.astype(jnp.float32) * (scale or hd**-0.5),
        k.astype(jnp.float32),
    )
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    pos = pos_offset + jnp.arange(S, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    causal &= (window == 0) | (pos[:, None] - pos[None, :] < window)
    valid = pos[None, :] < lengths[:, None]
    mask = causal[None, None, None] & valid[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)  # fully-masked rows → l == 0
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return jnp.where(l > 0, out / jnp.where(l > 0, l, 1.0), 0.0)


def ulysses_attention_local(
    q: jnp.ndarray,  # [B, H, Sl, hd]
    k: jnp.ndarray,  # [B, Hkv, Sl, hd]
    v: jnp.ndarray,  # [B, Hkv, Sl, hd]
    lengths: jnp.ndarray,  # [B] int32
    *,
    axis_name: str = "sp",
    window: jnp.ndarray | int = 0,
    softcap: float = 0.0,
    scale: float = 0.0,
) -> jnp.ndarray:
    """All-to-all context parallelism (Ulysses): swap S-sharding for
    head-sharding, attend dense over the full sequence, swap back.

    Requires axis size | n_kv_heads (each shard keeps whole GQA groups).
    """
    B, H, Sl, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    nshards = jax.lax.psum(1, axis_name)
    if Hkv % nshards:
        raise ValueError(
            f"ulysses needs axis size {nshards} | kv heads {Hkv}; use ring instead"
        )
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # [B, H, Sl, hd] -> [B, H/P, S, hd]: contiguous head blocks keep GQA
    # groups aligned with their KV heads as long as P | Hkv.
    qh = a2a(q, split_axis=1, concat_axis=2)
    kh = a2a(k, split_axis=1, concat_axis=2)
    vh = a2a(v, split_axis=1, concat_axis=2)
    Hl = qh.shape[1]
    out = _dense_causal_attention(
        qh.reshape(B, Hl // G, G, qh.shape[2], hd), kh, vh, lengths,
        window=window, softcap=softcap, scale=scale,
    )
    out = out.reshape(B, Hl, -1, hd).astype(q.dtype)
    return a2a(out, split_axis=2, concat_axis=1)  # back to [B, H, Sl, hd]


# ---------------------------------------------------------------------------
# Standalone sharded attention entrypoints
# ---------------------------------------------------------------------------

_ATTN_IMPLS = {"ring": ring_attention_local, "ulysses": ulysses_attention_local}


def sp_prefill_attention(
    mesh: Mesh,
    q: jnp.ndarray,  # [B, H, S, hd] global
    k: jnp.ndarray,  # [B, Hkv, S, hd]
    v: jnp.ndarray,  # [B, Hkv, S, hd]
    lengths: jnp.ndarray,  # [B]
    impl: str = "ring",
) -> jnp.ndarray:
    """Context-parallel causal attention over the full mesh: batch on dp,
    heads on tp, sequence on sp."""
    fn = functools.partial(_ATTN_IMPLS[impl], axis_name="sp")
    spec_q = P("dp", "tp", "sp", None)
    spec_kv = P("dp", "tp", "sp", None)
    return _shard_map(
        fn, mesh, (spec_q, spec_kv, spec_kv, P("dp")), spec_q
    )(q, k, v, lengths)


# ---------------------------------------------------------------------------
# Full sequence-parallel Llama prefill (SP × TP × DP under one shard_map)
# ---------------------------------------------------------------------------


def llama_prefill_sp(
    cfg: ModelConfig,
    params: dict[str, Any],
    tokens: jnp.ndarray,  # [B, S] int32, S sharded over sp
    lengths: jnp.ndarray,  # [B] int32 true prompt lengths
    mesh: Mesh,
    attn_impl: str = "ring",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Long-context prefill with the sequence axis sharded over `sp` and
    Megatron tensor parallelism over `tp`, all inside one shard_map.

    Equivalent to `models.llama.llama_prefill` (tests assert agreement) but
    activations are [B, S/sp, D] per chip and K/V shards are produced
    directly in the engine cache's sharded layout — no full-sequence gather
    ever materializes. This is what lets one serving process accept prompts
    whose KV exceeds a single chip's HBM.

    Composes with the whole family surface (Qwen biases, Gemma offset norms
    / softcaps / embed scale / post-norms, Mistral/Gemma2 sliding windows via
    per-layer window masks threaded into the ring/Ulysses kernels) and with
    int8-quantized weights (the shared `qdot`/`embed_lookup`/`logits_head`
    ops dequantize inside the shard_map). MoE stays on the GSPMD prefill
    path — its expert all-to-all belongs to the `ep` axis, not `sp`.
    """
    from ..models.llama import (  # local import to avoid cycle
        _act,
        _norm,
        _qkv,
        _softcap,
        layer_windows,
    )
    from ..models.quant import embed_lookup, is_quantized, logits_head, qdot
    from .sharding import llama_param_specs  # local import to avoid cycle

    if cfg.n_experts:
        raise ValueError("sp prefill does not cover MoE (experts ride ep)")
    hd = cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    tp = mesh.shape["tp"]
    sp = mesh.shape["sp"]
    if Hkv % tp or cfg.vocab_size % tp:
        raise ValueError(f"tp={tp} must divide n_kv_heads={Hkv} and vocab")
    if tokens.shape[1] % sp:
        raise ValueError(f"sp={sp} must divide sequence {tokens.shape[1]}")
    if attn_impl == "ulysses" and (Hkv // tp) % sp:
        raise ValueError(
            f"ulysses needs sp={sp} | local kv heads {Hkv // tp}; use ring"
        )
    attn = functools.partial(
        _ATTN_IMPLS[attn_impl],
        axis_name="sp",
        softcap=cfg.attn_softcap,
        scale=cfg.attn_scale,
    )

    def local_fn(params, tokens, lengths):
        Bl, Sl = tokens.shape
        Hl, Hkvl = H // tp, Hkv // tp
        sp_idx = jax.lax.axis_index("sp")
        tp_idx = jax.lax.axis_index("tp")
        s0 = sp_idx * Sl  # global position offset of this sequence shard

        # Vocab-parallel embedding: each tp shard holds [V/tp, D]; lookups
        # outside the local range contribute 0 and psum restores the row
        # (embed_lookup dequantizes int8 embedding rows in place).
        embed = params["embed"]
        Vl = embed["q"].shape[0] if isinstance(embed, dict) else embed.shape[0]
        v0 = tp_idx * Vl
        local_ids = tokens - v0
        in_range = (local_ids >= 0) & (local_ids < Vl)
        rows = embed_lookup(embed, jnp.clip(local_ids, 0, Vl - 1))
        h = rows * in_range[..., None].astype(rows.dtype)
        h = jax.lax.psum(h, "tp")  # [Bl, Sl, D]
        if cfg.embed_scale:
            h = h * jnp.asarray(cfg.dim**0.5, dtype=h.dtype)

        positions = (s0 + jnp.arange(Sl, dtype=jnp.int32))[None, :]
        cos, sin = rope_tables(cfg, hd, positions)

        def layer(h, xs):
            lp, win = xs
            x = _norm(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)  # qdot: dequant + bias, tp-local
            q = apply_rope(q.reshape(Bl, Sl, Hl, hd), cos, sin)
            k = apply_rope(k.reshape(Bl, Sl, Hkvl, hd), cos, sin)
            v = v.reshape(Bl, Sl, Hkvl, hd)
            kh = k.transpose(0, 2, 1, 3)  # [Bl, Hkvl, Sl, hd]
            vh = v.transpose(0, 2, 1, 3)
            ctx = attn(q.transpose(0, 2, 1, 3), kh, vh, lengths, window=win)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(Bl, Sl, Hl * hd)
            # wo input dim sharded on tp — partial products reduce over tp
            # BEFORE any post-norm (norming partial sums would be wrong math).
            out = jax.lax.psum(qdot(ctx, lp["wo"]), "tp")
            if cfg.post_norms:
                out = _norm(cfg, out, lp["post_attn_norm"])
            h = h + out

            x = _norm(cfg, h, lp["ffn_norm"])
            gate = _act(cfg, qdot(x, lp["w1"]))
            up = qdot(x, lp["w3"])
            out = jax.lax.psum(qdot(gate * up, lp["w2"]), "tp")
            if cfg.post_norms:
                out = _norm(cfg, out, lp["post_ffn_norm"])
            h = h + out
            return h, (kh, vh)

        h, (ks, vs) = jax.lax.scan(layer, h, (params["layers"], layer_windows(cfg)))

        # The last valid position lives on exactly one sp shard: every shard
        # contributes its row (or zeros) and a psum over sp assembles [Bl, D].
        last_pos = lengths - 1  # [Bl] global
        local_last = jnp.clip(last_pos - s0, 0, Sl - 1)
        mine = (last_pos >= s0) & (last_pos < s0 + Sl)
        h_last = jnp.take_along_axis(h, local_last[:, None, None], axis=1)[:, 0]
        h_last = jax.lax.psum(h_last * mine[:, None].astype(h_last.dtype), "sp")

        h_last = _norm(cfg, h_last, params["final_norm"])
        src = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        # vocab-parallel logits [B, V/tp] (logits_head dequantizes int8 heads)
        logits = _softcap(
            logits_head(src, h_last, tied=cfg.tie_embeddings), cfg.logit_softcap
        )
        return logits, ks, vs

    pspecs = llama_param_specs(cfg)
    if is_quantized(params["layers"]["wq"]):
        from ..models.quant import quantized_specs

        pspecs = quantized_specs(pspecs)
    out_specs = (
        P("dp", "tp"),  # vocab-parallel logits [B, V]
        P(None, "dp", "tp", "sp", None),  # ks [L, B, Hkv, S, hd]
        P(None, "dp", "tp", "sp", None),  # vs
    )
    return _shard_map(
        local_fn, mesh, (pspecs, P("dp", "sp"), P("dp")), out_specs
    )(params, tokens, lengths)
