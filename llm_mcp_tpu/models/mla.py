"""Multi-head Latent Attention (MLA) — DeepSeek-V2/V3-style KV compression,
TPU-first.

Why it exists here: decode is cache-bandwidth-bound (see
kernels/attention.py), and long-context serving is capped by KV bytes per
token. GQA at 8B-class shapes stores 2 * n_kv_heads * head_dim = 2048
values/token/layer; MLA stores ONE shared latent (kv_lora_rank) plus a
shared rope key (qk_rope_head_dim) — 576 values/token/layer at DeepSeek
proportions, ~3.6x more context per HBM byte, with per-head K/V
re-expanded from the latent by weight matrices that live in HBM once.

TPU-first choices:
  - **Decode runs ABSORBED**: queries fold through the k-up-projection
    (q̃ = q_nope @ W_uk per head) so attention works directly against the
    latent cache — two dense einsums on the MXU, no per-head K/V ever
    materialized at decode time. The value side re-expands only the
    attended context vector (H x kv_lora_rank @ kv_lora_rank x v_dim).
  - **Prefill runs EXPANDED**: at prompt lengths the O(S) per-head K/V is
    cheap relative to the weight pass, and the expanded form is one
    standard masked attention XLA fuses well.
  - **Engine compatibility by shape**: the latent cache poses as a
    one-kv-head llama cache — k-cache := latents [L, B, 1, S, kv_lora_rank],
    v-cache := rope keys [L, B, 1, S, qk_rope_head_dim] — so the engine's
    entire slot machinery (bucketed inserts, chunk writes, compaction
    scatter, donation, recovery) works unchanged. `llama_prefill` /
    `llama_decode_step` dispatch here when cfg.kv_lora_rank > 0.
  - **The int8 rope keys lie in rows of whole lanes**: P = 128 //
    qk_rope_head_dim positions abreast, s8[L, B, 1, S / P, P * dr], position
    s in row s mod S/P, lanes [(s div S/P) dr, +dr)
    (`kernels/attention.py:positions_abreast`, `rope_abreast` / `rope_apart`;
    P = 2 at the published 64). A minor dimension of 64 the chip lays out
    with positions minor, and every step program re-laid the whole member
    for its Mosaic call and again for its append. Every reader and writer
    here goes through that module's functions (`rope_put`, `rope_append`,
    `rope_rows`, `rope_queries`); what is CUT OUT of the cache (a prompt's
    rows, a prefix entry, a pool's block, a host copy) lies apart, [.., n, dr].

Reference parity note: the reference serves deepseek-architecture models
only through Ollama (`discovery.go:510` infers metadata from the name);
this module is what "serving a deepseek-class architecture in-process"
means TPU-side. Rope here is the repo's split-half convention (the loader
de-interleaves a checkpoint's rope columns once, models/weights.py).

What the module runs: the query dense (`wq_mla`, DeepSeek-V2-Lite) or through a
latent of its own (`q_lora_rank`: `w_dq`, `q_a_norm`, `w_uq`, DeepSeek-V2/V3),
yarn-scaled rope, and after `first_dense_layers` dense layers
(`params["dense_layers"]`, a stack of its own) a feed-forward of routed experts
in one of two forms, by what the preset states (`moe.share_form`): `moe.moe_ffn`'s
capacity dispatch with a softmax router (`tiny-v2`, `deepseek-v2-lite`), or
`moe.moe_share_ffn` (`joyai-llm-flash-ep16`, `tiny-joyai`): DeepSeek-V3's router
(sigmoid scores, a selection bias, renormalised gates times a factor), dropless
in every program, this process holding a share of the published experts, the
banks handed over STACKED and never sliced by a layer scan, and its counts
riding the cache pair's second member as {"v": the rope keys, "moe": [2, Le, 5]}
(`executor/layout.py:CacheLayout.counted`; models/hybrid.py says what the
counts are). The multi-token-prediction module (`cfg.mtp_layers`) is a tree and
a forward of its own, `init_mtp_params` and `mtp_logits`, built and run where a
caller asks: no step program holds it. Every product of the attention half
carries a `jax.named_scope` under the prefix `mla.` (`q_down`, `q_up` or `q`,
`kv_down`, `kv_up` or `absorb`, `attend`, `out`), which a trace's operations keep.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.attention import (
    paged_gather,
    positions_abreast,
    ragged_prefill_attend_mla,
    rope_append,
    rope_put,
    rope_queries,
    rope_rows,
)
from ..ops.norms import rms_norm as _rms_norm
from ..ops.rope import apply_rope, rope_tables
from .configs import ModelConfig
from .moe import expert_stack, share_form
from .quant import qdot

# llama.py imports this module only lazily inside its dispatch functions, so
# pulling the shared decoder helpers in at module level is cycle-free
from .llama import (
    _embed_in,
    _ffn as _share_ffn,
    _ffn_residual,
    _logits,
    _norm,
    _rows,
    _second,
    quantize_kv,
    ragged_write_rows,
)

Params = Any


def _dims(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """(n_heads, qk_nope, qk_rope, v_dim)."""
    return cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim


def mla_scale(cfg: ModelConfig) -> float:
    # yarn_attn_mscale folds DeepSeek-V2's yarn magnitude correction
    # ((0.1·mscale_all_dim·ln(factor)+1)²) into the softmax scale
    return (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ) ** -0.5 * cfg.yarn_attn_mscale


def _mla_attn_weights(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype, L: int
) -> Params:
    """Stacked [L, ...] MLA attention weights: the query dense (`wq_mla`) or,
    with `q_lora_rank`, through its own latent (`w_dq`, `q_a_norm`, `w_uq`)."""
    H, dn, dr, dv = _dims(cfg)
    D, R, Rq = cfg.dim, cfg.kv_lora_rank, cfg.q_lora_rank

    def w(k, shape, fan_in):
        return (
            jax.random.normal(k, shape, dtype=jnp.float32) * (fan_in**-0.5)
        ).astype(dtype)

    kq = jax.random.split(key, 4)
    if Rq:
        k_dq, k_uq = jax.random.split(kq[0])
        query = {"w_dq": w(k_dq, (L, D, Rq), D), "q_a_norm": jnp.ones((L, Rq), dtype=dtype),
                 "w_uq": w(k_uq, (L, Rq, H * (dn + dr)), Rq)}
    else:
        query = {"wq_mla": w(kq[0], (L, D, H * (dn + dr)), D)}
    return {
        **query,
        # one matmul produces (latent c_kv | shared rope key), HF
        # kv_a_proj_with_mqa layout
        "w_dkv": w(kq[1], (L, D, R + dr), D),
        "kv_norm": jnp.ones((L, R), dtype=dtype),  # kv_a_layernorm
        # up-projection from the latent to per-head (k_nope | v)
        "w_ukv": w(kq[2], (L, R, H * (dn + dv)), R),
        "wo_mla": w(kq[3], (L, H * dv, D), H * dv),
    }


def init_mla_params(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    """Random-init MLA decoder weights.

    With cfg.first_dense_layers > 0 (DeepSeek-V2 MoE), the layer stack
    splits into params["dense_layers"] (layers 0..k-1, dense FFN at
    ffn_hidden) and params["layers"] (the MoE stack) — two uniform scans
    instead of one, since the FFN weight shapes differ. A configuration in the
    share form (`moe.share_form`) is drawn as ONE jitted program (an eager draw
    a tensor is a compile a tensor on the chip) and a sigmoid router gets its
    selection bias, normal with deviation 0.01 (models/hybrid.py:
    init_hybrid_params says why no larger)."""
    if share_form(cfg):
        return jax.jit(lambda k: _init(cfg, k, dtype))(key)
    return _init(cfg, key, dtype)


def _init(cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype) -> Params:
    import dataclasses

    from .llama import init_llama_params  # local: dispatch entry point

    k_dense = cfg.first_dense_layers if cfg.n_experts else 0
    L_main = cfg.n_layers - k_dense
    # the base init skips wq/wk/wv/wo for MLA configs (they would be
    # built at full GQA size only to be discarded — a ~4 GB transient at
    # 8B-class shapes)
    cfg_main = (
        dataclasses.replace(cfg, n_layers=L_main) if k_dense else cfg
    )
    base = init_llama_params(cfg_main, key, dtype=dtype, _dispatch=False)
    layers = base["layers"]
    for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"):
        layers.pop(k, None)
    layers.update(_mla_attn_weights(cfg, jax.random.fold_in(key, 7), dtype, L_main))
    if cfg.n_experts and cfg.router_score == "sigmoid":
        layers["router_bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(key, 17), (L_main, cfg.router_width), jnp.float32)
    if k_dense:
        cfg_dense = dataclasses.replace(cfg, n_layers=k_dense, n_experts=0)
        dense = init_llama_params(
            cfg_dense, jax.random.fold_in(key, 11), dtype=dtype, _dispatch=False
        )["layers"]
        for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"):
            dense.pop(k, None)
        dense.update(
            _mla_attn_weights(cfg, jax.random.fold_in(key, 13), dtype, k_dense)
        )
        base["dense_layers"] = dense
    return base


def init_mla_cache(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    dtype: jnp.dtype = jnp.bfloat16,
    quantized: bool = False,
) -> dict[str, Any]:
    """Latent cache in the engine's (k, v) pair convention:
    k := latents [L, B, 1, S, kv_lora_rank], v := rope keys
    [L, B, 1, S, qk_rope_head_dim], the int8 ones P positions abreast in rows
    of whole lanes, [L, B, 1, S / P, P * dr] (`positions_abreast`: a function
    of S and dr alone; the scales stay one a position, [L, B, 1, S]). The
    fake one-head axis keeps every slot-machinery code path (inserts, chunked
    writes, compaction) byte-compatible with the llama cache layout.

    `quantized=True` stores int8 payloads with per-token scales (the same
    post-dot scale-folding scheme as the GQA int8 cache): MLA's latent is
    already ~3.6x smaller than GQA K/V by VALUE COUNT; int8 makes it
    ~7x smaller by BYTES — double the context per HBM byte again. In the share
    form the second member is {"v": the rope keys, "moe": the expert counts}."""
    L, R, dr = cfg.n_layers, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    if quantized:
        P = positions_abreast(max_seq, dr)
        pair = {
            "k": {
                "q": jnp.zeros((L, batch, 1, max_seq, R), dtype=jnp.int8),
                "s": jnp.zeros((L, batch, 1, max_seq), dtype=dtype),
            },
            "v": {
                "q": jnp.zeros((L, batch, 1, max_seq // P, P * dr), dtype=jnp.int8),
                "s": jnp.zeros((L, batch, 1, max_seq), dtype=dtype),
            },
        }
    else:
        pair = {
            "k": jnp.zeros((L, batch, 1, max_seq, R), dtype=dtype),
            "v": jnp.zeros((L, batch, 1, max_seq, dr), dtype=dtype),
        }
    if share_form(cfg):
        # the expert layer's counts ride the pair's second member, beside the
        # rope keys (models/hybrid.py: what they are; `_rows`, `_second`)
        Le = L - cfg.first_dense_layers
        pair["v"] = {"v": pair["v"], "moe": jnp.zeros((2, Le, 5), jnp.int32)}
    return pair


def _latents(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    """x [..., D] → (c_kv [..., R] normed, k_rope [..., dr] pre-rope)."""
    R = cfg.kv_lora_rank
    with jax.named_scope("mla.kv_down"):
        ckr = qdot(x, lp["w_dkv"])  # [..., R + dr]
        c = _rms_norm(ckr[..., :R], lp["kv_norm"], cfg.norm_eps)
    return c, ckr[..., R:]


def _queries(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    """x [..., D] → (q_nope [..., H, dn], q_rope [..., H, dr]): one dense
    product, or with `q_lora_rank` through the query's own latent, c_q =
    RMSNorm(x W_dq; q_a_norm), q = c_q W_uq. The one query of all four programs."""
    H, dn, dr, _ = _dims(cfg)
    if "w_dq" in lp:
        with jax.named_scope("mla.q_down"):
            cq = _rms_norm(qdot(x, lp["w_dq"]), lp["q_a_norm"], cfg.norm_eps)
        with jax.named_scope("mla.q_up"):
            q = qdot(cq, lp["w_uq"])
        # `w_uq`'s columns are every head's content part, then every head's rope
        # part, [H dn | H dr]: both cuts fall on whole lanes. With a head's two
        # parts side by side (192 a head, as `wq_mla` has them) the compiler
        # re-laid the whole stack out every round to cut inside a head (0.69 GiB
        # at the published size, seen in the described-chip compile).
        return (q[..., : H * dn].reshape(*x.shape[:-1], H, dn),
                q[..., H * dn :].reshape(*x.shape[:-1], H, dr))
    with jax.named_scope("mla.q"):
        q = qdot(x, lp["wq_mla"])
    q = q.reshape(*x.shape[:-1], H, dn + dr)
    return q[..., :dn], q[..., dn:]


def _n_dense(params: Params) -> int:
    return params["dense_layers"]["attn_norm"].shape[0] if "dense_layers" in params else 0


def _ffn(cfg: ModelConfig, lp: Params, banks: Params | None, le, h, valid=None, capacity: int = 0):
    """Feed-forward half of one layer and residual add: (h, counts [5] of an
    expert layer in the share form (`banks` and its index `le` among the expert
    layers), None for every other)."""
    if banks is not None and "router" in lp:
        return _share_ffn(cfg, lp, banks, le, h, valid)
    return _ffn_residual(cfg, lp, h, moe_capacity=capacity, moe_valid=valid), None


def _prefill_attn(cfg: ModelConfig, lp: Params, h, cos, sin, valid_k):
    """The attention half of one layer over whole prompts h [B, S, D], expanded
    and query-blocked (`mla_prefill`): (h after the residual add, the layer's
    latents [B, S, R], its rope keys [B, S, dr] post-rope)."""
    H, dn, dr, dv = _dims(cfg)
    B, S, _ = h.shape
    scale = mla_scale(cfg)
    key_pos = jnp.arange(S, dtype=jnp.int32)
    neg = jnp.float32(-1e30)
    QB = next((c for c in (256, 128, 64, 32, 16, 8, 4, 2, 1) if S % c == 0))
    nb = S // QB
    x = _norm(cfg, h, lp["attn_norm"])
    qn, qr = _queries(cfg, lp, x)  # [B, S, H, dn/dr]
    qr = apply_rope(qr, cos, sin)
    c, kr = _latents(cfg, lp, x)  # [B, S, R], [B, S, dr]
    kr = apply_rope(kr[..., None, :], cos, sin)[..., 0, :]  # shared key
    with jax.named_scope("mla.kv_up"):
        kv = qdot(c, lp["w_ukv"]).reshape(B, S, H, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]

    # query blocks ride a scan: [nb, B, QB, H, d] xs against the full
    # (linear-size) keys closed over — one block's [B, H, QB, S] scores
    # live at a time
    qn_b = qn.reshape(B, nb, QB, H, dn).transpose(1, 0, 2, 3, 4)
    qr_b = qr.reshape(B, nb, QB, H, dr).transpose(1, 0, 2, 3, 4)
    pos_b = jnp.arange(S, dtype=jnp.int32).reshape(nb, QB)

    def qblock(_, xs):
        qnj, qrj, posj = xs  # [B, QB, H, ·], [QB]
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qnj, kn)
            + jnp.einsum("bqhd,bkd->bhqk", qrj, kr)
        ).astype(jnp.float32) * scale
        mask = (key_pos[None, :] <= posj[:, None])[None, None] & valid_k[
            :, None, None, :
        ]  # [B, 1|QB, S] → [B, 1, QB, S]
        scores = jnp.where(mask, scores, neg)
        probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)  # [B, QB, H, dv]
        return None, ctx

    with jax.named_scope("mla.attend"):
        _, ctx_b = jax.lax.scan(qblock, None, (qn_b, qr_b, pos_b))
    ctx = ctx_b.transpose(1, 0, 2, 3, 4).reshape(B, S, H * dv)
    with jax.named_scope("mla.out"):
        h = h + qdot(ctx, lp["wo_mla"])
    return h, c, kr


def mla_prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32 right-padded prompts
    lengths: jnp.ndarray,  # [B] int32 true lengths
    quant_kv: bool = False,  # int8 latents (per-token scales) inside the scan
    hidden: bool = False,  # the residual stream after the last layer [B, S, D] for the logits
) -> tuple[jnp.ndarray, Any, Any]:
    """Causal prefill with QUERY-BLOCKED expanded attention: per-head K/V
    re-materialize once (O(S) memory), but scores/probs only ever exist for
    one query block at a time — [B, H, QB, S] instead of [B, H, S, S].
    A naive expanded form would build an 8.6 GB f32 score tensor per layer
    at S=8192/H=32; blocking keeps long-context prefill linear in S (the
    same job chunked prefill does for the llama families).

    Returns (last_logits [B, V] f32, latents [L, B, 1, S, R], rope_keys
    [L, B, 1, S, dr]) — the cache rows to insert at the request's slot
    (post-rope, decode-ready). In the share form the third is {"v": the rope
    keys, "moe": the call's expert counts [Le, 5]}: the engine inserts the rows
    and adds the counts once (`hybrid.add_counts`)."""
    dr = cfg.qk_rope_head_dim
    B, S = tokens.shape
    h = _embed_in(cfg, params, tokens)  # [B, S, D]
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    cos, sin = rope_tables(cfg, dr, positions)  # [1, S, dr/2]
    valid_k = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]  # [B, S]
    banks, stack = expert_stack(cfg, params["layers"])

    def scan_layer(carry, lp):
        h, le = carry
        h, c, kr = _prefill_attn(cfg, lp, h, cos, sin, valid_k)
        h, counts = _ffn(cfg, lp, banks, le, h, valid=valid_k)
        if quant_kv:
            # quantize INSIDE the scan: the stacked bf16 latents of a long
            # admission never materialize (llama_prefill's same trick)
            return (h, le + 1), (quantize_kv(c), quantize_kv(kr), counts)
        return (h, le + 1), (c, kr, counts)

    if "dense_layers" in params:
        # DeepSeek first-dense prologue (layers 0..k-1): same layer fn, the
        # FFN shape difference lives in the params (see _ffn_residual)
        (h, _), (cs_d, krs_d, _) = jax.lax.scan(
            scan_layer, (h, jnp.int32(0)), params["dense_layers"])
    (h, _), (cs, krs, counts) = jax.lax.scan(scan_layer, (h, jnp.int32(0)), stack)
    if "dense_layers" in params:
        cs = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0), cs_d, cs)
        krs = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0), krs_d, krs)
    last = jnp.clip(lengths - 1, 0, S - 1)
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    logits = h if hidden else _logits(cfg, params, h_last)

    def to_engine_layout(x):
        # [L, B, S, ·] → engine layout [L, B, 1, S, ·]
        if isinstance(x, dict):
            return {"q": x["q"][:, :, None], "s": x["s"][:, :, None]}
        return x[:, :, None]

    vs = to_engine_layout(krs)
    if counts is not None:  # the call's expert counts [Le, 5], as hybrid_prefill hands them over
        vs = {"v": vs, "moe": counts}
    return logits, to_engine_layout(cs), vs


def mla_prefill_chunk_batch(
    cfg: ModelConfig,
    params: Params,
    cache_c: Any,  # [L, B, 1, S, R] latents (or int8 {"q","s"} pytree)
    cache_r: Any,  # [L, B, 1, S, dr] rope keys (int8: [L, B, 1, S / P, P * dr])
    tokens: jnp.ndarray,  # [A, C] int32 — right-padded chunks, one per slot
    slots: jnp.ndarray,  # [A] int32 engine slots
    starts: jnp.ndarray,  # [A] int32 absolute position of each chunk's start
    nvalid: jnp.ndarray,  # [A] int32 valid tokens per chunk
    skey: int = 0,  # STATIC bound on the PAST key range (0 = whole S)
    all_logits: bool = False,  # STATIC: logits at every chunk position
    paged: dict | None = None,  # {"tbl","k","v"} physical paging operand
) -> tuple[jnp.ndarray, Any, Any]:
    """Batched chunked prefill for MLA — the absorbed-attention analog of
    `llama_prefill_chunk_batch` (same engine contract: one bounded chunk for
    up to A slots in a single dispatch, read-past-then-write-in-place,
    static (C, skey) buckets).

    The chunk's queries fold through W_uk exactly as `mla_decode_step` does,
    so the PAST segment scores straight against the latent cache — context
    prefilled by earlier chunks is never re-expanded to per-head K/V. The
    SELF segment scores against the chunk's own in-register latents (exact
    bf16 even over an int8 cache — the decode kernel's current-token
    override, generalized to C tokens). One joint softmax over [past |
    self]; the value side re-expands only the attended [H, R] context
    through W_uv. This is what unlocks the engine's prompt-prefix KV cache
    for the MLA family: a prefix hit copies latent rows, and the suffix
    rides this path with start = P0.
    """
    H, dn, dr, dv = _dims(cfg)
    pair_r, cache_r = cache_r, _rows(cache_r)
    quantized = isinstance(cache_c, dict)
    L, B, _, S, R = (cache_c["q"] if quantized else cache_c).shape
    Pr = S // cache_r["q"].shape[3] if quantized else 1  # positions abreast
    A, C = tokens.shape
    banks, stack = expert_stack(cfg, params["layers"])
    k_dense = _n_dense(params)
    Sk = min(skey, S) if skey else S
    scale = mla_scale(cfg)
    neg = jnp.float32(-1e30)
    slots = jnp.asarray(slots, dtype=jnp.int32)
    starts = jnp.asarray(starts, dtype=jnp.int32)
    nvalid = jnp.asarray(nvalid, dtype=jnp.int32)

    h = _embed_in(cfg, params, tokens)  # [A, C, D]
    q_pos = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [A, C]
    cos, sin = rope_tables(cfg, dr, q_pos)  # [A, C, dr/2]
    key_pos = jnp.arange(Sk, dtype=jnp.int32)
    # past segment: cache rows strictly before each chunk's start
    past_mask = jnp.broadcast_to(
        key_pos[None, None, :] < starts[:, None, None], (A, C, Sk)
    )
    # self segment: causal within the chunk (pad rows past nvalid are
    # written but never attended by valid queries — llama chunk invariant)
    c_idx = jnp.arange(C, dtype=jnp.int32)
    self_mask = jnp.broadcast_to((c_idx[None, :] <= c_idx[:, None])[None], (A, C, C))

    # Block-indirect past reads through each slot's table (shared prefix
    # latents resolve to pool rows); only the blocks covering the static
    # skey bucket are gathered. Writes stay contiguous — chunk positions
    # are private blocks, which live at their identity homes.
    ptbl = None
    if paged is not None:
        nbs_full = paged["tbl"].shape[1]
        bt = S // nbs_full
        nsel = max(1, -(-Sk // bt))
        ptbl = jnp.take(paged["tbl"], slots, axis=0)[:, :nsel]

    def layer(carry, lp):
        h, cc_all, cr_all, li = carry
        x = _norm(cfg, h, lp["attn_norm"])
        qn, qr = _queries(cfg, lp, x)  # [A, C, H, dn/dr]
        qr = apply_rope(qr, cos, sin)
        c, kr = _latents(cfg, lp, x)  # [A, C, R], [A, C, dr]
        kr = apply_rope(kr[..., None, :], cos, sin)[..., 0, :]
        with jax.named_scope("mla.absorb"):
            w_uk, w_uv = _absorbed_w(lp, h.dtype, R, H, dn, dv)
            qt = jnp.einsum("achd,rhd->achr", qn, w_uk)  # [A, C, H, R]

        # ---- reads first: past latents/rope keys from the PRE-write cache
        def past_rows(cache, d, pool=None):
            if ptbl is not None:
                return paged_gather(
                    jax.lax.dynamic_index_in_dim(cache, li, 0, keepdims=False),
                    jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False),
                    ptbl, nbs=nbs_full,
                )[:, 0, :Sk]  # [A, Sk, d] (d absent for scale planes)
            return jnp.stack(
                [
                    jax.lax.dynamic_slice(
                        cache, (li, slots[a], 0, 0, 0), (1, 1, 1, Sk, d)
                    )[0, 0, 0]
                    for a in range(A)
                ]
            )  # [A, Sk, d]

        def past_rope_scores(qr, cache, pool=None):
            """[A, H, C, Sk] f32 of the rope queries against the past int8 rope
            keys. They are multiplied AS THEY LIE, P positions abreast in rows
            of whole lanes: the queries go to one lane group of an otherwise
            zero operand (`rope_queries`), a product over whole rows is then
            the group's own, and the groups side by side are the positions in
            order. Pulled apart to rows of 64 at the scan's edge the compiler
            re-lays the whole member (models/llama.py:_chunk_attention)."""
            if ptbl is not None:  # block-indirect: gathered apart, as a pool lies
                rop = rope_rows(
                    jax.lax.dynamic_index_in_dim(cache, li, 0, keepdims=False), Pr, tables=ptbl,
                    pool=jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False), nbs=nbs_full,
                )[:, :Sk]
                return jnp.einsum("achd,asd->ahcs", qr, rop.astype(qr.dtype)).astype(jnp.float32)
            n = min(Sk, S // Pr)  # rows that hold a past position
            G = -(-Sk // n)  # and the lane groups that do
            rows = jnp.stack([
                jax.lax.dynamic_slice(
                    cache, (li, slots[a], 0, 0, 0), (1, 1, 1, n, Pr * dr))[0, 0, 0]
                for a in range(A)
            ])  # [A, n, P*dr]
            qg = rope_queries(qr, Pr).reshape(A, C, Pr, H, Pr * dr)[:, :, :G]
            s = jnp.einsum("acphw,arw->ahcpr", qg, rows.astype(qr.dtype))
            return s.astype(jnp.float32).reshape(A, H, C, G * n)[..., :Sk]

        def past_scales(cache_s, pool_s=None):
            if ptbl is not None:
                return past_rows(cache_s, 0, pool_s).astype(jnp.float32)
            return jnp.stack(
                [
                    jax.lax.dynamic_slice(
                        cache_s, (li, slots[a], 0, 0), (1, 1, 1, Sk)
                    )[0, 0, 0]
                    for a in range(A)
                ]
            ).astype(jnp.float32)  # [A, Sk]

        with jax.named_scope("mla.attend"):
            pk = None if paged is None else paged["k"]
            pv = None if paged is None else paged["v"]
            if quantized:
                lat = past_rows(cc_all["q"], R, pk and pk["q"])
                ls = past_scales(cc_all["s"], pk and pk["s"])
                rs = past_scales(cr_all["s"], pv and pv["s"])
                # per-token dequant scales fold POST-DOT (decode path's trick)
                s_past = (
                    jnp.einsum("achr,asr->ahcs", qt, lat.astype(qt.dtype)).astype(
                        jnp.float32
                    )
                    * ls[:, None, None, :]
                    + past_rope_scores(qr, cr_all["q"], pv and pv["q"])
                    * rs[:, None, None, :]
                ) * scale
            else:
                lat = past_rows(cc_all, R, pk)
                rop = past_rows(cr_all, dr, pv)
                s_past = (
                    jnp.einsum("achr,asr->ahcs", qt, lat.astype(qt.dtype))
                    + jnp.einsum("achd,asd->ahcs", qr, rop.astype(qr.dtype))
                ).astype(jnp.float32) * scale
            s_self = (
                jnp.einsum("achr,atr->ahct", qt, c)
                + jnp.einsum("achd,atd->ahct", qr, kr)
            ).astype(jnp.float32) * scale
            s_past = jnp.where(past_mask[:, None], s_past, neg)
            s_self = jnp.where(self_mask[:, None], s_self, neg)

            # joint softmax over [past | self]
            s = jnp.concatenate([s_past, s_self], axis=-1)  # [A, H, C, Sk+C]
            probs = jax.nn.softmax(s, axis=-1)
            p_past, p_self = probs[..., :Sk], probs[..., Sk:]
            if quantized:
                p_past = p_past * ls[:, None, None, :]  # value-side dequant
            ctx_lat = jnp.einsum(
                "ahcs,asr->achr", p_past.astype(h.dtype), lat.astype(h.dtype)
            ) + jnp.einsum("ahct,atr->achr", p_self.astype(h.dtype), c)
        with jax.named_scope("mla.absorb"):
            ctx = jnp.einsum("achr,rhd->achd", ctx_lat, w_uv).reshape(A, C, H * dv)
        with jax.named_scope("mla.out"):
            h = h + qdot(ctx, lp["wo_mla"])
        h, counts = _ffn(cfg, lp, banks, li - k_dense, h, valid=c_idx[None, :] < nvalid[:, None])

        # ---- writes last: in place (write-after-read)
        if quantized:
            cq = quantize_kv(c, scale_dtype=cc_all["s"].dtype)
            rq = quantize_kv(kr, scale_dtype=cr_all["s"].dtype)
            for a in range(A):
                cc_all = {
                    "q": jax.lax.dynamic_update_slice(
                        cc_all["q"], cq["q"][a][None, None, None],
                        (li, slots[a], 0, starts[a], 0),
                    ),
                    "s": jax.lax.dynamic_update_slice(
                        cc_all["s"], cq["s"][a][None, None, None],
                        (li, slots[a], 0, starts[a]),
                    ),
                }
                cr_all = {
                    "q": rope_put(
                        cr_all["q"], rq["q"][a][None, None, None], (li, slots[a]), starts[a]
                    ),
                    "s": jax.lax.dynamic_update_slice(
                        cr_all["s"], rq["s"][a][None, None, None],
                        (li, slots[a], 0, starts[a]),
                    ),
                }
        else:
            for a in range(A):
                cc_all = jax.lax.dynamic_update_slice(
                    cc_all, c[a][None, None, None].astype(cc_all.dtype),
                    (li, slots[a], 0, starts[a], 0),
                )
                cr_all = jax.lax.dynamic_update_slice(
                    cr_all, kr[a][None, None, None].astype(cr_all.dtype),
                    (li, slots[a], 0, starts[a], 0),
                )
        return (h, cc_all, cr_all, li + 1), counts

    carry = (h, cache_c, cache_r, jnp.int32(0))
    if "dense_layers" in params:
        # DeepSeek first-dense prologue; carried li keeps cache rows aligned
        # with absolute layer position
        carry, _ = jax.lax.scan(layer, carry, params["dense_layers"])
    (h, new_c, new_r, _), counts = jax.lax.scan(layer, carry, stack)
    new_r = _second(pair_r, new_r, 1, counts)
    if all_logits:
        return _logits(cfg, params, h), new_c, new_r  # [A, C, V]
    last = jnp.take_along_axis(
        h, jnp.clip(nvalid - 1, 0, C - 1)[:, None, None], axis=1
    )[:, 0]  # [A, D]
    return _logits(cfg, params, last), new_c, new_r


def mla_prefill_chunk_ragged(
    cfg: ModelConfig,
    params: Params,
    cache_c: Any,  # [L, B, 1, S, R] latents (or int8 {"q","s"} pytree)
    cache_r: Any,  # [L, B, 1, S, dr] rope keys (int8: [L, B, 1, S / P, P * dr])
    tokens: jnp.ndarray,  # [T] int32 — PACKED chunks, rows back-to-back
    rowids: jnp.ndarray,  # [T] int32 — descriptor row per token, sorted
    #   ascending; pads carry rowid == Rn
    positions: jnp.ndarray,  # [T] int32 — absolute positions; pads carry S
    slots: jnp.ndarray,  # [Rn] int32
    starts: jnp.ndarray,  # [Rn] int32 cached-prefix length per row
    last_idx: jnp.ndarray,  # [Rn] int32 packed index of each row's last token
    skey: int = 0,  # STATIC past bound for the XLA arm (kernel arm ignores)
    paged: dict | None = None,  # {"tbl","k","v"} physical paging operand
    impl: str | None = None,  # the engine's resolved "kernel" | "xla"
) -> tuple[jnp.ndarray, Any, Any]:
    """Ragged chunked prefill for MLA — the packed-descriptor twin of
    `mla_prefill_chunk_batch` (see `llama_prefill_chunk_ragged` for the
    descriptor contract). Queries fold through W_uk so the cached prefix
    scores straight against latent rows, streamed block-indirect by
    `kernels/attention.py:ragged_prefill_attend_mla`; the chunk's own
    latents/rope keys stay exact bf16 from registers; the value side
    re-expands only the attended [H, R] context through W_uv.

    Returns (logits [Rn, V] f32 at each row's `last_idx` token, new_c, new_r).
    """
    H, dn, dr, dv = _dims(cfg)
    pair_r, cache_r = cache_r, _rows(cache_r)
    quantized = isinstance(cache_c, dict)
    L, B, _, S, R = (cache_c["q"] if quantized else cache_c).shape
    T = tokens.shape[0]
    banks, stack = expert_stack(cfg, params["layers"])
    k_dense = _n_dense(params)
    Rn = slots.shape[0]
    scale = mla_scale(cfg)
    slots = jnp.asarray(slots, dtype=jnp.int32)
    starts = jnp.asarray(starts, dtype=jnp.int32)
    rowids = jnp.asarray(rowids, dtype=jnp.int32)
    positions = jnp.asarray(positions, dtype=jnp.int32)
    offsets = jnp.concatenate(
        [
            jnp.zeros((1,), jnp.int32),
            jnp.sum(
                (rowids[None, :] < jnp.arange(1, Rn + 1, dtype=jnp.int32)[:, None]),
                axis=1,
                dtype=jnp.int32,
            ),
        ]
    )  # [Rn+1]
    moe_valid = rowids < Rn
    btbl = paged["tbl"] if paged is not None else None
    pool_c = paged["k"] if paged is not None else None
    pool_r = paged["v"] if paged is not None else None

    h = _embed_in(cfg, params, tokens)  # [T, D]
    cos, sin = rope_tables(cfg, dr, positions)  # [T, dr/2]

    # the cache is scan-INVARIANT: every layer reads it pre-append, the new
    # rows stack out as scan ys and land once after the scans
    # (models/llama.py:ragged_write_rows says why — a cache carried through
    # a scan that a Pallas call reads and a scatter updates costs a second
    # copy of the whole cache in HBM)
    def layer(carry, lp):
        h, li = carry
        x = _norm(cfg, h, lp["attn_norm"])
        qn, qr = _queries(cfg, lp, x)  # [T, H, dn/dr]
        qr = apply_rope(qr, cos, sin)
        c, kr = _latents(cfg, lp, x)  # [T, R], [T, dr]
        kr = apply_rope(kr[..., None, :], cos, sin)[..., 0, :]
        with jax.named_scope("mla.absorb"):
            w_uk, w_uv = _absorbed_w(lp, h.dtype, R, H, dn, dv)
            qt = jnp.einsum("thd,rhd->thr", qn, w_uk)  # [T, H, R]

        # ---- ragged attention over [cached past | packed self]
        with jax.named_scope("mla.attend"):
            ctx_lat = ragged_prefill_attend_mla(
                qt, qr, c, kr, cache_c, cache_r, li, rowids, offsets, slots, starts,
                scale=scale, skey=skey, block_tables=btbl,
                pool_c=pool_c, pool_r=pool_r, impl=impl,
            )  # [T, H, R]
        with jax.named_scope("mla.absorb"):
            ctx = jnp.einsum("thr,rhd->thd", ctx_lat, w_uv).reshape(T, H * dv)
        with jax.named_scope("mla.out"):
            h = h + qdot(ctx, lp["wo_mla"])
        h, counts = _ffn(cfg, lp, banks, li - k_dense, h, valid=moe_valid)

        # ---- this layer's rows in the cache's own form, [1 (head), T, ..]
        if quantized:
            cq = quantize_kv(c, scale_dtype=cache_c["s"].dtype)
            rq = quantize_kv(kr, scale_dtype=cache_r["s"].dtype)
            new = (cq["q"][None], cq["s"][None], rq["q"][None], rq["s"][None])
        else:
            new = (c.astype(cache_c.dtype)[None], kr.astype(cache_r.dtype)[None])
        return (h, li + 1), (new, counts)

    carry = (h, jnp.int32(0))
    stacks = []
    if "dense_layers" in params:
        carry, (ys, _) = jax.lax.scan(layer, carry, params["dense_layers"])
        stacks.append(ys)
    (h, _), (ys, counts) = jax.lax.scan(layer, carry, stack)
    stacks.append(ys)
    new = [jnp.concatenate(parts, axis=0) for parts in zip(*stacks)]  # [L, 1, T, ..]

    # ---- writes last, all layers at once, in place
    def land(cache, rows):
        return ragged_write_rows(cache, rows, slots, starts, offsets)

    if quantized:
        new_c = {"q": land(cache_c["q"], new[0]), "s": land(cache_c["s"], new[1])}
        new_r = {"q": land(cache_r["q"], new[2]), "s": land(cache_r["s"], new[3])}
    else:
        new_c, new_r = land(cache_c, new[0]), land(cache_r, new[1])
    last = jnp.take(h, jnp.clip(last_idx, 0, T - 1), axis=0)  # [Rn, D]
    return _logits(cfg, params, last), new_c, _second(pair_r, new_r, 1, counts)


def _absorbed_w(lp, h_dtype, R, H, dn, dv):
    """(W_uk [R,H,dn], W_uv [R,H,dv]) from this layer's (possibly int8)
    up-projection — dequantized once per step."""
    w_ukv = lp["w_ukv"]
    if isinstance(w_ukv, dict):
        w_ukv = w_ukv["q"].astype(h_dtype) * w_ukv["s"].astype(h_dtype)
    w_ukv = w_ukv.reshape(R, H, dn + dv)
    return w_ukv[:, :, :dn], w_ukv[:, :, dn:]


def mla_decode_step(
    cfg: ModelConfig,
    params: Params,
    cache_c: jnp.ndarray,  # [L, B, 1, S, R] latents (engine "k")
    cache_r: jnp.ndarray,  # [L, B, 1, S, dr] rope keys (engine "v"; int8: P abreast)
    tokens: jnp.ndarray,  # [Ba] int32
    lengths: jnp.ndarray,  # [Ba] int32 — write position per row
    slot_ids: jnp.ndarray | None = None,  # [Ba] compaction indirection
    attn_impl: str = "xla",
    paged: dict | None = None,  # {"tbl","k","v"} physical paging operand
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One absorbed-attention decode step for all slots.

    Attention runs IN LATENT SPACE: q̃[h] = q_nope[h] @ W_uk[:, h] gives
    per-head queries against the shared latents; the value side re-expands
    only the attended [H, R] context. The caches follow the llama xla-path
    structure (scan carry, in-place scatter at `lengths`, OOB rows
    dropped → parked-slot invariant preserved).

    With an int8 latent cache and attn_impl="pallas", attention runs the
    s8-MXU kernel (kernels/attention.py:decode_attend_q8_mla) against the
    PRE-append cache (the kernel overrides position w with the exact
    vectors), and the appends defer to ONE batched scatter per cache after
    the layer scan — instead of L per-layer scatters, each of which XLA
    turns into a full-cache copy."""
    H, dn, dr, dv = _dims(cfg)
    pair_r, cache_r = cache_r, _rows(cache_r)
    quantized = isinstance(cache_c, dict)
    L, B, _, S, R = (cache_c["q"] if quantized else cache_c).shape
    Ba = tokens.shape[0]
    banks, stack = expert_stack(cfg, params["layers"])
    k_dense = _n_dense(params)
    # in the share form a parked or padding row routes nothing and counts nothing
    live = lengths < S if banks is not None else None
    scale = mla_scale(cfg)
    h = _embed_in(cfg, params, tokens)  # [Ba, D]
    cos, sin = rope_tables(cfg, dr, lengths)  # [Ba, dr/2]

    rows = jnp.arange(B, dtype=jnp.int32) if slot_ids is None else slot_ids
    b_idx = rows[:, None]  # [Ba, 1] scatter rows
    w_idx = lengths[:, None]  # [Ba, 1] — broadcast to [Ba, 1(head)]
    key_pos = jnp.arange(S)[None, :]
    attn_mask = key_pos <= lengths[:, None]  # [Ba, S]
    neg = jnp.float32(-1e30)

    def rowsel(x):
        return x if slot_ids is None else jnp.take(x, slot_ids, axis=0)

    ptbl = None if paged is None else jnp.take(paged["tbl"], rows, axis=0)

    def layer(carry, lp):
        h, cc_all, cr_all, li = carry
        x = _norm(cfg, h, lp["attn_norm"])
        qn, qr = _queries(cfg, lp, x)  # [Ba, H, dn/dr]
        qr = apply_rope(qr, cos, sin)
        c, kr = _latents(cfg, lp, x)  # [Ba, R], [Ba, dr]
        kr = apply_rope(kr[:, None], cos, sin)[:, 0]
        # scatter this step's latent/rope-key at (layer, row, 0, position) —
        # in place on the scan-carried donated buffers (the llama xla-path
        # pattern: per-layer one-token scatters, never a full-cache copy);
        # OOB (parked) rows dropped
        zero = jnp.zeros_like(b_idx)
        if quantized:
            cq, krq = quantize_kv(c), quantize_kv(kr)
            cc_all = {
                "q": cc_all["q"].at[li, b_idx, zero, w_idx].set(cq["q"][:, None]),
                "s": cc_all["s"].at[li, b_idx, zero, w_idx].set(
                    cq["s"][:, None].astype(cc_all["s"].dtype)
                ),
            }
            cr_all = {
                "q": rope_append(cr_all["q"], krq["q"], li, rows, lengths),
                "s": cr_all["s"].at[li, b_idx, zero, w_idx].set(
                    krq["s"][:, None].astype(cr_all["s"].dtype)
                ),
            }
        else:
            cc_all = cc_all.at[li, b_idx, zero, w_idx].set(
                c[:, None].astype(cc_all.dtype)
            )
            cr_all = cr_all.at[li, b_idx, zero, w_idx].set(
                kr[:, None].astype(cr_all.dtype)
            )
        # absorbed queries: q̃[h] = q_nope[h] @ W_uk[:, h]  → [Ba, H, R]
        with jax.named_scope("mla.absorb"):
            w_uk, w_uv = _absorbed_w(lp, h.dtype, R, H, dn, dv)
            qt = jnp.einsum("bhd,rhd->bhr", qn, w_uk)

        def sel(x, pool=None):
            xl = jax.lax.dynamic_index_in_dim(x, li, 0, keepdims=False)
            if ptbl is None:
                return rowsel(xl[:, 0])
            pp = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)
            return paged_gather(xl, pp, ptbl)[:, 0]

        pk = None if paged is None else paged["k"]
        pv = None if paged is None else paged["v"]
        if quantized:
            lat = sel(cc_all["q"], pk and pk["q"])  # [Ba, S, R] int8 payload
            # the rope keys' bytes pulled apart, [Ba, S, dr] int8 (this path is
            # the one no chip runs: the kernels multiply the rows as they lie)
            Pr = S // cr_all["q"].shape[3]
            rop_l = jax.lax.dynamic_index_in_dim(cr_all["q"], li, 0, keepdims=False)
            rop = rope_rows(rop_l, Pr, slot_ids) if ptbl is None else rope_rows(
                rop_l, Pr, tables=ptbl, pool=jax.lax.dynamic_index_in_dim(pv["q"], li, 0, keepdims=False))
            ls = sel(cc_all["s"], pk and pk["s"]).astype(jnp.float32)  # [Ba, S]
            rs = sel(cr_all["s"], pv and pv["s"]).astype(jnp.float32)
            # per-token dequant scales fold POST-DOT (the GQA int8 cache's
            # trick): each dot's scores multiply by its own scale row, and
            # the value-side scale folds into the probs before the PV dot
            s_nope = jnp.einsum("bhr,bsr->bhs", qt, lat.astype(qt.dtype)).astype(
                jnp.float32
            ) * ls[:, None, :]
            s_rope = jnp.einsum("bhd,bsd->bhs", qr, rop.astype(qr.dtype)).astype(
                jnp.float32
            ) * rs[:, None, :]
            scores = (s_nope + s_rope) * scale
            scores = jnp.where(attn_mask[:, None, :], scores, neg)
            probs = jax.nn.softmax(scores, axis=-1)
            pl = (probs * ls[:, None, :]).astype(h.dtype)
            ctx_lat = jnp.einsum("bhs,bsr->bhr", pl, lat.astype(h.dtype))
        else:
            lat = sel(cc_all, pk)  # [Ba, S, R]
            rop = sel(cr_all, pv)  # [Ba, S, dr]
            scores = (
                jnp.einsum("bhr,bsr->bhs", qt, lat.astype(qt.dtype))
                + jnp.einsum("bhd,bsd->bhs", qr, rop.astype(qr.dtype))
            ).astype(jnp.float32) * scale
            scores = jnp.where(attn_mask[:, None, :], scores, neg)
            probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
            ctx_lat = jnp.einsum("bhs,bsr->bhr", probs, lat.astype(probs.dtype))
        with jax.named_scope("mla.absorb"):
            ctx = jnp.einsum("bhr,rhd->bhd", ctx_lat, w_uv).reshape(Ba, H * dv)
        with jax.named_scope("mla.out"):
            h = h + qdot(ctx, lp["wo_mla"])
        h, counts = _ffn(cfg, lp, banks, li - k_dense, h, valid=live, capacity=Ba)  # dropless at decode
        return (h, cc_all, cr_all, li + 1), counts

    if quantized and attn_impl == "pallas":
        from ..kernels.attention import decode_attend_q8_mla

        def layer_k(carry, lp):
            h, li = carry
            x = _norm(cfg, h, lp["attn_norm"])
            qn, qr = _queries(cfg, lp, x)
            qr = apply_rope(qr, cos, sin)
            c, kr = _latents(cfg, lp, x)
            kr = apply_rope(kr[:, None], cos, sin)[:, 0]
            with jax.named_scope("mla.absorb"):
                w_uk, w_uv = _absorbed_w(lp, h.dtype, R, H, dn, dv)
                qt = jnp.einsum("bhd,rhd->bhr", qn, w_uk)
            with jax.named_scope("mla.attend"):
                ctx_lat = decode_attend_q8_mla(
                    qt, qr, c, kr, cache_c, cache_r, li, lengths,
                    slot_ids=slot_ids, scale=scale,
                    block_tables=None if paged is None else paged["tbl"],
                    pool_c=None if paged is None else paged["k"],
                    pool_r=None if paged is None else paged["v"],
                )
            with jax.named_scope("mla.absorb"):
                ctx = jnp.einsum("bhr,rhd->bhd", ctx_lat.astype(h.dtype), w_uv)
            with jax.named_scope("mla.out"):
                h = h + qdot(ctx.reshape(Ba, H * dv), lp["wo_mla"])
            h, counts = _ffn(cfg, lp, banks, li - k_dense, h, valid=live, capacity=Ba)
            return (h, li + 1), (c, kr, counts)

        carry = (h, jnp.int32(0))
        cs_d = krs_d = None
        if "dense_layers" in params:
            carry, (cs_d, krs_d, _) = jax.lax.scan(
                layer_k, carry, params["dense_layers"]
            )
        (h, _), (cs, krs, counts) = jax.lax.scan(layer_k, carry, stack)
        if cs_d is not None:
            cs = jnp.concatenate([cs_d, cs], axis=0)
            krs = jnp.concatenate([krs_d, krs], axis=0)
        # ONE batched append per cache for all layers (OOB/parked rows drop)
        cq, rq = quantize_kv(cs), quantize_kv(krs)
        l_idx = jnp.arange(L)[:, None]
        bb = rows[None, :]
        ww = lengths[None, :]
        cache_c = {
            "q": cache_c["q"].at[l_idx, bb, 0, ww].set(cq["q"]),
            "s": cache_c["s"].at[l_idx, bb, 0, ww].set(
                cq["s"].astype(cache_c["s"].dtype)
            ),
        }
        cache_r = {
            "q": rope_append(cache_r["q"], rq["q"], l_idx, bb, ww),
            "s": cache_r["s"].at[l_idx, bb, 0, ww].set(
                rq["s"].astype(cache_r["s"].dtype)
            ),
        }
        return _logits(cfg, params, h), cache_c, _second(pair_r, cache_r, 0, counts)

    carry = (h, cache_c, cache_r, jnp.int32(0))
    if "dense_layers" in params:
        # dense prologue first — the carried layer index li keeps the cache
        # rows aligned with absolute layer position
        carry, _ = jax.lax.scan(layer, carry, params["dense_layers"])
    (h, cache_c, cache_r, _), counts = jax.lax.scan(layer, carry, stack)
    return _logits(cfg, params, h), cache_c, _second(pair_r, cache_r, 0, counts)


def init_mtp_params(cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """ONE multi-token-prediction module's own weights, seeded (DeepSeek-V3's
    form): `hnorm`, `enorm` [D] and `eh_proj` [2 D, D] that join the main
    model's hidden state with the next token's embedding, one latent-attention
    expert layer (`layers`: the main expert stack's leaves, stacked [1, ...])
    and `final_norm` [D], the main final norm's twin. The embedding and the
    head are the main model's, shared. Built where a caller asks, never by the
    engine's boot: no step program runs the module (models/hybrid.py has the
    GQA families' twin)."""
    import dataclasses

    assert cfg.mtp_layers and share_form(cfg), cfg.name
    D = cfg.dim

    def build(key):
        ks = jax.random.split(key, 2)
        one = dataclasses.replace(cfg, n_layers=1, first_dense_layers=0)
        return {"hnorm": jnp.ones((D,), dtype), "enorm": jnp.ones((D,), dtype),
                "eh_proj": (jax.random.normal(ks[0], (2 * D, D), jnp.float32)
                            * (2 * D) ** -0.5).astype(dtype),
                "layers": _init(one, ks[1], dtype)["layers"],
                "final_norm": jnp.ones((D,), dtype)}

    return jax.jit(build)(key)


def mtp_logits(cfg, params, mtp, h, next_tokens, lengths):
    """The module's forward over whole sequences: `h` [B, S, D] the main
    model's residual stream after its last layer (`mla_prefill(hidden=True)`),
    `next_tokens` [B, S] the token AFTER each position; logits [B, S, V] for the
    token after that. h' = eh_proj [norm(h) ; norm(embed(next))], one
    latent-attention expert layer as `mla_prefill` runs it, the module's final
    norm and the main model's head."""
    S = next_tokens.shape[1]
    cos, sin = rope_tables(cfg, cfg.qk_rope_head_dim, jnp.arange(S, dtype=jnp.int32)[None, :])
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
    x = qdot(jnp.concatenate([
        _norm(cfg, h, mtp["hnorm"]), _norm(cfg, _embed_in(cfg, params, next_tokens), mtp["enorm"]),
    ], axis=-1), mtp["eh_proj"])
    banks, stack = expert_stack(cfg, mtp["layers"])
    lp = jax.tree.map(lambda a: a[0], stack)
    x, _, _ = _prefill_attn(cfg, lp, x, cos, sin, valid)
    x, _ = _ffn(cfg, lp, banks, 0, x, valid=valid)
    return _logits(cfg, {**params, "final_norm": mtp["final_norm"]}, x)
