"""A decoder whose layers are of unlike kinds: softmax GQA layers among
recurrent layers of ONE kind (`cfg.recurrent_kind`): gated delta-rule
linear-attention layers ("kda", models/kda.py: KDA or Gated DeltaNet, by
`cfg.lin_gates`) or Mamba-2 state-space layers ("ssm", models/ssm.py); every
layer with a feed-forward that is either routed experts, of which this process
may hold a share (models/moe.py), or, without experts (`cfg.n_experts` 0), the
dense family's gated MLP.

The layer stack is one PERIOD of kinds repeated (`cfg.layer_period`, e.g.
gqa, kda, kda, kda, or kda, kda, kda, gqa, or five ssm, gqa, four ssm), so the
program scans over periods and unrolls one period inside the scan body: one
period's XLA program compiled once, whatever the depth. The parameter tree:

    params["embed"], ["final_norm"], ["lm_head"]
    params["layers"]: what EVERY layer has, stacked [L, ...]: attn_norm,
        ffn_norm, and either router [D, Er], router_bias [Er] (sigmoid
        routers), w1e, w3e [E, D, F], w2e [E, F, D] (the E experts held here),
        w1s, w3s, w2s, or the dense w1, w3 [D, F], w2 [F, D]
    params["gqa"]: the GQA layers', stacked [Lg, ...]: wq, wk, wv, wo, wg
        [D, H hd] with cfg.attn_gate, q_norm and k_norm with cfg.qk_norm
    params["kda"] or params["ssm"]: the recurrent layers', stacked [Lk, ...]
        (models/kda.py, models/ssm.py)

The one norm of a sub-layer sits on its input or on its output
(`cfg.norm_placement`, `llama._sub_in`): the weights are the same leaves.

What a sequence owns, beside the rows of the KV cache that its GQA layers
write (cache layers 0..Lg-1, the dense family's layout and kernels), is the
recurrent layers' state. The engine threads both through every step
program as the cache pair (cache_k, cache_v): `cache_v` is
{"v": the KV cache's second member, "state": {"S", "conv"}} and, with routed
experts only, "moe": counts; built by `init_hybrid_cache`. "moe" [2, L, 5]
int32 is the expert layer's own member, beside the state and not of it: the
sums of its counts (moe.moe_share_ffn) over every call the process has made,
decode steps under [0] and prefills under [1]; the engine reads it back with
each decode round (executor/memory.py: ExpertCounts). A dense feed-forward
counts nothing and the member is absent.

No rope anywhere when cfg.use_rope is False; the GQA layers then attend by
content and causality alone."""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.attention import (
    append_kv_bf16,
    append_kv_q8,
    decode_attend_bf16,
    decode_attend_q8,
)
from . import kda, ssm
from .configs import ModelConfig
from .moe import init_moe_layer_params, moe_share_ffn

Params = dict[str, Any]

# A recurrent layer kind's functions, under one set of names: its key in the
# parameter tree is the kind's name. `scope` names the decode layer's
# `jax.named_scope` (the chunk form opens `<scope>_prefill` itself). `prefill`
# and `decode` are the whole layer for rows of one sort; a mixed step, whose rows
# are of both, composes it from the parts they are made of (models/kda.py says
# which part is a product over rows and which is a row's own).
_RECURRENT = {
    "kda": SimpleNamespace(
        init_params=kda.init_kda_params, init_state=kda.init_kda_state,
        prefill=kda.kda_prefill, decode=kda.kda_decode, zero_state=kda.zero_state,
        pool_rows=kda.pool_rows, head_major=kda.head_major,
        project=kda.project, operands=kda.operands, step_rows=kda.step_rows,
        scan_packed=kda.scan_packed, output=kda.output,
        taps=lambda cfg: cfg.lin_conv, scope=lambda cfg: cfg.lin_gates),  # "kda" | "gdn"
    "ssm": SimpleNamespace(
        init_params=ssm.init_ssm_params, init_state=ssm.init_ssm_state,
        prefill=ssm.ssm_prefill, decode=ssm.ssm_decode, zero_state=ssm.zero_state,
        pool_rows=ssm.pool_rows, head_major=ssm.head_major,
        project=ssm.project, operands=ssm.operands, step_rows=ssm.step_rows,
        scan_packed=ssm.scan_packed, output=ssm.output,
        taps=lambda cfg: cfg.ssm_conv, scope=lambda cfg: "ssd"),
}


def _rec(cfg: ModelConfig) -> SimpleNamespace:
    return _RECURRENT[cfg.recurrent_kind]


def _layout(cfg: ModelConfig) -> tuple[tuple[str, ...], int, int, int]:
    """(period, periods, GQA layers a period, recurrent layers a period)."""
    period = cfg.layer_period
    ng = period.count("gqa")
    return period, cfg.n_layers // len(period), ng, len(period) - ng


def init_hybrid_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Seeded random weights, as ONE jitted program (an eager draw a tensor is
    a compile a tensor on the chip). The router's selection bias is normal
    with deviation 0.01: enough to move a choice between experts whose scores
    lie close, far too little to override the scores. It is the same for every
    row, so a larger one sends every row to the same few experts: at 0.1
    (against scores that spread by 0.2) the 40 held got 0.72 of their share of
    the pairs and the fullest 9.7 times the mean (v5e, PR 32's first run)."""
    from .llama import qk_norm_widths

    _, P, ng, nk = _layout(cfg)
    hd, D, H, Hkv, V = cfg.resolved_head_dim, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.vocab_size
    L, Lg, Lk, F = cfg.n_layers, P * ng, P * nk, cfg.ffn_hidden

    def build(key):
        ks = jax.random.split(key, 14)

        def w(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

        layers = {"attn_norm": jnp.ones((L, D), dtype), "ffn_norm": jnp.ones((L, D), dtype)}
        if cfg.n_experts:
            layers.update(init_moe_layer_params(cfg, ks[0], dtype))
        else:
            layers.update(w1=w(ks[0], (L, D, F), D), w3=w(ks[10], (L, D, F), D),
                          w2=w(ks[11], (L, F, D), F))
        if cfg.n_experts and cfg.router_score == "sigmoid":
            layers["router_bias"] = 0.01 * jax.random.normal(
                ks[1], (L, cfg.router_width), jnp.float32)
        gqa = {
            "wq": w(ks[2], (Lg, D, H * hd), D),
            "wk": w(ks[3], (Lg, D, Hkv * hd), D),
            "wv": w(ks[4], (Lg, D, Hkv * hd), D),
            "wo": w(ks[5], (Lg, H * hd, D), H * hd),
        }
        if cfg.attn_gate:
            gqa["wg"] = w(ks[6], (Lg, D, H * hd), D)
        if cfg.qk_norm:
            nq, nk_ = qk_norm_widths(cfg)
            gqa["q_norm"], gqa["k_norm"] = jnp.ones((Lg, nq), dtype), jnp.ones((Lg, nk_), dtype)
        params = {
            # a table that is multiplied on the way in (Granite's 12) is drawn that
            # much smaller: the stream then starts at the scale it has in every
            # other configuration, and a TIED head does not read the input token
            # back as every row's largest logit, whatever the layers compute
            "embed": w(ks[7], (V, D), D * cfg.embed_multiplier**2),
            "layers": layers,
            "gqa": gqa,
            cfg.recurrent_kind: _rec(cfg).init_params(cfg, ks[8], dtype, Lk),
            "final_norm": jnp.ones((D,), dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = w(ks[9], (D, V), D)
        return params

    return jax.jit(build)(key)


def init_hybrid_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype, quantized: bool) -> dict:
    """{"k", "v"} as models/llama.py:init_kv_cache gives them for the GQA
    layers alone, the second member wrapped with the recurrent state."""
    from .llama import init_kv_cache

    _, P, _, nk = _layout(cfg)
    kv = init_kv_cache(
        _gqa_view(cfg), batch, max_seq, dtype=dtype, quantized=quantized)
    cache_v = {"v": kv["v"], "state": _rec(cfg).init_state(cfg, P * nk, batch, dtype)}
    if cfg.n_experts:
        cache_v["moe"] = jnp.zeros((2, cfg.n_layers, 5), jnp.int32)
    return {"k": kv["k"], "v": cache_v}


def _gqa_view(cfg: ModelConfig) -> ModelConfig:
    """The config as the dense family's cache code reads it: only the GQA
    layers own cache rows."""
    import dataclasses

    return dataclasses.replace(cfg, n_layers=cfg.n_attn_layers, gqa_layers=())


BANKS = ("w1e", "w3e", "w2e")  # never sliced by layer: moe.moe_share_ffn says why


def _at(tree, i):
    """Layer `i` (traced) of a stacked tree. Always of the WHOLE stack, by the
    layer's own index: a period's slice [n, ...] taken first and indexed after
    is copied out every period (1.4 GiB of temporaries a decode round at
    Olmo-Hybrid's widths, seen in the described-chip compile)."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _ffn(cfg: ModelConfig, lp: Params, banks: Params, li, h: jnp.ndarray, valid, prompt=None):
    """Feed-forward of layer `li` and residual add on [..., D]: (h, counts [5]
    of the expert layer, None for the dense gated MLP); with `prompt` [N] (a
    mixed step's rows: which are a prompt's) the counts of each phase, [2, 5]."""
    from .llama import _ffn_residual, _residual, _sub_in, _sub_out

    if not cfg.n_experts:
        return _ffn_residual(cfg, lp, h), None
    with jax.named_scope("ffn"):
        x = _sub_in(cfg, h, lp["ffn_norm"])
        y, counts = moe_share_ffn(
            cfg, lp, x.reshape(-1, x.shape[-1]),
            valid=None if valid is None else valid.reshape(-1), banks=banks, layer=li,
            prompt=prompt)
        return _residual(cfg, h, _sub_out(cfg, y.reshape(h.shape), lp["ffn_norm"])), counts


def _counted(cache_v: dict, phase: int, counts) -> dict:
    """The expert counts of one call onto the running sums the cache pair
    carries (decode steps under 0, prefills under 1); nothing without them."""
    return {} if counts is None else {"moe": cache_v["moe"].at[phase].add(counts)}


def _period_scan(cfg: ModelConfig, params: Params, h, carry, gqa_layer, rec_layer, valid,
                 prompt=None):
    """Scan the periods. `gqa_layer(h, carry, lp, ig)` and `rec_layer(h,
    carry, lp, ik)` run one layer's mixing half on the running `carry` (the
    caches, as the caller shapes it), `ig` / `ik` being the layer's index
    among its kind; the feed-forward follows either. Returns (h, carry,
    counts [L, 5] of the expert layers ([L, 2, 5] with `prompt`: `_ffn`) or
    None), and whatever the GQA layers stacked as ys, [P ng, ...]."""
    period, P, ng, nk = _layout(cfg)
    rec_params = params[cfg.recurrent_kind]
    banks = {k: params["layers"][k] for k in BANKS if k in params["layers"]}
    layers = {k: v for k, v in params["layers"].items() if k not in BANKS}

    def body(c, _):
        h, carry, p = c
        ig = ik = 0
        ys, counts = [], []
        for i, kind in enumerate(period):
            li = p * len(period) + i
            lp = _at(layers, li)
            if kind == "gqa":
                h, carry, y = gqa_layer(h, carry, {**lp, **_at(params["gqa"], p * ng + ig)}, p * ng + ig)
                ys.append(y)
                ig += 1
            else:
                h, carry = rec_layer(h, carry, {**lp, **_at(rec_params, p * nk + ik)}, p * nk + ik)
                ik += 1
            h, n = _ffn(cfg, lp, banks, li, h, valid, prompt)
            counts.append(n)
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys) if ys and ys[0] is not None else None
        return (h, carry, p + 1), (ys, jnp.stack(counts) if cfg.n_experts else None)

    (h, carry, _), (ys, counts) = jax.lax.scan(
        body, (h, carry, jnp.int32(0)), None, length=P)
    if ys is not None:
        ys = jax.tree.map(lambda a: a.reshape(P * ng, *a.shape[2:]), ys)
    return h, carry, None if counts is None else counts.reshape(
        cfg.n_layers, *counts.shape[2:]), ys


def hybrid_prefill(cfg, params, tokens, lengths, attn_impl="xla", quant_kv=False):
    """Whole fresh prompts [B, S] from zero state: (last logits [B, V], ks,
    vs) with ks the GQA layers' prompt K/V as `llama_prefill` returns them and
    vs = {"v": their second member, "state": each row's S [Lk, B, ...] (in
    the pool's layout) and conv tails, and with routed experts "moe": the
    call's expert counts [L, 5]}; the engine inserts row by row
    (`insert_state_row`) and adds the counts once (`add_counts`)."""
    from .llama import (
        _embed_in, _logits, _residual, _sub_in, _sub_out, fuse_prompt_kv, prefill_attn,
        prefill_masks)

    B, S = tokens.shape
    _, P, _, nk = _layout(cfg)
    rec = _rec(cfg)
    h = _embed_in(cfg, params, tokens)
    cos, sin, mask = prefill_masks(cfg, S, lengths)
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
    S0, tail0 = rec.zero_state(cfg, B, h.dtype)

    def gqa_layer(h, carry, lp, ig):
        h, (kh, vh) = prefill_attn(cfg, lp, h, cos, sin, mask, lengths, attn_impl)
        return h, carry, ((fuse_prompt_kv(kh, vh), {}) if quant_kv else (kh, vh))

    def rec_layer(h, carry, lp, ik):
        Ss, tails = carry
        y, S_new, tail = rec.prefill(
            cfg, lp, _sub_in(cfg, h, lp["attn_norm"]), lengths, S0, tail0)
        return _residual(cfg, h, _sub_out(cfg, y, lp["attn_norm"])), (
            Ss.at[ik].set(rec.pool_rows(cfg, S_new)), tails.at[ik].set(tail.reshape(B, -1)))

    carry = (jnp.zeros((P * nk, *rec.pool_rows(cfg, S0).shape), jnp.float32),
             jnp.zeros((P * nk, B, tail0[0].size), tail0.dtype))
    h, (Ss, tails), counts, (ks, vs) = _period_scan(
        cfg, params, h, carry, gqa_layer, rec_layer, valid)
    last = jnp.take_along_axis(h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return _logits(cfg, params, last), ks, {
        "v": vs, "state": {"S": Ss, "conv": tails},
        **({} if counts is None else {"moe": counts})}


def insert_state_row(state: dict, new: dict, i, slot) -> dict:
    """Row `i` of a prefill's state into pool row `slot` (both traced)."""
    def put(pool, rows):
        row = jax.lax.dynamic_slice_in_dim(rows, i, 1, 1)
        return jax.lax.dynamic_update_slice(
            pool, row.astype(pool.dtype), (0, slot) + (0,) * (pool.ndim - 2))

    return {"S": put(state["S"], new["S"]), "conv": put(state["conv"], new["conv"])}


def add_counts(cache_v: dict, new: dict) -> dict:
    """A prefill's expert counts [L, 5] (the call's, not a row's) onto the
    running sums the cache pair carries."""
    return dict(cache_v, **_counted(cache_v, 1, new.get("moe")))


def hybrid_prefill_chunk_batch(
    cfg, params, cache_k, cache_v, tokens, slots, starts, nvalid,
    skey=0, all_logits=False, paged=None,
):
    """`llama_prefill_chunk_batch` for the hybrid stack: the GQA layers read
    and write the KV cache as there; a recurrent layer continues each slot's state
    and convolution tail from the pool, from ZERO where the chunk is a
    prompt's first (start 0: a reused slot's old state is never read), and
    writes both back. Rows that duplicate row 0 (the engine's padding) write
    what row 0 writes."""
    from .llama import _chunk_attention, _logits, _residual, _sub_in, _sub_out

    A, C = tokens.shape
    rec = _rec(cfg)
    kv_v, state = cache_v["v"], cache_v["state"]
    h, attend, write = _chunk_attention(
        cfg, params, cache_k, tokens, slots, starts, nvalid, skey=skey, paged=paged)
    slots = jnp.asarray(slots, jnp.int32)
    fresh = jnp.asarray(starts, jnp.int32) == 0
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < nvalid[:, None]

    def gqa_layer(h, carry, lp, ig):
        ck, cv, Ss, tails = carry
        h, kh, vh = attend(h, ck, cv, ig, lp, 0)
        ck, cv = write(ck, cv, kh, vh, ig)
        return h, (ck, cv, Ss, tails), None

    # Each row's state and tail of EVERY recurrent layer come out of the pool
    # before the layer scan and go back after it, row by row (a gather of rows of
    # 384 lanes made the compiler copy the whole pool in three slabs of 128: 2 GiB
    # at Olmo-Hybrid's size). The pool itself stays out of the scan: carried
    # through it, a pool of square [128, 128] tiles was re-laid out whole on the
    # way in and out (two copies of 4.5 GiB at Granite-4.0-H's size, seen in the
    # described-chip compile), the layout being the chunk form's to choose where
    # no kernel holds it.
    def rows_of(pool):  # [Lk, A, ...]
        return jnp.concatenate([jax.lax.dynamic_slice(
            pool, (0, slots[a]) + (0,) * (pool.ndim - 2), (pool.shape[0], 1, *pool.shape[2:]))
            for a in range(A)], axis=1)

    def rec_layer(h, carry, lp, ik):
        ck, cv, Ss, tails = carry
        S0 = jnp.where(fresh[:, None, None, None], 0.0, rec.head_major(cfg, Ss[ik]))
        tail0 = jnp.where(fresh[:, None, None], 0, tails[ik].reshape(A, rec.taps(cfg) - 1, -1))
        y, S_new, tail = rec.prefill(
            cfg, lp, _sub_in(cfg, h, lp["attn_norm"]), nvalid, S0, tail0)
        return _residual(cfg, h, _sub_out(cfg, y, lp["attn_norm"])), (
            ck, cv, Ss.at[ik].set(rec.pool_rows(cfg, S_new)), tails.at[ik].set(tail.reshape(A, -1)))

    h, (ck, cv, Ss, tails), counts, _ = _period_scan(
        cfg, params, h, (cache_k, kv_v, rows_of(state["S"]), rows_of(state["conv"])),
        gqa_layer, rec_layer, valid)
    S, conv = state["S"], state["conv"]
    for a in range(A):  # row by row: duplicates of row 0 land on row 0's values
        S = jax.lax.dynamic_update_slice(S, Ss[:, a : a + 1], (0, slots[a], 0, 0, 0))
        conv = jax.lax.dynamic_update_slice(conv, tails[:, a : a + 1], (0, slots[a], 0))
    new_v = {"v": cv, "state": {"S": S, "conv": conv}, **_counted(cache_v, 1, counts)}
    if all_logits:
        return _logits(cfg, params, h), ck, new_v
    last = jnp.take_along_axis(h, (nvalid - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return _logits(cfg, params, last), ck, new_v


def hybrid_decode_step(cfg, params, cache_k, cache_v, tokens, lengths, slot_ids=None, paged=None):
    """One token a row. The GQA layers take the dense family's decode
    structure: the KV cache is a scan-invariant operand read by the decode
    attention kernel, the step's K/V stack out of the scan and one append
    kernel lands them. A recurrent layer steps its rows of the state pool in
    place (kernels/kda.py). A parked or padding row (length >= the cache's) moves
    nothing: not its cache rows, not its state."""
    from .llama import (
        _attn_residual, _cache_shape, _embed_in, _logits, _qkv, _residual, _sub_in, _sub_out)

    if paged is not None:
        raise NotImplementedError("a recurrent configuration's blocks are never shared")
    quantized = isinstance(cache_k, dict)
    kv_v, state = cache_v["v"], cache_v["state"]
    S_cache, hd = _cache_shape(cache_k)[3], cfg.resolved_head_dim
    Ba, H, Hkv = tokens.shape[0], cfg.n_heads, cfg.n_kv_heads
    rows = None if slot_ids is None else slot_ids.astype(jnp.int32)
    live = lengths < S_cache
    attend = decode_attend_q8 if quantized else decode_attend_bf16
    rec = _rec(cfg)
    h = _embed_in(cfg, params, tokens)

    def gqa_layer(h, carry, lp, ig):
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            k, v = k.reshape(Ba, Hkv, hd), v.reshape(Ba, Hkv, hd)
            ctx = attend(
                q.reshape(Ba, Hkv, H // Hkv, hd), k, v, cache_k, kv_v, ig, lengths,
                slot_ids=slot_ids, scale=cfg.attn_scale,
            ).reshape(Ba, H * hd)
            return _attn_residual(cfg, lp, ctx, h, x), carry, (k, v)

    def rec_layer(h, carry, lp, ik):
        with jax.named_scope(rec.scope(cfg)):  # "kda" | "gdn" | "ssd"
            y, carry = rec.decode(
                cfg, lp, _sub_in(cfg, h, lp["attn_norm"]), carry, ik, rows, live)
            return _residual(cfg, h, _sub_out(cfg, y, lp["attn_norm"])), carry

    h, lin, counts, (knew, vnew) = _period_scan(
        cfg, params, h, state, gqa_layer, rec_layer, live)
    with jax.named_scope("kv_append"):
        append = append_kv_q8 if quantized else append_kv_bf16
        new_k, new_kv_v = append(cache_k, kv_v, knew, vnew, lengths, slot_ids=slot_ids)
    return _logits(cfg, params, h), new_k, {
        "v": new_kv_v, "state": lin, **_counted(cache_v, 0, counts)}


def hybrid_mixed_step(
    cfg, params, cache_k, cache_v, tokens, lengths, p_tokens, p_rowids, p_positions,
    p_slots, p_last_idx, paged=None,
):
    """`hybrid_decode_step` for the full batch with admitted prompts riding the
    same pass over the weights: `llama.mixed_step_q8` (whose operands and
    returns these are) for a stack with recurrent layers. Every product that is
    rows of a matmul runs ONCE over the B decode rows and the T prompt tokens
    stacked: a recurrent layer's `project` and `output` (and, row by row,
    `operands`), a GQA layer's `_qkv`, gate and output projection, the
    feed-forward. Between them each sort of row takes its own part: the decode
    rows the convolution step and the state kernel on the pool, or the decode
    attention through the cache, as `hybrid_decode_step` has them; the prompt
    tokens the causal convolution and the chunked recurrence from ZERO state (a
    riding prompt is always fresh), or causal attention over their own K/V, in
    the precision `hybrid_prefill` computes them in.

    The prompts stay packed in ONE row of T positions, and the recurrence runs
    over the chunks of it that hold tokens and no more (a rung is an
    executable's size, not its work). Each prompt starts at a multiple of
    `kda.CHUNK` (the engine stages them so: `_stage_ride`; the positions
    between are padding, row id R), so a prompt's first chunk is the scan's
    chunk whose first position is 0: there the state is zeroed, and a prompt's
    own state is the scan's after its last chunk. Padding inside a prompt's
    last chunk leaves the state alone.

    After the scan the decode rows' K/V is appended, the prompts' K/V lands in
    their slots, and each prompt's state and convolution tail land in its row
    of the pool, row by row, outside the scan (`hybrid_prefill_chunk_batch`
    says why). The prompts' slots are parked rows of the decode batch: the
    append, the state kernel and the convolution step move nothing there."""
    from .llama import (
        _attn_residual, _cache_shape, _embed_in, _logits, _qkv, _residual, _sub_in, _sub_out,
        fuse_prompt_kv, packed_prompt_attn, write_prompt_rows)

    if paged is not None:
        raise NotImplementedError("a recurrent configuration's blocks are never shared")
    kv_v, state = cache_v["v"], cache_v["state"]
    S_cache, hd = _cache_shape(cache_k)[3], cfg.resolved_head_dim
    B, T, R = tokens.shape[0], p_tokens.shape[0], p_slots.shape[0]
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    _, P, _, nk = _layout(cfg)
    rec = _rec(cfg)
    assert T % kda.CHUNK == 0, (T, kda.CHUNK)
    p_rowids = jnp.asarray(p_rowids, jnp.int32)
    live = lengths < S_cache
    token = p_rowids < R  # [T]: a prompt's token, not padding
    prompt = jnp.arange(B + T) >= B  # [N]: which rows are the prompts'
    fresh = p_positions.reshape(-1, kda.CHUNK)[:, 0] == 0  # [T / C]: a prompt's first chunk
    # the chunks up to the last staged token's: the scan's trip count
    staged = jnp.max(jnp.where(token, jnp.arange(T) // kda.CHUNK + 1, 0))
    ends = jnp.clip(p_last_idx, 0, T - 1)
    h = _embed_in(cfg, params, jnp.concatenate([tokens, p_tokens]))  # [N, D]

    def gqa_layer(h, carry, lp, ig):
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q = q.reshape(B + T, H, hd)
            k, v = k.reshape(B + T, Hkv, hd), v.reshape(B + T, Hkv, hd)
            ctx_d = decode_attend_q8(
                q[:B].reshape(B, Hkv, H // Hkv, hd), k[:B], v[:B], cache_k, kv_v, ig, lengths,
                scale=cfg.attn_scale).reshape(B, H * hd)
            ctx_p = packed_prompt_attn(cfg, q[B:], k[B:], v[B:], p_rowids)
            h = _attn_residual(cfg, lp, jnp.concatenate([ctx_d, ctx_p]), h, x)
        with jax.named_scope("kv_append"):
            fused = fuse_prompt_kv(
                k[B:].transpose(1, 0, 2), v[B:].transpose(1, 0, 2),
                scale_dtype=cache_k["s"].dtype)
        return h, carry, (k[:B], v[:B], fused["q"], fused["s"])

    def rec_layer(h, carry, lp, ik):
        pool, Ss, tails = carry
        with jax.named_scope(rec.scope(cfg)):  # "kda" | "gdn" | "ssd"
            x = _sub_in(cfg, h, lp["attn_norm"])
            conv_in, side = rec.project(cfg, lp, x)  # [N, W]
            mixed_d, conv, rows = kda.conv_step(
                pool["conv"], ik, None, live, conv_in[:B], lp["conv_w"])
            mixed_p, tail = kda.conv_packed(conv_in[B:], p_positions, ends, lp["conv_w"])
            ops, side = rec.operands(cfg, lp, jnp.concatenate([mixed_d, mixed_p]), side)
            o_d, S = rec.step_rows(
                cfg, pool["S"], ik, rows, live, jax.tree.map(lambda a: a[:B], ops))
            o_p, after = rec.scan_packed(
                jax.tree.map(lambda a: a[B:][None], ops), token[None], fresh, staged)
            y = rec.output(cfg, lp, jnp.concatenate([o_d, o_p[0]]), side, x.dtype)
            own = jnp.take(after[:, 0], ends // kda.CHUNK, axis=0)  # [R, H, dk, dv]
            return _residual(cfg, h, _sub_out(cfg, y, lp["attn_norm"])), (
                {"S": S, "conv": conv},
                Ss.at[ik].set(rec.pool_rows(cfg, own).reshape(Ss.shape[1:])),
                tails.at[ik].set(tail.reshape(R, -1).astype(tails.dtype)))

    # the prompts' states ride the scan as [Lk, R, (H / P) dk, P dv]: with the
    # pool's own last two axes, both 128 at Granite-4.0-H's size, the compiler
    # carried them transposed and then re-laid the WHOLE pool out to take them
    # (4.5 GiB of temporaries, seen in the described-chip compile)
    G, dk, W = state["S"].shape[2:]
    carry = (state, jnp.zeros((P * nk, R, G * dk, W), jnp.float32),
             jnp.zeros((P * nk, R, state["conv"].shape[2]), state["conv"].dtype))
    h, (pool, Ss, tails), counts, (knew, vnew, pq, ps) = _period_scan(
        cfg, params, h, carry, gqa_layer, rec_layer, jnp.concatenate([live, token]), prompt)
    count = jnp.sum(p_rowids[None, :] == jnp.arange(R, dtype=jnp.int32)[:, None],
                    axis=1, dtype=jnp.int32)  # [R] tokens of each prompt; 0: an unused row
    starts = ends + 1 - count
    with jax.named_scope("kv_append"):
        new_k, new_kv_v = append_kv_q8(cache_k, kv_v, knew, vnew, lengths)
        new_k = {
            "q": write_prompt_rows(new_k["q"], pq, p_slots, starts, count),
            "s": write_prompt_rows(new_k["s"], ps, p_slots, starts, count),
        }
        S, conv = pool["S"], pool["conv"]
        Ss = Ss.reshape(P * nk, R, G, dk, W)
        for r in range(R):  # an unused row writes back what it read
            S = _put_row(S, Ss[:, r : r + 1], p_slots[r], count[r] > 0)
            conv = _put_row(conv, tails[:, r : r + 1], p_slots[r], count[r] > 0)
    new_v = {"v": new_kv_v, "state": {"S": S, "conv": conv}}
    if counts is not None:
        new_v["moe"] = cache_v["moe"] + jnp.moveaxis(counts, 1, 0)
    last = jnp.take(h[B:], ends, axis=0)  # [R, D]
    return _logits(cfg, params, jnp.concatenate([h[:B], last])), new_k, new_v


def _put_row(pool, row, slot, live):
    """`row` [Lk, 1, ...] into row `slot` of the pool, in place; not `live`:
    the pool's own row back."""
    at = (0, slot) + (0,) * (pool.ndim - 2)
    cur = jax.lax.dynamic_slice(pool, at, row.shape)
    return jax.lax.dynamic_update_slice(pool, jnp.where(live, row.astype(pool.dtype), cur), at)
