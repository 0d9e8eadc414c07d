"""A decoder whose layers are of unlike kinds: softmax GQA layers with rows
of the full-length KV cache among layers of ONE other kind
(`cfg.recurrent_kind`) whose per-slot state has a fixed size: gated delta-rule
linear-attention layers ("kda", models/kda.py: KDA or Gated DeltaNet, by
`cfg.lin_gates`), Mamba-2 state-space layers ("ssm", models/ssm.py), gated
short convolutions ("conv", models/shortconv.py), or WINDOW
attention layers ("win": the same attention over the layer's last
`cfg.sliding_window` positions, kept in a ring of `cfg.ring_len` a slot); every
layer with a feed-forward that is either routed experts, of which this process
may hold a share (models/moe.py), or, without experts (`cfg.n_experts` 0), the
dense family's gated MLP. A stack with experts may start with
`cfg.first_dense_layers` layers whose feed-forward is the dense MLP: they are
unrolled before the scan and the periods are those of the layers after them.

The layer stack is one PERIOD of kinds repeated (`cfg.layer_period`, e.g.
gqa, kda, kda, kda, or kda, kda, kda, gqa, or five ssm, gqa, four ssm), so the
program scans over periods and unrolls one period inside the scan body: one
period's XLA program compiled once, whatever the depth. The parameter tree:

    params["embed"], ["final_norm"], ["lm_head"]
    params["layers"]: what EVERY scanned layer has, stacked [L - k, ...]:
        attn_norm, ffn_norm, and either router [D, Er], router_bias [Er]
        (sigmoid routers), w1e, w3e [E, D, F], w2e [E, F, D] (the E experts held
        here), w1s, w3s, w2s, or the dense w1, w3 [D, F], w2 [F, D]
    params["gqa"]: the scanned GQA layers', stacked [Lg, ...]: wq, wk, wv, wo,
        wg [D, H hd] with cfg.attn_gate, q_norm and k_norm with cfg.qk_norm
    params["kda"], params["ssm"] or params["conv"]: the scanned recurrent
        layers', stacked [Lk, ...] (models/kda.py, models/ssm.py,
        models/shortconv.py); params["win"]: the scanned window layers', the
        leaves of params["gqa"]
    params["first"]: the k leading dense layers, a list of whole layers, each
        its own leaves UNSTACKED (attn_norm, ffn_norm, the mixing half's, w1,
        w3, w2): a leaf of a stack taken at a fixed index before the scan is
        copied out every step (0.7 GB a step at K-EXAONE's dense width, seen in
        the described-chip compile)

The one norm of a sub-layer sits on its input or on its output
(`cfg.norm_placement`, `llama._sub_in`): the weights are the same leaves.

What a sequence owns, beside the rows of the KV cache that its GQA layers
write (cache layers 0..Lg-1, the dense family's layout and kernels), is the
other layers' state. The engine threads both through every step
program as the cache pair (cache_k, cache_v): `cache_v` is
{"v": the KV cache's second member, "state": {"S", "conv"}} (a kind whose
only state is its convolution's tail has no "S": `_pool`), or for window
layers "win": their ring, a KV cache pair {"k", "v"} of its own in the KV
cache's form over [Lw, slots, .., R, hd] (int8: {"q": [Lw, slots, 2 Hkv / P + p,
R, P hd], "s": [Lw, slots, 2 Hkv, R]} and {}) with position p at index p mod R
(`SLOT_MEMBERS`), and, with routed
experts only, "moe": counts; built by `init_hybrid_cache`. "moe" [2, Le, 5]
int32 (Le the expert layers) is the expert layer's own member, beside the state
and not of it: the
sums of its counts (moe.moe_share_ffn) over every call the process has made,
decode steps under [0] and prefills under [1]; the engine reads it back with
each decode round (executor/memory.py: ExpertCounts). A dense feed-forward
counts nothing and the member is absent.

No rope anywhere when cfg.use_rope is False; the GQA layers then attend by
content and causality alone. With it, window layers rotate and GQA layers do
where `cfg.global_rope` says so (`_rotates`).

The multi-token-prediction module (`cfg.mtp_layers`) is a tree and a forward of
its own, `init_mtp_params` and `mtp_logits`, built and run where a caller asks:
no step program holds it."""

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
from . import kda, shortconv, ssm
from .configs import ModelConfig
from .moe import BANKS, init_moe_layer_params

Params = dict[str, Any]

# A recurrent layer kind's functions, under one set of names: its key in the
# parameter tree is the kind's name. `scope` names the decode layer's
# `jax.named_scope` (the chunk form opens `<scope>_prefill` itself). `prefill`
# and `decode` are the whole layer for rows of one sort; a mixed step, whose rows
# are of both, composes it from the parts they are made of (models/kda.py says
# which part is a product over rows and which is a row's own). A kind's state is
# a matrix state S and its convolution's tail; a kind without a matrix state
# ("conv") hands None over wherever the others hand S, and its pool has no "S".
_RECURRENT = {
    "kda": SimpleNamespace(
        init_params=kda.init_kda_params, init_state=kda.init_kda_state,
        prefill=kda.kda_prefill, decode=kda.kda_decode, zero_state=kda.zero_state,
        project=kda.project, operands=kda.operands, step_rows=kda.step_rows,
        scan_packed=kda.scan_packed, output=kda.output,
        taps=lambda cfg: cfg.lin_conv, scope=lambda cfg: cfg.lin_gates),  # "kda" | "gdn"
    "ssm": SimpleNamespace(
        init_params=ssm.init_ssm_params, init_state=ssm.init_ssm_state,
        prefill=ssm.ssm_prefill, decode=ssm.ssm_decode, zero_state=ssm.zero_state,
        project=ssm.project, operands=ssm.operands, step_rows=ssm.step_rows,
        scan_packed=ssm.scan_packed, output=ssm.output,
        taps=lambda cfg: cfg.ssm_conv, scope=lambda cfg: "ssd"),
    "conv": SimpleNamespace(
        init_params=shortconv.init_conv_params, init_state=shortconv.init_conv_state,
        prefill=shortconv.conv_prefill, decode=shortconv.conv_decode,
        zero_state=shortconv.zero_state, project=shortconv.project,
        operands=shortconv.operands, step_rows=shortconv.step_rows,
        scan_packed=shortconv.scan_packed, output=shortconv.output,
        taps=lambda cfg: cfg.conv_taps, scope=lambda cfg: "conv"),
}


def _rec(cfg: ModelConfig) -> SimpleNamespace:
    return _RECURRENT[cfg.recurrent_kind]


def _pool(S, conv) -> dict:
    """The recurrent state's tree: a kind without a matrix state has no "S"."""
    return {"conv": conv} if S is None else {"S": S, "conv": conv}


def _layer_set(stack, i, new):
    """`new` as layer `i` of `stack` [Lk, ...]; None (no matrix state) stays None."""
    return None if stack is None else stack.at[i].set(new.reshape(stack.shape[1:]))


# The members of `cache_v` that hold one row a slot: what a whole prompt's
# prefill returns a row of and the engine inserts (`insert_state_row`).
SLOT_MEMBERS = ("state", "win")


def _dense_first(cfg: ModelConfig) -> int:
    """Leading layers whose feed-forward is the dense MLP in a stack with experts."""
    return cfg.first_dense_layers if cfg.n_experts else 0


def _layout(cfg: ModelConfig) -> tuple[tuple[str, ...], int, int, int]:
    """(period, periods, GQA layers a period, other layers a period) of the
    layers after the leading dense ones."""
    period = cfg.layer_period
    ng = period.count("gqa")
    return period, (cfg.n_layers - _dense_first(cfg)) // len(period), ng, len(period) - ng


def _kind(cfg: ModelConfig, i: int) -> str:
    return "gqa" if i in cfg.gqa_layers else cfg.recurrent_kind


def _rotates(cfg: ModelConfig, kind: str) -> bool:
    """Whether a layer of `kind` rotates its queries and keys."""
    return cfg.use_rope and (kind == "win" or cfg.global_rope)


def _attn_params(cfg: ModelConfig, ks, n: int, w, dtype) -> Params:
    """`n` softmax-attention layers' weights, stacked (GQA or window layers)."""
    from .llama import qk_norm_widths

    hd, D, H, Hkv = cfg.resolved_head_dim, cfg.dim, cfg.n_heads, cfg.n_kv_heads
    out = {
        "wq": w(ks[0], (n, D, H * hd), D),
        "wk": w(ks[1], (n, D, Hkv * hd), D),
        "wv": w(ks[2], (n, D, Hkv * hd), D),
        "wo": w(ks[3], (n, H * hd, D), H * hd),
    }
    if cfg.attn_gate:
        out["wg"] = w(ks[4], (n, D, H * hd), D)
    if cfg.qk_norm:
        nq, nk_ = qk_norm_widths(cfg)
        out["q_norm"], out["k_norm"] = jnp.ones((n, nq), dtype), jnp.ones((n, nk_), dtype)
    return out


def init_hybrid_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Seeded random weights, as ONE jitted program (an eager draw a tensor is
    a compile a tensor on the chip). The router's selection bias is normal
    with deviation 0.01: enough to move a choice between experts whose scores
    lie close, far too little to override the scores. It is the same for every
    row, so a larger one sends every row to the same few experts: at 0.1
    (against scores that spread by 0.2) the 40 held got 0.72 of their share of
    the pairs and the fullest 9.7 times the mean (v5e, PR 32's first run)."""
    D, V = cfg.dim, cfg.vocab_size
    F, k = cfg.ffn_hidden, _dense_first(cfg)
    L = cfg.n_layers - k  # the scanned layers; the k leading ones are params["first"]
    Lg = sum(i >= k for i in cfg.gqa_layers)
    Lk = L - Lg

    def build(key):
        ks = jax.random.split(key, 14)

        def w(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

        layers = {"attn_norm": jnp.ones((L, D), dtype), "ffn_norm": jnp.ones((L, D), dtype)}
        if cfg.n_experts:
            layers.update(init_moe_layer_params(cfg, ks[0], dtype, L))
        else:
            layers.update(w1=w(ks[0], (L, D, F), D), w3=w(ks[10], (L, D, F), D),
                          w2=w(ks[11], (L, F, D), F))
        if cfg.n_experts and cfg.router_score == "sigmoid":
            layers["router_bias"] = 0.01 * jax.random.normal(
                ks[1], (L, cfg.router_width), jnp.float32)

        def mixing(kind, key, n):
            if kind == "gqa" or kind == "win":
                return _attn_params(cfg, jax.random.split(key, 5), n, w, dtype)
            return _rec(cfg).init_params(cfg, key, dtype, n)

        params = {
            # a table that is multiplied on the way in (Granite's 12) is drawn that
            # much smaller: the stream then starts at the scale it has in every
            # other configuration, and a TIED head does not read the input token
            # back as every row's largest logit, whatever the layers compute
            "embed": w(ks[7], (V, D), D * cfg.embed_multiplier**2),
            "layers": layers,
            "gqa": _attn_params(cfg, ks[2:7], Lg, w, dtype),
            cfg.recurrent_kind: mixing(cfg.recurrent_kind, ks[8], Lk),
            "final_norm": jnp.ones((D,), dtype),
        }
        if k:
            first = []
            for i in range(k):
                kd = jax.random.split(jax.random.fold_in(ks[12], i), 4)
                one = {"attn_norm": jnp.ones((1, D), dtype), "ffn_norm": jnp.ones((1, D), dtype),
                       **mixing(_kind(cfg, i), kd[0], 1),
                       "w1": w(kd[1], (1, D, F), D), "w3": w(kd[2], (1, D, F), D),
                       "w2": w(kd[3], (1, F, D), F)}
                first.append(jax.tree.map(lambda a: a[0], one))
            params["first"] = first
        if not cfg.tie_embeddings:
            params["lm_head"] = w(ks[9], (D, V), D)
        return params

    return jax.jit(build)(key)


def init_hybrid_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype, quantized: bool) -> dict:
    """{"k", "v"} as models/llama.py:init_kv_cache gives them for the GQA
    layers alone, the second member wrapped with the other layers' state: the
    recurrent state, or the window layers' ring, a KV cache pair of
    `cfg.ring_len` positions in the same form."""
    from .llama import init_kv_cache

    Lk = cfg.n_layers - cfg.n_attn_layers
    kv = init_kv_cache(
        _gqa_view(cfg), batch, max_seq, dtype=dtype, quantized=quantized)
    if cfg.recurrent_kind == "win":
        cache_v = {"v": kv["v"], "win": init_kv_cache(
            _gqa_view(cfg, Lk), batch, cfg.ring_len, dtype=dtype, quantized=quantized)}
    else:
        cache_v = {"v": kv["v"], "state": _rec(cfg).init_state(cfg, Lk, batch, dtype)}
    if cfg.n_experts:
        cache_v["moe"] = jnp.zeros((2, cfg.n_layers - _dense_first(cfg), 5), jnp.int32)
    return {"k": kv["k"], "v": cache_v}


def _gqa_view(cfg: ModelConfig, n_layers: int | None = None) -> ModelConfig:
    """The config as the dense family's cache code reads it: only the GQA
    layers own cache rows (or `n_layers` layers of their shape: the ring)."""
    import dataclasses

    return dataclasses.replace(
        cfg, n_layers=cfg.n_attn_layers if n_layers is None else n_layers, gqa_layers=(),
        sliding_window=0, sliding_windows=(), n_experts=0)  # the counts ride this pair's own member


def _at(tree, i):
    """Layer `i` (traced) of a stacked tree. Always of the WHOLE stack, by the
    layer's own index: a period's slice [n, ...] taken first and indexed after
    is copied out every period (1.4 GiB of temporaries a decode round at
    Olmo-Hybrid's widths, seen in the described-chip compile)."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _ffn(cfg: ModelConfig, lp: Params, banks: Params, li, h: jnp.ndarray, valid, prompt=None):
    """Feed-forward of layer `li` and residual add on [..., D]: (h, counts [5]
    of the expert layer, None for the dense gated MLP); with `prompt` [N] (a
    mixed step's rows: which are a prompt's) the counts of each phase, [2, 5].
    An expert layer here is always the share form (`llama._ffn`)."""
    from .llama import _ffn as ffn

    return ffn(cfg, lp, banks if cfg.n_experts else None, li, h, valid, prompt)


def _counted(cache_v: dict, phase: int, counts) -> dict:
    """The expert counts of one call onto the running sums the cache pair
    carries (decode steps under 0, prefills under 1); nothing without them."""
    return {} if counts is None else {"moe": cache_v["moe"].at[phase].add(counts)}


def _period_scan(cfg: ModelConfig, params: Params, h, carry, gqa_layer, rec_layer, valid,
                 prompt=None):
    """Run the leading dense layers, then scan the periods. `gqa_layer(h,
    carry, lp, ig)` and `rec_layer(h, carry, lp, ik)` run one layer's mixing
    half on the running `carry` (the caches, as the caller shapes it), `ig` /
    `ik` being the layer's index among its kind, and return (h, carry, y); the
    feed-forward follows either. Returns (h, carry, counts [Le, 5] of the Le
    expert layers ([Le, 2, 5] with `prompt`: `_ffn`; a leading dense layer has
    no row) or None), and what the layers gave as y, stacked by kind:
    {"gqa": [Lg, ...] or None, the other kind: [Lk, ...] or None}."""
    from .llama import _ffn_residual

    period, P, ng, nk = _layout(cfg)
    k, rec = _dense_first(cfg), cfg.recurrent_kind
    mix = {"gqa": (gqa_layer, params["gqa"]), rec: (rec_layer, params[rec])}
    banks = {n: params["layers"][n] for n in BANKS if n in params["layers"]}
    layers = {n: v for n, v in params["layers"].items() if n not in BANKS}
    seen = {"gqa": 0, rec: 0}
    first: dict[str, list] = {"gqa": [], rec: []}
    for i, lp in enumerate(params.get("first", ())):  # the leading dense layers, unrolled
        kind = _kind(cfg, i)
        h, carry, y = mix[kind][0](h, carry, lp, seen[kind])
        first[kind].append(y)
        seen[kind] += 1
        h = _ffn_residual(cfg, lp, h)
    g0, k0 = seen["gqa"], seen[rec]

    def body(c, _):
        h, carry, p = c
        ig = ik = 0
        ys, counts = {"gqa": [], rec: []}, []
        for i, kind in enumerate(period):
            li = p * len(period) + i  # the layer's index among the scanned ones
            lp = _at(layers, li)
            if kind == "gqa":
                at, before = p * ng + ig, g0
                ig += 1
            else:
                at, before = p * nk + ik, k0
                ik += 1
            layer, stack = mix[kind]
            # `at` among the scanned layers' weights; the caches hold the leading layers' rows first
            h, carry, y = layer(h, carry, {**lp, **_at(stack, at)}, at + before if before else at)
            ys[kind].append(y)
            h, n = _ffn(cfg, lp, banks, li, h, valid, prompt)
            counts.append(n)
        ys = {kind: jax.tree.map(lambda *a: jnp.stack(a), *y) if y and y[0] is not None else None
              for kind, y in ys.items()}
        return (h, carry, p + 1), (ys, jnp.stack(counts) if cfg.n_experts else None)

    (h, carry, _), (ys, counts) = jax.lax.scan(
        body, (h, carry, jnp.int32(0)), None, length=P)
    for kind, y in ys.items():
        if y is not None:
            y = jax.tree.map(lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), y)
            if first[kind]:
                y = jax.tree.map(lambda *a: jnp.concatenate([jnp.stack(a[:-1]), a[-1]]),
                                 *first[kind], y)
            ys[kind] = y
    if counts is not None:
        counts = counts.reshape(cfg.n_layers - k, *counts.shape[2:])
    return h, carry, counts, ys


def hybrid_prefill(cfg, params, tokens, lengths, attn_impl="xla", quant_kv=False, hidden=False):
    """Whole fresh prompts [B, S] from zero state: (last logits [B, V], or with
    `hidden` the residual stream after the last layer [B, S, D], what
    `mtp_logits` reads; ks, vs) with ks the GQA layers' prompt K/V as
    `llama_prefill` returns them and
    vs = {"v": their second member, "state": each row's S [Lk, B, ...] (in
    the pool's layout; none for a kind without one) and conv tails, or "win": each row's ring, the pair
    {"k", "v"} over [Lw, B, .., R, ..] holding its prompt's last R positions at
    their wrapped indices, and with routed experts "moe": the call's expert
    counts [Le, 5]}; the engine inserts row by row (`insert_state_row`) and adds the counts once
    (`add_counts`)."""
    from .llama import (
        _embed_in, _logits, _residual, _ring_positions, _sub_in, _sub_out, fuse_prompt_kv,
        prefill_attn, prefill_masks)

    B, S = tokens.shape
    Lk = cfg.n_layers - cfg.n_attn_layers
    h = _embed_in(cfg, params, tokens)
    cos, sin, mask = prefill_masks(cfg, S, lengths)
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]

    def gqa_layer(h, carry, lp, ig):
        h, (kh, vh) = prefill_attn(
            cfg, lp, h, cos, sin, mask, lengths, attn_impl, rope=_rotates(cfg, "gqa"))
        return h, carry, ((fuse_prompt_kv(kh, vh), {}) if quant_kv else (kh, vh))

    if cfg.recurrent_kind == "win":
        # the prompt's row that each index of the ring holds after it; an index
        # the prompt has not reached holds row 0, which no mask lets through
        take = jnp.clip(_ring_positions(lengths, cfg.ring_len), 0, S - 1)  # [B, R]

        def turned(rows):  # [B, Hx, S, ..] -> [B, Hx, R, ..]
            at = take.reshape((B, 1, -1) + (1,) * (rows.ndim - 3))
            return jnp.take_along_axis(rows, at, axis=2)

        def rec_layer(h, carry, lp, iw):
            with jax.named_scope(_attn_scope(cfg, "win")):
                h, (kh, vh) = prefill_attn(
                    cfg, lp, h, cos, sin, mask, lengths, attn_impl,
                    window=cfg.sliding_window, rope=_rotates(cfg, "win"))
                pair = (fuse_prompt_kv(kh, vh), {}) if quant_kv else (kh, vh)
                return h, carry, jax.tree.map(turned, dict(zip("kv", pair)))

        carry = None
    else:
        rec = _rec(cfg)
        S0, tail0 = rec.zero_state(cfg, B, h.dtype)

        def rec_layer(h, carry, lp, ik):
            Ss, tails = carry
            y, S_new, tail = rec.prefill(
                cfg, lp, _sub_in(cfg, h, lp["attn_norm"]), lengths, S0, tail0)
            return _residual(cfg, h, _sub_out(cfg, y, lp["attn_norm"])), (
                _layer_set(Ss, ik, S_new), tails.at[ik].set(tail.reshape(B, -1))), None

        carry = (None if S0 is None else jnp.zeros((Lk, *S0.shape), jnp.float32),
                 jnp.zeros((Lk, B, tail0[0].size), tail0.dtype))
    h, carry, counts, ys = _period_scan(cfg, params, h, carry, gqa_layer, rec_layer, valid)
    ks, vs = ys["gqa"]
    last = jnp.take_along_axis(h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return h if hidden else _logits(cfg, params, last), ks, {
        "v": vs,
        **({"win": ys["win"]} if carry is None else {"state": _pool(*carry)}),
        **({} if counts is None else {"moe": counts})}


def insert_state_row(cache_v: dict, new: dict, i, slot) -> dict:
    """Row `i` of what a prefill returns for the members of `cache_v` that hold
    one row a slot (`SLOT_MEMBERS`: the recurrent state, the window layers'
    ring) into row `slot` of each (both traced): {member: its tree}."""
    def put(pool, rows):
        row = jax.lax.dynamic_slice_in_dim(rows, i, 1, 1)
        return jax.lax.dynamic_update_slice(
            pool, row.astype(pool.dtype), (0, slot) + (0,) * (pool.ndim - 2))

    return {m: jax.tree.map(put, cache_v[m], new[m]) for m in SLOT_MEMBERS if m in cache_v}


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
    writes both back; a window layer reads its ring as it stood before the
    chunk and leaves it holding each row's last positions (`_chunk_attention`).
    Rows that duplicate row 0 (the engine's padding) write what row 0 writes."""
    from .llama import _chunk_attention, _residual, _sub_in, _sub_out

    A, C = tokens.shape
    kv_v = cache_v["v"]
    h, attend, write = _chunk_attention(
        cfg, params, cache_k, tokens, slots, starts, nvalid, skey=skey, paged=paged)
    slots = jnp.asarray(slots, jnp.int32)
    fresh = jnp.asarray(starts, jnp.int32) == 0
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < nvalid[:, None]

    def gqa_layer(h, carry, lp, ig):
        ck, cv, *rest = carry
        h, kh, vh = attend(h, ck, cv, ig, lp, 0, _rotates(cfg, "gqa"))
        ck, cv = write(ck, cv, kh, vh, ig)
        return h, (ck, cv, *rest), None

    if cfg.recurrent_kind == "win":
        ring = cache_v["win"]
        _, attend_w, write_w = _chunk_attention(
            cfg, params, ring["k"], tokens, slots, starts, nvalid, ring=cfg.sliding_window)

        def rec_layer(h, carry, lp, iw):
            ck, cv, rk, rv = carry
            with jax.named_scope(_attn_scope(cfg, "win")):
                h, kh, vh = attend_w(h, rk, rv, iw, lp, 0, _rotates(cfg, "win"))
                rk, rv = write_w(rk, rv, kh, vh, iw)
            return h, (ck, cv, rk, rv), None

        def back(rk, rv):
            return {"win": {"k": rk, "v": rv}}

        own = (ring["k"], ring["v"])
    else:
        rec, state = _rec(cfg), cache_v["state"]

        # Each row's state and tail of EVERY recurrent layer come out of the pool
        # before the layer scan and go back after it, row by row (a gather of rows of
        # 384 lanes made the compiler copy the whole pool in three slabs of 128: 2 GiB
        # at Olmo-Hybrid's size). The pool itself stays out of the scan: carried
        # through it, a pool of square [128, 128] tiles was re-laid out whole on the
        # way in and out (two copies of 4.5 GiB at Granite-4.0-H's size, seen in the
        # described-chip compile), the layout being the chunk form's to choose where
        # no kernel holds it.
        def rows_of(pool):  # [Lk, A, ...]; no matrix state: None
            return None if pool is None else jnp.concatenate([jax.lax.dynamic_slice(
                pool, (0, slots[a]) + (0,) * (pool.ndim - 2), (pool.shape[0], 1, *pool.shape[2:]))
                for a in range(A)], axis=1)

        def rec_layer(h, carry, lp, ik):
            ck, cv, Ss, tails = carry
            S0 = None if Ss is None else jnp.where(fresh[:, None, None, None], 0.0, Ss[ik])
            tail0 = jnp.where(fresh[:, None, None], 0, tails[ik].reshape(A, rec.taps(cfg) - 1, -1))
            y, S_new, tail = rec.prefill(
                cfg, lp, _sub_in(cfg, h, lp["attn_norm"]), nvalid, S0, tail0)
            return _residual(cfg, h, _sub_out(cfg, y, lp["attn_norm"])), (
                ck, cv, _layer_set(Ss, ik, S_new),
                tails.at[ik].set(tail.reshape(A, -1))), None

        def back(Ss, tails):
            S, conv = state.get("S"), state["conv"]
            for a in range(A):  # row by row: duplicates of row 0 land on row 0's values
                if S is not None:
                    S = jax.lax.dynamic_update_slice(S, Ss[:, a : a + 1], (0, slots[a], 0, 0, 0))
                conv = jax.lax.dynamic_update_slice(conv, tails[:, a : a + 1], (0, slots[a], 0))
            return {"state": _pool(S, conv)}

        own = (rows_of(state.get("S")), rows_of(state["conv"]))
    h, (ck, cv, *own), counts, _ = _period_scan(
        cfg, params, h, (cache_k, kv_v, *own), gqa_layer, rec_layer, valid)
    new_v = {"v": cv, **back(*own), **_counted(cache_v, 1, counts)}
    return _chunk_logits(cfg, params, h, nvalid, all_logits), ck, new_v


def _chunk_logits(cfg, params, h, nvalid, all_logits: bool):
    """A chunk's logits: at every position, or at each row's last valid one."""
    from .llama import _logits

    if all_logits:
        return _logits(cfg, params, h)
    last = jnp.take_along_axis(h, (nvalid - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return _logits(cfg, params, last)


def hybrid_decode_step(cfg, params, cache_k, cache_v, tokens, lengths, slot_ids=None, paged=None):
    """One token a row. The GQA layers take the dense family's decode
    structure: the KV cache is a scan-invariant operand read by the decode
    attention kernel, the step's K/V stack out of the scan and one append
    kernel lands them. A window layer does the same on its ring: the kernel's
    window arm reads it (`decode_attend_q8(window=)`), and the append lands the
    step's row at the position's wrapped index. A recurrent layer steps its
    rows of the state pool in place (kernels/kda.py). A parked or padding row
    (length >= the cache's) moves nothing: not its cache rows, not its state."""
    from ..ops.rope import apply_rope, rope_tables
    from .llama import (
        _attn_residual, _cache_shape, _embed_in, _logits, _qkv, _residual, _sub_in, _sub_out)

    if paged is not None:
        raise NotImplementedError("a recurrent configuration's blocks are never shared")
    quantized = isinstance(cache_k, dict)
    kv_v, ring = cache_v["v"], cache_v.get("win")
    S_cache, hd = _cache_shape(cache_k)[3], cfg.resolved_head_dim
    Ba, H, Hkv = tokens.shape[0], cfg.n_heads, cfg.n_kv_heads
    rows = None if slot_ids is None else slot_ids.astype(jnp.int32)
    live = lengths < S_cache
    attend = decode_attend_q8 if quantized else decode_attend_bf16
    h = _embed_in(cfg, params, tokens)
    if cfg.use_rope:
        cos, sin = rope_tables(cfg, hd, lengths)  # [Ba, hd/2]

    def attn_layer(kind, cache, second, **arm):
        def layer(h, carry, lp, i):
            with jax.named_scope(_attn_scope(cfg, kind)):
                x = _sub_in(cfg, h, lp["attn_norm"])
                q, k, v = _qkv(cfg, lp, x)
                k, v = k.reshape(Ba, Hkv, hd), v.reshape(Ba, Hkv, hd)
                if _rotates(cfg, kind):
                    q = apply_rope(q.reshape(Ba, 1, H, hd), cos[:, None], sin[:, None])[:, 0]
                    k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
                ctx = attend(
                    q.reshape(Ba, Hkv, H // Hkv, hd), k, v, cache, second, i, lengths,
                    slot_ids=slot_ids, scale=cfg.attn_scale, **arm,
                ).reshape(Ba, H * hd)
                return _attn_residual(cfg, lp, ctx, h, x), carry, (k, v)

        return layer

    if ring is not None:
        rec_layer, state = attn_layer(
            "win", ring["k"], ring["v"], window=cfg.sliding_window), None
    else:
        rec, state = _rec(cfg), cache_v["state"]

        def rec_layer(h, carry, lp, ik):
            with jax.named_scope(rec.scope(cfg)):  # "kda" | "gdn" | "ssd"
                y, carry = rec.decode(
                    cfg, lp, _sub_in(cfg, h, lp["attn_norm"]), carry, ik, rows, live)
                return _residual(cfg, h, _sub_out(cfg, y, lp["attn_norm"])), carry, None

    h, state, counts, ys = _period_scan(
        cfg, params, h, state, attn_layer("gqa", cache_k, kv_v), rec_layer, live)
    with jax.named_scope("kv_append"):
        append = append_kv_q8 if quantized else append_kv_bf16
        new_k, new_kv_v = append(cache_k, kv_v, *ys["gqa"], lengths, slot_ids=slot_ids)
        if ring is not None:
            R = cfg.ring_len
            ring = dict(zip("kv", append(
                ring["k"], ring["v"], *ys["win"], jnp.where(live, lengths % R, R),
                slot_ids=slot_ids)))
    return _logits(cfg, params, h), new_k, {
        "v": new_kv_v, **({"state": state} if ring is None else {"win": ring}),
        **_counted(cache_v, 0, counts)}


def _attn_scope(cfg: ModelConfig, kind: str) -> str:
    """The `jax.named_scope` of a softmax-attention layer: a stack that mixes
    window and global layers names them apart."""
    return "attn" if cfg.recurrent_kind != "win" else "attn_win" if kind == "win" else "attn_full"


def hybrid_mixed_step(
    cfg, params, cache_k, cache_v, tokens, lengths, p_tokens, p_rowids, p_positions,
    p_slots, p_last_idx, paged=None,
):
    """`hybrid_decode_step` for the full batch with admitted prompts riding the
    same pass over the weights: `llama.mixed_step_q8` (whose operands and
    returns these are) for a stack with recurrent layers. Every product that is
    rows of a matmul runs ONCE over the B decode rows and the T prompt tokens
    stacked: a recurrent layer's `project` and `output` (and, row by row,
    `operands`), a GQA layer's `_qkv` (rotated where the attention layers
    rotate: a decode row at its length, a prompt's token at its own position),
    gate and output projection, the feed-forward. Between them each sort of row takes its own part: the decode
    rows the convolution step and the state kernel on the pool, or the decode
    attention through the cache, as `hybrid_decode_step` has them; the prompt
    tokens the causal convolution and the chunked recurrence from ZERO state (a
    riding prompt is always fresh), or causal attention over their own K/V, in
    the precision `hybrid_prefill` computes them in.

    The prompts stay packed in ONE row of T positions, and the recurrence runs
    over the chunks of it that hold tokens and no more (a rung is an
    executable's size, not its work): with one decay a head as ONE Pallas call
    a layer (kernels/kda.py:chunk_scan), which hands each prompt's state over
    in the pool's layout. Each prompt starts at a multiple of
    `kda.CHUNK` (the engine stages them so: `_stage_ride`; the positions
    between are padding, row id R), so a prompt's first chunk is the scan's
    chunk whose first position is 0: there the state is zeroed, and a prompt's
    own state is the scan's after its last chunk. Padding inside a prompt's
    last chunk leaves the state alone.

    After the scan the decode rows' K/V is appended, the prompts' K/V lands in
    their slots, and each prompt's state and convolution tail (the tail alone
    for a kind without a matrix state) land in its row of the pool, row by row,
    outside the scan (`hybrid_prefill_chunk_batch`
    says why). The prompts' slots are parked rows of the decode batch: the
    append, the state kernel and the convolution step move nothing there."""
    from ..ops.rope import apply_rope, rope_tables
    from .llama import (
        _attn_residual, _cache_shape, _embed_in, _logits, _qkv, _residual, _sub_in, _sub_out,
        fuse_prompt_kv, packed_prompt_attn, write_prompt_rows)

    if paged is not None:
        raise NotImplementedError("a recurrent configuration's blocks are never shared")
    if cfg.recurrent_kind == "win":
        raise NotImplementedError("no mixed step over a ring: llama.mixed_step_supported")
    kv_v, state = cache_v["v"], cache_v["state"]
    S_cache, hd = _cache_shape(cache_k)[3], cfg.resolved_head_dim
    B, T, R = tokens.shape[0], p_tokens.shape[0], p_slots.shape[0]
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
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
    if _rotates(cfg, "gqa"):  # a decode row at its length, a prompt's token at its own position
        cos, sin = rope_tables(cfg, hd, jnp.concatenate([lengths, p_positions]))

    def gqa_layer(h, carry, lp, ig):
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q = q.reshape(B + T, H, hd)
            k, v = k.reshape(B + T, Hkv, hd), v.reshape(B + T, Hkv, hd)
            if _rotates(cfg, "gqa"):
                q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
                k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
            ctx_d = decode_attend_q8(
                q[:B].reshape(B, Hkv, H // Hkv, hd), k[:B], v[:B], cache_k, kv_v, ig, lengths,
                scale=cfg.attn_scale).reshape(B, H * hd)
            ctx_p = packed_prompt_attn(cfg, q[B:], k[B:], v[B:], p_rowids, p_positions)
            h = _attn_residual(cfg, lp, jnp.concatenate([ctx_d, ctx_p]), h, x)
        with jax.named_scope("kv_append"):
            fused = fuse_prompt_kv(
                k[B:].transpose(1, 0, 2), v[B:].transpose(1, 0, 2),
                scale_dtype=cache_k["s"].dtype)
        return h, carry, (k[:B], v[:B], fused["q"], fused["s"])

    def rec_layer(h, carry, lp, ik):
        pool, Ss, tails = carry
        with jax.named_scope(rec.scope(cfg)):  # "kda" | "gdn" | "ssd" | "conv"
            x = _sub_in(cfg, h, lp["attn_norm"])
            conv_in, side = rec.project(cfg, lp, x)  # [N, W]
            mixed_d, conv, rows = kda.conv_step(
                pool["conv"], ik, None, live, conv_in[:B], lp["conv_w"])
            mixed_p, tail = kda.conv_packed(conv_in[B:], p_positions, ends, lp["conv_w"])
            ops, side = rec.operands(cfg, lp, jnp.concatenate([mixed_d, mixed_p]), side)
            o_d, S = rec.step_rows(
                cfg, pool.get("S"), ik, rows, live, jax.tree.map(lambda a: a[:B], ops))
            o_p, after = rec.scan_packed(
                jax.tree.map(lambda a: a[B:][None], ops), token[None], fresh, staged)
            y = rec.output(cfg, lp, jnp.concatenate([o_d, o_p[0]]), side, x.dtype)
            # each prompt's own state [R, H / P, dk, P dv]; none without a matrix state
            own = None if after is None else jnp.take(after[:, 0], ends // kda.CHUNK, axis=0)
            return _residual(cfg, h, _sub_out(cfg, y, lp["attn_norm"])), (
                _pool(S, conv), _layer_set(Ss, ik, own),
                tails.at[ik].set(tail.reshape(R, -1).astype(tails.dtype))), None

    # the prompts' states ride the scan as [Lk, R, (H / P) dk, P dv]: with the
    # pool's own last two axes, both 128 at Granite-4.0-H's size, the compiler
    # carried them transposed and then re-laid the WHOLE pool out to take them
    # (4.5 GiB of temporaries, seen in the described-chip compile)
    # (the pool's layers: a leading dense layer of the recurrent kind has a row of its own)
    S_pool, Lk = state.get("S"), state["conv"].shape[0]
    carry = (state,
             None if S_pool is None else jnp.zeros(
                 (Lk, R, S_pool.shape[2] * S_pool.shape[3], S_pool.shape[4]), jnp.float32),
             jnp.zeros((Lk, R, state["conv"].shape[2]), state["conv"].dtype))
    h, (pool, Ss, tails), counts, ys = _period_scan(
        cfg, params, h, carry, gqa_layer, rec_layer, jnp.concatenate([live, token]), prompt)
    knew, vnew, pq, ps = ys["gqa"]
    count = jnp.sum(p_rowids[None, :] == jnp.arange(R, dtype=jnp.int32)[:, None],
                    axis=1, dtype=jnp.int32)  # [R] tokens of each prompt; 0: an unused row
    starts = ends + 1 - count
    with jax.named_scope("kv_append"):
        new_k, new_kv_v = append_kv_q8(cache_k, kv_v, knew, vnew, lengths)
        new_k = {
            "q": write_prompt_rows(new_k["q"], pq, p_slots, starts, count),
            "s": write_prompt_rows(new_k["s"], ps, p_slots, starts, count),
        }
        S, conv = pool.get("S"), pool["conv"]
        if S is not None:
            Ss = Ss.reshape(Lk, R, *S.shape[2:])
        for r in range(R):  # an unused row writes back what it read
            if S is not None:
                S = _put_row(S, Ss[:, r : r + 1], p_slots[r], count[r] > 0)
            conv = _put_row(conv, tails[:, r : r + 1], p_slots[r], count[r] > 0)
    new_v = {"v": new_kv_v, "state": _pool(S, conv)}
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


def init_mtp_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """ONE multi-token-prediction module's own weights, seeded (DeepSeek-V3's
    form, whose key K-EXAONE's config uses): `hnorm`, `enorm` [D] and `eh_proj`
    [2 D, D] that join the main model's hidden state with the next token's
    embedding, one decoder layer of the expert kind with full attention
    (`layers`, `gqa`: the main tree's leaves, stacked [1, ...]) and
    `final_norm` [D], the main model's final norm's twin. The embedding and the
    head are the main model's, shared. Built where a caller asks, never by the
    engine's boot: no step program runs the module."""
    assert cfg.mtp_layers and cfg.n_experts, cfg.name
    D = cfg.dim

    def build(key):
        ks = jax.random.split(key, 4)

        def w(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

        layers = {"attn_norm": jnp.ones((1, D), dtype), "ffn_norm": jnp.ones((1, D), dtype),
                  **init_moe_layer_params(cfg, ks[0], dtype, 1)}
        if cfg.router_score == "sigmoid":
            layers["router_bias"] = 0.01 * jax.random.normal(
                ks[1], (1, cfg.router_width), jnp.float32)
        return {"hnorm": jnp.ones((D,), dtype), "enorm": jnp.ones((D,), dtype),
                "eh_proj": w(ks[2], (2 * D, D), 2 * D), "layers": layers,
                "gqa": _attn_params(cfg, jax.random.split(ks[3], 5), 1, w, dtype),
                "final_norm": jnp.ones((D,), dtype)}

    return jax.jit(build)(key)


def mtp_logits(cfg, params, mtp, h, next_tokens, lengths):
    """The module's forward over whole sequences: `h` [B, S, D] the main
    model's residual stream after its last layer (`hybrid_prefill(hidden=True)`),
    `next_tokens` [B, S] the token AFTER each position; logits [B, S, V] for the
    token after that. h' = eh_proj [norm(h) ; norm(embed(next))], one decoder
    layer (`prefill_attn` as a GQA layer runs it, the expert feed-forward), the
    module's final norm and the main model's head."""
    from .llama import _embed_in, _logits, _norm, prefill_attn, prefill_masks
    from .quant import qdot

    S = next_tokens.shape[1]
    cos, sin, mask = prefill_masks(cfg, S, lengths)
    x = qdot(jnp.concatenate([
        _norm(cfg, h, mtp["hnorm"]), _norm(cfg, _embed_in(cfg, params, next_tokens), mtp["enorm"]),
    ], axis=-1), mtp["eh_proj"])
    layers = {n: v for n, v in mtp["layers"].items() if n not in BANKS}
    lp = {**_at(layers, 0), **_at(mtp["gqa"], 0)}
    x, _ = prefill_attn(cfg, lp, x, cos, sin, mask, lengths, rope=_rotates(cfg, "gqa"))
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
    x, _ = _ffn(cfg, lp, {n: mtp["layers"][n] for n in BANKS}, 0, x, valid)
    return _logits(cfg, {**params, "final_norm": mtp["final_norm"]}, x)
