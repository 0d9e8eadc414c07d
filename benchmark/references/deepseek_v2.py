"""Plain float32 reference forward for the DeepSeek-V2 family: multi-head
latent attention over a compressed key/value latent with one rope key shared
by all heads (the query dense, or through a latent of its own), yarn-scaled
rope, and feed-forward layers of routed experts plus always-on shared experts
after a dense prologue; the router greedy over all experts or limited to the
best groups of them (the paper's device-limited routing), and the chip under
test may hold a SHARE of the routed experts.

Written from the published description (DeepSeek-V2, arXiv 2405.04434, section
2.1 and appendix C; the released `modeling_deepseek.py` for what the paper
leaves to the code: yarn's two mscale terms, `MoEGate`'s greedy and
group-limited greedy top-k, the gate scaling).
The same weights go through those equations one layer at a time in float32
`jax.numpy` at `Precision.HIGHEST`: every position expands its own per-head
keys and values from the latent (no cache, no absorbed projections), every
token goes to all of its k experts (no capacity, nothing dropped), no kernels,
no quantized dots. It shares nothing with llm_mcp_tpu/models/mla.py and
models/moe.py but the names of the parameter tree:

    params["embed"], ["final_norm"], ["lm_head"] (absent when the head is tied)
    params["dense_layers"]: the first `first_dense_layers` layers, stacked [k, ...]
    params["layers"]: the routed layers, stacked [L - k, ...] (every layer, dense,
        for a configuration without experts)
    in both: attn_norm, ffn_norm, wq_mla [D, H (dn + dr)], w_dkv [D, R + dr],
        kv_norm [R], w_ukv [R, H (dn + dv)], wo_mla [H dv, D]
    dense FFN: w1, w3 [D, F], w2 [F, D], or w13 = [w1 | w3] side by side
    routed FFN: router [D, Er] (Er = `cfg.router_width`, the published count of
        experts), w1e, w3e [E, D, Fm], w2e [E, Fm, D] (the E = `cfg.n_experts`
        experts held here, the first E of the router's order; E = Er without a
        share), shared experts as one gated MLP w1s, w3s [D, n_shared Fm], w2s

**Routing** (`_gates`; `MoEGate` of the released code): s = softmax(x W_r) in
float32 over all Er experts; with `n_group` > 1 the experts are `n_group` groups
of Er / n_group consecutive ones, a group's score is its best expert's, the
`topk_group` best groups keep their scores and every other score is 0; the top
k of what is left are the row's experts with their s as gates, renormalised
where `norm_topk_prob` is set and k > 1, else times `routed_scaling_factor`.
`n_group` and `topk_group` are read from the program's configuration where it
has the fields (`getattr(cfg, "n_group", 1)`: the names are for the
`model_config` PR that brings the path to meet); at 1 and 1 the forward is the
greedy one, bit for bit. **A share** (`references/exaone_moe.py`'s way): the
router scores the published width and groups and the top k are chosen over all
of it; only the experts [0, E) are applied, what the absent ones would have
added is left out, and that partial result goes on; the shared experts are
applied whole.

A linear is a plain array or the int8 form {"q", "s"} with one scale an output
channel; both are multiplied out to float32, one layer (and one expert) at a
time, so that no float32 copy of a whole stack or a whole bank of experts is
alive beside an engine that fills the chip.

Departures from the published description, each forced by the tree and none a
change of the mathematics:

- Rope pairs dimension i with i + dr/2 (split halves), where the released code
  pairs 2i with 2i + 1: the program's loader permutes the rope columns of
  `q_proj` and `kv_a_proj_with_mqa` once, and a seeded random tree has no order.
- `kv_a_proj_with_mqa` is `w_dkv` (latent | rope key), `kv_b_proj` is `w_ukv`
  with each head's (k_nope | v) side by side; the n shared experts are one
  gated MLP of n times the routed width, which is the same sum.
- With `q_lora_rank` the query goes through `wq_a` [D, rq], RMSNorm `q_a_norm`
  and `wq_b` [rq, H (dn + dr)] (the released names); the program has no such
  path yet (it raises), so no test holds this branch to it.
- The layer is ONE jitted function that indexes the stacked tree, as in
  benchmark/reference.py: one executable a kind of layer, found in the compile
  cache on the next run. Callers pad the sequence to a fixed length (causal:
  what follows a row does not move it). Every expert is applied to every row
  and weighted by the row's gate for it, 0 where it was not chosen: dropless by
  construction, E/k times the arithmetic, no gather or scatter to get wrong.
- Sigmoid scores and the selection bias of V3 are not here, nor a share that
  cuts a routing group: `check` refuses all three.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# float32 means float32: a TPU's default matmul precision rounds f32 operands
# to bf16
_HI = jax.lax.Precision.HIGHEST
_mm = partial(jnp.matmul, precision=_HI)
FFN_BLOCKS = 4

# Served tokens against this forward, as a share of a row's max |logit|: a FIRST
# value, argued and not yet read on the chip at published widths; the
# `model_config` PR that first runs it there states both readings of
# BENCHMARK.json's rule (the program's largest over a dozen seeds, the int8
# control's smallest) and may tighten it. The argument: the dense part of the
# error is the dense family's (int8 x int8 dots, int8 cache, bf16 activations:
# 0.028 at worst over 36 layers on the v5e, benchmark/reference.py). What this
# family adds is the router: a hidden state that is 1-2% off moves a score by
# as much, two experts whose scores lie that close at the k-th place change
# places, and the row then gets g f_b(x) for g f_a(x) in that layer, g being
# the SMALLEST of its k gates. Read at V2-Lite's routing (64 experts, top 6,
# raw gates, 2 shared, 1 + 26 layers) on tiny-v2's widths, float32 on the CPU,
# 12 seeds x 28 rows (a count of what the equations do, not a device number;
# tests/benchmark/test_bench_reference.py keeps the experiment): giving every
# row whose 6th and 7th scores lie within 2**-6 of each other the 7th expert
# (15% of rows a layer) moves a row's logits by 0.03-0.09 of its max |logit| and
# the regret of its greedy token to 0.053 at worst; within 2**-5 (28% of rows)
# 0.048; EVERY row in every layer 0.088; within 2**-8 (4%, bf16's own rounding)
# 0.0005. So: the dense family's worst plus the router's, 0.028 + 0.053, times
# one and a half. Another request's logits, a lost latent row or a layer left
# out miss by the spread of the logits themselves (0.5 and more).
SERVED_TOL_REL = 0.12


# -- what a configuration's file states beyond run.py's own tables ----------------


def _groups(cfg) -> tuple[int, int]:
    """(n_group, topk_group) the program computes with: 1 and 1, greedy over all
    experts, for a configuration without the fields."""
    return int(getattr(cfg, "n_group", 1) or 1), int(getattr(cfg, "topk_group", 1) or 1)


# `n_group` and `topk_group` are paths of run.py's `ONLY_VALUE`: this family
# brings the behaviour, so it holds them to what its program computes with
# (`run.load_reference` lets `HELD`, and only `HELD`, take them over).
HELD = {
    "n_group": lambda c: _groups(c)[0],
    "topk_group": lambda c: _groups(c)[1],
    "scoring_func": lambda c: c.router_score,
    "topk_method": lambda c: "group_limited_greedy" if _groups(c)[0] > 1 else "greedy",
    # the router keeps the published width while n_routed_experts counts the held
    "published.n_routed_experts": lambda c: c.router_width,
}
STATED = {"seq_aux": "a switch of the training loss (the auxiliary loss a sequence): nothing in a forward"}


def check(cfg) -> None:
    """Raises for a configuration these equations do not cover."""
    if not cfg.kv_lora_rank:
        raise NotImplementedError(f"{cfg.name!r} has no latent attention: not this family")
    if (cfg.sliding_window or cfg.attn_softcap or cfg.logit_softcap or cfg.post_norms
            or cfg.norm_weight_offset or cfg.embed_scale or cfg.qkv_bias or cfg.qk_norm
            or cfg.act != "silu" or cfg.query_pre_attn_scalar):
        raise NotImplementedError(f"no plain DeepSeek-V2 reference for {cfg.name!r}")
    if cfg.rope_factor > 1.0 and cfg.rope_type != "yarn":
        raise NotImplementedError(f"reference rope type {cfg.rope_type!r}")
    if not cfg.n_experts:
        return
    if cfg.router_score != "softmax":
        raise NotImplementedError(f"reference router score {cfg.router_score!r}: softmax alone, "
                                  f"no sigmoid scores and no selection bias")
    n_group, topk_group = _groups(cfg)
    width, held = cfg.router_width, cfg.n_experts
    if width % n_group or not 1 <= topk_group <= n_group or held > width:
        raise NotImplementedError(
            f"{cfg.name!r}: {n_group} groups, {topk_group} a token, over {width} experts of which "
            f"{held} are held")
    if cfg.experts_per_tok > topk_group * (width // n_group):
        raise NotImplementedError(
            f"{cfg.name!r}: {cfg.experts_per_tok} experts a token out of {topk_group} groups of "
            f"{width // n_group}")
    if held < width and held % (width // n_group):
        raise NotImplementedError(
            f"{cfg.name!r}: a share of {held} experts is not whole groups of {width // n_group} "
            f"in the router's order")


# -- yarn ------------------------------------------------------------------------


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 and mscale else 1.0


def _rope_tables(cfg, T: int) -> tuple[np.ndarray, np.ndarray]:
    """cos, sin [T, dr/2] in float64. Under yarn the frequencies that turn more
    than `beta_fast` times inside the original context are kept, those that
    turn less than `beta_slow` times are divided by the factor, a linear ramp
    between; cos and sin carry mscale(mscale) / mscale(mscale_all_dim)."""
    dr = cfg.qk_rope_head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr))
    m = 1.0
    if cfg.rope_factor > 1.0 and cfg.rope_orig_max:
        def turns_to_dim(turns: float) -> float:
            return dr * math.log(cfg.rope_orig_max / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

        low = max(math.floor(turns_to_dim(cfg.yarn_beta_fast)), 0)
        high = min(math.ceil(turns_to_dim(cfg.yarn_beta_slow)), dr - 1)
        ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / cfg.rope_factor * ramp + inv * (1.0 - ramp)
        m = (_yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
             / _yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang) * m, np.sin(ang) * m


def _softmax_scale(cfg) -> float:
    """1 / sqrt(dn + dr), times yarn's mscale(mscale_all_dim) squared."""
    m = _yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim) if cfg.rope_orig_max else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


# -- the tree ----------------------------------------------------------------------


def _pick(leaf, index):
    for i in index:
        leaf = jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
    return leaf


def _linear(w, index, rows=slice(None), cols=slice(None)):
    """The linear at `index` of a stacked leaf (layer, or layer and expert) as
    float32 [in[rows], out[cols]]: a plain array, or {"q", "s"} multiplied out."""
    f32 = jnp.float32
    if isinstance(w, dict):
        return (_pick(w["q"], index)[rows, cols].astype(f32)
                * _pick(w["s"], index)[cols].astype(f32)[None, :])
    return _pick(w, index)[rows, cols].astype(f32)


def _rms(x, w, eps: float):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope(x, cos, sin):
    """x [T, ..., dr] with cos, sin [T, dr/2]; pairs (i, i + dr/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _swiglu(x, gate_w, up_w, down_w):
    gate = _mm(x, gate_w)
    return _mm(gate * (1.0 / (1.0 + jnp.exp(-gate))) * _mm(x, up_w), down_w)


# -- one layer ---------------------------------------------------------------------


def _queries(cfg, stack, li, x):
    """x [T, D] -> q [T, H, dn + dr], dense or through the query latent."""
    if cfg.q_lora_rank:
        cq = _rms(_mm(x, _linear(stack["wq_a"], (li,))),
                  _pick(stack["q_a_norm"], (li,)).astype(jnp.float32), cfg.norm_eps)
        q = _mm(cq, _linear(stack["wq_b"], (li,)))
    else:
        q = _mm(x, _linear(stack["wq_mla"], (li,)))
    return q.reshape(x.shape[0], cfg.n_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _head_keys(k_nope, k_rope, head: int):
    """Head `head`'s keys [T, dn + dr]: its own part expanded from the latent,
    and the ONE rope key of the position, which every head shares."""
    return jnp.concatenate([k_nope[:, head], k_rope], axis=-1)


def _attention(cfg, stack, li, x, cos, sin):
    T = x.shape[0]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = _queries(cfg, stack, li, x)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], axis=-1)
    down = _mm(x, _linear(stack["w_dkv"], (li,)))  # [T, R + dr]
    latent = _rms(down[:, :R], _pick(stack["kv_norm"], (li,)).astype(jnp.float32), cfg.norm_eps)
    k_rope = _rope(down[:, R:], cos, sin)
    kv = _mm(latent, _linear(stack["w_ukv"], (li,))).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = _softmax_scale(cfg)
    heads = []
    for head in range(H):  # one head at a time: [T, T] scores, not [H, T, T]
        s = _mm(q[:, head], _head_keys(k_nope, k_rope, head).T) * scale
        s = jnp.where(causal, s, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        heads.append(_mm(p / jnp.sum(p, axis=-1, keepdims=True), v[:, head]))
    return _mm(jnp.concatenate(heads, axis=-1), _linear(stack["wo_mla"], (li,)))


def _dense_ffn(cfg, stack, li, x):
    F = cfg.ffn_hidden
    step = -(-F // FFN_BLOCKS)
    out = jnp.zeros_like(x)
    for lo in range(0, F, step):  # the hidden width in blocks
        cols = slice(lo, min(lo + step, F))
        if "w13" in stack:  # [w1 | w3] side by side
            gate_w = _linear(stack["w13"], (li,), cols=cols)
            up_w = _linear(stack["w13"], (li,), cols=slice(F + cols.start, F + cols.stop))
        else:
            gate_w, up_w = (_linear(stack[n], (li,), cols=cols) for n in ("w1", "w3"))
        out = out + _swiglu(x, gate_w, up_w, _linear(stack["w2"], (li,), rows=cols))
    return out


def _gates(cfg, scores):
    """scores [T, Er] float32 softmax over every expert the router scores ->
    the weight of every one of them for every row, 0 for all but the row's top
    k: greedy over all experts, or with `n_group` > 1 over the experts of the
    `topk_group` groups whose best score is largest (every score outside them
    is 0 before the top k are taken)."""
    n_group, topk_group = _groups(cfg)
    width = scores.shape[-1]
    if n_group > 1:
        best = jnp.max(scores.reshape(-1, n_group, width // n_group), axis=-1)  # [T, G]
        _, keep = jax.lax.top_k(best, topk_group)
        kept = jnp.any(keep[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)  # [T, G]
        scores = jnp.where(jnp.repeat(kept, width // n_group, axis=1), scores, 0.0)
    top, idx = jax.lax.top_k(scores, cfg.experts_per_tok)
    if cfg.norm_topk_prob and cfg.experts_per_tok > 1:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        top = top * cfg.routed_scaling_factor
    chosen = idx[:, :, None] == jnp.arange(width)[None, None, :]
    return jnp.sum(jnp.where(chosen, top[:, :, None], 0.0), axis=1)  # [T, Er]


def _routed_ffn(cfg, stack, li, x):
    """This chip's part of the routed experts' sum (all of it without a share),
    and the shared experts whole."""
    if "router_bias" in stack:
        raise NotImplementedError(f"{cfg.name!r}: a selection bias on the router is not this family's")
    logits = _mm(x, _pick(stack["router"], (li,)).astype(jnp.float32))  # [T, Er]: every published expert
    scores = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    gates = _gates(cfg, scores / jnp.sum(scores, axis=-1, keepdims=True))

    def expert(e, out):  # e < the experts held here
        y = _swiglu(x, *(_linear(stack[n], (li, e)) for n in ("w1e", "w3e", "w2e")))
        return out + y * jax.lax.dynamic_index_in_dim(gates, e, 1, keepdims=True)

    out = jax.lax.fori_loop(0, cfg.n_experts, expert, jnp.zeros_like(x))
    if cfg.n_shared_experts:
        out = out + _swiglu(x, *(_linear(stack[n], (li,)) for n in ("w1s", "w3s", "w2s")))
    return out


@partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg, routed: bool, stack, li, h, cos, sin):
    """One decoder layer over h [T, D], float32; `stack` is the stacked tree
    the layer lives in and `li` its index there."""
    def vec(name):
        return _pick(stack[name], (li,)).astype(jnp.float32)

    h = h + _attention(cfg, stack, li, _rms(h, vec("attn_norm"), cfg.norm_eps), cos, sin)
    x = _rms(h, vec("ffn_norm"), cfg.norm_eps)
    return h + (_routed_ffn(cfg, stack, li, x) if routed else _dense_ffn(cfg, stack, li, x))


# -- the model ---------------------------------------------------------------------


@jax.jit
def _embed_rows(embed, tokens):
    """Rows of the [V, D] table; int8 rows carry one scale each."""
    f32 = jnp.float32
    if isinstance(embed, dict):
        return embed["q"][tokens].astype(f32) * embed["s"][tokens].astype(f32)[:, None]
    return embed[tokens].astype(f32)


def hidden_states(cfg, params, tokens: np.ndarray):
    """Final-normed hidden states [T, D] (float32) of one unbatched sequence."""
    check(cfg)
    f32 = jnp.float32
    cos, sin = (jnp.asarray(t, f32) for t in _rope_tables(cfg, int(tokens.shape[0])))
    h = _embed_rows(params["embed"], jnp.asarray(tokens, jnp.int32))
    n_dense = cfg.first_dense_layers if cfg.n_experts else 0
    for li in range(n_dense):
        h = _layer(cfg, False, params["dense_layers"], jnp.int32(li), h, cos, sin)
    for li in range(cfg.n_layers - n_dense):
        h = _layer(cfg, bool(cfg.n_experts), params["layers"], jnp.int32(li), h, cos, sin)
    return _rms(h, jnp.asarray(params["final_norm"], f32), cfg.norm_eps)


def logits(cfg, params, tokens: np.ndarray, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """Logits [len(rows), len(cols)]: row t is the distribution over token
    t+1, cut to the token ids `cols` so that a 102k vocabulary costs nothing."""
    out = hidden_states(cfg, params, tokens)[jnp.asarray(rows)]
    pick = jnp.asarray(cols)
    if cfg.tie_embeddings:
        return np.asarray(_mm(out, _embed_rows(params["embed"], pick).T), np.float32)
    return np.asarray(_mm(out, _linear(params["lm_head"], (), cols=pick)), np.float32)
