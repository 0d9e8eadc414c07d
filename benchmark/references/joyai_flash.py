"""Plain float32 reference forward for JoyAI-LLM-Flash (`model_type`
`joyai_llm_flash`; every layer's equations are DeepSeek-V3's): multi-head latent
attention in every layer, the query through a latent of its own (`q_lora_rank`),
keys and values expanded a head from ONE compressed latent a position plus one
rope key that all heads share, rope on ADJACENT pairs of the rope dimensions
(`rope_interleave`), no rope scaling; one leading dense feed-forward layer, then
routed experts (sigmoid scores, a selection bias that chooses and does not weigh,
`noaux_tc`; greedy over all experts, `n_group` = `topk_group` = 1; the chosen
scores renormalised and then times `routed_scaling_factor`) beside one shared
expert, of which the chip under test may hold a SHARE: the router scores all
published experts, the experts [0, n held) add their part, and what the absent
ones would add is left out, here as in the program. And the multi-token-prediction
module's forward (`mtp_logits`).

Written from the row's `config` and DeepSeek-V3's published description (arXiv
2412.19437, sections 2.1 and 2.2; the released `modeling_deepseek.py` for what
the paper leaves to the code: `MoEGate`'s `noaux_tc` selection, the gate scaling,
`apply_rotary_pos_emb`'s interleaved pairs). One unbatched sequence goes through
one layer at a time in float32 `jax.numpy` at `Precision.HIGHEST`, in the EXPANDED
form: every position's per-head keys and values are multiplied out of its latent
(no cache, no absorbed projections, one [T, T] score matrix a head), every held
expert is applied to every row and weighted by the row's gate for it (0 where not
chosen: dropless by construction). No kernels, no grouped products, no batching.
It imports nothing from llm_mcp_tpu and shares with models/mla.py only the names
of the parameter tree:

    params["embed"] [V, D], ["final_norm"] [D], ["lm_head"] [D, V]
    params["dense_layers"]: the k leading dense layers, stacked [k, ...]
    params["layers"]: the L - k expert layers, stacked [L - k, ...]
    in both: attn_norm, ffn_norm [D], w_dq [D, Rq], q_a_norm [Rq], w_uq [Rq, H dn |
        H dr] (every head's content columns, then every head's rope columns), w_dkv
        [D, R + dr] (latent | rope key), kv_norm [R], w_ukv [R, H (dn + dv)] (a
        head's k_nope | v side by side), wo_mla [H dv, D]
    dense feed-forward: w1, w3 [D, F], w2 [F, D]
    expert layers: router [D, Er], router_bias [Er], w1e, w3e [E, D, Fm], w2e [E,
        Fm, D] (the E experts held), w1s, w3s [D, Fm], w2s [Fm, D] (the shared one)
    the module (`mtp_logits`): hnorm, enorm, final_norm [D], eh_proj [2 D, D],
        "layers" as the expert layers', stacked [1, ...]

Departures from the published description, none a change of the mathematics:

- **Rope columns.** The program rotates a head's first half of the rope
  dimensions against its second (split halves) on columns that its loader
  de-interleaves once (llm_mcp_tpu/models/weights.py:`_rope_perm`: program column
  j < dr/2 holds the checkpoint's column 2j, column dr/2 + j its 2j + 1). This
  forward rotates ADJACENT pairs, as the released code does, so the comparison
  feeds it the program's tree with the rope columns of `w_uq` (a head at a time)
  and of `w_dkv` put back in the checkpoint's order by that permutation's inverse
  (`_interleaved`). A seeded tree has no order of its own; what the comparison
  holds is that the program's rotation of ITS columns is the published rotation of
  the checkpoint's.
- A layer is ONE jitted function a kind that indexes the stacked tree: callers
  pad the sequence to a fixed length (causal: what follows a row does not move it).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
# The controls of SERVED_TOL_REL (below): the same forward with one thing the
# configuration states lowered or left out. None is the reference. "bf16" rounds
# every matrix and the left operand of every product to bfloat16, the precision
# the configuration STATES (it must come out correct); "int8_latent" keeps every
# position's latent and rope key as the int8 cache holds them, one scale a
# position each (stated too: correct); "fp8" rounds matrices and left operands to
# float8 e4m3, the nearest floating precision under the stated bfloat16 (it must
# NOT); "no_scale" leaves `routed_scaling_factor` out of the gates; "rope_halves"
# rotates split halves on the checkpoint's interleaved columns (the loader's
# permutation forgotten). Set only by scripts/solar_tolerance.py and the tests
# (read when a layer is traced: clear jax's caches after a change), never by run.py.
LOWER: str | None = None
CONTROLS = ("bf16", "int8_latent", "fp8", "no_scale", "rope_halves")


def _lower(x):
    if LOWER == "fp8":  # saturating, as a conversion to float8 is
        x = jnp.clip(x, -448.0, 448.0)
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    if LOWER == "bf16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _mm(a, b):
    return jnp.matmul(_lower(a), b, precision=_HI)


# Served tokens against this forward, as a share of a row's max |logit|
# (`correctness.hold_to_reference`; one reading a seed = the worst of the 16 served
# tokens of the reference request, a prompt of 200 bytes = 207 tokens through the
# engine's 1 x 256 admit program and then 16 decode steps through the int8 latent
# cache and the whole-S kernel). Both readings of BENCHMARK.json's rule, by
# scripts/solar_tolerance.py --config joyai-llm-flash-ep16-bf16 (my chip run, PR
# 57, call 2, TPU v5 lite, the published widths, seeds 5700003000-11):
# - the program, 12 seeds: median 0.0105, the largest 0.082, every seed correct
#   (the twelve benchmark runs of calls 2 and 3, seeds of their own: 0.0-0.087,
#   eight of them under 0.02). As in the other
#   expert shares it is no rounding in the dense sense: the bfloat16 stream now and
#   then moves a router's eighth choice, and where the expert is one of the 16 held
#   the row's feed-forward output changes by a whole gated expert times 2.5.
# - the two controls the configuration STATES, 4 of those seeds each: bfloat16
#   products 0.0-0.0096, the int8 latent cache 0.0-0.065: every seed correct, both
#   inside the program's own band, as they must be.
# - float8 (LOWER = "fp8", the nearest floating precision under the stated
#   bfloat16), 4 seeds: 1.338-1.917, every seed NOT correct. The limit lies between
#   the two with room on both sides: 3.5 times the program's largest of all 24
#   readings, 0.22 of float8's smallest.
# - the two structural controls, 4 seeds each, by this limit: the gates' factor
#   left out 0.0-0.117 (every seed still correct: inside the program's band, as in
#   K-EXAONE's file); the loader's permutation forgotten 0.0, 0.251, 0.270, 0.398:
#   one seed of four not correct at this limit. Sixteen greedy tokens cannot tell
#   either from the program on every seed; what holds them is the comparison of
#   LOGITS at the tiny preset (tests/test_joyai.py: the program through float
#   caches agrees to 1e-4, and each of these moves the rows' median by 0.44-0.78).
SERVED_TOL_REL = 0.30

# -- what the configuration's file states beyond run.py's own tables -----------

HELD = {
    "scoring_func": lambda c: c.router_score,
    "num_nextn_predict_layers": lambda c: c.mtp_layers,
    "qk_head_dim": lambda c: c.qk_nope_head_dim + c.qk_rope_head_dim,
    # the router keeps the published width while n_routed_experts counts the held
    "published.n_routed_experts": lambda c: c.router_width,
}
ONLY = {
    "topk_method": "noaux_tc",  # the bias chooses and does not weigh: moe.route, `_gates`
    "rope_interleave": True,  # adjacent pairs in the checkpoint's order: `_interleaved`
}
STATED = {
    "ep_size": "the released code's own expert-parallel degree at load, 1 in the published file: "
               "a placement, nothing in a forward; THIS file's 16-way group is its `deployment`",
}


def check(cfg) -> None:
    """Raises for a configuration these equations do not cover."""
    if not (cfg.kv_lora_rank and cfg.q_lora_rank):
        raise NotImplementedError(f"{cfg.name!r} has no latent attention with a low-rank query: "
                                  f"not this family")
    if (cfg.router_score != "sigmoid" or not cfg.n_experts or cfg.n_shared_experts != 1
            or not cfg.first_dense_layers or not cfg.norm_topk_prob or cfg.rope_factor > 1.0
            or cfg.n_experts > cfg.router_width or cfg.tie_embeddings or cfg.gqa_layers):
        raise NotImplementedError(f"no plain JoyAI-LLM-Flash reference for {cfg.name!r}")
    if (cfg.sliding_window or cfg.attn_softcap or cfg.logit_softcap or cfg.post_norms
            or cfg.norm_weight_offset or cfg.embed_scale or cfg.qkv_bias or cfg.qk_norm
            or cfg.act != "silu" or cfg.query_pre_attn_scalar or cfg.attn_multiplier
            or cfg.norm_placement != "input" or cfg.embed_multiplier != 1.0
            or cfg.residual_multiplier != 1.0 or cfg.logits_divisor != 1.0):
        raise NotImplementedError(f"no plain JoyAI-LLM-Flash reference for {cfg.name!r}")


# -- the tree ----------------------------------------------------------------------


def _at(leaf, *index):
    for i in index:
        leaf = jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
    leaf = leaf.astype(jnp.float32)
    return _lower(leaf) if leaf.ndim == 2 else leaf


def _interleaved(w, dr: int, heads: int = 1):
    """`w` [in, heads x (.. | dr)] with the LAST dr columns of every head put
    back from the program's order (halves) into the checkpoint's (adjacent
    pairs): the inverse of the loader's permutation."""
    perm = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(dr)
    per = w.shape[-1] // heads
    cols = np.concatenate([np.arange(per - dr), per - dr + inv])
    return w.reshape(w.shape[0], heads, per)[:, :, cols].reshape(w.shape)


def _rms(x, w, eps: float):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _swiglu(x, gate_w, up_w, down_w):
    g = _mm(x, gate_w)
    return _mm(g * _sigmoid(g) * _mm(x, up_w), down_w)


def _rotate(x, theta: float):
    """x [T, ..., dr] at positions 0..T-1: the pair (2i, 2i + 1) turned by
    position / theta**(2 i / dr), no scaling (`rope_scaling` null)."""
    T, dr = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # [T, dr/2]
    shape = (T,) + (1,) * (x.ndim - 2) + (dr // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if LOWER == "rope_halves":
        a, b = x[..., : dr // 2], x[..., dr // 2 :]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _as_cached(x):
    """A position's vector as the int8 cache holds it: one scale a position."""
    if LOWER != "int8_latent":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


# -- latent attention, expanded -----------------------------------------------------


def _attention(cfg, stack, li, x):
    T = x.shape[0]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    c_q = _rms(_mm(x, _at(stack["w_dq"], li)), _at(stack["q_a_norm"], li), cfg.norm_eps)
    w_uq = _at(stack["w_uq"], li)  # [Rq, H dn | H dr]
    q_nope = _mm(c_q, w_uq[:, : H * dn]).reshape(T, H, dn)
    q_rope = _mm(c_q, _interleaved(w_uq[:, H * dn :], dr, H)).reshape(T, H, dr)
    q = jnp.concatenate([q_nope, _rotate(q_rope, cfg.rope_theta)], axis=-1)
    down = _mm(x, _interleaved(_at(stack["w_dkv"], li), dr))  # [T, R + dr]
    latent = _as_cached(_rms(down[:, :R], _at(stack["kv_norm"], li), cfg.norm_eps))
    k_rope = _as_cached(_rotate(down[:, R:], cfg.rope_theta))  # ONE key a position, all heads'
    kv = _mm(latent, _at(stack["w_ukv"], li)).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    causal = jnp.tril(jnp.ones((T, T), bool))
    heads = []
    for head in range(H):  # one head at a time: [T, T] scores
        keys = jnp.concatenate([k_nope[:, head], k_rope], axis=-1)  # [T, dn + dr]
        s = jnp.where(causal, _mm(q[:, head], keys.T) * (dn + dr) ** -0.5, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        heads.append(_mm(p / jnp.sum(p, axis=-1, keepdims=True), v[:, head]))
    return _mm(jnp.concatenate(heads, axis=-1), _at(stack["wo_mla"], li))


# -- the feed-forward -----------------------------------------------------------------


def _gates(cfg, stack, li, x):
    """[T, Er]: the weight of every published expert for every row, 0 for all
    but the row's k: chosen by score + bias over ALL of them, weighed by the
    score alone, renormalised, times the factor."""
    scores = _sigmoid(_mm(x, _at(stack["router"], li)))
    _, chosen = jax.lax.top_k(scores + _at(stack["router_bias"], li), cfg.experts_per_tok)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    if LOWER != "no_scale":
        top = top * cfg.routed_scaling_factor
    onehot = chosen[:, :, None] == jnp.arange(scores.shape[-1])[None, None, :]
    return jnp.sum(jnp.where(onehot, top[:, :, None], 0.0), axis=1)


def held_part(cfg, stack, li, x, first: int = 0):
    """The part of the routed experts' sum that the experts held in `stack` give,
    `first` being the published index of its expert 0 (a share other than the
    first: the test that adds the shares up)."""
    gates = _gates(cfg, stack, li, x)

    def expert(e, out):
        y = _swiglu(x, *(_at(stack[n], li, e) for n in ("w1e", "w3e", "w2e")))
        return out + y * jax.lax.dynamic_index_in_dim(gates, first + e, 1, keepdims=True)

    return jax.lax.fori_loop(0, stack["w1e"].shape[1], expert, jnp.zeros_like(x))


def shared_part(stack, li, x):
    return _swiglu(x, *(_at(stack[n], li) for n in ("w1s", "w3s", "w2s")))


@partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg, dense: bool, stack, li, h):
    """One layer over h [T, D]; `stack` is the stacked tree the layer lives in
    and `li` its index there. Norms on the sub-layers' inputs."""
    h = h + _attention(cfg, stack, li, _rms(h, _at(stack["attn_norm"], li), cfg.norm_eps))
    x = _rms(h, _at(stack["ffn_norm"], li), cfg.norm_eps)
    if dense:
        return h + _swiglu(x, *(_at(stack[n], li) for n in ("w1", "w3", "w2")))
    return h + held_part(cfg, stack, li, x) + shared_part(stack, li, x)


# -- the model ---------------------------------------------------------------------------


def residual_stream(cfg, params, tokens: np.ndarray):
    """The residual stream after the last layer [T, D] (float32) of one
    unbatched sequence: what the final norm, and `mtp_logits`, read."""
    check(cfg)
    h = jnp.asarray(params["embed"])[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    k = cfg.first_dense_layers
    for li in range(k):
        h = _layer(cfg, True, params["dense_layers"], jnp.int32(li), h)
    for li in range(cfg.n_layers - k):
        h = _layer(cfg, False, params["layers"], jnp.int32(li), h)
    return h


def _head(cfg, params, norm_w, h, rows, cols) -> np.ndarray:
    out = _rms(h, jnp.asarray(norm_w, jnp.float32), cfg.norm_eps)[jnp.asarray(rows)]
    head = jnp.asarray(params["lm_head"])[:, jnp.asarray(cols)].astype(jnp.float32)
    return np.asarray(_mm(out, _lower(head)), np.float32)


def logits(cfg, params, tokens: np.ndarray, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """Logits [len(rows), len(cols)]: row t is the distribution over token t+1,
    cut to the token ids `cols`."""
    return _head(cfg, params, params["final_norm"], residual_stream(cfg, params, tokens), rows, cols)


def mtp_logits(cfg, params, mtp, tokens: np.ndarray, rows: np.ndarray,
               cols: np.ndarray) -> np.ndarray:
    """The multi-token-prediction module over one unbatched sequence: logits
    [len(rows), len(cols)], row t the distribution over token t+2 from the main
    model's residual stream at t and token t+1's embedding: h' = eh_proj
    [norm(h_t) ; norm(embed(x_{t+1}))], one latent-attention expert layer, the
    module's own final norm, the main model's head. The last position has no next
    token and takes token 0: ask for rows before it."""
    h = residual_stream(cfg, params, tokens)
    nxt = jnp.asarray(np.append(np.asarray(tokens)[1:], 0), jnp.int32)
    e = jnp.asarray(params["embed"])[nxt].astype(jnp.float32)
    x = _mm(jnp.concatenate([_rms(h, _at(mtp["hnorm"]), cfg.norm_eps),
                             _rms(e, _at(mtp["enorm"]), cfg.norm_eps)], axis=-1),
            _at(mtp["eh_proj"]))
    x = _layer(cfg, False, mtp["layers"], jnp.int32(0), x)
    return _head(cfg, params, mtp["final_norm"], x, rows, cols)
