"""Plain float32 reference forward for the K-EXAONE family (`model_type`
`exaone_moe`): softmax GQA layers of two kinds mixed by a per-layer list of
window sizes (a window layer sees its last `sliding_window` positions and
rotates its queries and keys; a global layer, window 0, sees every earlier
position and does not rotate), a q/k norm a head, every sub-layer's norm on
its OUTPUT, a leading dense feed-forward layer and after it routed experts
(sigmoid router with a selection bias, gates renormalised and then times
`routed_scaling_factor`) plus one shared expert, of which the chip under test
may hold a SHARE: the router scores all published experts, the experts [0,
num_experts held) add their part, and what the absent ones would add is left
out, here as in the program. And the multi-token-prediction module's forward
(`mtp_logits`), DeepSeek-V3's form.

Written from ISSUE 43's equations (the configuration's file lists what they
assume). One unbatched sequence goes through one layer at a time in float32
`jax.numpy` at `Precision.HIGHEST`: attention is one [T, T] score matrix a
head with the window as a MASK over a full-length sequence (no cache, no
ring), every held expert is applied to every row and weighted by the row's
gate for it (0 where not chosen): no kernels, no grouped products, no
batching. It imports nothing from llm_mcp_tpu/models or kernels and shares with
them only the names of the parameter tree:

    params["embed"] [V, D], ["final_norm"] [D], ["lm_head"] [D, V]
    params["first"]: the k leading dense layers, a list, each layer's own
        leaves unstacked: attn_norm, ffn_norm [D], the attention's (below),
        w1, w3 [D, Fd], w2 [Fd, D]
    params["layers"], stacked over the L - k expert layers: attn_norm,
        ffn_norm [D], router [D, Er], router_bias [Er], w1e, w3e [E, D, F], w2e
        [E, F, D] (the E experts held), w1s, w3s [D, F], w2s [F, D] (the shared
        expert)
    params["gqa"], stacked over the global layers among them in order, and
        params["win"], over the window layers: wq [D, H hd], wk, wv [D, Hkv hd],
        wo [H hd, D], q_norm, k_norm [hd]
    the module (`mtp_logits`): hnorm, enorm, final_norm [D], eh_proj [2 D, D],
        "layers" and "gqa" as above, stacked [1, ...]

Departures from the published description, none a change of the mathematics:

- The layer is ONE jitted function a kind that indexes the stacked tree
  (benchmark/reference.py's way): callers pad the sequence to a fixed length;
  causal, so what follows a row does not move it.
- Rotation is the split-half form (a head's first half paired with its
  second), the released code's `rotate_half`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
# The controls of SERVED_TOL_REL (below): the same forward with one thing the
# configuration states left out or lowered. None is the reference. "fp8"
# rounds every matrix and the left operand of every product to float8 e4m3 (the
# nearest floating precision under the stated bfloat16); "no_window" lets a
# window layer see every earlier position; "rope_global" rotates the global
# layers too; "no_scale" leaves `routed_scaling_factor` out of the gates;
# "lost_ring" hides from a window layer's query every EARLIER position that is a
# multiple of `RING` from `RING` on: what a ring of that length loses when the
# index a wrapped position lands on is read one turn late (one key of every
# window once the ring has wrapped). Set only by scripts/solar_tolerance.py
# (read when a layer is traced: clear jax's caches after a change), never by
# run.py.
LOWER: str | None = None
CONTROLS = ("fp8", "no_window", "rope_global", "no_scale", "lost_ring")
RING = 128


def _lower(x):
    if LOWER == "fp8":  # saturating, as a conversion to float8 is
        x = jnp.clip(x, -448.0, 448.0)
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    return x


def _mm(a, b):
    return jnp.matmul(_lower(a), b, precision=_HI)


# Served tokens against this forward, as a share of a row's max |logit|
# (`correctness.hold_to_reference`; one reading a seed = the worst of the 16
# served tokens of the reference request, a prompt of 700 bytes: five windows,
# every ring wrapped before the first decode step; the prompt goes through the
# 1 x 768 admit program). Both readings of BENCHMARK.json's rule, by
# scripts/solar_tolerance.py --config k-exaone-236b-ep8-bf16 (my chip runs, PR
# 43, call 2, TPU v5 lite, the published widths, seeds 4300002000-23):
# - the program, 24 seeds: median 0.003, the largest 0.186, every seed correct.
#   Not rounding in the dense sense: as in Solar-Open2's share, the bfloat16
#   stream now and then moves a router's eighth choice, and where the expert is
#   one of the 16 held the row's feed-forward output changes by a whole gated
#   expert times 2.5.
# - float8 (LOWER = "fp8", the nearest floating precision under the stated
#   bfloat16), 8 of those seeds: 1.081-1.776, every seed not correct. The limit
#   lies between the two with room on both sides: 2.4 times the program's
#   largest, 0.42 of float8's smallest.
# - the four structural controls, on the same 8 seeds, by this limit: the window
#   ignored 0.209-0.800 (median 0.354, 3 of 8 not correct); rope on the global
#   layer 0.000-0.137, the 2.5 left out 0.000-0.147, a lost ring position
#   0.000-0.128: every seed still correct, all three INSIDE the program's own
#   band. Sixteen greedy tokens cannot tell them from the program: a reading is
#   non-zero only where a fault changes a token, and these move a logit by less
#   than the router's own noise does. What holds them is the comparison of
#   LOGITS: at the tiny preset (tests/test_exaone_moe.py: the program through
#   float32 caches agrees to 1e-4, through the int8 cache and rings to a median
#   of 0.06, and each control moves the median by 0.13 to 3.9) and at the
#   published widths on the chip (LOGIT_TOL_REL, below); the cell's `why` says
#   that `correct` does not hold the ring.
SERVED_TOL_REL = 0.45

# The step programs' LOGITS against this forward at the published widths and the
# cell's cache shapes (scripts/logit_hold.py: a prompt of 707 tokens through the
# engine's 1 x 768 admit program into a used slot of the 64 x 4096 int8 cache and
# its rings, then 384 teacher-forced decode steps, three turns of the ring,
# through both attention arms and the append kernels; a row's largest difference
# over the row's largest |logit|, one reading = the MEDIAN over the 384 rows). My
# chip run, PR 43, second session, call 1, TPU v5 lite, seeds 4300003000-01:
# - the program: 0.0232 and 0.0233 (by turn of the ring 0.0216, 0.0232-0.0240,
#   0.0230-0.0239: no worse after a wrap); a tenth of the rows read 0.10-0.11 and
#   the worst 0.20-0.24: the router's moved choices, which the median leaves out.
# - the controls, every one OUTSIDE on both seeds: rope on the global layer
#   0.128-0.136, a lost ring position 0.155-0.160, the 2.5 left out 0.156-0.157,
#   the window ignored 1.11-1.19, float8 1.30-1.31.
# The limit is 2.6 times the program's larger reading and 0.47 of the weakest
# control's smaller. Not a limit of `correct`: run.py does not read it.
LOGIT_TOL_REL = 0.06

# -- what the configuration's file states beyond run.py's own tables -----------


def _kinds(c) -> list[str]:
    return ["sliding_attention" if w else "full_attention" for w in _windows(c)]


def _windows(c) -> list[int]:
    return list(c.sliding_windows) or [0] * c.n_layers


def _pattern(c) -> str:
    """One period of the layers' kinds as letters, L a window layer and G a
    global one, read off the published (uncut) order: the cut keeps the
    model's first layers, so its list is a prefix of the period repeated."""
    wins = _windows(c)
    for p in range(1, len(wins) + 1):
        if all(bool(wins[i]) == bool(wins[i % p]) for i in range(len(wins))) and not wins[p - 1]:
            return "".join("L" if w else "G" for w in wins[:p])
    return "".join("L" if w else "G" for w in wins)


HELD = {
    "num_experts": lambda c: c.n_experts,
    # the router keeps the published width while num_experts counts the held
    "published.num_experts": lambda c: c.router_width,
    "num_shared_experts": lambda c: c.n_shared_experts,
    "scoring_func": lambda c: c.router_score,
    "layer_types": _kinds,
    "sliding_windows": _windows,
    "sliding_window_pattern": _pattern,
    "mlp_layer_types": lambda c: ["dense"] * c.first_dense_layers
    + ["sparse"] * (c.n_layers - c.first_dense_layers),
    "num_nextn_predict_layers": lambda c: c.mtp_layers,
    "rope_parameters.rope_theta": lambda c: c.rope_theta,
}
ONLY = {
    "rope_parameters.rope_type": "default",  # plain frequencies, no scaling
    "mtp_layer_types": ["full_attention"],  # the module's one layer sees every position
    "mtp_sliding_windows": [0],
}
STATED: dict[str, str] = {}


def check(cfg) -> None:
    """Raises for a configuration these equations do not cover."""
    if not cfg.gqa_layers or cfg.recurrent_kind != "win" or not cfg.sliding_window:
        raise NotImplementedError(f"{cfg.name!r} mixes no window and global layers: not this family")
    if (not cfg.use_rope or cfg.global_rope or cfg.router_score != "sigmoid" or not cfg.qk_norm
            or cfg.qk_norm_whole or cfg.norm_placement != "output" or not cfg.n_experts
            or cfg.rope_factor > 1.0 or sorted(cfg.gqa_layers) != [
                i for i, w in enumerate(_windows(cfg)) if not w]
            or any(w not in (0, cfg.sliding_window) for w in _windows(cfg))):
        raise NotImplementedError(f"no plain K-EXAONE reference for {cfg.name!r}")
    if (cfg.kv_lora_rank or cfg.attn_softcap or cfg.logit_softcap or cfg.post_norms
            or cfg.norm_weight_offset or cfg.embed_scale or cfg.qkv_bias or cfg.attn_gate
            or cfg.act != "silu" or cfg.tie_embeddings or cfg.attn_multiplier
            or cfg.embed_multiplier != 1.0 or cfg.residual_multiplier != 1.0
            or cfg.logits_divisor != 1.0 or cfg.query_pre_attn_scalar):
        raise NotImplementedError(f"no plain K-EXAONE reference for {cfg.name!r}")


# -- the tree ----------------------------------------------------------------------


def _at(leaf, *index):
    for i in index:
        leaf = jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
    leaf = leaf.astype(jnp.float32)
    return _lower(leaf) if leaf.ndim == 2 else leaf


def _rms(x, w, eps: float):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _swiglu(x, gate_w, up_w, down_w):
    g = _mm(x, gate_w)
    return _mm(g * _sigmoid(g) * _mm(x, up_w), down_w)


def _rotate(x, theta: float):
    """x [T, heads, hd] at positions 0..T-1: pairs (i, i + hd/2) turned by
    position / theta**(2 i / hd)."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# -- the two kinds of attention --------------------------------------------------------


def _attention(cfg, stack, li, x, window: int):
    """Causal softmax attention of one layer: a window layer (`window` > 0)
    rotates and sees positions p - window + 1 .. p, a global one neither."""
    T = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = _mm(x, _at(stack["wq"], li)).reshape(T, H, hd)
    k = _mm(x, _at(stack["wk"], li)).reshape(T, Hkv, hd)
    v = _mm(x, _at(stack["wv"], li)).reshape(T, Hkv, hd)
    q = _rms(q, _at(stack["q_norm"], li), cfg.norm_eps)
    k = _rms(k, _at(stack["k_norm"], li), cfg.norm_eps)
    if window or LOWER == "rope_global":
        q, k = _rotate(q, cfg.rope_theta), _rotate(k, cfg.rope_theta)
    pos = jnp.arange(T)
    back = pos[:, None] - pos[None, :]  # how far behind the query the key lies
    seen = back >= 0
    if window and LOWER != "no_window":
        seen = seen & (back < window)
    if window and LOWER == "lost_ring":
        seen = seen & ~((pos[None, :] % RING == 0) & (pos[None, :] >= RING) & (back > 0))
    heads = []
    for head in range(H):  # one head at a time: [T, T] scores
        kv = head // (H // Hkv)
        s = jnp.where(seen, _mm(q[:, head], k[:, kv].T) * hd**-0.5, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        heads.append(_mm(p / jnp.sum(p, axis=-1, keepdims=True), v[:, kv]))
    return _mm(jnp.concatenate(heads, axis=-1), _at(stack["wo"], li))


# -- the feed-forward ------------------------------------------------------------------


def _experts(cfg, stack, li, x):
    """This chip's part of the routed experts' sum, and the shared expert."""
    scores = _sigmoid(_mm(x, _at(stack["router"], li)))  # [T, Er]: every published expert
    _, chosen = jax.lax.top_k(scores + _at(stack["router_bias"], li), cfg.experts_per_tok)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) if cfg.norm_topk_prob else top
    if LOWER != "no_scale":
        top = top * cfg.routed_scaling_factor
    onehot = chosen[:, :, None] == jnp.arange(scores.shape[-1])[None, None, :]
    gates = jnp.sum(jnp.where(onehot, top[:, :, None], 0.0), axis=1)  # [T, Er]

    def expert(e, out):  # e < the experts held here
        y = _swiglu(x, *(_at(stack[n], li, e) for n in ("w1e", "w3e", "w2e")))
        return out + y * jax.lax.dynamic_index_in_dim(gates, e, 1, keepdims=True)

    out = jax.lax.fori_loop(0, stack["w1e"].shape[1], expert, jnp.zeros_like(x))
    return out + _swiglu(x, *(_at(stack[n], li) for n in ("w1s", "w3s", "w2s")))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(cfg, window: int, dense: bool, layers, mixing, li, mi, h):
    """One layer over h [T, D]: `layers` holds the norms and the feed-forward
    and `li` the layer's index in it; `mixing` is params["gqa"] or
    params["win"] and `mi` the layer's index among its kind. Norms on the
    sub-layers' outputs."""
    h = h + _rms(_attention(cfg, mixing, mi, h, window), _at(layers["attn_norm"], li), cfg.norm_eps)
    y = (_swiglu(h, *(_at(layers[n], li) for n in ("w1", "w3", "w2"))) if dense
         else _experts(cfg, layers, li, h))
    return h + _rms(y, _at(layers["ffn_norm"], li), cfg.norm_eps)


def residual_stream(cfg, params, tokens: np.ndarray):
    """The residual stream after the last layer [T, D] (float32) of one
    unbatched sequence: what the final norm, and `mtp_logits`, read."""
    check(cfg)
    h = jnp.asarray(params["embed"])[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    seen = {"gqa": 0, "win": 0}
    zero = jnp.int32(0)
    for li, window in enumerate(_windows(cfg)):
        kind = "win" if window else "gqa"
        if li < cfg.first_dense_layers:  # a whole layer of its own, as a stack of one
            one = jax.tree.map(lambda a: jnp.asarray(a)[None], params["first"][li])
            h = _layer(cfg, window, True, one, one, zero, zero, h)
            continue
        h = _layer(cfg, window, False, params["layers"], params[kind],
                   jnp.int32(li - cfg.first_dense_layers), jnp.int32(seen[kind]), h)
        seen[kind] += 1
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
    [norm(h_t) ; norm(embed(x_{t+1}))], one layer of the expert kind with global
    attention, the module's own final norm, the main model's head. The last
    position has no next token and takes token 0: ask for rows before it."""
    h = residual_stream(cfg, params, tokens)
    nxt = jnp.asarray(np.append(np.asarray(tokens)[1:], 0), jnp.int32)
    e = jnp.asarray(params["embed"])[nxt].astype(jnp.float32)
    x = _mm(jnp.concatenate([_rms(h, _at(mtp["hnorm"]), cfg.norm_eps),
                             _rms(e, _at(mtp["enorm"]), cfg.norm_eps)], axis=-1),
            _at(mtp["eh_proj"]))
    x = _layer(cfg, 0, False, mtp["layers"], mtp["gqa"], jnp.int32(0), jnp.int32(0), x)
    return _head(cfg, params, mtp["final_norm"], x, rows, cols)
