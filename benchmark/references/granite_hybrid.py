"""Plain float32 reference forward for the Granite-4.0-H family
(`granitemoehybrid` without routed experts): Mamba-2 state-space layers, a
softmax attention layer without positional encoding at every `gqa_layers` index
(the sixth of every ten), a dense gated feed-forward in every layer, the
embedding table tied to the head, and Granite's four multipliers.

Written from ISSUE 41's equations (Hugging Face `GraniteMoeHybrid*`; Dao, Gu,
"Transformers are SSMs", arXiv 2405.21060, for the state-space layer). One
unbatched sequence goes through one layer at a time in float32 `jax.numpy` at
`Precision.HIGHEST`: the recurrence is a `lax.scan` over TOKENS (no chunks),
attention is one [T, T] score matrix a head: no cache, no state pool, no
kernels, no batching. It imports nothing from llm_mcp_tpu/models and shares
with them only the names of the parameter tree:

    params["embed"] [V, D] (also the head: tied), ["final_norm"] [D]
    params["layers"], every layer, stacked [L, ...]: attn_norm, ffn_norm [D],
        w1, w3 [D, F], w2 [F, D]
    params["gqa"], stacked over the attention layers in order: wq [D, H hd],
        wk, wv [D, Hkv hd], wo [H hd, D]
    params["ssm"], stacked over the state-space layers in order (I = Hs P,
        W = I + 2 N): w_in [D, I + W] (z | x B C), w_dt [D, Hs], conv_w [taps, W]
        (tap j multiplies the projection taps-1-j positions back), conv_b [W],
        dt_bias, A_log, D [Hs], norm [I], w_out [I, D]

    h0     = E[token] * embedding_multiplier
    layer:   h = h + r Mix(RMSNorm(h)); h = h + r W2 (SiLU(n W1) * (n W3)),
             n = RMSNorm(h), r = residual_multiplier
    mamba:   [z | xBC | dt] = x W_in; xBC = SiLU(conv(xBC) + b_conv) (causal,
             depthwise, 4 taps); [x | B | C] = xBC;
             dt = softplus(dt + dt_bias), a = exp(-exp(A_log) dt) a head;
             S_t = a_t S_{t-1} + B_t (dt_t x_t)^T (S [N, P] a head);
             y_t = S_t^T C_t + D x_t; out = RMSNorm(y * SiLU(z)) W_out, the norm
             over the WHOLE inner width with one weight vector
    attention: q, k, v = x Wq, x Wk, x Wv (no bias, no rotation); scores
             q k^T * attention_multiplier (NOT head_dim**-0.5); causal softmax; Wo
    logits = RMSNorm(h) E^T / logits_scaling

Departures from the published description, none a change of the mathematics:

- W1 and W3 are the source's one `input_linear` cut in two (first half the
  gate); `shared_intermediate_size` is their width, and with
  `num_local_experts` 0 nothing is added to them. Likewise the source's one
  `in_proj` is stored as its first I + W columns (z | x B C) and its last Hs
  (dt): a product by columns is the columns' products.
- The source clamps dt to `time_step_limit`, whose default (0, inf) clamps
  nothing: no clamp here.
- The source's kernel works in blocks of `mamba_chunk_size` positions; the
  recurrence here is token by token, which is what a block computes.
- The layer is ONE jitted function a kind that indexes the stacked tree
  (benchmark/reference.py's way): callers pad the sequence to a fixed length;
  causal, so what follows a row does not move it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
# The controls of SERVED_TOL_REL (below): the same forward in a precision under
# what the configuration states (bfloat16 weights and activations, a float32
# state), or with a layer's memory gone. None is the reference. "int8" rounds
# every matrix to 8 bits with one scale a column and the left operand of every
# product with one a row; "fp8" rounds both to float8 e4m3; "state_bf16" rounds
# the recurrent state to bfloat16 after every token; "lost_state" empties the
# FIRST state-space layer's state before every token. Set only by
# scripts/solar_tolerance.py and the tests (read when a layer is traced: clear
# jax's caches after a change), never by run.py.
LOWER: str | None = None
CONTROLS = ("int8", "fp8", "state_bf16", "lost_state")


def _lower(x, axis: int):
    """`x` in the control's precision; `axis` is the one an int8 scale spans."""
    if LOWER == "fp8":  # saturating, as a conversion to float8 is: e4m3's largest finite is 240 here
        return jax.lax.reduce_precision(jnp.clip(x, -240.0, 240.0), exponent_bits=4, mantissa_bits=3)
    if LOWER == "int8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return jnp.round(x / scale) * scale
    return x


def _mm(a, b):
    return jnp.matmul(_lower(a, -1), b, precision=_HI)


# Served tokens against this forward, as a share of a row's max |logit|. Both
# readings of BENCHMARK.json's rule, by scripts/solar_tolerance.py --config
# granite-4.0-h-micro-bf16 (my chip runs, PR 41, call 3, TPU v5 lite, the
# published widths): the harness's own request (a prompt of 200 bytes, 16
# greedy tokens through cache and state pool) is served, and
# `correctness.hold_to_reference` holds the served tokens to this forward as it
# is and computed under each control (`LOWER`); one reading a seed = the worst
# of the 16 tokens.
# - the program, 96 seeds (3200006000-95): median 0.010, the largest 0.049,
#   every seed correct. Rounding alone, as Olmo-Hybrid's (0.073 over 128 seeds):
#   a dense model has no router whose choice a bfloat16 hidden state could flip.
# - float8 (LOWER = "fp8", the nearest floating precision under the stated
#   bfloat16, saturating), 16 seeds: 1.042-1.594, every seed not correct. A lost
#   state ("lost_state", the first state-space layer's), 16 seeds: 0.680-1.874,
#   every seed not correct. The limit lies between the program's largest and
#   the smallest of these with room on both sides: 4 times the one, under a
#   third of the other.
# - what the limit does NOT refuse on every seed: int8 weights and activations
#   (LOWER = "int8"), 16 seeds: 0.082-0.519, 14 of 16 not correct; a bfloat16
#   state ("state_bf16"): 0.000-0.047, every seed still correct, inside the
#   program's own band. The configuration's file therefore holds the two
#   precisions by `program.expect` (`weights_dtype`, `state_dtype`), which
#   run.py's comparison refuses when the engine reports another. SO `correct`
#   DOES NOT HOLD THE STATE'S PRECISION BY A COMPUTED NUMBER: a program that
#   reported float32 and kept a bfloat16 state would pass. A limit on served
#   logits (the engine's logprobs of the served tokens against this forward's)
#   would tell them apart; `benchmark/correctness.py` compares tokens alone, and
#   changing it is a `benchmark` PR's (PERF.md section 7).
# - the controls never round the embedding table (`logits`, `hidden_states`):
#   drawn 12 times smaller than a fan-in matrix (the configuration's `assumed`),
#   it underflows float8 to zeros, every logit reads 0 and every token passes
#   with a regret of 0 (call 2 read float8 as 0.000 on 8 of 8 seeds that way).
SERVED_TOL_REL = 0.2

# -- what the configuration's file states beyond run.py's own tables -----------

KINDS = {"gqa": "attention", "ssm": "mamba"}

HELD = {
    "layer_types": lambda c: [KINDS[k] for k in c.layer_period] * (c.n_layers // len(c.layer_period)),
    "attention_multiplier": lambda c: c.attn_scale,
    "embedding_multiplier": lambda c: c.embed_multiplier,
    "residual_multiplier": lambda c: c.residual_multiplier,
    "logits_scaling": lambda c: c.logits_divisor,
    "mamba_n_heads": lambda c: c.ssm_heads,
    "mamba_d_head": lambda c: c.ssm_head_dim,
    "mamba_d_state": lambda c: c.ssm_state,
    "mamba_d_conv": lambda c: c.ssm_conv,
    "mamba_expand": lambda c: c.ssm_heads * c.ssm_head_dim / c.dim,
    # the gate | up product's width: the dense MLP every layer has
    "shared_intermediate_size": lambda c: c.ffn_hidden,
}
ONLY = {
    "mamba_n_groups": 1,  # B and C are one group for every head (models/ssm.py)
    "mamba_conv_bias": True,  # the convolution always adds its bias
    "mamba_proj_bias": False,  # W_in and W_out have none
    "num_local_experts": 0,  # no routed part: the feed-forward is the shared MLP alone
    "position_embedding_type": "nope",  # nothing rotates (use_rope False, checked below)
    "normalization_function": "rmsnorm",
}
STATED = {
    "mamba_chunk_size": "the source kernel's block of positions; the recurrence a block computes is "
                        "the same for any block, and the program's chunk is what its buckets divide",
}


def check(cfg) -> None:
    """Raises for a configuration these equations do not cover."""
    if not cfg.gqa_layers or not cfg.ssm_heads:
        raise NotImplementedError(f"{cfg.name!r} has no state-space layers: not this family")
    if (cfg.use_rope or cfg.attn_gate or cfg.n_experts or cfg.norm_placement != "input"
            or cfg.qk_norm or not cfg.tie_embeddings):
        raise NotImplementedError(f"no plain Granite-4.0-H reference for {cfg.name!r}")
    if (cfg.kv_lora_rank or cfg.sliding_window or cfg.attn_softcap or cfg.logit_softcap
            or cfg.post_norms or cfg.norm_weight_offset or cfg.embed_scale or cfg.qkv_bias
            or cfg.act != "silu"):
        raise NotImplementedError(f"no plain Granite-4.0-H reference for {cfg.name!r}")


# -- the tree ----------------------------------------------------------------------


def _at(leaf, *index):
    for i in index:
        leaf = jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
    leaf = leaf.astype(jnp.float32)
    return _lower(leaf, 0) if leaf.ndim == 2 else leaf


def _rms(x, w, eps: float):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


# -- the two kinds of mixing ---------------------------------------------------------


def _attention(cfg, stack, li, x):
    """Causal softmax attention, no positional encoding, scores times
    `attention_multiplier`."""
    T = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = _mm(x, _at(stack["wq"], li)).reshape(T, H, hd)
    k = _mm(x, _at(stack["wk"], li)).reshape(T, Hkv, hd)
    v = _mm(x, _at(stack["wv"], li)).reshape(T, Hkv, hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    heads = []
    for head in range(H):  # one head at a time: [T, T] scores
        kv = head // (H // Hkv)
        s = jnp.where(causal, _mm(q[:, head], k[:, kv].T) * cfg.attn_multiplier, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        heads.append(_mm(p / jnp.sum(p, axis=-1, keepdims=True), v[:, kv]))
    return _mm(jnp.concatenate(heads, axis=-1), _at(stack["wo"], li))


def _mamba(cfg, stack, li, x, lost: bool):
    """The Mamba-2 recurrence, one token after another (`lost`: the control
    that empties this layer's state before every token)."""
    T = x.shape[0]
    H, P, N, taps = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
    inner, W = H * P, H * P + 2 * N
    proj = _mm(x, _at(stack["w_in"], li))  # [T, I + W]
    z, xBC, dt = proj[:, :inner], proj[:, inner:], _mm(x, _at(stack["w_dt"], li))
    back = jnp.concatenate([jnp.zeros((taps - 1, W), jnp.float32), xBC])
    conv_w = _at(stack["conv_w"], li)
    xBC = _silu(sum(back[j : j + T] * conv_w[j] for j in range(taps)) + _at(stack["conv_b"], li))
    xh = xBC[:, :inner].reshape(T, H, P)
    B, C = xBC[:, inner : inner + N], xBC[:, inner + N :]
    f = dt + _at(stack["dt_bias"], li)  # [T, H]
    dt = jnp.maximum(f, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(f)))  # softplus
    a = jnp.exp(-jnp.exp(_at(stack["A_log"], li)) * dt)  # [T, H]

    def token(S, xs):  # S [H, N keys, P values]
        xh, B, C, dt, a = xs
        if lost:
            S = jnp.zeros_like(S)
        S = S * a[:, None, None] + B[None, :, None] * (dt[:, None] * xh)[:, None, :]
        if LOWER == "state_bf16":  # not a pair of converts: the compiler may drop those
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("k,hkv->hv", C, S, precision=_HI)

    _, y = jax.lax.scan(token, jnp.zeros((H, N, P), jnp.float32), (xh, B, C, dt, a))
    y = (y + _at(stack["D"], li)[:, None] * xh).reshape(T, inner) * _silu(z)
    return _mm(_rms(y, _at(stack["norm"], li), cfg.norm_eps), _at(stack["w_out"], li))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(cfg, kind: str, lost: bool, layers, mixing, li, mi, h):
    """One layer over h [T, D]: `layers` holds what every layer has and `li`
    the layer's index, `mixing` is params["gqa"] or params["ssm"] and `mi`
    the layer's index among its kind. Norms on the sub-layers' inputs, both
    residual adds times `residual_multiplier`."""
    r = cfg.residual_multiplier
    x = _rms(h, _at(layers["attn_norm"], li), cfg.norm_eps)
    mixed = _attention(cfg, mixing, mi, x) if kind == "gqa" else _mamba(cfg, mixing, mi, x, lost)
    h = h + r * mixed
    n = _rms(h, _at(layers["ffn_norm"], li), cfg.norm_eps)
    w1, w3, w2 = (_at(layers[k], li) for k in ("w1", "w3", "w2"))
    return h + r * _mm(_silu(_mm(n, w1)) * _mm(n, w3), w2)


def hidden_states(cfg, params, tokens: np.ndarray):
    """Final-normed hidden states [T, D] (float32) of one unbatched sequence."""
    check(cfg)
    h = jnp.asarray(params["embed"])[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    h = h * cfg.embed_multiplier
    seen = {"gqa": 0, "ssm": 0}
    for li in range(cfg.n_layers):
        kind = "gqa" if li in cfg.gqa_layers else "ssm"
        lost = LOWER == "lost_state" and kind == "ssm" and seen[kind] == 0
        h = _layer(cfg, kind, lost, params["layers"], params[kind],
                   jnp.int32(li), jnp.int32(seen[kind]), h)
        seen[kind] += 1
    return _rms(h, jnp.asarray(params["final_norm"], jnp.float32), cfg.norm_eps)


def logits(cfg, params, tokens: np.ndarray, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """Logits [len(rows), len(cols)]: row t is the distribution over token t+1,
    cut to the token ids `cols`; the head is the embedding table, tied."""
    out = hidden_states(cfg, params, tokens)[jnp.asarray(rows)]
    # the table is never rounded by a control, on the way in or here: drawn 12
    # times smaller than a fan-in matrix, it would underflow float8 to all zeros
    head = jnp.asarray(params["embed"])[jnp.asarray(cols)].astype(jnp.float32).T
    return np.asarray(_mm(out, head) / cfg.logits_divisor, np.float32)
