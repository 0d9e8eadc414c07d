"""Kimi Delta Attention (KDA): a gated delta-rule linear-attention layer with a
per-channel decay, as a layer of the hybrid decoder (models/hybrid.py).

For every sequence and head the layer keeps a float32 state S of
[keys dk, values dv] instead of keys and values of the past:

    q, k, v = SiLU(conv(x Wq)), SiLU(conv(x Wk)), SiLU(conv(x Wv))
              (causal depthwise convolution, `lin_conv` taps a channel)
    q, k    = q / |q| * dk**-0.5, k / |k|            (L2 over the head)
    g_t     = -exp(A_log_h) * softplus(x Wf_down Wf_up + dt_bias)   per channel
    beta_t  = sigmoid(x W_beta) (* 2 with `lin_neg_eigval`)          per head
    S'      = diag(exp(g_t)) S_{t-1}
    S_t     = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t     = S_t^T q_t
    y       = (RMSNorm_head(o_t) * sigmoid(x Wg_down Wg_up)) Wo

What a slot owns of a layer is S and the convolution's tail: the last
`lin_conv - 1` rows of x [Wq | Wk | Wv] (before the convolution), so that the
next token, or the next chunk of a prompt, continues where this one stopped.

Two forms of one recurrence:

- `kda_chunk_scan`, for prompts: the sequence in chunks of `CHUNK` tokens, a
  `lax.scan` that carries S from chunk to chunk. Inside a chunk the rank-1
  corrections depend on each other only through a unit lower-triangular
  system, solved once a chunk; the rest is products of [C, d] blocks. Every
  decay enters as exp of a difference of cumulative log decays that is <= 0
  (a later position against an earlier one), never as a quotient of two
  exponentials, so a channel that forgets fast cannot overflow.
- one token (`kda_decode`): the Pallas kernel of kernels/kda.py on the pool.

The layer's parameters (stacked [Lk, ...] under params["kda"]; C = H dk):
wqkv_lin [D, 3C], conv_w [taps, 3C], wfg_down [D, 2r] (decay | output gate),
wf_up [r, C], wg_up [r, C], dt_bias [C], A_log [H], w_beta [D, H],
o_norm [dv], wo_lin [C, D]."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.kda import kda_decode_step
from ..ops.norms import rms_norm
from .configs import ModelConfig
from .quant import qdot

CHUNK = 32  # tokens a chunk of the prompt form; a chunk length divides the bucket
_HI = jax.lax.Precision.HIGHEST
_L2_EPS = 1e-6


def kda_sizes(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """(heads, head size, gate rank, taps). The low rank of the decay and
    output gates is the head size (Kimi Linear's convention): no configuration
    states another, so it is no field."""
    d = cfg.lin_head_dim
    return cfg.lin_heads, d, d, cfg.lin_conv


def init_kda_params(cfg: ModelConfig, key: jax.Array, dtype, n_layers: int) -> dict[str, Any]:
    """Seeded stacked [Lk, ...] weights. Projections are normal with fan-in
    scaling like every other linear. The decay's two leaves follow the
    state-space convention so that no channel is degenerate: A = exp(A_log)
    log-uniform in [1, 16] a head, and dt_bias the inverse softplus of a step
    log-uniform in [1e-3, 1e-1] a channel: with the low-rank projection's
    unit-variance output on top, alpha = exp(g) spreads from about 0.1 (a
    channel that forgets within a few tokens) to 0.999 (one that remembers
    for a thousand)."""
    H, d, r, taps = kda_sizes(cfg)
    D, C, L = cfg.dim, H * d, n_layers
    ks = jax.random.split(key, 10)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    dt = jnp.exp(jax.random.uniform(ks[6], (L, C), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "wqkv_lin": w(ks[0], (L, D, 3 * C), D),
        "conv_w": w(ks[1], (L, taps, 3 * C), taps),
        "wfg_down": w(ks[2], (L, D, 2 * r), D),
        "wf_up": w(ks[3], (L, r, C), r),
        "wg_up": w(ks[4], (L, r, C), r),
        "w_beta": w(ks[5], (L, D, H), D),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt), float32
        "A_log": jnp.log(jax.random.uniform(ks[7], (L, H), jnp.float32, 1.0, 16.0)),
        "o_norm": jnp.ones((L, d), dtype),
        "wo_lin": w(ks[8], (L, C, D), C),
    }


def init_kda_state(cfg: ModelConfig, n_layers: int, slots: int, dtype) -> dict[str, jnp.ndarray]:
    """The pool: {"S": f32 [Lk, slots, H, dk, dv], "conv": [Lk, slots, taps-1, 3C]}."""
    H, d, _, taps = kda_sizes(cfg)
    return {
        "S": jnp.zeros((n_layers, slots, H, d, d), jnp.float32),
        "conv": jnp.zeros((n_layers, slots, taps - 1, 3 * H * d), dtype),
    }


def _gates(cfg: ModelConfig, kp: dict, x: jnp.ndarray):
    """x [..., D] -> (g [..., H, dk] f32 log decay <= 0, beta [..., H] f32,
    output gate [..., C] in x's dtype)."""
    H, d, r, _ = kda_sizes(cfg)
    low = qdot(x, kp["wfg_down"])
    f = qdot(low[..., :r], kp["wf_up"]).astype(jnp.float32) + kp["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(kp["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f.reshape(*f.shape[:-1], H, d))
    beta = jax.nn.sigmoid(qdot(x, kp["w_beta"]).astype(jnp.float32))
    if cfg.lin_neg_eigval:
        beta = beta * 2.0
    out_gate = jax.nn.sigmoid(qdot(low[..., r:], kp["wg_up"]).astype(jnp.float32)).astype(x.dtype)
    return g, beta, out_gate


def _heads(cfg: ModelConfig, mixed: jnp.ndarray):
    """SiLU(conv(.)) rows [..., 3C] -> q, k, v [..., H, d] float32, q and k
    L2-normalised a head and q scaled by d**-0.5."""
    H, d, _, _ = kda_sizes(cfg)
    a = jax.nn.silu(mixed.astype(jnp.float32)).reshape(*mixed.shape[:-1], 3, H, d)
    q, k, v = a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)

    return unit(q) * d**-0.5, unit(k), v


def _out(cfg: ModelConfig, kp: dict, o: jnp.ndarray, out_gate: jnp.ndarray, dtype) -> jnp.ndarray:
    """o [..., H, dv] f32 -> the layer's output [..., D]."""
    o = rms_norm(o, kp["o_norm"], cfg.norm_eps).astype(dtype)
    return qdot(o.reshape(*o.shape[:-2], -1) * out_gate, kp["wo_lin"])


def kda_chunk_scan(q, k, v, g, beta, S0):
    """The recurrence over a whole (padded) sequence, chunk by chunk.

    q, k [A, T, H, dk], v [A, T, H, dv], g [A, T, H, dk] (log decay, 0 at a
    padding position), beta [A, T, H] (0 at a padding position), S0
    [A, H, dk, dv]; all float32. Returns (o [A, T, H, dv], S_T). A padding
    position leaves the state as it was (alpha 1, beta 0) and its output is
    never read.

    Inside a chunk, with G the cumulative log decay (inclusive) and
    kk[t, s] = sum_d k_t k_s exp(G_t - G_s) for s < t, the corrections U solve
    (I + diag(beta) kk) U = beta (V - (K exp(G)) S0); then
    o = (Q exp(G)) S0 + qk U with qk[t, s] likewise for s <= t, and
    S_end = exp(G_end) S0 + (K exp(G_end - G))^T U."""
    A, T, H, dk = q.shape
    C = math.gcd(T, CHUNK)
    N = T // C

    def chunks(x):  # [A, T, H, ...] -> [N, A, H, C, ...]
        x = x.reshape(A, N, C, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    t_idx = jnp.arange(C)
    earlier = t_idx[:, None] > t_idx[None, :]
    eye = jnp.eye(C, dtype=jnp.float32)

    def step(S, xs):
        q, k, v, g, beta = xs  # [A, H, C, .]; beta [A, H, C, 1]
        G = jnp.cumsum(g, axis=2)
        # decay from position s to position t >= s, per channel: exp(<= 0)
        decay = jnp.exp(jnp.minimum(G[:, :, :, None, :] - G[:, :, None, :, :], 0.0))
        kd = k[:, :, None, :, :] * decay  # [A, H, t, s, dk]
        kk = jnp.where(earlier, jnp.sum(k[:, :, :, None, :] * kd, axis=-1), 0.0)
        qk = jnp.where(earlier | eye.astype(bool),
                       jnp.sum(q[:, :, :, None, :] * kd, axis=-1), 0.0)
        eG = jnp.exp(G)
        rhs = beta * (v - jnp.matmul(k * eG, S, precision=_HI))
        U = jax.scipy.linalg.solve_triangular(
            eye + beta * kk, rhs, lower=True, unit_diagonal=True)  # [A, H, C, dv]
        o = jnp.matmul(q * eG, S, precision=_HI) + jnp.matmul(qk, U, precision=_HI)
        k_out = k * jnp.exp(G[:, :, -1:, :] - G)  # carries a correction to the chunk's end
        S = eG[:, :, -1, :, None] * S + jnp.einsum(
            "ahck,ahcv->ahkv", k_out, U, precision=_HI)
        return S, o

    S, o = jax.lax.scan(
        step, S0, (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta[..., None])))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)  # [A, N, C, H, dv]
    return o.reshape(A, T, H, v.shape[-1]), S


def kda_prefill(
    cfg: ModelConfig,
    kp: dict,  # this layer's weights (un-stacked)
    x: jnp.ndarray,  # [A, T, D] normed activations of a chunk (or a whole prompt)
    nvalid: jnp.ndarray,  # [A] int32: valid positions of each row
    S0: jnp.ndarray,  # [A, H, dk, dv] f32
    tail0: jnp.ndarray,  # [A, taps-1, 3C]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The layer over a chunk that continues (S0, tail0): (y [A, T, D], S,
    tail), the last two as they stand after each row's `nvalid` positions."""
    A, T, _ = x.shape
    taps = cfg.lin_conv
    with jax.named_scope("kda_prefill"):
        proj = qdot(x, kp["wqkv_lin"])  # [A, T, 3C]
        full = jnp.concatenate([tail0.astype(proj.dtype), proj], axis=1)  # [A, taps-1+T, 3C]
        mixed = sum(
            full[:, j : j + T] * kp["conv_w"][j].astype(proj.dtype) for j in range(taps))
        tail = jax.vmap(
            lambda rows, n: jax.lax.dynamic_slice_in_dim(rows, n, taps - 1, axis=0)
        )(full, nvalid)  # rows [n, n + taps - 1) of `full` are the last taps-1 projections
        q, k, v = _heads(cfg, mixed)
        g, beta, out_gate = _gates(cfg, kp, x)
        valid = (jnp.arange(T)[None, :] < nvalid[:, None])[..., None]  # [A, T, 1]
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid, beta, 0.0)
        o, S = kda_chunk_scan(q, k, v, g, beta, S0)
        return _out(cfg, kp, o, out_gate, x.dtype), S, tail.astype(tail0.dtype)


def kda_decode(
    cfg: ModelConfig,
    kp: dict,
    x: jnp.ndarray,  # [Ba, D] normed activations, one token a row
    state: dict,  # the pool (init_kda_state)
    layer: jnp.ndarray,  # int32 scalar: the pool's layer
    slot_ids: jnp.ndarray,  # [Ba] int32 pool rows
    live: jnp.ndarray,  # [Ba] bool: a parked or padding row moves nothing
) -> tuple[jnp.ndarray, dict]:
    """One token through the layer on the pool: (y [Ba, D], the pool)."""
    proj = qdot(x, kp["wqkv_lin"])  # [Ba, 3C]
    tail = state["conv"][layer, slot_ids]  # [Ba, taps-1, 3C]
    full = jnp.concatenate([tail.astype(proj.dtype), proj[:, None]], axis=1)  # [Ba, taps, 3C]
    mixed = jnp.sum(full * kp["conv_w"].astype(proj.dtype)[None], axis=1)
    new_tail = jnp.where(live[:, None, None], full[:, 1:].astype(tail.dtype), tail)
    conv = state["conv"].at[layer, slot_ids].set(new_tail)
    q, k, v = _heads(cfg, mixed)
    g, beta, out_gate = _gates(cfg, kp, x)
    o, S = kda_decode_step(state["S"], layer, slot_ids, live, q, k, v, jnp.exp(g), beta)
    return _out(cfg, kp, o, out_gate, x.dtype), {"S": S, "conv": conv}
