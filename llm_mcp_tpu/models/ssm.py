"""A Mamba-2 state-space layer (Dao, Gu, "Transformers are SSMs", arXiv
2405.21060) as the recurrent layer of the hybrid decoder (models/hybrid.py), as
Granite-4.0-H lays it out: H heads of P values each, a state of N keys a head,
B and C ONE group shared by every head.

    [z | xBC | dt] = x W_in                  (H P | H P + 2 N | H, in this order)
    xBC = SiLU(conv(xBC) + b_conv)           (causal, depthwise, `ssm_conv` taps)
    [x | B | C] = xBC                        (H P | N | N)
    dt = softplus(dt + dt_bias),  a = exp(dt A),  A = -exp(A_log)      per head
    S_t = a_t S_{t-1} + B_t (dt_t x_t)^T     S [N, P] a head
    y_t = S_t^T C_t + D x_t
    out = RMSNorm(y * SiLU(z)) W_out         the norm over the WHOLE H P width

This is the delta-rule layer's recurrence (models/kda.py) with keys B, queries
C, values dt x, one decay a head and NO correction: the state takes its input
as it is. So the two forms are that layer's own: prompts go through
`kda_chunk_scan` without its triangular solve (the chunk kernel of
kernels/kda.py under the name `ssd_chunk_scan`, B and C one group that no copy
a head is made of), one token through the state pool's kernel without the
delta rule (under the name `ssd_decode_step`), B and C handed over as one row
a batch row.

What a slot owns of a layer is S (float32, in the pool's layout: P' heads
abreast so that a row is a whole number of lanes) and the convolution's tail:
the last `ssm_conv - 1` rows of xBC before the convolution.

The layer's parameters (stacked [Ls, ...] under params["ssm"]; I = H P,
W = I + 2 N): w_in [D, I + W] (z | xBC), w_dt [D, H], conv_w [taps, W], conv_b
[W], dt_bias, A_log, D [H] (float32), norm [I], w_out [I, D]. W_in's last H
columns, dt's, are a leaf of their own: I + W + H is no whole number of 128
lanes (8512 at Granite-4.0-H's sizes), and the chip then lays a [D, I + W + H]
stack out with D minor and every step program copies it whole (1.2 GiB a decode
round, seen in the described-chip compile)."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.kda import heads_abreast, kda_decode_step
from ..ops.norms import rms_norm
from .configs import ModelConfig
from .kda import conv_chunk, conv_step, kda_chunk_scan, kda_packed_scan
from .quant import qdot

STEP_KERNEL = "ssd_decode_step"  # the one-step state kernel's name in a trace


def ssm_sizes(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """(heads, values a head, keys of the state, taps)."""
    return cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv


def conv_width(cfg: ModelConfig) -> int:
    """Channels of x | B | C, which the convolution runs over."""
    H, P, N, _ = ssm_sizes(cfg)
    return H * P + 2 * N


def state_abreast(cfg: ModelConfig) -> int:
    """Heads side by side in the pool's layout (kernels/kda.py)."""
    return heads_abreast(cfg.ssm_heads, cfg.ssm_head_dim)


def init_ssm_params(cfg: ModelConfig, key: jax.Array, dtype, n_layers: int) -> dict[str, Any]:
    """Seeded stacked [Ls, ...] weights. Projections are normal with fan-in
    scaling like every other linear; the decay's two leaves follow the
    state-space convention the delta-rule layers draw by (A = exp(A_log)
    log-uniform in [1, 16] a head, dt_bias the inverse softplus of a step
    log-uniform in [1e-3, 1e-1]); the skip D is ones, the convolution's bias
    normal with deviation 0.02, the gated norm's weight ones."""
    H, P, N, taps = ssm_sizes(cfg)
    D, inner, W, L = cfg.dim, H * P, conv_width(cfg), n_layers
    ks = jax.random.split(key, 7)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    dt = jnp.exp(jax.random.uniform(ks[2], (L, H), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "w_in": w(ks[0], (L, D, inner + W), D),
        "w_dt": w(ks[6], (L, D, H), D),
        "conv_w": w(ks[1], (L, taps, W), taps),
        "conv_b": (0.02 * jax.random.normal(ks[5], (L, W), jnp.float32)).astype(dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt), float32
        "A_log": jnp.log(jax.random.uniform(ks[3], (L, H), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((L, H), jnp.float32),
        "norm": jnp.ones((L, inner), dtype),
        "w_out": w(ks[4], (L, inner, D), inner),
    }


def init_ssm_state(cfg: ModelConfig, n_layers: int, slots: int, dtype) -> dict[str, jnp.ndarray]:
    """The pool: {"S": f32 [Ls, slots, H / P', N, P' P] (P' heads abreast),
    "conv": [Ls, slots, (taps-1) W]}, members and layout as the delta-rule
    layers' pool has them (models/kda.py:init_kda_state says why)."""
    H, P, N, taps = ssm_sizes(cfg)
    ab = state_abreast(cfg)
    return {
        "S": jnp.zeros((n_layers, slots, H // ab, N, ab * P), jnp.float32),
        "conv": jnp.zeros((n_layers, slots, (taps - 1) * conv_width(cfg)), dtype),
    }


def zero_state(cfg: ModelConfig, rows: int, dtype) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(S0 [rows, H / P', N, P' P] f32: the pool's layout, tail0 [rows, taps-1, W])
    of fresh prompts."""
    H, P, N, taps = ssm_sizes(cfg)
    abreast = state_abreast(cfg)
    return (jnp.zeros((rows, H // abreast, N, abreast * P), jnp.float32),
            jnp.zeros((rows, taps - 1, conv_width(cfg)), dtype))


def _project(cfg: ModelConfig, kp: dict, x: jnp.ndarray):
    """x [..., D] -> (z [..., I], xBC [..., W], dt [..., H]) = x W_in."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    proj = qdot(x, kp["w_in"])
    return proj[..., :inner], proj[..., inner:], qdot(x, kp["w_dt"])


def _inputs(cfg: ModelConfig, kp: dict, mixed: jnp.ndarray, dt: jnp.ndarray):
    """The convolution's output [..., W] and the raw steps [..., H] -> float32
    (x [..., H, P], B, C [..., N], dt [..., H], the log decay dt A [..., H])."""
    H, P, N, _ = ssm_sizes(cfg)
    a = jax.nn.silu(mixed.astype(jnp.float32) + kp["conv_b"].astype(jnp.float32))
    x = a[..., : H * P].reshape(*a.shape[:-1], H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + kp["dt_bias"].astype(jnp.float32))
    g = dt * -jnp.exp(kp["A_log"].astype(jnp.float32))
    return x, a[..., H * P : H * P + N], a[..., H * P + N :], dt, g


def _out(cfg: ModelConfig, kp: dict, o, x, z, dtype) -> jnp.ndarray:
    """The state's output o and the heads' input x [..., H, P] f32, the gate z
    [..., I] -> the layer's output [..., D]."""
    y = o + kp["D"].astype(jnp.float32)[:, None] * x
    y = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(jnp.float32))
    return qdot(rms_norm(y, kp["norm"], cfg.norm_eps).astype(dtype), kp["w_out"])


# The layer in the parts a step program composes it from, under the names the
# delta-rule layer gives them (models/kda.py says which is which).


def project(cfg: ModelConfig, kp: dict, x: jnp.ndarray):
    """x [..., D] -> (the convolution's input xBC [..., W], (z, dt))."""
    z, xBC, dt = _project(cfg, kp, x)
    return xBC, (z, dt)


def operands(cfg: ModelConfig, kp: dict, mixed: jnp.ndarray, side):
    """The convolution's output [..., W] and `project`'s (z, dt) -> (the
    recurrence's (x, B, C, dt, g), float32; (x, z) for `output`)."""
    z, dt = side
    ops = _inputs(cfg, kp, mixed, dt)
    return ops, (ops[0], z)


def step_rows(cfg: ModelConfig, S, layer, slot_ids, live, ops):
    """One token a row on the pool's states: (o [Ba, H, P], the states)."""
    xh, B, C, dt, g = ops
    return kda_decode_step(
        S, layer, slot_ids, live, C, B, dt[..., None] * xh, jnp.exp(g), name=STEP_KERNEL)


def _masked(ops, valid):
    """The recurrence's operands (q, k, v, g, no beta) with `valid` [A, T] (a
    row's tokens) applied: a padding position leaves the state as it was, no
    decay and no input."""
    xh, B, C, dt, g = ops
    valid = valid[..., None]  # [A, T, 1]
    g = jnp.where(valid, g, 0.0)
    v = jnp.where(valid[..., None], dt[..., None] * xh, 0.0)
    return C[:, :, None, :], B[:, :, None, :], v, g, None


def scan_rows(ops, valid, S0):
    """Prompts [A, T, ...] in chunks from S0, `valid` [A, T] their tokens:
    `kda_chunk_scan`'s returns."""
    return kda_chunk_scan(*_masked(ops, valid), S0)


def scan_packed(ops, valid, fresh, staged):
    """Fresh prompts packed in one row [1, T, ...]: `kda_packed_scan`'s returns."""
    return kda_packed_scan(*_masked(ops, valid), fresh, staged)


def output(cfg: ModelConfig, kp: dict, o, side, dtype) -> jnp.ndarray:
    """The state's output o [..., H, P] and `operands`' (x, z) -> y [..., D]."""
    return _out(cfg, kp, o, *side, dtype)


def ssm_prefill(
    cfg: ModelConfig,
    kp: dict,  # this layer's weights (un-stacked)
    x: jnp.ndarray,  # [A, T, D] the layer's input of a chunk (or a whole prompt)
    nvalid: jnp.ndarray,  # [A] int32: valid positions of each row
    S0: jnp.ndarray,  # [A, H / P', N, P' P] f32: the pool's layout
    tail0: jnp.ndarray,  # [A, taps-1, W]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The layer over a chunk that continues (S0, tail0): (y [A, T, D], S,
    tail), the last two as they stand after each row's `nvalid` positions."""
    T = x.shape[1]
    with jax.named_scope("ssd_prefill"):
        xBC, side = project(cfg, kp, x)
        mixed, tail = conv_chunk(tail0, nvalid, xBC, kp["conv_w"])
        ops, side = operands(cfg, kp, mixed, side)
        o, S = scan_rows(ops, jnp.arange(T)[None, :] < nvalid[:, None], S0)
        return output(cfg, kp, o, side, x.dtype), S, tail.astype(tail0.dtype)


def ssm_decode(
    cfg: ModelConfig,
    kp: dict,
    x: jnp.ndarray,  # [Ba, D] the layer's input, one token a row
    state: dict,  # the pool (init_ssm_state)
    layer: jnp.ndarray,  # int32 scalar: the pool's layer
    slot_ids: jnp.ndarray | None,  # [Ba] int32 pool rows; None: row b is slot b, all of them
    live: jnp.ndarray,  # [Ba] bool: a parked or padding row moves nothing
) -> tuple[jnp.ndarray, dict]:
    """One token through the layer on the pool: (y [Ba, D], the pool)."""
    xBC, side = project(cfg, kp, x)
    mixed, conv, slot_ids = conv_step(state["conv"], layer, slot_ids, live, xBC, kp["conv_w"])
    ops, side = operands(cfg, kp, mixed, side)
    o, S = step_rows(cfg, state["S"], layer, slot_ids, live, ops)
    return output(cfg, kp, o, side, x.dtype), {"S": S, "conv": conv}


