"""A gated short convolution (LFM2's `conv` layer; Liquid AI, "LFM2 Technical
Report") as a layer kind of the hybrid decoder (models/hybrid.py): the mixing
half of a layer is

    [B | C | x] = u W_in                     (D -> 3 D, no bias, in this order)
    z_t = sum_{j < taps} w_j * (B * x)_{t - (taps-1) + j}
                                             (causal, depthwise, `conv_taps`
                                             taps a channel, no bias, NO
                                             activation)
    y   = (C * z) W_out                      (D -> D)

What a slot owns of such a layer is the convolution's tail alone: the last
`conv_taps - 1` rows of B * x, in the model's type. There is no matrix state,
no decay and no position: the "recurrence" between the convolution and the
output is the identity, and where the other kinds of `hybrid._RECURRENT` hand
a state S over, this one hands over None (an empty subtree to `jax.tree.map`).
The convolution's three forms are the delta-rule layer's own (models/kda.py:
`conv_step` on the pool's tails, `conv_chunk` for a prompt's chunk,
`conv_packed` for fresh prompts packed in one row), which return the
convolution before bias and activation: here that is the result.

The layer's parameters (stacked [Lc, ...] under params["conv"]): w_in [D, 3 D],
conv_w [taps, D] (tap j multiplies the product taps-1-j positions back), w_out
[D, D]."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .configs import ModelConfig
from .kda import conv_chunk, conv_step
from .quant import qdot


def init_conv_params(cfg: ModelConfig, key: jax.Array, dtype, n_layers: int) -> dict[str, Any]:
    """Seeded stacked [Lc, ...] weights: the two projections normal with
    fan-in scaling like every other linear, the taps normal with deviation
    taps**-0.5 as the other kinds draw theirs (the sum over the taps then keeps
    the product's scale)."""
    D, taps, L = cfg.dim, cfg.conv_taps, n_layers
    ks = jax.random.split(key, 3)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    return {"w_in": w(ks[0], (L, D, 3 * D), D), "conv_w": w(ks[1], (L, taps, D), taps),
            "w_out": w(ks[2], (L, D, D), D)}


def init_conv_state(cfg: ModelConfig, n_layers: int, slots: int, dtype) -> dict[str, jnp.ndarray]:
    """The pool: {"conv": [Lc, slots, (taps-1) D]}, a slot's tail rows end to
    end as the other kinds' pools hold theirs, and no "S"."""
    return {"conv": jnp.zeros((n_layers, slots, (cfg.conv_taps - 1) * cfg.dim), dtype)}


def zero_state(cfg: ModelConfig, rows: int, dtype) -> tuple[None, jnp.ndarray]:
    """(no matrix state, tail0 [rows, taps-1, D]) of fresh prompts."""
    return None, jnp.zeros((rows, cfg.conv_taps - 1, cfg.dim), dtype)


# The layer in the parts a step program composes it from, under the names the
# delta-rule layer gives them (models/kda.py says which is which).


def project(cfg: ModelConfig, kp: dict, x: jnp.ndarray):
    """x [..., D] -> (the convolution's input B * x [..., D], the gate C)."""
    D = cfg.dim
    proj = qdot(x, kp["w_in"])
    return proj[..., :D] * proj[..., 2 * D:], proj[..., D : 2 * D]


def operands(cfg: ModelConfig, kp: dict, mixed: jnp.ndarray, gate: jnp.ndarray):
    """The convolution's output is the recurrence's only operand."""
    return mixed, gate


def step_rows(cfg: ModelConfig, S, layer, slot_ids, live, ops):
    """One token a row: the identity, and no state to step."""
    return ops, S


def scan_packed(ops, valid, fresh, staged):
    """Fresh prompts packed in one row: the identity too (`conv_packed` has
    already kept each prompt's taps to its own tokens), and no states by chunk."""
    return ops, None


def output(cfg: ModelConfig, kp: dict, o, gate, dtype) -> jnp.ndarray:
    """z [..., D] and the gate C -> y [..., D]."""
    return qdot((gate * o).astype(dtype), kp["w_out"])


def conv_prefill(
    cfg: ModelConfig,
    kp: dict,  # this layer's weights (un-stacked)
    x: jnp.ndarray,  # [A, T, D] the layer's input of a chunk (or a whole prompt)
    nvalid: jnp.ndarray,  # [A] int32: valid positions of each row
    S0: None,
    tail0: jnp.ndarray,  # [A, taps-1, D]
) -> tuple[jnp.ndarray, None, jnp.ndarray]:
    """The layer over a chunk that continues `tail0`: (y [A, T, D], None, the
    tail as it stands after each row's `nvalid` positions)."""
    with jax.named_scope("conv_prefill"):
        bx, gate = project(cfg, kp, x)
        mixed, tail = conv_chunk(tail0, nvalid, bx, kp["conv_w"])
        return output(cfg, kp, mixed, gate, x.dtype), None, tail.astype(tail0.dtype)


def conv_decode(
    cfg: ModelConfig,
    kp: dict,
    x: jnp.ndarray,  # [Ba, D] the layer's input, one token a row
    state: dict,  # the pool (init_conv_state)
    layer: jnp.ndarray,  # int32 scalar: the pool's layer
    slot_ids: jnp.ndarray | None,  # [Ba] int32 pool rows; None: row b is slot b, all of them
    live: jnp.ndarray,  # [Ba] bool: a parked or padding row moves nothing
) -> tuple[jnp.ndarray, dict]:
    """One token through the layer on the pool's tails: (y [Ba, D], the pool)."""
    bx, gate = project(cfg, kp, x)
    mixed, conv, _ = conv_step(state["conv"], layer, slot_ids, live, bx, kp["conv_w"])
    return output(cfg, kp, mixed, gate, x.dtype), {"conv": conv}
