"""One decode step of the gated delta rule (KDA) on the per-slot state pool.

A linear-attention layer keeps, for every sequence and head, a float32 state
`S` of [keys, values] (128 x 128 at the published size: 64 KB a head, 4 MB a
layer and slot). A decode step reads ALL of it and writes all of it back:

    S' = diag(alpha) S            alpha = exp(g) in (0, 1), per key channel
    u  = beta (v - S'^T k)        the delta rule's correction, rank 1
    S  = S' + k u^T
    o  = S^T q

so the step is bound by the state's bytes, twice, and by nothing else. The
kernel aliases the pool to its output and rewrites only the tiles of the
rows in the batch, found through the slot ids (scalar prefetch: a compact
decode batch names its pool rows the way the attention kernels' cache rows
are named), at the layer the caller says. A row that is not live (a parked
slot, a compaction pad) gets its tile back unchanged.

`kda_decode_step_reference` is the same step in plain `jax.numpy`: what the
kernel is held to (tests/test_kda.py), and what shapes that Mosaic cannot tile
take on the chip (counted in `kernels.attention.reference_falls`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret, _note_fall

HEAD_BLOCK = 16  # heads a grid cell: 16 x 64 KB tiles in, as many out


def kda_decode_step_reference(state, layer, slot_ids, live, q, k, v, alpha, beta):
    """(o [Ba, H, dv] f32, new state): the step above by gather and scatter.
    Rows that are not live write back what they read."""
    S = state[layer][slot_ids]  # [Ba, H, dk, dv]
    Sd = S * alpha[..., :, None]
    kS = jnp.einsum("bhk,bhkv->bhv", k, Sd, precision=jax.lax.Precision.HIGHEST)
    u = beta[..., None] * (v - kS)
    Sn = Sd + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, Sn, precision=jax.lax.Precision.HIGHEST)
    keep = jnp.where(live[:, None, None, None], Sn, S)
    return o, state.at[layer, slot_ids].set(keep)


def _kda_step_kernel(
    layer_ref,  # [1] int32 (scalar prefetch): the pool's layer
    ids_ref,  # [Ba] int32 (scalar prefetch): pool row of each batch row
    live_ref,  # [Ba] int32 (scalar prefetch): 0 = leave the row's state alone
    qt_ref,  # [1, 1, dk, hb] f32: this cell's heads, keys on sublanes
    kt_ref,
    at_ref,  # alpha, the same layout
    v_ref,  # [1, hb, dv] f32
    b_ref,  # [1, hb, dv] f32: beta, broadcast along the values
    s_ref,  # [1, 1, hb, dk, dv] f32: the state tiles
    o_ref,  # [1, hb, dv] f32
    so_ref,  # aliased to the pool
    *,
    hb: int,
):
    del layer_ref, ids_ref  # consumed by the index maps
    live = live_ref[pl.program_id(0)] != 0
    for j in range(hb):
        S = s_ref[0, 0, j]  # [dk, dv]
        k = kt_ref[0, 0, :, j : j + 1]  # [dk, 1]: broadcasts along the values
        Sd = S * at_ref[0, 0, :, j : j + 1]
        kS = jnp.sum(Sd * k, axis=0, keepdims=True)  # [1, dv]
        u = b_ref[0, j : j + 1, :] * (v_ref[0, j : j + 1, :] - kS)
        Sn = Sd + k * u
        o_ref[0, j : j + 1, :] = jnp.sum(
            Sn * qt_ref[0, 0, :, j : j + 1], axis=0, keepdims=True
        )
        so_ref[0, 0, j] = jnp.where(live, Sn, S)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_step(
    state: jnp.ndarray,  # [Lk, B, H, dk, dv] f32: the pool, updated IN PLACE
    layer: jnp.ndarray,  # int32 scalar: which of the pool's layers
    slot_ids: jnp.ndarray,  # [Ba] int32: pool row of each batch row
    live: jnp.ndarray,  # [Ba] bool: rows whose state moves
    q: jnp.ndarray,  # [Ba, H, dk] f32, normalised and scaled
    k: jnp.ndarray,  # [Ba, H, dk] f32, normalised
    v: jnp.ndarray,  # [Ba, H, dv] f32
    alpha: jnp.ndarray,  # [Ba, H, dk] f32 in (0, 1)
    beta: jnp.ndarray,  # [Ba, H] f32
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(o [Ba, H, dv] f32, the pool with the batch's rows stepped)."""
    Lk, B, H, dk, dv = state.shape
    Ba = q.shape[0]
    interp = _interpret() if interpret is None else interpret
    hb = min(HEAD_BLOCK, H)
    if H % hb or (not interp and (dk % 128 or dv % 128 or hb % 8)):
        _note_fall("kda_decode_step", f"H={H} dk={dk} dv={dv}: no legal tile", interp)
        return kda_decode_step_reference(
            state, layer, slot_ids, live, q, k, v, alpha, beta)
    G = H // hb

    def keys_on_sublanes(x):  # [Ba, H, dk] -> [Ba, G, dk, hb]
        return x.reshape(Ba, G, hb, dk).transpose(0, 1, 3, 2)

    vec = pl.BlockSpec((1, 1, dk, hb), lambda b, g, li, ids, lv: (b, g, 0, 0))
    row = pl.BlockSpec((1, hb, dv), lambda b, g, li, ids, lv: (b, g, 0))
    tile = pl.BlockSpec(
        (1, 1, hb, dk, dv), lambda b, g, li, ids, lv: (li[0], ids[b], g, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(_kda_step_kernel, hb=hb),
        name="kda_decode_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Ba, G),
            in_specs=[vec, vec, vec, row, row, tile],
            out_specs=[row, tile],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Ba, H, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands: layer=0, ids=1, live=2, q=3, k=4, alpha=5, v=6, beta=7, state=8
        input_output_aliases={8: 1},
        interpret=interp,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        slot_ids.astype(jnp.int32),
        live.astype(jnp.int32),
        keys_on_sublanes(q),
        keys_on_sublanes(k),
        keys_on_sublanes(alpha),
        v,
        jnp.broadcast_to(beta[..., None], (Ba, H, dv)),
        state,
    )
    return o, new
