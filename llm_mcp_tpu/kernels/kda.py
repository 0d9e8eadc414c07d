"""One decode step of a recurrent layer on the per-slot state pool: the gated
delta rule, or a Mamba-2 state-space layer, which is the same step without the
delta rule's correction.

A recurrent layer keeps, for every sequence and head, a float32 state `S` of
[keys, values] (128 x 128 for Solar-Open2's KDA layers: 64 KB a head, 4 MB a
layer and slot; 96 x 192 for Olmo-Hybrid's Gated DeltaNet layers; 128 x 64 for
Granite-4.0-H's Mamba-2 layers, whose keys are the state dimension). A decode
step reads ALL of it and writes all of it back:

    S' = diag(alpha) S            alpha = exp(g) in (0, 1): a key channel's
                                  (KDA) or one a head (Gated DeltaNet, Mamba-2)
    u  = beta (v - S'^T k)        the delta rule's correction, rank 1; without
                                  `beta` (Mamba-2) the state takes the input as
                                  it is, u = v (there v = dt x)
    S  = S' + k u^T
    o  = S^T q

Mamba-2's keys and queries (its B and C) are ONE group: the same `dk` numbers
for every head of a row. They come as [Ba, dk], one lane row a batch row, and
the kernel turns the row into the column that multiplies the state's keys
(`_column`): no [Ba, H, dk] copy of them is made in HBM.

So the step is bound by the state's bytes, twice, and by nothing else. The
kernel aliases the pool to its output and rewrites only the tiles of the
rows in the batch, found through the slot ids (scalar prefetch: a compact
decode batch names its pool rows the way the attention kernels' cache rows
are named), at the layer the caller says. A row that is not live (a parked
slot, a compaction pad) gets its tile back unchanged.

**The pool's layout** is [layers, slots, H / P, dk, P dv]: P heads lie side by
side along the values (`heads_abreast`), P the least count for which P dv is
a multiple of the 128 lanes, so that a float32 tile of (8, 128) pads nothing
in HBM: P = 1 at dv = 128 (the layout is then [.., H, dk, dv] itself), P = 2
at dv = 192 (rows of 384 = 3 x 128; [.., 96, 192] alone would pad its rows to
256, a third more bytes in the pool and in every step). `pack_state` /
`unpack_state` go between a head-major [.., H, dk, dv] and that layout. Inside
a tile the P heads share every product but the broadcast of their own k, q
and decay along their own lanes (a select a head beyond the first).

`kda_decode_step_reference` is the same step in plain `jax.numpy`: what the
kernel is held to (tests/test_hybrid.py), and what shapes that Mosaic cannot
tile take on the chip (counted in `kernels.attention.reference_falls`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret, _note_fall

HEAD_BLOCK = 16  # tiles a grid cell at most: 16 x 64 KB in, as many out
TILE_BYTES = 1 << 20  # and at most this much state a cell and direction
LANES = 128


def heads_abreast(heads: int, dv: int) -> int:
    """P: heads side by side along the values in the pool's layout, the least
    count that makes a row a whole number of lanes; 1 where none divides the
    heads (such a pool pads in HBM and its step is the reference's)."""
    return next((p for p in range(1, heads + 1)
                 if heads % p == 0 and (p * dv) % LANES == 0), 1)


def pack_state(S: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """[..., H, dk, dv] -> [..., H / P, dk, P dv]."""
    if abreast == 1:
        return S
    *lead, H, dk, dv = S.shape
    S = S.reshape(*lead, H // abreast, abreast, dk, dv)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, H // abreast, dk, abreast * dv)


def unpack_state(S: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """[..., H / P, dk, P dv] -> [..., H, dk, dv]."""
    if abreast == 1:
        return S
    *lead, G, dk, W = S.shape
    S = S.reshape(*lead, G, dk, abreast, W // abreast)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, G * abreast, dk, W // abreast)


def kda_decode_step_reference(state, layer, slot_ids, live, q, k, v, alpha, beta=None):
    """(o [Ba, H, dv] f32, new state): the step above by gather and scatter,
    on the pool in its layout. Rows that are not live write back what they
    read. `alpha` [Ba, H, dk], or [Ba, H] for one decay a head; `q`, `k`
    [Ba, dk] are one group for every head; without `beta`, no delta rule."""
    P = v.shape[1] // state.shape[2]
    if q.ndim == 2:
        q, k = (jnp.broadcast_to(x[:, None, :], (*v.shape[:2], x.shape[-1])) for x in (q, k))
    if alpha.ndim == 2:
        alpha = jnp.broadcast_to(alpha[..., None], k.shape)
    S = unpack_state(state[layer][slot_ids], P)  # [Ba, H, dk, dv]
    Sd = S * alpha[..., :, None]
    if beta is None:
        u = v
    else:
        kS = jnp.einsum("bhk,bhkv->bhv", k, Sd, precision=jax.lax.Precision.HIGHEST)
        u = beta[..., None] * (v - kS)
    Sn = Sd + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, Sn, precision=jax.lax.Precision.HIGHEST)
    keep = jnp.where(live[:, None, None, None], Sn, S)
    return o, state.at[layer, slot_ids].set(pack_state(keep, P))


def _kda_step_kernel(
    layer_ref,  # [1] int32 (scalar prefetch): the pool's layer
    ids_ref,  # [Ba] int32 (scalar prefetch): pool row of each batch row
    live_ref,  # [Ba] int32 (scalar prefetch): 0 = leave the row's state alone
    qt_ref,  # [1, 1, dk, hb P] f32: this cell's heads, keys on sublanes;
    kt_ref,  # with one group for every head [1, 1, dk]: the row's, keys on lanes
    a_ref,  # alpha: the same layout a channel, or a row like beta's a head
    v_ref,  # [1, 1, hb, P dv] f32
    *rest,  # b_ref [1, 1, hb, P dv] f32: beta, broadcast along the values (the
    #         delta rule only); s_ref [1, 1, hb, dk, P dv] f32: the state tiles;
    #         o_ref [1, 1, hb, P dv] f32; so_ref, aliased to the pool
    hb: int,
    abreast: int,
    dv: int,
    head_decay: bool,
    delta: bool,
    one_group: bool,
):
    del layer_ref, ids_ref  # consumed by the index maps
    b_ref = rest[0] if delta else None
    s_ref, o_ref, so_ref = rest[-3:]
    live = live_ref[pl.program_id(0)] != 0
    P = abreast
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, P * dv), 1) if P > 1 else None

    def along_values(ref, j):
        """Tile j's heads' columns [dk, 1], each over its own head's lanes."""
        col = ref[0, 0, :, j * P : j * P + 1]  # [dk, 1]: broadcasts along the values
        for p in range(1, P):
            col = jnp.where(lane >= p * dv, ref[0, 0, :, j * P + p : j * P + p + 1], col)
        return col

    if one_group:
        k_all, q_all = _column(kt_ref[0]), _column(qt_ref[0])  # once a cell, for every tile

    for j in range(hb):
        S = s_ref[0, 0, j]  # [dk, P dv]
        k = k_all if one_group else along_values(kt_ref, j)
        Sd = S * (a_ref[0, 0, j : j + 1, :] if head_decay else along_values(a_ref, j))
        if delta:
            kS = jnp.sum(Sd * k, axis=0, keepdims=True)  # [1, P dv]
            u = b_ref[0, 0, j : j + 1, :] * (v_ref[0, 0, j : j + 1, :] - kS)
        else:
            u = v_ref[0, 0, j : j + 1, :]
        Sn = Sd + k * u
        o_ref[0, 0, j : j + 1, :] = jnp.sum(
            Sn * (q_all if one_group else along_values(qt_ref, j)), axis=0, keepdims=True)
        so_ref[0, 0, j] = jnp.where(live, Sn, S)


def _column(row: jnp.ndarray) -> jnp.ndarray:
    """A lane row [1, dk] as the column [dk, 1] that broadcasts along a state
    tile's values: the row on a diagonal, summed along the lanes (a transpose
    in three operations Mosaic is sure to take)."""
    dk = row.shape[-1]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def kda_decode_step(
    state: jnp.ndarray,  # [Lk, B, H / P, dk, P dv] f32: the pool, updated IN PLACE
    layer: jnp.ndarray,  # int32 scalar: which of the pool's layers
    slot_ids: jnp.ndarray,  # [Ba] int32: pool row of each batch row
    live: jnp.ndarray,  # [Ba] bool: rows whose state moves
    q: jnp.ndarray,  # [Ba, H, dk] f32, normalised and scaled; [Ba, dk]: one group
    k: jnp.ndarray,  # [Ba, H, dk] f32, normalised; [Ba, dk]: one group for every head
    v: jnp.ndarray,  # [Ba, H, dv] f32
    alpha: jnp.ndarray,  # [Ba, H, dk] f32 in (0, 1); [Ba, H] for one decay a head
    beta: jnp.ndarray | None = None,  # [Ba, H] f32; None: no delta rule, u = v
    *,
    name: str = "kda_decode_step",  # the Mosaic call's name in a trace
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(o [Ba, H, dv] f32, the pool with the batch's rows stepped)."""
    Lk, B, G, dk, W = state.shape
    Ba, H, dv = v.shape
    P = H // G
    interp = _interpret() if interpret is None else interpret
    tile = dk * W * state.dtype.itemsize
    hb = max(d for d in range(1, min(HEAD_BLOCK, G) + 1)
             if G % d == 0 and (d == 1 or d * tile <= TILE_BYTES))
    if not interp and (dk % 8 or W % LANES):
        _note_fall(name, f"H={H} dk={dk} dv={dv}: no legal tile", interp)
        return kda_decode_step_reference(
            state, layer, slot_ids, live, q, k, v, alpha, beta)
    Gb = G // hb
    head_decay, delta, one_group = alpha.ndim == 2, beta is not None, q.ndim == 2

    def keys_on_sublanes(x):  # [Ba, H, dk] -> [Ba, Gb, dk, hb P]
        return x.reshape(Ba, Gb, hb * P, dk).transpose(0, 1, 3, 2)

    def rows(x):  # [Ba, H, dv] -> [Ba, Gb, hb, P dv]: a tile's heads abreast
        return x.reshape(Ba, Gb, hb, W)

    def over_values(x):  # [Ba, H] -> the same, each head's scalar along its values
        return rows(jnp.broadcast_to(x[..., None], (Ba, H, dv)))

    vec = pl.BlockSpec((1, 1, dk, hb * P), lambda b, g, li, ids, lv: (b, g, 0, 0))
    shared = pl.BlockSpec((1, 1, dk), lambda b, g, li, ids, lv: (b, 0, 0))
    qk = shared if one_group else vec
    row = pl.BlockSpec((1, 1, hb, W), lambda b, g, li, ids, lv: (b, g, 0, 0))
    tiles = pl.BlockSpec(
        (1, 1, hb, dk, W), lambda b, g, li, ids, lv: (li[0], ids[b], g, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(
            _kda_step_kernel, hb=hb, abreast=P, dv=dv, head_decay=head_decay,
            delta=delta, one_group=one_group),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Ba, Gb),
            in_specs=[qk, qk, row if head_decay else vec, row, *([row] if delta else []), tiles],
            out_specs=[row, tiles],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Ba, Gb, hb, W), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands: layer=0, ids=1, live=2, q=3, k=4, alpha=5, v=6, (beta=7,) state last
        input_output_aliases={8 if delta else 7: 1},
        interpret=interp,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        slot_ids.astype(jnp.int32),
        live.astype(jnp.int32),
        q[:, None, :] if one_group else keys_on_sublanes(q),
        k[:, None, :] if one_group else keys_on_sublanes(k),
        over_values(alpha) if head_decay else keys_on_sublanes(alpha),
        rows(v),
        *([over_values(beta)] if delta else []),
        state,
    )
    return o.reshape(Ba, H, dv), new
