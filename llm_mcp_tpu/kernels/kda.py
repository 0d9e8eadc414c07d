"""A recurrent layer's state kernels: one decode step on the per-slot state
pool, and a prompt's chunked recurrence. The layer is the gated delta rule, or
a Mamba-2 state-space layer, which is the same recurrence without the delta
rule's correction.

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

**A prompt's recurrence, `chunk_scan`**, is the chunk form of models/kda.py
(`kda_chunk_scan` has the mathematics) where the decay is ONE A HEAD (Gated
DeltaNet; Mamba-2 without the delta rule: the names `gdn_chunk_scan` and
`ssd_chunk_scan` in a trace), as one call a layer: a grid of (rows, blocks of
tiles, chunks), the chunks last and sequential. A block's state stays in VMEM
from chunk to chunk: it comes from HBM once (a row's state before, in the
pool's layout) or never (a fresh sequence: zero, and a chunk marked `fresh`
zeroes it in place), and goes to HBM once a sequence, to the row of `states`
the caller names for it, in the pool's layout. A chunk behind the `staged`
ones (scalar prefetch) is not run and fetches nothing: its o is 0. Inside a
chunk a tile's P heads share every product but their own decay, which scales
rows (Q and K one group: the product itself is shared, `(Q e^G) S = e^G (Q
S)`) or operands (a head each: a product a head over the tile's whole width,
each head's lanes selected from its own). Every product has float32 operands
and float32 sums (`HIGHEST`). The chunk's positions ride the sublanes of q, v
and o and the lanes of k (handed over transposed: the state's update is K^T
U); v and o are [.., C, H dv], the layer's own layout, a tile's heads a lane
block. The delta rule's system (I + diag(beta) kk) of a chunk depends on no
state: it is inverted, and the inverse's columns scaled by beta, for every
chunk at once, outside the call (`_unit_lower_inverse`), and the sequential
part is U = T (V - (K e^G) S). What the kernel is
held to and what a shape no tile fits takes on the chip (chunks or keys off
whole sublanes, a state row off whole lanes; counted in
`kernels.attention.reference_falls` under the kernel's name) is the loop of
`models/kda.py:_chunk_step`; a decay a key channel (KDA) is another algorithm
inside the chunk and never comes here.
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
# tiles a cell of the chunk kernel: its body is unrolled a tile, and what a step
# program's trace and lowering cost, which no compile cache keeps, grows with it
# (16 tiles a cell: 17 s more on the serve thread and 35 s of Granite's set-up, PR 50)
CHUNK_TILES = 2
LANES = 128
_HI = jax.lax.Precision.HIGHEST  # float32 operands, float32 sums


def heads_abreast(heads: int, dv: int) -> int:
    """P: heads side by side along the values in the pool's layout, the least
    count that makes a row a whole number of lanes; 1 where none divides the
    heads (such a pool pads in HBM and its step is the reference's)."""
    return next((p for p in range(1, heads + 1)
                 if heads % p == 0 and (p * dv) % LANES == 0), 1)


def _tiles_a_cell(tiles: int, tile_bytes: int, most: int = HEAD_BLOCK) -> int:
    """State tiles a grid cell takes: the largest divisor of a row's tiles
    inside `most` and `TILE_BYTES`."""
    return max(d for d in range(1, min(most, tiles) + 1)
               if tiles % d == 0 and (d == 1 or d * tile_bytes <= TILE_BYTES))


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
    hb = _tiles_a_cell(G, dk * W * state.dtype.itemsize)
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


# ---------------------------------------------------------------------------
# The chunk form of the recurrence with ONE DECAY A HEAD, for prompts
# ---------------------------------------------------------------------------


def chunk_scan_tiles(C: int, dk: int, W: int) -> bool:
    """Whether Mosaic can tile the chunk kernel's blocks: chunks of whole
    sublanes, keys of whole sublanes, a state row of whole lanes."""
    return not (C % 8 or dk % 8 or W % LANES)


def _chunk_scan_kernel(
    staged_ref,  # [1] int32 (scalar prefetch): chunks that hold tokens, the rest are not run
    fresh_ref,  # [N] int32 (scalar prefetch): 1 = the chunk starts a sequence, from zero state
    at_ref,  # [A N] int32 (scalar prefetch): consumed by the index maps
    *refs,  # q, kT[, k], v, gc, gr[, T][, S0]; o, states; the carried state
    hb: int,
    abreast: int,
    dv: int,
    delta: bool,
    one_group: bool,
    from_state: bool,
):
    del at_ref
    it = iter(refs)
    q_ref = next(it)  # [1, 1, hb P, C, dk] f32, or [1, 1, 1, C, dk]: one group for every head
    kt_ref = next(it)  # the keys with the chunk's positions on lanes: [1, 1, hb P | 1, dk, C]
    k_ref = next(it) if delta else None  # and on sublanes, like q (the delta rule only)
    v_ref = next(it)  # [1, 1, C, hb P dv]: the tiles' heads abreast along the lanes
    gc_ref = next(it)  # [1, 1, 1, C, hb P]: G, the chunk's cumulative log decay, a column a head
    gr_ref = next(it)  # [1, 1, 1, hb P, C]: and a row a head
    t_ref = next(it) if delta else None  # [1, 1, hb P, C, C]: (I + diag(beta) kk)^-1 diag(beta)
    s0_ref = next(it) if from_state else None  # [1, hb, dk, P dv]
    o_ref, so_ref, s_scr = it  # [1, 1, C, hb P dv]; [1, hb, dk, P dv]; VMEM [hb, dk, P dv]
    n = pl.program_id(2)
    P, W = abreast, abreast * dv
    C = v_ref.shape[2]

    def dot(a, b):
        return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)

    @pl.when(n == 0)
    def _():
        s_scr[...] = s0_ref[0] if from_state else jnp.zeros(s_scr.shape, jnp.float32)

    @pl.when(jnp.logical_and(n > 0, fresh_ref[n] != 0))
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, jnp.float32)

    @pl.when(n >= staged_ref[0])
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(staged_ref[0] == 0)  # nothing is run: the one row of `states` the cells name reads 0
    def _():
        so_ref[...] = jnp.zeros(so_ref.shape, jnp.float32)

    @pl.when(n < staged_ref[0])
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) if P > 1 else None
        t_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        s_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)

        def abreast_of(parts):
            """A tile's P heads' own values, each over its own head's lanes."""
            out = parts[0]
            for p in range(1, P):
                out = jnp.where(lane >= p * dv, parts[p], out)
            return out

        if one_group:
            q, kT = q_ref[0, 0, 0], kt_ref[0, 0, 0]
            qk_all = dot(q, kT)  # [C, C], every head's before its decay
            k = k_ref[0, 0, 0] if delta else None
        for j in range(hb):
            heads = range(j * P, (j + 1) * P)
            S = s_scr[j]  # [dk, P dv]
            V = v_ref[0, 0, :, j * W : (j + 1) * W]  # [C, P dv]
            gc = [gc_ref[0, 0, 0, :, h : h + 1] for h in heads]  # [C, 1] a head
            g_end = [gc_ref[0, 0, 0, C - 1 : C, h : h + 1] for h in heads]  # [1, 1]
            # decay from position s to position t >= s: exp(<= 0)
            decay = [jnp.exp(jnp.minimum(c - gr_ref[0, 0, 0, h : h + 1, :], 0.0))
                     for c, h in zip(gc, heads)]  # [C, C]
            eG = [jnp.exp(c) for c in gc]
            if one_group:  # a head's decay scales the shared product's rows: (Q e^G) S = e^G (Q S)
                eG_v = abreast_of(eG)  # [C, P dv] once broadcast
            else:
                qs = [q_ref[0, 0, h] for h in heads]
                kTs = [kt_ref[0, 0, h] for h in heads]
            qk = [jnp.where(t_idx >= s_idx, (qk_all if one_group else dot(qs[p], kTs[p])) * decay[p], 0.0)
                  for p in range(P)]
            if delta:
                # U = T (V - (K e^G) S), T the chunk's system inverted, times diag(beta)
                kS = (eG_v * dot(k, S) if one_group else
                      abreast_of([dot(k_ref[0, 0, h] * e, S) for h, e in zip(heads, eG)]))
                U = abreast_of([dot(t_ref[0, 0, h], V - kS) for h in heads])
            else:
                U = V
            qS = (eG_v * dot(q, S) if one_group else
                  abreast_of([dot(qs[p] * eG[p], S) for p in range(P)]))
            o_ref[0, 0, :, j * W : (j + 1) * W] = qS + abreast_of([dot(m, U) for m in qk])
            # a correction carried to the chunk's end, and the state decayed to it
            if one_group:
                kU = dot(kT, U * abreast_of([jnp.exp(e - c) for e, c in zip(g_end, gc)]))
            else:
                kU = abreast_of([dot(kTs[p] * jnp.exp(g_end[p] - gr_ref[0, 0, 0, h : h + 1, :]), U)
                                 for p, h in enumerate(heads)])
            S = abreast_of([jnp.exp(e) for e in g_end]) * S + kU
            s_scr[j] = S
            so_ref[0, j] = S


def _unit_lower_inverse(M: jnp.ndarray) -> jnp.ndarray:
    """The inverses of unit lower-triangular systems [..., C, C], C a power of
    two, block by block: [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]
    from blocks of 1 up. X, the inverse of the diagonal blocks of size s, is
    block diagonal, so X - X B X with B the blocks below them is the next
    size's: log2 C levels of two batched float32 products each.
    (`solve_triangular` takes the systems one after another on the chip: 2.4 us
    a system, 0.59 ms a layer for a mixed step's 240.)"""
    C = M.shape[-1]
    t, u = jnp.arange(C)[:, None], jnp.arange(C)[None, :]

    def below(s):  # the blocks of size s under the diagonal blocks of size s, pair by pair
        return jnp.where((t // (2 * s) == u // (2 * s)) & (t % (2 * s) >= s) & (u % (2 * s) < s), M, 0.0)

    X = jnp.eye(C, dtype=M.dtype) - below(1)  # blocks of 1 are their own inverses
    s = 2
    while s < C:
        X = X - jnp.matmul(jnp.matmul(X, below(s), precision=_HI), X, precision=_HI)
        s *= 2
    return X


@functools.partial(jax.jit, static_argnames=("chunk", "rows", "name", "interpret"))
def chunk_scan(
    q: jnp.ndarray,  # [A, T, H, dk] f32, normalised and scaled; [A, T, 1, dk]: one group
    k: jnp.ndarray,  # [A, T, H, dk] f32, normalised; [A, T, 1, dk]: one group for every head
    v: jnp.ndarray,  # [A, T, H, dv] f32
    g: jnp.ndarray,  # [A, T, H] f32: the log decay, one a head, 0 at a padding position
    beta: jnp.ndarray | None,  # [A, T, H] f32, 0 at a padding position; None: no delta rule
    S0: jnp.ndarray | None,  # [A, H / P, dk, P dv] f32: each row's state before; None: zero
    fresh: jnp.ndarray,  # [T / chunk] bool: chunks that start from zero state
    staged: jnp.ndarray,  # int32 scalar: the chunks that are run, the first so many
    at: jnp.ndarray,  # [A, T / chunk] int32: the row of `states` a chunk's state is for
    *,
    chunk: int,
    rows: int,  # of `states`
    name: str,  # the Mosaic call's name in a trace
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(o [A, T, H, dv] f32, states [rows, H / P, dk, P dv] f32 in the pool's
    layout): the chunked recurrence over the first `staged` chunks of every
    row. Row at[a, n] of `states` holds the state after the LAST chunk run
    that names it (the chunks that name a row lie side by side); a row no run
    chunk names is not written, but that `staged` 0 leaves 0 in the row chunk 0
    names (`at` is a row of `states` for EVERY chunk, run or not). o is 0
    behind the staged chunks."""
    A, T, H, dv = v.shape
    Hq, dk = q.shape[2:]
    C, N = chunk, T // chunk
    P = heads_abreast(H, dv)
    G, W = H // P, P * dv
    delta, one_group = beta is not None, Hq == 1
    hb = _tiles_a_cell(G, dk * W * 4, CHUNK_TILES)
    Gb, hp = G // hb, hb * P

    def chunked(x):  # [A, T, Hq, d] -> [A, N, Hq, C, d]
        return x.reshape(A, N, C, *x.shape[2:]).transpose(0, 1, 3, 2, 4)

    def columns(x):  # [A, N, C, H] -> [A, N, Gb, C, hb P]: a column a head
        return x.reshape(A, N, C, Gb, hp).transpose(0, 1, 3, 2, 4)

    qc, kc = chunked(q), chunked(k)
    Gc = jnp.cumsum(g.reshape(A, N, C, H), axis=2)
    operands = [qc, jnp.swapaxes(kc, -1, -2), *([kc] if delta else []), v.reshape(A, N, C, H * dv),
                columns(Gc), jnp.swapaxes(columns(Gc), -1, -2)]
    staged = jnp.asarray(staged, jnp.int32).reshape(1)

    def run(n, st):  # a chunk behind the staged ones names the last staged: nothing new is fetched
        return jnp.maximum(jnp.minimum(n, st[0] - 1), 0)

    def spec(block, heads_at, behind=False):
        """An operand's block of a (row, chunk): the cell's head block on axis
        `heads_at` (None: one group for every head); `behind`: o's, which is
        written behind the staged chunks too."""
        def index(a, gb, n, st, fr, at):
            i = [a, n if behind else run(n, st)] + [0] * (len(block) - 2)
            if heads_at is not None:
                i[heads_at] = gb
            return tuple(i)

        return pl.BlockSpec(block, index)

    hq, heads_at = (1, None) if one_group else (hp, 2)
    qk, qkT = spec((1, 1, hq, C, dk), heads_at), spec((1, 1, hq, dk, C), heads_at)
    vals_in, vals_out = spec((1, 1, C, hb * W), 3), spec((1, 1, C, hb * W), 3, behind=True)
    col, row = spec((1, 1, 1, C, hp), 2), spec((1, 1, 1, hp, C), 2)
    in_specs = [qk, qkT, *([qk] if delta else []), vals_in, col, row]
    if delta:
        # the chunk's system (I + diag(beta) kk), kk the keys' products under the
        # decay strictly below the diagonal, depends on no state: inverted, and the
        # inverse's columns scaled by beta, for every chunk at once, outside the
        # sequential axis
        Gh = jnp.swapaxes(Gc, 2, 3)  # [A, N, H, C]
        decay = jnp.exp(jnp.minimum(Gh[..., :, None] - Gh[..., None, :], 0.0))
        kk = jnp.matmul(kc, jnp.swapaxes(kc, -1, -2), precision=_HI)
        idx = jnp.arange(C)
        bh = jnp.swapaxes(beta.reshape(A, N, C, H), 2, 3)[..., None]  # [A, N, H, C, 1]
        eye = jnp.eye(C, dtype=jnp.float32)
        system = eye + bh * jnp.where(idx[:, None] > idx[None, :], kk * decay, 0.0)
        operands.append(_unit_lower_inverse(system) * jnp.swapaxes(bh, -1, -2))
        in_specs.append(spec((1, 1, hp, C, C), 2))
    state = pl.BlockSpec((1, hb, dk, W), lambda a, gb, n, st, fr, at: (a, gb, 0, 0))
    if S0 is not None:
        operands.append(S0)
        in_specs.append(state)
    o, states = pl.pallas_call(
        functools.partial(
            _chunk_scan_kernel, hb=hb, abreast=P, dv=dv, delta=delta, one_group=one_group,
            from_state=S0 is not None),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(A, Gb, N),
            in_specs=in_specs,
            out_specs=[vals_out, pl.BlockSpec(
                (1, hb, dk, W),
                lambda a, gb, n, st, fr, at: (at[a * N + run(n, st)], gb, 0, 0))],
            scratch_shapes=[pltpu.VMEM((hb, dk, W), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((A, N, C, H * dv), jnp.float32),
            jax.ShapeDtypeStruct((rows, G, dk, W), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret() if interpret is None else interpret,
    )(staged, fresh.astype(jnp.int32), at.astype(jnp.int32).reshape(-1), *operands)
    return o.reshape(A, T, H, dv), states
