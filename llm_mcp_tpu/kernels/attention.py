"""Pallas TPU attention kernels for the serving hot loop.

The reference streams tokens computed by an external llama.cpp process
(`core/internal/api/handlers.go:2427-2587` proxies Ollama); its hot loop is a
line scanner. Here the hot loop is attention over the KV cache, so it gets
hand-written TPU kernels:

  - `flash_prefill_attention` — causal flash attention for prompt prefill.
    Online-softmax over key blocks: scores never materialize in HBM. A grid
    cell is one query block of the G query heads of a KV group, [G*BQ, hd]
    against each K/V block; it runs the key blocks its mask leaves (from the
    window's lower edge to the diagonal, cut at the prompt's length; none for
    a query block past the length), masks the edge blocks alone, holds the
    scores transposed ([BK, G*BQ]) so that the softmax's row statistics lie
    along the lanes, and feeds both products to the MXU in the operands' own
    dtype with a float32 accumulator.
  - `decode_attention` — single-position GQA attention over the cache for
    the continuous batch. Bandwidth-bound: the win is streaming K/V through
    VMEM exactly once per step in their native [S, hd] tiling and fusing
    mask + softmax + weighted sum, with the f32 score tile living only in
    VMEM.

Layout contract (chosen for TPU tiling — (sublane, lane) = trailing dims):

  q (prefill)  [B, H,   S, hd]
  k/v, cache   [B, Hkv, S, hd]     # S×hd trailing → native (8/16, 128) tiles
  q (decode)   [B, Hkv, G, hd]     # G = H // Hkv query heads per KV head
  lengths      [B] int32           # valid positions per slot/row

This is why the engine cache is [L, B, Hkv, S, hd] (heads BEFORE sequence):
a [.., S, 1, hd] block would tile as (1, 128) sublane-padded 8×, wasting
most of the HBM bandwidth the decode step is bound by.

Off the chip the kernels run in interpret mode, so the whole test suite
exercises them on the CPU backend (tests/conftest.py forces JAX_PLATFORMS=cpu).
A device that is neither a TPU nor the CPU is an error (utils/platform.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.recorder import get_recorder
from ..utils.platform import on_tpu as _on_tpu

NEG_INF = float(-1e30)
LANES = 128  # a row of the chip's tiles

# Kernel-to-reference falls: a dispatcher that was asked for a compiled
# (non-interpret) kernel and answered with the XLA reference math instead,
# because a shape gate failed. Decided at trace time, so each entry counts
# traced programs, not calls. chip_smoke.py asserts the table stays empty on
# the serving path; the same fall lands in the flight recorder as
# `kernel_fall`.
reference_falls: dict[str, int] = {}


def _note_fall(kernel: str, reason: str, interp: bool) -> None:
    if interp:
        return  # interpret-mode tests take the exact math by design
    reference_falls[kernel] = reference_falls.get(kernel, 0) + 1
    get_recorder().event("kernel_fall", kernel=kernel, reason=reason)


def _smem_spec() -> pl.BlockSpec:
    """Whole-array SMEM spec for the [B] lengths input (scalar reads drive
    masking)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def pallas_supported(seq_len: int, head_dim: int) -> bool:
    """Static (trace-time) eligibility for the Pallas path."""
    if os.environ.get("LLM_MCP_TPU_ATTN", "auto") == "xla":
        return False
    if head_dim % 128 != 0 and head_dim not in (32, 64):
        return False
    if seq_len >= 128:
        return seq_len % 128 == 0
    return seq_len & (seq_len - 1) == 0  # pow2 buckets below one block


def resolve_attn_impl(mesh=None) -> str:
    """Pick the attention implementation at trace time.

    env LLM_MCP_TPU_ATTN: auto (default) | pallas | xla.
    auto → pallas on a single TPU chip, xla elsewhere: sharded meshes keep
    the einsum path (GSPMD partitions it) until the shard_map kernel wrap
    lands alongside the ring-attention long-context path. CPU tests exercise
    the kernels in interpret mode by passing attn_impl="pallas" /
    LLM_MCP_TPU_ATTN=pallas explicitly — see tests/test_kernels.py.
    """
    if mesh is not None and mesh.size > 1:
        # Sharded mesh: the unwrapped pallas_call must not trace over GSPMD
        # inputs, even when LLM_MCP_TPU_ATTN=pallas is set.
        return "xla"
    mode = os.environ.get("LLM_MCP_TPU_ATTN", "auto")
    if mode in ("pallas", "xla"):
        return mode
    return "pallas" if _on_tpu() else "xla"


def decode_pallas_max_seq(
    head_dim: int, n_kv_heads: int, n_heads: int, quantized: bool
) -> int:
    """Longest cache row the whole-S decode kernels can stream through VMEM.

    Both whole-S decode arms load a full [.., S, hd] K/V tile per grid cell
    (plus f32 score/prob tiles), double-buffered by the pipeline. Beyond
    this cap a whole-S pallas_call would fail AT RUNTIME on a real chip
    with a VMEM allocation error — `decode_attend_q8`/`decode_attend_bf16`
    must pick their BLOCKED arm statically instead (VERDICT r1 #8: nothing
    enforced the boundary).

      q8 kernel (one cell = one batch row, all KV heads; the fused-layout
      BlockSpec reads only the 2·Hkv payload heads, never the packed
      scale row):
        2 × 2·Hkv·hd int8 payload (k+v fused, double-buffered)
        + 2·Hkv scale bytes + 2 × H f32 score/prob rows   per cache position
      bf16 kernel (one cell = one (row, head)):
        2 × hd·2 bf16 payload (k+v, double-buffered) + G·4 scores
    """
    budget = 12 * 1024 * 1024  # of ~16 MB VMEM; headroom for q/out/temps
    if quantized:
        per_pos = 2 * (2 * n_kv_heads * head_dim) + 8 * n_kv_heads + 2 * 4 * n_heads
    else:
        g = max(1, n_heads // n_kv_heads)
        per_pos = 2 * (2 * head_dim * 2) + 4 * g
    return max(128, budget // per_pos)


def resolve_decode_impl(
    mesh=None,
    quantized: bool = False,
    *,
    seq_len: int = 0,
    head_dim: int = 128,
    n_kv_heads: int = 8,
    n_heads: int = 32,
) -> str:
    """Attention impl for the DECODE step (prefill keeps resolve_attn_impl).

    For the INT8 cache the default on TPU is the `decode_attend_q8` Pallas
    kernel: XLA's int8 einsum path materializes a bf16 copy of the
    dequantized cache (measured 236 GB/s effective at 8B B=64 — slower than
    the bf16 cache), while the kernel streams the int8 payload into s8 MXU
    dots with no bulk converts.

    The bf16 cache now defaults to Pallas on a single TPU chip too:
    `decode_attend_bf16` runs the same scan-invariant-cache + post-scan
    batched-append structure as the q8 path (the structure that made q8
    fast), with a runtime whole-S/blocked hybrid. The old in-scan sliced
    kernel this resolver used to reject in favor of XLA (measured 10.4 vs
    6.2 ms/step at B=32) is gone from the decode routing. There is no seq
    cap either way anymore: past `decode_pallas_max_seq` both dtypes pick
    their blocked arm statically (HBM streaming, no VMEM cliff).
    env LLM_MCP_TPU_ATTN still forces either path for tests; the
    `head_dim`/`n_kv_heads`/`n_heads`/`seq_len` kwargs stay for callers
    and tests probing the VMEM budget."""
    del seq_len, head_dim, n_kv_heads, n_heads  # cap moved into the hybrids
    if mesh is not None and mesh.size > 1:
        # Same rule as resolve_attn_impl: the unwrapped pallas_call must not
        # trace over GSPMD-sharded cache operands (the einsum path partitions
        # cleanly; the q8 kernel would force replication or fail to compile).
        return "xla"
    mode = os.environ.get("LLM_MCP_TPU_ATTN", "auto")
    if mode in ("pallas", "xla"):
        return mode
    del quantized  # both cache dtypes default to the pallas hybrids on-chip
    return "pallas" if _on_tpu() else "xla"


def _interpret() -> bool:
    return not _on_tpu()


# ---------------------------------------------------------------------------
# Prefill: causal flash attention
# ---------------------------------------------------------------------------


def _flash_prefill_kernel(
    lengths_ref,  # [B] int32 (SMEM)
    window_ref,  # [1] int32 (SMEM) — sliding window, 0 = global
    q_ref,  # [1, G, BQ, hd] — one query block of a KV group's G heads
    k_ref,  # [1, 1, S, hd]
    v_ref,  # [1, 1, S, hd]
    o_ref,  # [1, G, BQ, hd]
    *,
    scale: float,
    block_k: int,
    softcap: float,
    block_len: int = 0,
):
    """One grid cell = one query block of the G query heads that share a KV
    head: their rows are ONE [G*BQ, hd] operand against each K and V block
    (row r is head r // BQ at position qi*BQ + r % BQ), so a K/V block is read
    from VMEM once for the group.

    The scores of a step are held TRANSPOSED, [BK, G*BQ]: keys down the
    sublanes, the cell's rows along the lanes. A row's max and sum over the
    keys are then element-wise work between vregs and one fold of eight
    sublanes, and m, l and alpha are [1, G*BQ], a vreg for every 128 rows;
    with the rows down the sublanes each of the two reductions was a lane
    reduction a vreg of scores and each of m, l, alpha a vreg for every EIGHT
    rows, which cost the step more than its exponentials (PERF.md section 6,
    PR 51). The accumulator is [hd, G*BQ] and is turned once, at the cell's end.

    The cell runs the key blocks its mask leaves and no other: from the block
    that holds the window's lower edge of the first query row (block 0 on a
    global layer) to the diagonal's, cut at the block that holds position
    valid_len - 1. A query block whose first row lies at or past valid_len
    runs no step and writes zeros. Of those blocks the ones that lie wholly
    under the diagonal, inside the window of every row and under the length
    take the unmasked body; the edges (the window's lower edge, the diagonal,
    the length's block) take the masked one, in a loop of their own after the
    others: the online softmax does not care in which order blocks arrive.

    Both products run on the operands' own dtype with a float32 accumulator
    (bfloat16 x bfloat16 on the chip: the MXU's full rate, and the same
    products a float32 cast gave). The scale and the soft cap are applied to
    the float32 scores; m, l, acc and the exponentials are float32; p is
    rounded to v's dtype for the second product, as the XLA arm of
    `models/llama.py:prefill_attn` rounds its probabilities (float32 inputs
    stay float32 throughout).

    With `block_len` L (a configuration that generates by diffusion over
    blocks, `cfg.block_len`; L divides both block sizes) a row sees every key of
    its own block of L positions too: the key blocks a cell runs and the ones
    that need no mask are the causal ones (a block of L never straddles a key
    block), and only the edge blocks' mask reads `k // L <= q // L`."""
    b = pl.program_id(0)
    qi = pl.program_id(2)
    _, G, bq, hd = q_ref.shape
    rows = G * bq
    bk = block_k
    valid_len = lengths_ref[b]
    window = window_ref[0]
    q_lo = qi * bq  # the block's first and last query positions
    q_hi = q_lo + bq - 1

    q = q_ref[0].reshape(rows, hd)
    q_pos = q_lo + (jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) & (bq - 1))

    # Key blocks [lo, hi) hold every key some row of the cell attends; of
    # them [full_lo, full_hi) need no mask.
    windowed = window > 0
    lo = jnp.where(windowed, jax.lax.div(jnp.maximum(q_lo - window + 1, 0), bk), 0)
    hi = jnp.minimum(jax.lax.div(q_hi, bk) + 1, jax.lax.div(valid_len + bk - 1, bk))
    hi = jnp.where(q_lo < valid_len, hi, lo)
    full_lo = jnp.where(
        windowed, jax.lax.div(jnp.maximum(q_hi - window + 1, 0) + bk - 1, bk), 0)
    full_lo = jnp.clip(full_lo, lo, hi)
    full_hi = jnp.clip(
        jnp.minimum(jax.lax.div(q_lo + 1, bk), jax.lax.div(valid_len, bk)), full_lo, hi)
    n_low = full_lo - lo  # edge blocks under the unmasked ones; the rest lie over

    def step(kb, carry, masked: bool):
        acc, m, l = carry  # [hd, rows], [1, rows], [1, rows]
        at = pl.multiple_of(kb * bk, bk)
        k = k_ref[0, 0, pl.ds(at, bk), :]
        v = v_ref[0, 0, pl.ds(at, bk), :]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BK, rows]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        if masked:
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            q_edge = q_pos
            if block_len:  # the last position of the row's own block
                q_edge = jax.lax.div(q_pos, block_len) * block_len + (block_len - 1)
            mask = (k_pos <= q_edge) & (k_pos < valid_len)
            mask &= jnp.logical_not(windowed) | (q_pos - k_pos < window)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            # Mask p explicitly: a fully-masked row keeps m_new == NEG_INF,
            # where exp(s - m_new) == 1 would silently average V; masked p
            # keeps l == 0 so the guard below emits 0 for such rows.
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [hd, rows]
        return acc, m_new, l

    carry = (
        jnp.zeros((hd, rows), dtype=jnp.float32),
        jnp.full((1, rows), NEG_INF, dtype=jnp.float32),
        jnp.zeros((1, rows), dtype=jnp.float32),
    )
    carry = jax.lax.fori_loop(
        full_lo, full_hi, functools.partial(step, masked=False), carry)

    def edge(e, carry):
        kb = jnp.where(e < n_low, lo + e, full_hi + e - n_low)
        return step(kb, carry, masked=True)

    acc, m, l = jax.lax.fori_loop(0, n_low + hi - full_hi, edge, carry)
    # l == 0 when a row saw no unmasked key (valid_len == 0, or a query block
    # wholly past valid_len, which ran no step) — emit 0 instead of 0/0 NaN.
    # Padding rows inside a block that holds valid rows still attend the
    # valid prefix and produce garbage the caller never reads.
    out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[0] = out.T.reshape(G, bq, hd).astype(o_ref.dtype)


def prefill_block(group: int, seq_len: int) -> int:
    """Query and key block of `flash_prefill_attention` (one size for both: a
    square diagonal block wastes least under the causal mask) for a KV group
    of `group` query heads at a bucket of `seq_len` positions: the largest
    power of two that divides `seq_len` and keeps a cell at 1,024 rows or
    under, between 128 and 512 (a bucket under 128 is one block). Measured on a
    v5e (scripts/flash_prefill_sweep.py; PERF.md section 6, PR 51): a group
    of 8 at 128 (a window layer 167 us a call of 1 x 1024 against 190 at key
    blocks of 256), of 4 at 256 (141 against 154 at 128), of 1 at 512 (168
    against 377 at 128: a cell of 128 rows pays its fixed costs eight times as
    often). A function of the shapes alone."""
    cap = min(512, max(128, 1024 // group))
    return next(b for b in (512, 256, 128, seq_len) if b <= cap and seq_len % b == 0)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "interpret", "softcap", "scale", "block_len"),
)
def flash_prefill_attention(
    q: jnp.ndarray,  # [B, H, S, hd]
    k: jnp.ndarray,  # [B, Hkv, S, hd]
    v: jnp.ndarray,  # [B, Hkv, S, hd]
    lengths: jnp.ndarray,  # [B] int32
    *,
    window: jnp.ndarray | int = 0,  # sliding window (0 = global); may be traced
    softcap: float = 0.0,  # Gemma2-style score soft-capping (0 = off)
    scale: float = 0.0,  # query scale override (0 = head_dim**-0.5)
    block_q: int = 0,  # 0 = the rule (`prefill_block`); given only by
    block_k: int = 0,  #   scripts/flash_prefill_sweep.py and tests
    interpret: bool | None = None,
    block_len: int = 0,  # causal between blocks of this many positions, whole inside one
) -> jnp.ndarray:
    """Causal + length-masked GQA flash attention. Returns [B, H, S, hd]; rows
    at or past a prompt's length hold nothing a caller may read."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    rule = prefill_block(G, S)
    bq, bk = min(block_q or rule, S), min(block_k or rule, S)
    assert S % bq == 0 and S % bk == 0 and bq & (bq - 1) == 0, (S, bq, bk)
    interp = _interpret() if interpret is None else interpret

    kernel = functools.partial(
        _flash_prefill_kernel,
        scale=scale or hd**-0.5,
        block_k=bk,
        softcap=softcap,
    )
    if block_len:
        assert bq % block_len == 0 and bk % block_len == 0, (bq, bk, block_len)
        kernel = functools.partial(kernel, block_len=block_len)
    win = jnp.reshape(jnp.asarray(window, dtype=jnp.int32), (1,))
    return pl.pallas_call(
        kernel,
        name="flash_prefill_attn",
        grid=(B, Hkv, S // bq),
        in_specs=[
            _smem_spec(),  # lengths [B]
            _smem_spec(),  # window [1]
            pl.BlockSpec((1, G, bq, hd), lambda b, h, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, qi: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, qi: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, bq, hd), lambda b, h, qi: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interp,
    )(lengths.astype(jnp.int32), win, q, k, v)


# ---------------------------------------------------------------------------
# Decode: one-position GQA attention over the KV cache
# ---------------------------------------------------------------------------


def _decode_attn_kernel(
    lengths_ref,  # [B] int32 (scalar prefetch)
    q_ref,  # [1, 1, G, hd]
    k_ref,  # [1, 1, S, hd]
    v_ref,  # [1, 1, S, hd]
    o_ref,  # [1, 1, G, hd]
    *,
    scale: float,
):
    b = pl.program_id(0)
    valid_len = lengths_ref[b]  # attend to positions 0..valid_len inclusive
    S = k_ref.shape[2]

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [G, hd]
    k = k_ref[0, 0].astype(jnp.float32)  # [S, hd]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [G, S]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    s = jnp.where(pos <= valid_len, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    ctx = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [G, hd]
    o_ref[0, 0] = (ctx / l).astype(o_ref.dtype)


def _attend_q8_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    ids_ref,  # [Ba] int32 (scalar prefetch) — cache row per batch position
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    q_ref,  # [1, R, P*G, W] — R = Hkv / P cache rows of P heads abreast,
    #         W = P*hd; a query group in its head's lanes (`q_abreast`)
    nk_ref,  # [1, R, 1, W] — this step's K vectors (post-rope), abreast
    nv_ref,  # [1, R, 1, W]
    kv_ref,  # [1, 1, 2*R, S, W] int8 — fused K|V payload, all heads
    s_ref,  # [1, 1, 2*Hkv, S] — fused K|V dequant scales
    o_ref,  # [1, R, P*G, W] — attention output (`ctx_apart` takes each
    #         group's own head's lanes)
    *,
    scale: float,
    window: int = 0,
):
    """One grid cell = one batch row, all KV heads.

    Where P heads lie abreast in a cache row (`kv_heads_abreast`) a row's
    product is over all W lanes: a query group's row is zero outside its own
    head's lanes, so the int8 scores are that head's exactly, and of the
    second product's W output lanes the group's own head's are its context.

    With `window` the tile is a window layer's RING of S positions (a power of
    two, at least the window): position p lies at index p mod S, so index j
    holds the position `(w - j) mod S` back from this step's, seen while that
    is inside the window and not before the sequence's start; the index this
    step's position wraps onto holds the position S back, out of the window,
    and takes the new vectors as any cache's position w does.

    The cache rides the FUSED layout (models/llama.py:init_kv_cache): K
    heads [0, Hkv), V heads [Hkv, 2*Hkv) of one int8 payload array, so the
    pipeline issues ONE payload DMA + one scales DMA per cell instead of
    four. The padded packed-scale pseudo-head (head 2*Hkv, blocked-kernel
    fuel) is excluded by the BlockSpec — this kernel reads the plain "s"
    rows.

    Perf-critical invariant: the int8 K/V payloads feed the MXU *as int8*
    (s8 x s8 -> s32 dots). Converting them elementwise would bottleneck on
    the VPU — int8->f32 converts run at ~1 elem/lane/cycle, about the same
    rate HBM delivers bytes, doubling step time. Only the tiny per-row
    tensors (q, scores, probs) are computed in f32.
    """
    b = pl.program_id(0)
    w = lengths_ref[b]  # this step's position; attend to 0..w inclusive
    S = kv_ref.shape[3]
    R = q_ref.shape[1]
    Hkv = s_ref.shape[2] // 2
    P = Hkv // R
    G = q_ref.shape[2] // P

    nk = nk_ref[0, :, 0].astype(jnp.float32)  # [R, W]
    nv = nv_ref[0, :, 0].astype(jnp.float32)
    q = q_ref[0].astype(jnp.float32)  # [R, P*G, W]
    ss = s_ref[0, 0].astype(jnp.float32)  # [2*Hkv, S]
    kss, vss = ss[:Hkv], ss[Hkv:]

    # quantize q per (h, g) row; fold the attention scale into the q scales
    qa = jnp.max(jnp.abs(q), axis=-1)  # [Hkv, G]
    qsc = jnp.maximum(qa / 127.0, 1e-30)
    q8 = jnp.round(q / qsc[..., None]).astype(jnp.int8)

    kvq = kv_ref[0, 0]  # [2*R, S, W] int8 — k rows then v rows
    s_i = jax.lax.dot_general(
        q8,
        kvq[:R],
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )  # [R, P*G, S]
    s = s_i.astype(jnp.float32) * (scale * qsc)[..., None] * _scales_by_row(kss, P, G)

    pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, S), 2)
    at_w, seen = _ring_masks(pos, w, S, window)
    # the tile holds the PRE-append cache — position w's score/value come
    # from the unquantized new vectors instead (exact; the quantized row
    # scatters into the cache outside the kernel)
    s_new = jnp.sum(q * nk[:, None, :], axis=-1, keepdims=True) * scale  # [R, P*G, 1]
    s = jnp.where(at_w(), s_new, s)
    s = jnp.where(seen(), s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p_w = jnp.sum(jnp.where(at_w(), p, 0.0), axis=-1, keepdims=True)  # [R, P*G, 1]
    # fold v's dequant scales into the probs, then quantize the prob rows so
    # the PV dot also runs s8 x s8 on the MXU
    pv = jnp.where(at_w(), 0.0, p * _scales_by_row(vss, P, G))  # [R, P*G, S]
    pa = jnp.max(pv, axis=-1)  # [R, P*G]
    psc = jnp.maximum(pa / 127.0, 1e-30)
    p8 = jnp.round(pv / psc[..., None]).astype(jnp.int8)
    ctx_i = jax.lax.dot_general(
        p8,
        kvq[R:],
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )  # [R, P*G, W]
    ctx = ctx_i.astype(jnp.float32) * psc[..., None] + p_w * nv[:, None, :]
    o_ref[0] = (ctx / l).astype(o_ref.dtype)


def _scales_by_row(ss, abreast: int, group: int):
    """Dequant scales [Hkv, N] of the heads, as they multiply the scores
    [Hkv / P, P*G, N] of P heads abreast: head p*R + r lies in cache row r
    (`kv_abreast`) and its G query rows are rows [p*G, (p+1)*G) there. P = 1:
    [Hkv, 1, N], a head a row."""
    if abreast == 1:
        return ss[:, None, :]
    R = ss.shape[0] // abreast
    row = jax.lax.broadcasted_iota(jnp.int32, (1, abreast * group, 1), 1)
    out = ss[(abreast - 1) * R :][:, None, :]
    for p in range(abreast - 2, -1, -1):
        out = jnp.where(row < (p + 1) * group, ss[p * R : (p + 1) * R][:, None, :], out)
    return out


def _ring_masks(pos, w, S: int, window: int):
    """(at_w, seen): which of a tile's indices `pos` holds this step's position
    `w`, and which hold positions the step attends, each as a function that
    makes the mask where it is used. A full-length tile holds position p at
    index p. A window layer's ring of S positions (`window` > 0) holds it at
    p mod S, so index j's position lies `(w - j) mod S` behind this step's."""
    if window:
        back = (w - pos) & (S - 1)
        return (lambda: back == 0), (lambda: (back < window) & (back <= w))
    return (lambda: pos == w), (lambda: pos <= w)


def _unpack_scale_lanes(srow, n_heads: int, scale_dtype):
    """In-kernel inverse of models/quant.py:pack_scales for one landed
    block: [BS, hd] int8 scale-row bytes -> [n_heads, BS] f32 scales.

    Mosaic lowers no width-changing bitcast (int8 -> bf16/f32), so the bytes
    are reassembled arithmetically. One s8 x s8 -> s32 MXU dot against a 0/1
    selection matrix per scale byte both picks that byte's lanes and
    transposes the block; masks and shifts then rebuild the bit pattern in
    an int32, widened to an f32 pattern where the scale is bf16, and a
    same-width bitcast reads it. Byte order is pack_scales' (little-endian);
    layout parity is pinned by the fused-layout parity tests."""
    it = jnp.dtype(scale_dtype).itemsize
    hd = srow.shape[1]
    h = jax.lax.broadcasted_iota(jnp.int32, (n_heads, hd), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_heads, hd), 1)
    bits = jnp.zeros((n_heads, srow.shape[0]), jnp.int32)
    for byte in range(it):
        # byte `byte` of head h's scale sits in lane h*it + byte
        sel = jnp.where(lane == h * it + byte, 1.0, 0.0).astype(jnp.int8)
        raw = jax.lax.dot_general(
            sel, srow, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
        )  # [n_heads, BS] sign-extended bytes
        bits = bits | ((raw & 0xFF) << (8 * byte + 8 * (4 - it)))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def blocked_row_blocks(w, seq_len: int, block_s: int, xp=jnp):
    """Blocks of `block_s` cache positions the blocked q8 arm streams for a
    row at position `w`: the attended prefix [0, w] rounded up to whole
    blocks, and ONE block for a parked or free row (w >= seq_len, the
    engine's convention: its output is discarded, and at low occupancy such
    rows would otherwise dominate the cache traffic). `xp` is `jnp` inside
    the step program and `numpy` for the host's count of the same traffic
    (`engine.perf_stats()["decode_attn"]`)."""
    nblk = xp.clip((w + block_s) // block_s, 1, seq_len // block_s)
    return xp.where(w >= seq_len, 1, nblk)


def _attend_q8_blocked_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    ids_ref,  # [Ba] int32 (scalar prefetch) — cache row per batch position
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    cum_ref,  # [Ba + 1] int32 (scalar prefetch) — running sum of the rows'
    #           block counts: row b's cells are cum[b] .. cum[b + 1] - 1
    q_ref,  # [1, R, P*G, W] VMEM — R = Hkv / P cache rows of P heads abreast,
    #         W = P*hd (`_attend_q8_kernel` says how the products stay exact)
    nk_ref,  # [1, R, W] VMEM — this step's K vectors (post-rope)
    nv_ref,  # [1, R, W] VMEM
    pay_hbm,  # [L, B, 2*R + p, S, W] int8 — fused K|V(|packed scales)
    #           payload, stays in HBM (ANY), DMA'd per block
    s_hbm,  # [L, B, 2*Hkv, S] — plain scales (read only when packed=False)
    o_ref,  # [1, R, P*G, W] VMEM out
    pay_buf,  # VMEM scratch [2, Hh, BS, W] int8 (double buffer);
    #           Hh = 2*R + 1 when packed else 2*R
    s_buf,  # [2, 2*Hkv, BS] (unused when packed — tiny, kept so both modes
    #        share one scratch list)
    sems,  # DMA semaphores [2, 2]
    *,
    scale: float,
    block_s: int,
    packed: bool,
    scale_dtype,
):
    """Dynamic-length decode attention: only the cache blocks that contain
    attended positions ([0, w]) ever leave HBM.

    The whole-S kernel's BlockSpec DMAs the full row regardless of how much
    of it is valid — at S=1024 with half-full slots that's 2x the necessary
    cache traffic, and decode is cache-bandwidth-bound. Here the row stays
    in HBM (memory_space=ANY) and a manual double-buffered DMA loop with a
    DYNAMIC trip count (ceil((w+1)/BS)) streams exactly the attended prefix,
    flash-style online softmax accumulating across blocks. Same s8-MXU dot
    discipline and exact current-position override as `_attend_q8_kernel`.

    **The (row, block) cells of the whole batch are ONE pipeline.** Cell c
    (row b's block j: c = cum[b] + j) lands in buffer c % 2, and cell c + 1's
    copy is started before cell c's is waited for, whether c + 1 is the same
    row's next block or the NEXT ROW's first one. The grid is sequential and
    the buffers and semaphores are scratch, so a copy started in one grid
    step is waited for in the next: the only wait with nothing before it is
    the call's first. A double buffer over ONE row's blocks overlaps nothing at
    one block a row, which is every short fill (PERF.md section 6, PR 36: 59 us
    a call of 32 rows against 34).

    One copy a cell where the scales travel with the payload:

      packed=True  — ONE copy: K, V and a bit-packed per-position scale
        pseudo-head travel in the same [2*R+1, BS, W] int8 block; the
        scales are unpacked in VMEM (`_unpack_scale_lanes`).
      packed=False — TWO copies: the [2*R, BS, W] payload head-slice
        plus one [2*Hkv, BS] block of the plain scales array. This is the
        fallback when the scale bytes don't fit one cache row
        (2*Hkv*itemsize > W) or LLM_MCP_TPU_Q8_SCALE_PACK=0. A [2*Hkv, BS]
        slice of the head-major scales array is a (sublane, lane)-tileable
        copy Mosaic accepts.
    """
    b = pl.program_id(0)
    li = li_ref[0]
    BS = block_s
    _, R, PG, W = q_ref.shape
    Hkv = s_buf.shape[1] // 2
    P = Hkv // R
    G = PG // P
    n_rows = ids_ref.shape[0]
    row = ids_ref[b]  # cache row for this batch position (compaction)
    next_row = ids_ref[jnp.minimum(b + 1, n_rows - 1)]
    w = lengths_ref[b]
    c0 = cum_ref[b]
    nblk = cum_ref[b + 1] - c0
    total = cum_ref[n_rows]

    def copies(row, j, slot):
        if packed:
            # one DMA: full head axis (K | V | packed-scale pseudo-head)
            return (
                pltpu.make_async_copy(
                    pay_hbm.at[li, row, :, pl.ds(j * BS, BS), :],
                    pay_buf.at[slot],
                    sems.at[slot, 0],
                ),
            )
        return (
            pltpu.make_async_copy(
                pay_hbm.at[li, row, pl.ds(0, 2 * R), pl.ds(j * BS, BS), :],
                pay_buf.at[slot],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                s_hbm.at[li, row, :, pl.ds(j * BS, BS)],
                s_buf.at[slot],
                sems.at[slot, 1],
            ),
        )

    def start(row, j, slot):
        for c in copies(row, j, slot):
            c.start()

    def wait(row, j, slot):
        for c in copies(row, j, slot):
            c.wait()

    @pl.when(b == 0)
    def _first_cell():  # the one copy nothing runs ahead of
        start(row, 0, 0)

    q = q_ref[0].astype(jnp.float32)  # [R, P*G, W]
    nk = nk_ref[0].astype(jnp.float32)  # [R, W]
    nv = nv_ref[0].astype(jnp.float32)
    qa = jnp.max(jnp.abs(q), axis=-1)
    qsc = jnp.maximum(qa / 127.0, 1e-30)
    q8 = jnp.round(q / qsc[..., None]).astype(jnp.int8)
    s_new = jnp.sum(q * nk[:, None, :], axis=-1, keepdims=True) * scale  # [R,P*G,1]

    acc0 = jnp.zeros((R, PG, W), jnp.float32)
    m0 = jnp.full((R, PG, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((R, PG, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        c = c0 + j
        slot = jax.lax.rem(c, 2)
        last = j + 1 == nblk

        @pl.when(c + 1 < total)
        def _prefetch():  # the batch's next cell: this row's, or the next row's first
            start(jnp.where(last, next_row, row), jnp.where(last, 0, j + 1), 1 - slot)

        wait(row, j, slot)
        buf = pay_buf[slot]  # [Hh, BS, W] int8 — k rows, v rows(, scales)
        k = buf[:R]  # [R, BS, W] int8
        if packed:
            ss = _unpack_scale_lanes(buf[2 * R], 2 * Hkv, scale_dtype)
        else:
            ss = s_buf[slot].astype(jnp.float32)
        # ss: [2*Hkv, BS] f32
        kss, vss = ss[:Hkv], ss[Hkv:]
        s_i = jax.lax.dot_general(
            q8, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.int32
        )  # [R, P*G, BS]
        s = s_i.astype(jnp.float32) * (scale * qsc)[..., None] * _scales_by_row(kss, P, G)
        pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, 1, BS), 2)
        s = jnp.where(pos == w, s_new, s)
        s = jnp.where(pos <= w, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(pos <= w, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p_w = jnp.sum(jnp.where(pos == w, p, 0.0), axis=-1, keepdims=True)
        pv = jnp.where(pos == w, 0.0, p * _scales_by_row(vss, P, G))
        pa = jnp.max(pv, axis=-1)
        psc = jnp.maximum(pa / 127.0, 1e-30)
        p8 = jnp.round(pv / psc[..., None]).astype(jnp.int8)
        ctx_i = jax.lax.dot_general(
            p8,
            buf[R : 2 * R],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )  # [R, P*G, W]
        acc_new = (
            acc * alpha + ctx_i.astype(jnp.float32) * psc[..., None] + p_w * nv[:, None, :]
        )
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(0, nblk, body, (acc0, m0, l0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _attend_q8_paged_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    tbl_ref,  # [Ba * nbs] int32 (scalar prefetch) — flattened per-row block
    #          tables: physical block id per logical block (already gathered
    #          to the compact batch; arena homes < pool_base, pool rows >=)
    q_ref,  # [1, R, P*G, W] VMEM — R = Hkv / P cache rows of P heads abreast
    nk_ref,  # [1, R, 1, W] VMEM
    nv_ref,  # [1, R, 1, W] VMEM
    pay_hbm,  # [L, B, 2*R + p, S, W] int8 — slot arena (identity homes)
    s_hbm,  # [L, B, 2*Hkv, S] — arena plain scales (packed=False only)
    pool_pay_hbm,  # [L, PXB, 2*R + p, bt, W] int8 — prefix block pool
    pool_s_hbm,  # [L, PXB, 2*Hkv, bt] — pool plain scales
    o_ref,  # [1, R, P*G, W] VMEM out
    pay_buf,  # VMEM scratch [2, Hh, BS, W] int8 (double buffer)
    s_buf,  # [2, 2*Hkv, BS]
    sems,  # DMA semaphores [2, 2]
    *,
    scale: float,
    block_s: int,
    seq_len: int,
    packed: bool,
    scale_dtype,
):
    """Block-indirect sibling of `_attend_q8_blocked_kernel` (vLLM
    PagedAttention, Kwon et al. 2023): identical math and double-buffered
    streaming, but each block's DMA source resolves through the per-row
    block table instead of a contiguous S-range. BS equals the ledger's
    block_tokens, so logical block j covers exactly table entry j.

    The one-DMA-per-cell property survives the indirection: per block the
    kernel still issues one packed copy (or two unpacked) — the table adds
    a scalar-prefetch lookup and a two-way `pl.when` on the source array
    (arena home vs. pool row), not extra copies. Both branches land the
    same block shape in the same scratch buffer, so wait() reconstructs
    the matching descriptor under the same branch."""
    b = pl.program_id(0)
    li = li_ref[0]
    w = lengths_ref[b]
    BS = block_s
    _, R, PG, W = q_ref.shape
    Hkv = s_buf.shape[1] // 2
    P = Hkv // R
    G = PG // P
    nbs = seq_len // BS
    pool_base = pay_hbm.shape[1] * nbs
    nblk = jnp.clip((w + BS) // BS, 1, nbs)
    # parked/free rows (w >= S, engine convention) stream one block; their
    # table rows are identity (reset on free), so the lookup is always safe
    nblk = jnp.where(w >= seq_len, 1, nblk)

    def arena_copies(phys, slot):
        arow = phys // nbs
        aoff = (phys % nbs) * BS
        if packed:
            return (
                pltpu.make_async_copy(
                    pay_hbm.at[li, arow, :, pl.ds(aoff, BS), :],
                    pay_buf.at[slot],
                    sems.at[slot, 0],
                ),
            )
        return (
            pltpu.make_async_copy(
                pay_hbm.at[li, arow, pl.ds(0, 2 * R), pl.ds(aoff, BS), :],
                pay_buf.at[slot],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                s_hbm.at[li, arow, :, pl.ds(aoff, BS)],
                s_buf.at[slot],
                sems.at[slot, 1],
            ),
        )

    def pool_copies(phys, slot):
        prow = phys - pool_base
        if packed:
            return (
                pltpu.make_async_copy(
                    pool_pay_hbm.at[li, prow], pay_buf.at[slot], sems.at[slot, 0]
                ),
            )
        return (
            pltpu.make_async_copy(
                pool_pay_hbm.at[li, prow, pl.ds(0, 2 * R)],
                pay_buf.at[slot],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                pool_s_hbm.at[li, prow], s_buf.at[slot], sems.at[slot, 1]
            ),
        )

    def issue(j, slot, op):
        phys = tbl_ref[b * nbs + j]
        ina = phys < pool_base

        @pl.when(ina)
        def _arena():
            for c in arena_copies(phys, slot):
                getattr(c, op)()

        @pl.when(jnp.logical_not(ina))
        def _pool():
            for c in pool_copies(phys, slot):
                getattr(c, op)()

    issue(0, 0, "start")

    q = q_ref[0].astype(jnp.float32)  # [R, P*G, W]
    nk = nk_ref[0, :, 0].astype(jnp.float32)  # [R, W]
    nv = nv_ref[0, :, 0].astype(jnp.float32)
    qa = jnp.max(jnp.abs(q), axis=-1)
    qsc = jnp.maximum(qa / 127.0, 1e-30)
    q8 = jnp.round(q / qsc[..., None]).astype(jnp.int8)
    s_new = jnp.sum(q * nk[:, None, :], axis=-1, keepdims=True) * scale  # [R,P*G,1]

    acc0 = jnp.zeros((R, PG, W), jnp.float32)
    m0 = jnp.full((R, PG, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((R, PG, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _prefetch():
            issue(j + 1, 1 - slot, "start")

        issue(j, slot, "wait")
        buf = pay_buf[slot]  # [Hh, BS, W] int8 — k rows, v rows(, scales)
        k = buf[:R]  # [R, BS, W] int8
        if packed:
            ss = _unpack_scale_lanes(buf[2 * R], 2 * Hkv, scale_dtype)
        else:
            ss = s_buf[slot].astype(jnp.float32)
        # ss: [2*Hkv, BS] f32
        kss, vss = ss[:Hkv], ss[Hkv:]
        s_i = jax.lax.dot_general(
            q8, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.int32
        )  # [R, P*G, BS]
        s = s_i.astype(jnp.float32) * (scale * qsc)[..., None] * _scales_by_row(kss, P, G)
        pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, 1, BS), 2)
        s = jnp.where(pos == w, s_new, s)
        s = jnp.where(pos <= w, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(pos <= w, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p_w = jnp.sum(jnp.where(pos == w, p, 0.0), axis=-1, keepdims=True)
        pv = jnp.where(pos == w, 0.0, p * _scales_by_row(vss, P, G))
        pa = jnp.max(pv, axis=-1)
        psc = jnp.maximum(pa / 127.0, 1e-30)
        p8 = jnp.round(pv / psc[..., None]).astype(jnp.int8)
        ctx_i = jax.lax.dot_general(
            p8,
            buf[R : 2 * R],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )  # [R, P*G, W]
        acc_new = (
            acc * alpha + ctx_i.astype(jnp.float32) * psc[..., None] + p_w * nv[:, None, :]
        )
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(0, nblk, body, (acc0, m0, l0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def paged_gather(arena, pool, tables, *, nbs=None):
    """XLA block-indirect gather: materialize contiguous-equivalent rows by
    resolving each logical block through the table — the read-side twin of
    the paged Pallas kernels for every XLA path (CPU serve, chunked-prefill
    past reads, exact fallbacks, multi-layer snapshot reads).

    arena  [B, Hx, S, *rest]   layer-selected slot arena (identity homes)
    pool   [PXB, Hx, bt, *rest] layer-selected prefix pool
    tables [A, nsel] int32     per-row block tables (compact batch); a
        PREFIX of the full table may be passed (chunked prefill gathers
        only the blocks covering its static `skey` bound) — then `nbs`
        must name the full blocks-per-slot so physical ids decode right
    returns [A, Hx, nsel*bt, *rest] rows as the contiguous layout holds them

    Works shape-generically over trailing dims (absent for int8 scale
    planes). Cost is one advanced-indexing gather per source plus a
    `jnp.where` — no full-arena copy beyond the [A, Hx, nsel*bt] result
    itself, which is exactly what the contiguous row-select produced."""
    B, Hx, S = arena.shape[0], arena.shape[1], arena.shape[2]
    rest = arena.shape[3:]
    A, nsel = tables.shape
    nbs = nsel if nbs is None else nbs
    bt = S // nbs
    pool_base = B * nbs
    blk = arena.reshape(B, Hx, nbs, bt, *rest)
    safe = jnp.clip(tables, 0, pool_base - 1)
    # advanced indices at axes 0 and 2 (separated by a slice) land in front:
    # [A, nbs, Hx, bt, *rest]
    arena_take = blk[safe // nbs, :, safe % nbs]
    pidx = jnp.clip(tables - pool_base, 0, max(pool.shape[0] - 1, 0))
    pool_take = pool[pidx]  # [A, nbs, Hx, bt, *rest]
    ina = (tables < pool_base).reshape(A, nsel, *([1] * (arena_take.ndim - 2)))
    g = jnp.where(ina, arena_take, pool_take)
    return jnp.swapaxes(g, 1, 2).reshape(A, Hx, nsel * bt, *rest)


# The blocked q8 arm's largest block, in bytes of one copy ([payload heads, BS,
# hd] int8). Measured on a v5e with the batch as one pipeline
# (scripts/attn_block_sweep.py; PERF.md section 6, PR 36): at 17 payload heads
# a cell costs about 0.5 us whatever it holds and 0.55 us more for 256
# positions, so one block of 0.56 MB (17 x 256) beats two of 0.28 MB by 20%
# (34 against 43 us a call of 32 rows, 123 against 152 of 64); at 61 heads a
# block of 2.0 MB (61 x 256) streams at 744 GB/s, bound by its bytes, and
# loses 9% to blocks of 1.0 MB that over-read less (341 against 309 us).
# Between 0.56 and 1.0 MB: not measured, no cell has such a shape.
Q8_BLOCK_BYTES_MAX = 1 << 20


def blocked_arm_fits(row_lanes: int, interp: bool) -> bool:
    """Whether the blocked q8 arm can run on a cache whose rows are `row_lanes`
    wide (P*hd): its copies cut blocks of whole 128-lane rows out of HBM.
    Every cache `init_kv_cache` makes of heads that divide the lanes has such
    rows (`kv_heads_abreast`); one that has not (a head of 96, an odd count of
    heads of 64) lies padded in HBM and takes the whole-S arm, whose tiles the
    pipeline copies. Interpret mode has no tiling."""
    return interp or row_lanes % LANES == 0


def q8_block_tokens(payload_heads: int, seq_len: int, head_dim: int) -> int:
    """Cache positions in one block of the blocked q8 arm: the largest of 256,
    128, 64, 32 that divides `seq_len` (a floored block count would drop the
    row's tail, the current position with it) and whose copy of
    `payload_heads` (2*Hkv + p) heads stays within `Q8_BLOCK_BYTES_MAX`; the
    smallest that divides where none stays within; 0 where none divides (no
    int8-tileable block: the dispatcher takes the whole-S arm or the reference).
    A function of the cache's shape alone."""
    fits = [c for c in (256, 128, 64, 32) if seq_len % c == 0]
    return next(
        (c for c in fits if payload_heads * c * head_dim <= Q8_BLOCK_BYTES_MAX),
        fits[-1] if fits else 0,
    )


class AttnStream:
    """Host-side book of what the int8 decode-attention arms stream
    (`decode_attend_q8`), from the positions the host packs
    for each decode round and the block size in force for the cache's shape
    (`q8_block_tokens`): over the steps of the rounds dispatched, for ONE layer's
    call a step, cache positions fetched (each row's blocks x `block_tokens`, a
    parked or padding row one block) and positions live (`w + 1` of the seated
    rows). Live over streamed is the share of the arm's traffic that is work.
    A count of what the arm WOULD fetch: a round whose fill takes the whole-S
    arm under the dispatcher's `lax.cond` (0.55 of rows x length; no cell of
    the benchmark comes near) is counted the same. With `window` the cache is a
    window layer's ring and the arm the whole-tile one alone: every row of a
    round streams the ring in full, and a seated row's live positions are its
    last `window` (`max_seq_len`: the length at which a row is parked, the
    full-length cache's). With `block_tokens` the cache is the int8 LATENT one
    (`decode_attend_q8_mla`, whose arm is chosen from the shape alone:
    `mla_stream_block`) and `cache_q_shape` its latents': `tokens_live` is then
    the latent positions the steps read, rows x their lengths, and
    `positions_abreast` says how many positions share a row of its rope keys."""

    def __init__(self, cache_q_shape: tuple[int, ...], window: int = 0, max_seq_len: int = 0,
                 kv_heads: int = 0, block_tokens: int | None = None, positions_abreast: int = 1):
        _, _, rows, self.seq_len, row_lanes = cache_q_shape
        self.window = window
        # P of the latent pair's rope keys (`positions_abreast`, read off the
        # pair's shapes by the engine); 1 for every other cache
        self.positions_abreast = positions_abreast
        self.parked_at = max_seq_len or self.seq_len
        # P: heads abreast in a payload row (`fused_q8_heads`' rule, from the
        # configuration's KV heads; without them, a head a row)
        self.heads_abreast = _payload_rows(rows, 2 * kv_heads)[1] if kv_heads else 1
        # 0: the whole-S arm alone runs here, and streams every row in full
        self.block_tokens = block_tokens if block_tokens is not None else (
            q8_block_tokens(rows, self.seq_len, row_lanes)
            if not window and blocked_arm_fits(row_lanes, _interpret()) else 0)
        self.steps = self.tokens_streamed = self.tokens_live = 0

    def dispatched(self, lengths: np.ndarray, steps: int) -> None:
        """A decode round of `steps` steps went out with the rows at
        `lengths` (this step's position a row; >= the cache's length: parked)."""
        w = lengths[:, None].astype(np.int64) + np.arange(steps)
        w = np.where(lengths[:, None] >= self.parked_at, self.parked_at, w)  # parked stays parked
        self.steps += steps
        if self.block_tokens:
            blocks = blocked_row_blocks(w, self.seq_len, self.block_tokens, xp=np)
            self.tokens_streamed += int(blocks.sum()) * self.block_tokens
        else:
            self.tokens_streamed += w.size * self.seq_len
        live = np.minimum(w + 1, self.window) if self.window else w + 1
        self.tokens_live += int(np.where(w < self.parked_at, live, 0).sum())

    def stats(self) -> dict:
        return {"block_tokens": self.block_tokens, "heads_abreast": self.heads_abreast,
                "positions_abreast": self.positions_abreast, "steps": self.steps,
                "tokens_streamed": self.tokens_streamed, "tokens_live": self.tokens_live,
                "live_over_streamed": round(self.tokens_live / self.tokens_streamed, 4)
                if self.tokens_streamed else None,
                **({"window": self.window, "ring_tokens": self.seq_len} if self.window else {})}


class BlockAttnStream:
    """Host-side book of what a block pass's attention streams
    (`perf_stats()["blocks"]["attn"]`), as `AttnStream` is the decode arms':
    over the passes of the rounds fetched, for ONE layer's call a pass, cache
    positions fetched and positions live (each seated row's past, [0, start):
    the block's own L keys come from registers). On the kernel's arm
    (`block_attend_q8`, `arm` "pallas") a row streams its past in whole blocks
    of `block_tokens` and a parked or padding row nothing; on the XLA arm
    (`block_tokens` 0) every row of the batch streams its whole cache row."""

    def __init__(self, arm: str, cache_q_shape: tuple[int, ...]):
        _, _, rows, self.seq_len, row_lanes = cache_q_shape
        self.arm = arm
        self.block_tokens = q8_block_tokens(rows, self.seq_len, row_lanes) if arm == "pallas" else 0
        self.passes = self.tokens_streamed = self.tokens_live = 0

    def fetched(self, starts: list[int], batch_rows: int, passes: int) -> None:
        """A round whose seated rows' blocks started at `starts`, in a batch of
        `batch_rows` rows, ran `passes` passes (the denoising ones and the commit)."""
        st = np.asarray(starts, np.int64)
        st = st[st < self.seq_len]
        self.passes += passes
        self.tokens_live += passes * int(st.sum())
        self.tokens_streamed += passes * (
            int(block_row_blocks(st, self.seq_len, self.block_tokens, xp=np).sum()) * self.block_tokens
            if self.block_tokens else batch_rows * self.seq_len)

    def stats(self) -> dict:
        return {"arm": self.arm, "block_tokens": self.block_tokens, "passes": self.passes,
                "tokens_streamed": self.tokens_streamed, "tokens_live": self.tokens_live,
                "live_over_streamed": round(self.tokens_live / self.tokens_streamed, 4)
                if self.tokens_streamed else None}


def kv_heads_abreast(n_kv_heads: int, head_dim: int) -> int:
    """P: KV heads that lie side by side in one row of the fused int8 cache.
    Where a head is narrower than the 128 lanes and divides them, as many as
    fill them, so that the cache's minor dimension is whole lanes and the
    chip's layout of it is the kernels' (a minor dimension of 64 is laid out
    with positions minor, and every step program copied the whole cache to the
    kernels' layout and back: PERF.md section 6, PR 55); 1 where the heads are
    128 wide or more, or P does not divide them. A function of the shape
    alone, as `kernels/kda.py:heads_abreast` is of the state pool's."""
    P = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 else 1
    return P if n_kv_heads % P == 0 else 1


def kv_abreast(x: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """[..., H, S, hd] -> [..., H / P, S, P*hd]: head p*R + r (R = H / P) in
    lanes [p*hd, (p+1)*hd) of row r, so that the heads a row holds are R
    apart and a contiguous run of R heads (their scales with them) is one
    lane group of every row."""
    if abreast == 1:
        return x
    *lead, H, S, hd = x.shape
    x = x.reshape(*lead, abreast, H // abreast, S, hd)
    return jnp.moveaxis(x, -4, -2).reshape(*lead, H // abreast, S, abreast * hd)


def kv_apart(x: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """[..., H / P, S, P*hd] -> [..., H, S, hd]: `kv_abreast`'s inverse."""
    if abreast == 1:
        return x
    *lead, R, S, W = x.shape
    x = x.reshape(*lead, R, S, abreast, W // abreast)
    return jnp.moveaxis(x, -2, -4).reshape(*lead, R * abreast, S, W // abreast)


def fused_kv(pay: jnp.ndarray, n_kv_heads: int, abreast: int):
    """(K, V) int8 [..., Hkv, S, hd] out of payload rows [..., 2*Hkv/P (+ p),
    S, P*hd] of the fused cache: what every reader outside the kernels takes."""
    R = n_kv_heads // abreast
    return kv_apart(pay[..., :R, :, :], abreast), kv_apart(pay[..., R : 2 * R, :, :], abreast)


def q_abreast(q: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """Queries [..., Hkv, G, hd] -> [..., Hkv / P, P*G, P*hd] for K/V rows of
    P heads abreast: head p*R + r's G rows are rows [p*G, (p+1)*G) of row r,
    in that head's lanes, zeros in the others'. A product with a row of P
    heads over all its lanes is then each head's own, exactly (the zeros add
    nothing), and no reader has to pull the heads of a row apart."""
    if abreast == 1:
        return q
    *lead, Hkv, G, hd = q.shape
    R, n = Hkv // abreast, len(lead)
    rows = jnp.moveaxis(q.reshape(*lead, abreast, R, G, hd), n, n + 1)  # [..., R, P, G, hd]
    own = jnp.eye(abreast, dtype=bool)[:, None, :, None]  # [P, 1, P, 1]
    return jnp.where(own, rows[..., None, :], 0).reshape(*lead, R, abreast * G, abreast * hd)


def ctx_apart(ctx: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """Contexts [..., Hkv / P, P*G, P*hd] of `q_abreast`'s rows against V rows
    of P heads abreast -> [..., Hkv, G, hd]: of a query group's P*hd output
    lanes, its own head's."""
    if abreast == 1:
        return ctx
    *lead, R, PG, W = ctx.shape
    G, hd, n = PG // abreast, W // abreast, len(lead)
    ctx = ctx.reshape(*lead, R, abreast, G, abreast, hd)
    own = jnp.stack([ctx[..., p, :, p, :] for p in range(abreast)], axis=n)  # [..., P, R, G, hd]
    return own.reshape(*lead, abreast * R, G, hd)


def positions_abreast(seq_len: int, width: int) -> int:
    """P: cache POSITIONS that lie side by side in one row of the latent pair's
    int8 rope keys. The pair has one "head", `width` (qk_rope_head_dim) wide;
    where that is narrower than the 128 lanes and divides them, as many
    positions as fill them share a row, so that the member's minor dimension
    is whole lanes and the chip's layout of it is the kernels' (a minor
    dimension of 64 was laid out with positions minor, and every step program
    re-laid the whole member for its Mosaic call and for its append: PERF.md
    section 6, PR 58); 1 where the width is 128 or more, or P does not divide
    the positions. A function of the shape alone, as `kv_heads_abreast` is."""
    P = LANES // width if width < LANES and LANES % width == 0 else 1
    return P if seq_len % P == 0 else 1


def rope_abreast(x: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """[..., S, d] -> [..., S / P, P*d]: position s in row s mod S/P, lanes
    [(s div S/P) d, +d). The P lane groups of the rows, laid end to end, are
    the positions in order: a product over whole rows with the queries in one
    group's lanes (`q_abreast`) gives that group's scores, and the groups'
    scores side by side are the scores in the latents' order, no interleave.
    The P runs of S/P positions, side by side along the lanes: written as that
    concatenation and not as a transpose, which made the compiler lay a step's
    few rows out with positions minor and re-lay the WHOLE cache to suit them
    (described-chip compile of the bucketed chunk, PR 58)."""
    if abreast == 1:
        return x
    half = x.shape[-2] // abreast
    return jnp.concatenate(
        [x[..., g * half:(g + 1) * half, :] for g in range(abreast)], axis=-1)


def rope_apart(x: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """[..., S / P, P*d] -> [..., S, d]: `rope_abreast`'s inverse, the lane
    groups end to end."""
    if abreast == 1:
        return x
    d = x.shape[-1] // abreast
    return jnp.concatenate([x[..., g * d:(g + 1) * d] for g in range(abreast)], axis=-2)


def rope_queries(qr: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """Rope queries [..., H, d] -> [..., P*H, P*d] for rope keys P positions
    abreast: rows [p*H, (p+1)*H) hold the queries in lane group p, zeros
    elsewhere (`q_abreast` with the P runs of positions as the heads)."""
    if abreast == 1:
        return qr
    *lead, H, d = qr.shape
    q = jnp.broadcast_to(qr[..., None, :, :], (*lead, abreast, H, d))
    return q_abreast(q, abreast)[..., 0, :, :]


def rope_put(cache, rows, at, start, keep=None):
    """Rows of rope keys [n_l, 1, 1, n, d], apart, into positions [start,
    start + n) of cache row `at[1]`, layers [at[0], at[0] + n_l), of `cache`
    [L, B, 1, S / P, P*d], in place; `keep` [n] bool: which of them land (all).
    At P = 1 the one dynamic_update_slice every family's insert is. Else the
    row's S/P x 128 lanes are read, the new positions selected into their
    lanes and the row written back whole: a write that starts or ends inside
    a row leaves its neighbours' bytes, and the update has the cache's own
    layout (models/llama.py:ragged_write_rows says what any other costs).
    `start` is clamped as dynamic_update_slice clamps it."""
    n_l, n, d = rows.shape[0], rows.shape[3], rows.shape[4]
    half, W = cache.shape[3:]
    P = W // d
    rows = rows.astype(cache.dtype)
    if P == 1 and keep is None:
        return jax.lax.dynamic_update_slice(cache, rows, (at[0], at[1], 0, start, 0))
    S = half * P
    start = jnp.clip(start, 0, S - n)
    whole = jax.lax.dynamic_update_slice(
        jnp.zeros((n_l, 1, 1, S, d), cache.dtype), rows, (0, 0, 0, start, 0))
    pos = (jnp.arange(W, dtype=jnp.int32) // d)[None, :] * half + jnp.arange(half, dtype=jnp.int32)[:, None]
    if keep is None:
        lands = (pos >= start) & (pos < start + n)
    else:
        hit = jax.lax.dynamic_update_slice(jnp.zeros((S,), bool), keep, (start,))
        lands = rope_abreast(jnp.broadcast_to(hit[:, None], (S, d)), P)
    origin = (at[0], at[1], 0, 0, 0)
    cur = jax.lax.dynamic_slice(cache, origin, (n_l, 1, 1, half, W))
    return jax.lax.dynamic_update_slice(
        cache, jnp.where(lands, rope_abreast(whole, P), cur), origin)


def rope_append(cache, new, layers, rows, w):
    """One position a cache row: `new` [..., Ba, d] lands at position `w` [Ba]
    of rows `rows` [Ba], layers `layers` (a scalar, or [L, 1] against [1, Ba]
    indices), of `cache` [L, B, 1, S / P, P*d]; a parked row (w >= S) is
    dropped. At P = 1 the scatter of one position it always was. Else the
    position's row of 128 lanes is gathered, its lane group selected and the
    row scattered back whole: the neighbours' bytes stay."""
    half, W = cache.shape[3:]
    d = new.shape[-1]
    P = W // d
    new = new.astype(cache.dtype)
    if P == 1:
        return cache.at[layers, rows, 0, w].set(new)
    r = jnp.where(w < half * P, w % half, half)  # out of range: dropped
    cur = cache.at[layers, rows, 0, r].get(mode="clip")  # [..., Ba, W]
    own = (jnp.arange(W, dtype=jnp.int32) // d) == (w // half)[..., None]
    return cache.at[layers, rows, 0, r].set(jnp.where(own, jnp.tile(new, P), cur))


def rope_rows(plane, abreast: int, slots=None, tables=None, pool=None, nbs=None):
    """Rope keys of one layer's plane [B, 1, S / P, P*d], PULLED APART, for the
    rows a call reads, the positions in order [A, S', d]: rows `slots` (None:
    every row), or block-indirect through `tables` and `pool` (a pool's rows,
    as every row cut out of the cache, lie apart). What the readers OUTSIDE the
    kernels' main path take: the references, the XLA decode path, the packed
    chunk's few gathered rows."""
    if tables is not None:
        return paged_gather(rope_apart(plane, abreast), pool, tables, nbs=nbs)[:, 0]
    return rope_apart((plane if slots is None else jnp.take(plane, slots, axis=0))[:, 0], abreast)


def fused_q8_heads(cache_k: dict) -> tuple[int, int, int]:
    """(Hkv, p, P) of a FUSED int8 GQA cache, from its two members' shapes: the
    plain "s" array always has exactly 2*Hkv rows; the payload carries
    2*Hkv / P rows of P heads abreast (`kv_heads_abreast`: P divides Hkv, so
    their count is even) plus p in {0, 1} packed-scale pseudo-heads."""
    Hs = cache_k["s"].shape[2]
    return (Hs // 2, *_payload_rows(cache_k["q"].shape[2], Hs))


def _payload_rows(rows: int, scale_rows: int) -> tuple[int, int]:
    """(p, P) of a payload of `rows` rows beside 2*Hkv = `scale_rows` plain
    scale rows: the K and V rows are an even count, so an odd one holds the
    pseudo-head, and P is how many heads each of the others holds."""
    p = rows % 2
    return p, scale_rows // (rows - p)


def _decode_attend_q8_fallback(
    q, new_k, new_v, cache_k, cache_v, layer, lengths, sc, slot_ids=None,
    block_tables=None, pool=None,
):
    """Exact-f32 mirror of the q8 kernels' math (no q/prob requant) over the
    FUSED cache layout. Used on CPU builds without pallas-tpu and for cache
    lengths no int8-tileable block size divides. `cache_v` is the fused
    layout's empty-dict placeholder (V lives in cache_k's head axis). With
    `block_tables`/`pool` the rows are block-indirect-gathered first
    (`paged_gather`), so this is also the exact reference for the paged
    kernels and the CPU serve path under physical paging."""
    del cache_v
    S = cache_k["q"].shape[3]
    Hkv, _, P = fused_q8_heads(cache_k)
    pay = jax.lax.dynamic_index_in_dim(cache_k["q"], layer, 0, keepdims=False)
    ss = jax.lax.dynamic_index_in_dim(cache_k["s"], layer, 0, keepdims=False)
    if block_tables is not None:
        tbl = (
            block_tables
            if slot_ids is None
            else jnp.take(block_tables, slot_ids, 0)
        )
        pp = jax.lax.dynamic_index_in_dim(pool["q"], layer, 0, keepdims=False)
        ps = jax.lax.dynamic_index_in_dim(pool["s"], layer, 0, keepdims=False)
        pay = paged_gather(pay, pp, tbl)
        ss = paged_gather(ss, ps, tbl)
    elif slot_ids is not None:
        pay = jnp.take(pay, slot_ids, 0)
        ss = jnp.take(ss, slot_ids, 0)
    kf, vf = fused_kv(pay, Hkv, P)
    kss, vss = ss[:, :Hkv], ss[:, Hkv:]
    qf = q.astype(jnp.float32) * sc
    s = jnp.einsum("bhgd,bhsd->bhgs", qf, kf.astype(jnp.float32)) * kss.astype(
        jnp.float32
    )[:, :, None, :]
    pos = jnp.arange(S)[None, None, None, :]
    w = lengths[:, None, None, None]
    s_new = jnp.einsum("bhgd,bhd->bhg", qf, new_k.astype(jnp.float32))
    s = jnp.where(pos == w, s_new[..., None], s)
    s = jnp.where(pos <= w, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p_w = jnp.sum(jnp.where(pos == w, p, 0.0), axis=-1)  # [B, Hkv, G]
    pv = jnp.where(pos == w, 0.0, p * vss.astype(jnp.float32)[:, :, None, :])
    ctx = jnp.einsum("bhgs,bhsd->bhgd", pv, vf.astype(jnp.float32))
    ctx = ctx + p_w[..., None] * new_v.astype(jnp.float32)[:, :, None, :]
    return ctx.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "scale", "block_s", "window"))
def decode_attend_q8(
    q: jnp.ndarray,  # [Ba, Hkv, G, hd] — COMPACT batch (active rows only)
    new_k: jnp.ndarray,  # [Ba, Hkv, hd] — post-rope K for this step
    new_v: jnp.ndarray,  # [Ba, Hkv, hd]
    cache_k: dict,  # FUSED: {"q": int8 [L,B,2*Hkv/P+p,S,P*hd], "s": [L,B,2*Hkv,S]}
    cache_v: dict,  # {} — V rides cache_k's head axis (layout invariant)
    layer: jnp.ndarray,  # scalar int32
    lengths: jnp.ndarray,  # [Ba] int32 — this step's position per row
    *,
    slot_ids: jnp.ndarray | None = None,  # [Ba] int32 cache rows (None = 1:1)
    block_tables: jnp.ndarray | None = None,  # [n_slots, nbs] int32 physical
    #   block tables (executor/physical.py); None = contiguous layout
    pool_k: dict | None = None,  # prefix pool mirroring cache_k's structure:
    #   {"q": int8 [L,PXB,2*Hkv/P+p,bt,P*hd], "s": [L,PXB,2*Hkv,bt]}
    scale: float = 0.0,  # query scale (0 = head_dim**-0.5)
    interpret: bool | None = None,
    block_s: int | None = None,  # the blocked arm's block; None = the rule
    #   (`q8_block_tokens`). Given only by scripts/attn_block_sweep.py and tests
    window: int = 0,  # > 0: `cache_k` is a window layer's RING of S positions,
    #   position p at index p mod S, and a row sees its last `window` positions
) -> jnp.ndarray:
    """Attention over the FUSED int8 KV cache for one layer of the decode
    step (layout: models/llama.py:init_kv_cache — K heads, V heads, and an
    optional bit-packed scale pseudo-head share one payload array, PRE-
    append).

    With `block_tables`/`pool_k` the cache is block-indirect: a runtime
    identity check keeps the exact contiguous dispatch (including the
    whole-S/blocked hybrid) whenever no row references a shared block —
    raw decode without prefix sharing pays one `jnp.all` on a tiny int32
    table, not a gather — and otherwise streams through
    `_attend_q8_paged_kernel`. `LLM_MCP_TPU_Q8_DECODE=paged` forces the
    paged arm (parity tests).

    The int8 payload streams from HBM straight into s8 x s8 -> s32 MXU dots
    (XLA's einsum path materializes a dequantized bf16 copy and runs ~2x
    slower than the bf16 cache); per-token dequant scales fold in post-dot.
    A window layer's ring (`window`) is short by construction and takes the
    whole-tile arm alone, under a name of its own (`decode_attn_win_q8`): it
    streams the ring and nothing else, and wraps by a mask.

    The caller owns the cache append (single-row write-back blocks would
    violate TPU (8, 128) block alignment): whether the row at `lengths[b]`
    has been scattered yet or not, the kernel overrides that position's
    score/value with the exact `new_k`/`new_v` vectors, so the appended
    token is always attended at full precision.

    Returns ctx [B, Hkv, G, hd].
    """
    B, Hkv, G, hd = q.shape
    S = cache_k["q"].shape[3]
    interp = _interpret() if interpret is None else interpret
    sc = scale or hd**-0.5
    _, p, P = fused_q8_heads(cache_k)
    # P heads abreast in a cache row of W lanes: R rows of K, R of V
    R, PG, W = Hkv // P, P * G, cache_k["q"].shape[4]
    assert W == P * hd, (cache_k["q"].shape, hd)

    nk4 = kv_abreast(new_k.reshape(B, Hkv, 1, hd), P)  # [B, R, 1, W]
    nv4 = kv_abreast(new_v.reshape(B, Hkv, 1, hd), P)
    can_whole = S <= decode_pallas_max_seq(hd, Hkv, Hkv * G, quantized=True)
    BS = block_s or q8_block_tokens(2 * R + p, S, W)
    if not blocked_arm_fits(W, interp):
        BS = 0
    if window and (not can_whole or S & (S - 1) or S < window or block_tables is not None):
        raise NotImplementedError(f"a ring of {S} positions for a window of {window}")
    if not can_whole and BS == 0:
        # no whole-S fit and no int8-tileable block divides S: exact f32
        # math of the reference (slower, never wrong)
        _note_fall("decode_attend_q8", f"S={S}: no whole-S fit, no block size", interp)
        return _decode_attend_q8_fallback(
            q, new_k, new_v, cache_k, cache_v, layer, lengths, sc, slot_ids,
            block_tables, pool_k,
        )
    # 1-DMA packed blocks need the scale pseudo-head present in the layout
    packed = p == 1 and os.environ.get("LLM_MCP_TPU_Q8_SCALE_PACK", "1") != "0"
    ids = (
        jnp.arange(B, dtype=jnp.int32)
        if slot_ids is None
        else slot_ids.astype(jnp.int32)
    )
    args = (
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        ids,
        lengths.astype(jnp.int32),
        q_abreast(q, P),
        nk4,
        nv4,
        cache_k["q"],
        cache_k["s"],
    )
    qw = args[3]  # [B, R, P*G, W]
    out_shape = jax.ShapeDtypeStruct((B, R, PG, W), q.dtype)

    def run_whole():
        # whole-S tiles fit VMEM: one payload + one scales DMA per cell,
        # pipelined across grid cells — the cheaper shape once rows are
        # mostly full. The payload block stops at head 2*Hkv: the packed
        # scale pseudo-head is blocked-arm fuel and never enters VMEM here.
        kernel = functools.partial(_attend_q8_kernel, scale=sc, window=window)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer [1], slot ids [Ba], lengths [Ba]
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, R, PG, W), lambda b, li, ids, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, R, 1, W), lambda b, li, ids, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, R, 1, W), lambda b, li, ids, lens: (b, 0, 0, 0)),
                # cache tiles follow the compaction indirection: batch cell b
                # reads cache row ids[b]. Head-block index 0 of the ragged
                # (2*R + p) head axis covers exactly the 2*R payload rows.
                pl.BlockSpec(
                    (1, 1, 2 * R, S, W),
                    lambda b, li, ids, lens: (li[0], ids[b], 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, 2 * Hkv, S), lambda b, li, ids, lens: (li[0], ids[b], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, R, PG, W), lambda b, li, ids, lens: (b, 0, 0, 0)
            ),
        )
        return ctx_apart(pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_win_q8" if window else "decode_attn_q8_whole",
        )(*args), P)

    def run_blocked():
        # rows stream blockwise from HBM with a dynamic trip count — no
        # VMEM cliff at any S, and only the attended prefix [0, w] is ever
        # read. The batch's (row, block) cells are one pipeline (see the
        # kernel): their order and count are a running sum of the rows'
        # block counts, made here from `lengths` and prefetched as scalars.
        Hh = 2 * R + 1 if packed else 2 * R
        li, _, lens = args[:3]
        # [B, R, W]; at P = 1 the operands as they came, so that the program is
        # the one it was (a reshape there and back is another text)
        nk3, nv3 = (new_k, new_v) if P == 1 else (nk4[:, :, 0], nv4[:, :, 0])
        cum = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(blocked_row_blocks(lens, S, BS))]
        ).astype(jnp.int32)
        kernel = functools.partial(
            _attend_q8_blocked_kernel,
            scale=sc,
            block_s=BS,
            packed=packed,
            scale_dtype=cache_k["s"].dtype,
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # layer [1], slot ids, lengths, cells [Ba + 1]
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, R, PG, W), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, R, W), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, R, W), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # fused payload
                pl.BlockSpec(memory_space=pl.ANY),  # plain scales
            ],
            out_specs=pl.BlockSpec((1, R, PG, W), lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, Hh, BS, W), jnp.int8),
                pltpu.VMEM((2, 2 * Hkv, BS), cache_k["s"].dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        )
        return ctx_apart(pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_q8_blocked",
            # sequential: a cell's copy is started in the grid step before its own
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        )(li, ids, lens, cum, qw, nk3, nv3, cache_k["q"], cache_k["s"]), P)

    def run_paged():
        # block-indirect arm: BS is pinned to the ledger's block_tokens so
        # table entry j covers exactly the kernel's block j
        nbs = block_tables.shape[1]
        bt = S // nbs
        Hh = 2 * R + 1 if packed else 2 * R
        tblf = jnp.take(block_tables, ids, 0).reshape(-1).astype(jnp.int32)
        kernel = functools.partial(
            _attend_q8_paged_kernel,
            scale=sc,
            block_s=bt,
            seq_len=S,
            packed=packed,
            scale_dtype=cache_k["s"].dtype,
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer [1], lengths [Ba], tables [Ba*nbs]
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, R, PG, W), lambda b, li, lens, tbl: (b, 0, 0, 0)),
                pl.BlockSpec((1, R, 1, W), lambda b, li, lens, tbl: (b, 0, 0, 0)),
                pl.BlockSpec((1, R, 1, W), lambda b, li, lens, tbl: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # fused payload (arena)
                pl.BlockSpec(memory_space=pl.ANY),  # plain scales (arena)
                pl.BlockSpec(memory_space=pl.ANY),  # fused payload (pool)
                pl.BlockSpec(memory_space=pl.ANY),  # plain scales (pool)
            ],
            out_specs=pl.BlockSpec(
                (1, R, PG, W), lambda b, li, lens, tbl: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((2, Hh, bt, W), jnp.int8),
                pltpu.VMEM((2, 2 * Hkv, bt), cache_k["s"].dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        )
        return ctx_apart(pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_q8_paged",
        )(
            jnp.reshape(layer, (1,)).astype(jnp.int32),
            lengths.astype(jnp.int32),
            tblf,
            qw,
            nk4,
            nv4,
            cache_k["q"],
            cache_k["s"],
            pool_k["q"],
            pool_k["s"],
        ), P)

    mode = os.environ.get("LLM_MCP_TPU_Q8_DECODE", "auto")

    def run_contig():
        if window or (mode == "whole" and can_whole):
            return run_whole()
        if mode == "blocked" and BS:
            return run_blocked()
        if not can_whole:
            return run_blocked()
        if BS == 0 or interp:
            # interpret mode keeps the static whole-S choice: a runtime cond
            # would emulate BOTH kernels per call in tests. Parity tests force
            # the blocked arm via LLM_MCP_TPU_Q8_DECODE=blocked instead.
            return run_whole()
        # Runtime hybrid (both executables compile once). The threshold 0.55
        # is NOT MEASURED on this layout: it was projected from a 4-copy layout
        # two rewrites ago (r05: crossover at a traffic ratio of about 0.4), and
        # no cell of the benchmark fills its cache past 0.4, so every measured
        # round takes the blocked arm (PERF.md section 7). The env knob is the
        # re-tuning surface until a long-context cell measures the crossing.
        # Compare the kernels' ACTUAL traffic: whole-S DMAs all B rows in full
        # (parked/pad rows included), blocked streams the attended prefix per
        # active row and ONE block per parked row — so the ratio denominator is
        # B·S, not active·S (normalizing by active rows would overestimate the
        # whole-S path exactly in the low-occupancy regime blocked wins).
        thr = float(os.environ.get("LLM_MCP_TPU_Q8_HYBRID", "0.55"))
        w_eff = jnp.where(lengths < S, jnp.minimum(lengths + 1, S), BS)
        ratio = jnp.sum(w_eff.astype(jnp.float32)) / (B * S)
        return jax.lax.cond(ratio < thr, run_blocked, run_whole)

    if block_tables is None:
        return run_contig()
    nbs = block_tables.shape[1]
    paged_ok = (
        pool_k is not None and nbs > 0 and S % nbs == 0
        and (S // nbs) in (32, 64, 128, 256)
    )
    if not paged_ok:
        # table present but the ledger block size has no int8-tileable arm
        # (the engine gates physical mode on this; belt): exact gather math
        _note_fall("decode_attend_q8", f"paged: block size {S}/{nbs} untileable", interp)
        return _decode_attend_q8_fallback(
            q, new_k, new_v, cache_k, cache_v, layer, lengths, sc, slot_ids,
            block_tables, pool_k,
        )
    if mode == "paged":
        return run_paged()
    if interp:
        # a runtime identity-cond would emulate both arms per call in tests;
        # parity tests force the paged kernel via LLM_MCP_TPU_Q8_DECODE=paged,
        # everything else takes the exact gather math
        return _decode_attend_q8_fallback(
            q, new_k, new_v, cache_k, cache_v, layer, lengths, sc, slot_ids,
            block_tables, pool_k,
        )
    # Identity tables (no row references a shared block — the raw-decode
    # case, and every freed slot resets to identity) keep the contiguous
    # dispatch bit-for-bit, hybrid included; only actual sharing pays the
    # table-gather arm.
    n_slots = cache_k["q"].shape[1]
    ident = jnp.all(
        block_tables
        == jnp.arange(n_slots * nbs, dtype=block_tables.dtype).reshape(n_slots, nbs)
    )
    return jax.lax.cond(ident, run_contig, run_paged)


# ---------------------------------------------------------------------------
# A block pass's attention over the fused int8 cache (`cfg.block_len`)
# ---------------------------------------------------------------------------


def block_row_blocks(starts, seq_len: int, block_s: int, xp=jnp):
    """Blocks of `block_s` cache positions the block-attention arm streams for
    a row whose block starts at `starts`: the past [0, start) rounded up to
    whole blocks. None for a reply's first block at position 0, and none for a
    parked row (start >= seq_len: its output is discarded, and its own L keys
    give the softmax something to sum). `xp` as in `blocked_row_blocks`."""
    return xp.where(starts >= seq_len, 0, (starts + block_s - 1) // block_s)


def _block_attend_q8_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    ids_ref,  # [A] int32 (scalar prefetch) — cache row per batch row
    starts_ref,  # [A] int32 (scalar prefetch) — each block's first position
    cum_ref,  # [A + 1] int32 (scalar prefetch) — running sum of the rows'
    #           block counts: row b's cells are cum[b] .. cum[b + 1] - 1
    nxt_ref,  # [A + 1] int32 (scalar prefetch) — cache row of the first batch
    #           row that has a cell ([0]) and of the next one after row b
    #           ([b + 1]): a row without a past has none and is stepped over
    q_ref,  # [1, L, R, P*G, W] VMEM — the block's L positions, each
    #         `q_abreast`'s rows: R = Hkv / P cache rows of P heads abreast, a
    #         head's G query heads in its own lanes, W = P*hd
    ks_ref,  # [1, L, R, W] VMEM — the block's own keys (post-rope), abreast
    vs_ref,  # [1, L, R, W] VMEM
    pay_hbm,  # [Lyr, B, 2*R + p, S, W] int8 — fused K|V(|packed scales)
    #           payload, stays in HBM (ANY), DMA'd per block
    s_hbm,  # [Lyr, B, 2*Hkv, S] — plain scales (read only when packed=False)
    o_ref,  # [1, L, R, P*G, W] VMEM out
    pay_buf,  # VMEM scratch [2, Hh, BS, W] int8 (double buffer);
    #           Hh = 2*R + 1 when packed else 2*R
    s_buf,  # [2, 2*Hkv, BS] (unused when packed)
    sems,  # DMA semaphores [2, 2]
    *,
    scale: float,
    block_s: int,
    packed: bool,
    scale_dtype,
):
    """The attention of one row's BLOCK of L positions (`cfg.block_len`;
    models/llama.py:block_pass): the L positions folded into the query-row
    axis, M = P*L*G query rows a cache row (a head's L*G rows together, so
    that `_scales_by_row` spreads the scales as for a decode step's), against
    the row's past [0, start), streamed out of HBM in blocks as
    `_attend_q8_blocked_kernel` streams a decode step's, and against the
    block's own L keys, every one of them (inside a block the mask is whole).
    The fold is made HERE, of float32 pieces of whole tiles (G = 8 rows), and
    undone where the output is stored: the queries come and the contexts go as
    the projections around the call have them, [L, heads, hd] a row, and no
    transpose of either stands in the program.

    The SELF segment comes first and from registers, exact: it starts the
    online softmax, so a row with no past block (a reply's first block at
    position 0, a parked row) runs no cell at all and still divides by a sum.
    The PAST segment is `ceil(start / BS)` cells; the cells of the whole batch
    are ONE pipeline (`_attend_q8_blocked_kernel` says why), which steps over
    the rows that have none (`nxt_ref`).

    Precision is the bucketed chunk's (`models/llama.py:_chunk_attention`),
    NOT the decode kernels': the int8 rows are converted in VMEM to the
    queries' dtype and multiplied as such (float32 accumulator), the K scales
    multiply the scores after the product and the V scales the probabilities
    before theirs, the softmax is float32. No query or probability is
    requantized: a block pass states bfloat16 against int8 rows, and is bound
    by bytes and overheads, not by the MXU."""
    b = pl.program_id(0)
    li = li_ref[0]
    BS = block_s
    _, L, R, PG, _ = q_ref.shape
    Hkv = s_buf.shape[1] // 2
    P = Hkv // R
    G = PG // P
    n_rows = ids_ref.shape[0]
    row = ids_ref[b]
    start_pos = starts_ref[b]
    c0 = cum_ref[b]
    nblk = cum_ref[b + 1] - c0
    total = cum_ref[n_rows]

    def copies(row, j, slot):
        if packed:
            return (
                pltpu.make_async_copy(
                    pay_hbm.at[li, row, :, pl.ds(j * BS, BS), :],
                    pay_buf.at[slot],
                    sems.at[slot, 0],
                ),
            )
        return (
            pltpu.make_async_copy(
                pay_hbm.at[li, row, pl.ds(0, 2 * R), pl.ds(j * BS, BS), :],
                pay_buf.at[slot],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                s_hbm.at[li, row, :, pl.ds(j * BS, BS)],
                s_buf.at[slot],
                sems.at[slot, 1],
            ),
        )

    def start(row, j, slot):
        for c in copies(row, j, slot):
            c.start()

    def wait(row, j, slot):
        for c in copies(row, j, slot):
            c.wait()

    @pl.when((b == 0) & (total > 0))
    def _first_cell():  # the one copy nothing runs ahead of
        start(nxt_ref[0], 0, 0)

    # head p's rows of position l at [(p*L + l)*G, +G)
    qf = jnp.concatenate(
        [q_ref[0, l, :, p * G : (p + 1) * G].astype(jnp.float32)
         for p in range(P) for l in range(L)], axis=1)  # [R, M, W]
    q = qf.astype(q_ref.dtype)
    # the block's own keys: L scores a query row, exact
    s_self = [
        jnp.sum(qf * ks_ref[0, t].astype(jnp.float32)[:, None, :], axis=-1, keepdims=True) * scale
        for t in range(L)
    ]  # L x [R, M, 1]
    m0 = functools.reduce(jnp.maximum, s_self)
    p_self = [jnp.exp(s - m0) for s in s_self]
    l0 = functools.reduce(jnp.add, p_self)
    acc0 = functools.reduce(
        jnp.add,
        [p * vs_ref[0, t].astype(jnp.float32)[:, None, :] for t, p in enumerate(p_self)],
    )  # [R, M, W]

    def body(j, carry):
        acc, m, l = carry
        c = c0 + j
        slot = jax.lax.rem(c, 2)
        last = j + 1 == nblk

        @pl.when(c + 1 < total)
        def _prefetch():  # the batch's next cell: this row's, or the next row's that has one
            start(jnp.where(last, nxt_ref[b + 1], row), jnp.where(last, 0, j + 1), 1 - slot)

        wait(row, j, slot)
        buf = pay_buf[slot]  # [Hh, BS, W] int8 — k rows, v rows(, scales)
        if packed:
            ss = _unpack_scale_lanes(buf[2 * R], 2 * Hkv, scale_dtype)
        else:
            ss = s_buf[slot].astype(jnp.float32)
        kss, vss = ss[:Hkv], ss[Hkv:]  # [Hkv, BS] f32
        s = jax.lax.dot_general(
            q, buf[:R].astype(q.dtype), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [R, M, BS]
        s = s * _scales_by_row(kss, P, L * G) * scale
        seen = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, 1, BS), 2) < start_pos
        s = jnp.where(seen, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        ctx = jax.lax.dot_general(
            (p * _scales_by_row(vss, P, L * G)).astype(q.dtype),
            buf[R : 2 * R].astype(q.dtype),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [R, M, W]
        return acc * alpha + ctx, m_new, l_new

    acc, _, l = jax.lax.fori_loop(0, nblk, body, (acc0, m0, l0))
    out = (acc / l).astype(o_ref.dtype)
    for p in range(P):
        for t in range(L):
            o_ref[0, t, :, p * G : (p + 1) * G] = out[:, (p * L + t) * G : (p * L + t + 1) * G]


@functools.partial(jax.jit, static_argnames=("interpret", "scale", "block_s"))
def block_attend_q8(
    q: jnp.ndarray,  # [A, L, Hkv, G, hd] — the block's queries (post-rope)
    k_self: jnp.ndarray,  # [A, Hkv, L, hd] — the block's own keys (post-rope)
    v_self: jnp.ndarray,  # [A, Hkv, L, hd]
    cache_k: dict,  # FUSED: {"q": int8 [Lyr,B,2*Hkv/P+p,S,P*hd], "s": [Lyr,B,2*Hkv,S]}
    layer: jnp.ndarray,  # scalar int32
    starts: jnp.ndarray,  # [A] int32 — each block's first position; >= S: parked
    *,
    slot_ids: jnp.ndarray | None = None,  # [A] int32 cache rows (None = 1:1)
    scale: float = 0.0,  # query scale (0 = head_dim**-0.5)
    interpret: bool | None = None,
    block_s: int | None = None,  # None = the rule (`q8_block_tokens`); tests give others
) -> jnp.ndarray:
    """Attention of a block pass (`cfg.block_len`; models/llama.py:block_pass)
    over the FUSED int8 KV cache for one layer: every query of a row's block
    of L positions against the row's cache [0, start) (PRE-write: the commit
    pass reads, then writes) and against the block's own L keys and values,
    which are given exact. Only the blocks that hold past positions leave HBM
    (`block_row_blocks`), and the layer is cut out of the stacked cache by the
    kernel's copies: no slice of a layer's payload stands in the program.

    The caller has checked that the arm fits (`blocked_arm_fits`, a block size
    that divides the row). Returns ctx [A, L, Hkv, G, hd]; a parked row's is
    its own keys' and is discarded."""
    A, L, Hkv, G, hd = q.shape
    S = cache_k["q"].shape[3]
    interp = _interpret() if interpret is None else interpret
    sc = scale or hd**-0.5
    _, p, P = fused_q8_heads(cache_k)
    R, PG, W = Hkv // P, P * G, cache_k["q"].shape[4]
    assert W == P * hd, (cache_k["q"].shape, hd)
    BS = block_s or q8_block_tokens(2 * R + p, S, W)
    assert BS and blocked_arm_fits(W, interp), (cache_k["q"].shape, BS)
    # 1-DMA packed blocks need the scale pseudo-head present in the layout
    packed = p == 1 and os.environ.get("LLM_MCP_TPU_Q8_SCALE_PACK", "1") != "0"
    Hh = 2 * R + 1 if packed else 2 * R

    ids = jnp.arange(A, dtype=jnp.int32) if slot_ids is None else slot_ids.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    nblk = block_row_blocks(starts, S, BS)
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(nblk)])
    # the batch row that holds the first cell at or after row b: the least
    # index with a cell, taken from the back (A where none: any row will do)
    idx = jnp.where(nblk > 0, jnp.arange(A, dtype=jnp.int32), A)
    at_or_after = jax.lax.cummin(idx, reverse=True)
    nxt = ids[jnp.minimum(jnp.concatenate([at_or_after, jnp.full((1,), A, jnp.int32)]), A - 1)]

    qw = q_abreast(q, P)  # [A, L, R, P*G, W]
    ks = jnp.swapaxes(kv_abreast(k_self, P), 1, 2)  # [A, L, R, W]
    vs = jnp.swapaxes(kv_abreast(v_self, P), 1, 2)
    kernel = functools.partial(
        _block_attend_q8_kernel, scale=sc, block_s=BS, packed=packed,
        scale_dtype=cache_k["s"].dtype,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # layer [1], cache rows, starts, cells, next rows [A + 1]
        grid=(A,),
        in_specs=[
            pl.BlockSpec((1, L, R, PG, W), lambda b, *_: (b, 0, 0, 0, 0)),
            pl.BlockSpec((1, L, R, W), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, L, R, W), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # fused payload, all layers
            pl.BlockSpec(memory_space=pl.ANY),  # plain scales
        ],
        out_specs=pl.BlockSpec((1, L, R, PG, W), lambda b, *_: (b, 0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, Hh, BS, W), jnp.int8),
            pltpu.VMEM((2, 2 * Hkv, BS), cache_k["s"].dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, interpret=interp,
        out_shape=jax.ShapeDtypeStruct((A, L, R, PG, W), q.dtype),
        name="block_attn_q8",  # no reader of `decode_attn*` picks it up
        # sequential: a cell's copy is started in the grid step before its own
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), ids, starts, cum, nxt,
      qw, ks, vs, cache_k["q"], cache_k["s"])
    return ctx_apart(out, P)


def _attend_bf16_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    ids_ref,  # [Ba] int32 (scalar prefetch) — cache row per batch position
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    q_ref,  # [1, 1, G, hd]
    nk_ref,  # [1, 1, 1, hd] — this step's K vector (post-rope)
    nv_ref,  # [1, 1, 1, hd]
    k_ref,  # [1, 1, 1, S, hd] — cache tile, PRE-append
    v_ref,  # [1, 1, 1, S, hd]
    o_ref,  # [1, 1, G, hd]
    *,
    scale: float,
    window: int = 0,
):
    """Whole-S bf16 decode attention, one grid cell = one (batch row, KV
    head) — the bf16 sibling of `_attend_q8_kernel`, with the same
    compaction indirection (slot ids), traced layer index, and exact
    current-position override. A per-(row, head) cell keeps the VMEM
    per-position cost at ~2·hd·2 bytes so the whole-S arm reaches the same
    ~12K-position cap as the q8 arm (`decode_pallas_max_seq`). `window`: the
    tile is a window layer's ring, as in `_attend_q8_kernel`."""
    b = pl.program_id(0)
    w = lengths_ref[b]
    S = k_ref.shape[3]

    k = k_ref[0, 0, 0]  # [S, hd] cache dtype — fed to the MXU un-upcast
    v = v_ref[0, 0, 0]
    q = q_ref[0, 0]  # [G, hd]
    nk = nk_ref[0, 0, 0].astype(jnp.float32)  # [hd]
    nv = nv_ref[0, 0, 0].astype(jnp.float32)

    s = (
        jax.lax.dot_general(
            q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [G, S]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    at_w, seen = _ring_masks(pos, w, S, window)
    # the tile holds the PRE-append cache — position w's score/value come
    # from the exact new vectors (append happens outside the kernel)
    s_new = (
        jnp.sum(q.astype(jnp.float32) * nk[None, :], axis=-1, keepdims=True) * scale
    )  # [G, 1]
    s = jnp.where(at_w(), s_new, s)
    s = jnp.where(seen(), s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p_w = jnp.sum(jnp.where(at_w(), p, 0.0), axis=-1, keepdims=True)  # [G, 1]
    pv = jnp.where(at_w(), 0.0, p)
    ctx = jax.lax.dot_general(
        pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [G, hd]
    ctx = ctx + p_w * nv[None, :]
    o_ref[0, 0] = (ctx / l).astype(o_ref.dtype)


def _attend_bf16_blocked_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    ids_ref,  # [Ba] int32 (scalar prefetch) — cache row per batch position
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    q_ref,  # [1, Hkv, G, hd] VMEM
    nk_ref,  # [1, Hkv, 1, hd] VMEM
    nv_ref,  # [1, Hkv, 1, hd] VMEM
    k_hbm,  # [L, B, Hkv, S, hd] — stays in HBM (ANY), DMA'd per block
    v_hbm,  # [L, B, Hkv, S, hd]
    o_ref,  # [1, Hkv, G, hd] VMEM out
    k_buf,  # VMEM scratch [2, Hkv, BS, hd] cache dtype (double buffer)
    v_buf,
    sems,  # DMA semaphores [2, 2]
    *,
    scale: float,
    block_s: int,
    seq_len: int,
):
    """Blocked bf16 decode attention — the GQA bf16 sibling of
    `_attend_q8_blocked_kernel`: dynamic trip count streams only the
    attended prefix [0, w], flash-style online softmax across blocks, one
    grid cell = one batch row (all KV heads). Two DMAs per cell (split K and
    V arrays); the bf16 cache keeps its bare split layout because there are
    no scale rows to fuse."""
    b = pl.program_id(0)
    li = li_ref[0]
    row = ids_ref[b]
    w = lengths_ref[b]
    BS = block_s
    Hkv = q_ref.shape[1]
    nblk_max = seq_len // BS
    nblk = jnp.clip((w + BS) // BS, 1, nblk_max)
    # parked/free rows (w >= S, engine convention): stream one block
    nblk = jnp.where(w >= seq_len, 1, nblk)

    def copies(j, slot):
        return (
            pltpu.make_async_copy(
                k_hbm.at[li, row, :, pl.ds(j * BS, BS), :],
                k_buf.at[slot],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                v_hbm.at[li, row, :, pl.ds(j * BS, BS), :],
                v_buf.at[slot],
                sems.at[slot, 1],
            ),
        )

    def start(j, slot):
        for c in copies(j, slot):
            c.start()

    def wait(j, slot):
        for c in copies(j, slot):
            c.wait()

    start(0, 0)

    q = q_ref[0]  # [Hkv, G, hd]
    nk = nk_ref[0, :, 0].astype(jnp.float32)  # [Hkv, hd]
    nv = nv_ref[0, :, 0].astype(jnp.float32)
    qc = q.astype(k_buf.dtype)
    s_new = (
        jnp.sum(q.astype(jnp.float32) * nk[:, None, :], axis=-1, keepdims=True) * scale
    )  # [Hkv, G, 1]

    G = q_ref.shape[2]
    hd = q_ref.shape[3]
    acc0 = jnp.zeros((Hkv, G, hd), jnp.float32)
    m0 = jnp.full((Hkv, G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _prefetch():
            start(j + 1, 1 - slot)

        wait(j, slot)
        k = k_buf[slot]  # [Hkv, BS, hd]
        v = v_buf[slot]
        s = (
            jax.lax.dot_general(
                qc, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Hkv, G, BS]
        pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, 1, BS), 2)
        s = jnp.where(pos == w, s_new, s)
        s = jnp.where(pos <= w, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(pos <= w, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p_w = jnp.sum(jnp.where(pos == w, p, 0.0), axis=-1, keepdims=True)
        pv = jnp.where(pos == w, 0.0, p)
        ctx = jax.lax.dot_general(
            pv.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, hd]
        acc_new = acc * alpha + ctx + p_w * nv[:, None, :]
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(0, nblk, body, (acc0, m0, l0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _attend_bf16_paged_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    tbl_ref,  # [Ba * nbs] int32 (scalar prefetch) — flattened block tables
    q_ref,  # [1, Hkv, G, hd] VMEM
    nk_ref,  # [1, Hkv, 1, hd] VMEM
    nv_ref,  # [1, Hkv, 1, hd] VMEM
    k_hbm,  # [L, B, Hkv, S, hd] — slot arena (identity homes), HBM
    v_hbm,  # [L, B, Hkv, S, hd]
    pool_k_hbm,  # [L, PXB, Hkv, bt, hd] — prefix block pool
    pool_v_hbm,  # [L, PXB, Hkv, bt, hd]
    o_ref,  # [1, Hkv, G, hd] VMEM out
    k_buf,  # VMEM scratch [2, Hkv, BS, hd] cache dtype (double buffer)
    v_buf,
    sems,  # DMA semaphores [2, 2]
    *,
    scale: float,
    block_s: int,
    seq_len: int,
):
    """Block-indirect sibling of `_attend_bf16_blocked_kernel`: same math
    and double-buffered streaming, each block's two DMAs (split K/V)
    resolved through the per-row block table — arena home vs. pool row,
    same block shape either way (see `_attend_q8_paged_kernel`)."""
    b = pl.program_id(0)
    li = li_ref[0]
    w = lengths_ref[b]
    BS = block_s
    Hkv = q_ref.shape[1]
    nbs = seq_len // BS
    pool_base = k_hbm.shape[1] * nbs
    nblk = jnp.clip((w + BS) // BS, 1, nbs)
    # parked/free rows (w >= S): one block; freed rows reset to identity
    nblk = jnp.where(w >= seq_len, 1, nblk)

    def arena_copies(phys, slot):
        arow = phys // nbs
        aoff = (phys % nbs) * BS
        return (
            pltpu.make_async_copy(
                k_hbm.at[li, arow, :, pl.ds(aoff, BS), :],
                k_buf.at[slot],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                v_hbm.at[li, arow, :, pl.ds(aoff, BS), :],
                v_buf.at[slot],
                sems.at[slot, 1],
            ),
        )

    def pool_copies(phys, slot):
        prow = phys - pool_base
        return (
            pltpu.make_async_copy(
                pool_k_hbm.at[li, prow], k_buf.at[slot], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                pool_v_hbm.at[li, prow], v_buf.at[slot], sems.at[slot, 1]
            ),
        )

    def issue(j, slot, op):
        phys = tbl_ref[b * nbs + j]
        ina = phys < pool_base

        @pl.when(ina)
        def _arena():
            for c in arena_copies(phys, slot):
                getattr(c, op)()

        @pl.when(jnp.logical_not(ina))
        def _pool():
            for c in pool_copies(phys, slot):
                getattr(c, op)()

    issue(0, 0, "start")

    q = q_ref[0]  # [Hkv, G, hd]
    nk = nk_ref[0, :, 0].astype(jnp.float32)  # [Hkv, hd]
    nv = nv_ref[0, :, 0].astype(jnp.float32)
    qc = q.astype(k_buf.dtype)
    s_new = (
        jnp.sum(q.astype(jnp.float32) * nk[:, None, :], axis=-1, keepdims=True) * scale
    )  # [Hkv, G, 1]

    G = q_ref.shape[2]
    hd = q_ref.shape[3]
    acc0 = jnp.zeros((Hkv, G, hd), jnp.float32)
    m0 = jnp.full((Hkv, G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _prefetch():
            issue(j + 1, 1 - slot, "start")

        issue(j, slot, "wait")
        k = k_buf[slot]  # [Hkv, BS, hd]
        v = v_buf[slot]
        s = (
            jax.lax.dot_general(
                qc, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Hkv, G, BS]
        pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, 1, BS), 2)
        s = jnp.where(pos == w, s_new, s)
        s = jnp.where(pos <= w, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(pos <= w, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p_w = jnp.sum(jnp.where(pos == w, p, 0.0), axis=-1, keepdims=True)
        pv = jnp.where(pos == w, 0.0, p)
        ctx = jax.lax.dot_general(
            pv.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, hd]
        acc_new = acc * alpha + ctx + p_w * nv[:, None, :]
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(0, nblk, body, (acc0, m0, l0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _decode_attend_bf16_fallback(
    q, new_k, new_v, cache_k, cache_v, layer, lengths, sc, slot_ids=None,
    block_tables=None, pool_k=None, pool_v=None,
):
    """Exact-f32 einsum mirror of the bf16 kernels' math (whole-S reference
    for the parity tests; the serving path on CPU / multi-chip meshes).
    With `block_tables` the rows gather block-indirectly first."""
    S = cache_k.shape[3]
    k = jax.lax.dynamic_index_in_dim(cache_k, layer, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache_v, layer, 0, keepdims=False)
    if block_tables is not None:
        tbl = (
            block_tables
            if slot_ids is None
            else jnp.take(block_tables, slot_ids, 0)
        )
        k = paged_gather(
            k, jax.lax.dynamic_index_in_dim(pool_k, layer, 0, keepdims=False), tbl
        )
        v = paged_gather(
            v, jax.lax.dynamic_index_in_dim(pool_v, layer, 0, keepdims=False), tbl
        )
    elif slot_ids is not None:
        k = jnp.take(k, slot_ids, 0)
        v = jnp.take(v, slot_ids, 0)
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bhgd,bhsd->bhgs", qf, k.astype(jnp.float32)) * sc
    pos = jnp.arange(S)[None, None, None, :]
    w = lengths[:, None, None, None]
    s_new = jnp.einsum("bhgd,bhd->bhg", qf, new_k.astype(jnp.float32)) * sc
    s = jnp.where(pos == w, s_new[..., None], s)
    s = jnp.where(pos <= w, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p_w = jnp.sum(jnp.where(pos == w, p, 0.0), axis=-1)  # [B, Hkv, G]
    pv = jnp.where(pos == w, 0.0, p)
    ctx = jnp.einsum("bhgs,bhsd->bhgd", pv, v.astype(jnp.float32))
    ctx = ctx + p_w[..., None] * new_v.astype(jnp.float32)[:, :, None, :]
    return ctx.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "scale", "window"))
def decode_attend_bf16(
    q: jnp.ndarray,  # [Ba, Hkv, G, hd] — COMPACT batch (active rows only)
    new_k: jnp.ndarray,  # [Ba, Hkv, hd] — post-rope K for this step
    new_v: jnp.ndarray,  # [Ba, Hkv, hd]
    cache_k: jnp.ndarray,  # [L, B, Hkv, S, hd] — FULL stacked cache, PRE-append
    cache_v: jnp.ndarray,  # [L, B, Hkv, S, hd]
    layer: jnp.ndarray,  # scalar int32
    lengths: jnp.ndarray,  # [Ba] int32 — this step's position per row
    *,
    slot_ids: jnp.ndarray | None = None,  # [Ba] int32 cache rows (None = 1:1)
    block_tables: jnp.ndarray | None = None,  # [n_slots, nbs] int32 physical
    #   block tables (executor/physical.py); None = contiguous layout
    pool_k: jnp.ndarray | None = None,  # prefix pool [L, PXB, Hkv, bt, hd]
    pool_v: jnp.ndarray | None = None,
    scale: float = 0.0,  # query scale (0 = head_dim**-0.5)
    interpret: bool | None = None,
    window: int = 0,  # > 0: the caches are a window layer's RING (`decode_attend_q8`)
) -> jnp.ndarray:
    """Attention over the bf16 (or f32) split KV cache for one layer of the
    decode step — the bf16 twin of `decode_attend_q8`: same scan-invariant
    PRE-append cache contract, compaction indirection, exact
    current-position override, and runtime whole-S/blocked hybrid
    (`LLM_MCP_TPU_BF16_DECODE` forces an arm, `LLM_MCP_TPU_BF16_HYBRID`
    re-tunes the traffic-ratio threshold). With `block_tables`/pools the
    cache is block-indirect with the same identity-check fast path as
    `decode_attend_q8` (`LLM_MCP_TPU_BF16_DECODE=paged` forces the paged
    arm). Returns ctx [B, Hkv, G, hd]."""
    B, Hkv, G, hd = q.shape
    S = cache_k.shape[3]
    interp = _interpret() if interpret is None else interpret
    sc = scale or hd**-0.5

    nk4 = new_k.reshape(B, Hkv, 1, hd)
    nv4 = new_v.reshape(B, Hkv, 1, hd)
    can_whole = S <= decode_pallas_max_seq(hd, Hkv, Hkv * G, quantized=False)
    # BS must divide S (a floored block count would silently drop the tail)
    BS = next((c for c in (256, 128, 64, 32) if S % c == 0), 0)
    if window and (not can_whole or S & (S - 1) or S < window or block_tables is not None):
        raise NotImplementedError(f"a ring of {S} positions for a window of {window}")
    if not can_whole and BS == 0:
        _note_fall("decode_attend_bf16", f"S={S}: no whole-S fit, no block size", interp)
        return _decode_attend_bf16_fallback(
            q, new_k, new_v, cache_k, cache_v, layer, lengths, sc, slot_ids,
            block_tables, pool_k, pool_v,
        )
    ids = (
        jnp.arange(B, dtype=jnp.int32)
        if slot_ids is None
        else slot_ids.astype(jnp.int32)
    )
    args = (
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        ids,
        lengths.astype(jnp.int32),
        q,
        nk4,
        nv4,
        cache_k,
        cache_v,
    )
    out_shape = jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype)

    def run_whole():
        kernel = functools.partial(_attend_bf16_kernel, scale=sc, window=window)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer [1], slot ids [Ba], lengths [Ba]
            grid=(B, Hkv),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, li, ids, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 1, hd), lambda b, h, li, ids, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 1, hd), lambda b, h, li, ids, lens: (b, h, 0, 0)),
                pl.BlockSpec(
                    (1, 1, 1, S, hd),
                    lambda b, h, li, ids, lens: (li[0], ids[b], h, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, 1, S, hd),
                    lambda b, h, li, ids, lens: (li[0], ids[b], h, 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, G, hd), lambda b, h, li, ids, lens: (b, h, 0, 0)
            ),
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_win_bf16" if window else "decode_attn_bf16_whole",
        )(*args)

    def run_blocked():
        kernel = functools.partial(
            _attend_bf16_blocked_kernel, scale=sc, block_s=BS, seq_len=S
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer [1], slot ids [Ba], lengths [Ba]
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, Hkv, G, hd), lambda b, li, ids, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, Hkv, 1, hd), lambda b, li, ids, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, Hkv, 1, hd), lambda b, li, ids, lens: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # K cache
                pl.BlockSpec(memory_space=pl.ANY),  # V cache
            ],
            out_specs=pl.BlockSpec(
                (1, Hkv, G, hd), lambda b, li, ids, lens: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((2, Hkv, BS, hd), cache_k.dtype),
                pltpu.VMEM((2, Hkv, BS, hd), cache_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_bf16_blocked",
        )(*args)

    def run_paged():
        # block-indirect arm: BS pinned to the ledger's block_tokens
        nbs = block_tables.shape[1]
        bt = S // nbs
        tblf = jnp.take(block_tables, ids, 0).reshape(-1).astype(jnp.int32)
        kernel = functools.partial(
            _attend_bf16_paged_kernel, scale=sc, block_s=bt, seq_len=S
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer [1], lengths [Ba], tables [Ba*nbs]
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, Hkv, G, hd), lambda b, li, lens, tbl: (b, 0, 0, 0)),
                pl.BlockSpec((1, Hkv, 1, hd), lambda b, li, lens, tbl: (b, 0, 0, 0)),
                pl.BlockSpec((1, Hkv, 1, hd), lambda b, li, lens, tbl: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # K arena
                pl.BlockSpec(memory_space=pl.ANY),  # V arena
                pl.BlockSpec(memory_space=pl.ANY),  # K pool
                pl.BlockSpec(memory_space=pl.ANY),  # V pool
            ],
            out_specs=pl.BlockSpec(
                (1, Hkv, G, hd), lambda b, li, lens, tbl: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((2, Hkv, bt, hd), cache_k.dtype),
                pltpu.VMEM((2, Hkv, bt, hd), cache_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_bf16_paged",
        )(
            jnp.reshape(layer, (1,)).astype(jnp.int32),
            lengths.astype(jnp.int32),
            tblf,
            q,
            nk4,
            nv4,
            cache_k,
            cache_v,
            pool_k,
            pool_v,
        )

    mode = os.environ.get("LLM_MCP_TPU_BF16_DECODE", "auto")

    def run_contig():
        if window or (mode == "whole" and can_whole):
            return run_whole()
        if mode == "blocked" and BS:
            return run_blocked()
        if not can_whole:
            return run_blocked()
        if BS == 0 or interp:
            # interpret mode keeps the static whole-S choice (same reasoning
            # as decode_attend_q8); parity tests force the blocked arm via
            # LLM_MCP_TPU_BF16_DECODE=blocked.
            return run_whole()
        # Runtime hybrid, same traffic-ratio rule as the q8 path and the same
        # 0.55, NOT MEASURED: no cell runs a bf16 cache. This blocked arm pays
        # two copies a cell (split K/V) and still waits at every row's edge
        # (the q8 arm's batch-wide pipeline, PR 36, is not ported: ROADMAP C4).
        thr = float(os.environ.get("LLM_MCP_TPU_BF16_HYBRID", "0.55"))
        w_eff = jnp.where(lengths < S, jnp.minimum(lengths + 1, S), BS)
        ratio = jnp.sum(w_eff.astype(jnp.float32)) / (B * S)
        return jax.lax.cond(ratio < thr, run_blocked, run_whole)

    if block_tables is None:
        return run_contig()
    nbs = block_tables.shape[1]
    paged_ok = (
        pool_k is not None and nbs > 0 and S % nbs == 0
        and (S // nbs) in (32, 64, 128, 256)
    )
    if not paged_ok or interp and mode != "paged":
        # engine gates physical mode on a tileable block size (belt), and
        # interpret runs keep a static arm choice — exact gather math
        _note_fall("decode_attend_bf16", f"paged: block size {S}/{nbs} untileable", interp)
        return _decode_attend_bf16_fallback(
            q, new_k, new_v, cache_k, cache_v, layer, lengths, sc, slot_ids,
            block_tables, pool_k, pool_v,
        )
    if mode == "paged":
        return run_paged()
    # identity tables keep the contiguous dispatch (see decode_attend_q8)
    n_slots = cache_k.shape[1]
    ident = jnp.all(
        block_tables
        == jnp.arange(n_slots * nbs, dtype=block_tables.dtype).reshape(n_slots, nbs)
    )
    return jax.lax.cond(ident, run_contig, run_paged)


def _rope_scores(qr, rop_rows, lo: int, n: int, half: int, H: int):
    """Scores [H, n] f32, before the scales, of positions [lo, lo + n) (static)
    of a row whose int8 rope keys lie P abreast (`rope_abreast`): `qr` [P*H,
    P*dr] f32 are `rope_queries`' rows, `rop_rows(r0, m)` the row's rows [r0,
    r0 + m) whole, [m, P*dr] int8. A run of positions inside one lane group is
    ONE product over whole rows with that group's query rows (zeros in the
    other groups' lanes add nothing), and the runs laid side by side are the
    positions in order."""
    parts, s = [], lo
    while s < lo + n:
        g, r0 = divmod(s, half)
        m = min(lo + n - s, half - r0)
        parts.append(jax.lax.dot_general(
            qr[g * H:(g + 1) * H], rop_rows(r0, m).astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32))
        s += m
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _attend_q8_mla_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    ids_ref,  # [Ba] int32 (scalar prefetch) — cache row per batch position
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    qt_ref,  # [1, H, R] — absorbed queries (latent space)
    qr_ref,  # [1, P*H, P*dr] — rope queries, a lane group a row group (`rope_queries`)
    nc_ref,  # [1, 1, R] — this step's exact latent
    nr_ref,  # [1, 1, P*dr] — this step's exact rope key in lane group 0, zeros beside
    lat_ref,  # [1, 1, 1, S, R] int8 — latent payload (cache row ids[b])
    lats_ref,  # [1, 1, 1, S] — latent scales
    rop_ref,  # [1, 1, 1, S/P, P*dr] int8 — rope-key payload, P positions abreast
    rops_ref,  # [1, 1, 1, S] — rope-key scales
    o_ref,  # [1, H, R] — context in latent space
    *,
    scale: float,
):
    """Absorbed MLA decode attention over the int8 latent cache — one grid
    cell per batch row.

    The absorbed form is MQA-shaped (one shared latent row serves every
    head), so this mirrors `_attend_q8_kernel` at Hkv=1/G=H/hd=R with one
    structural difference: scores take a SECOND additive term from the
    shared rope keys. The latent side (R = 512 at DeepSeek shapes — the
    bulk of the HBM traffic) runs s8 x s8 -> s32 on the MXU with post-dot
    scale folding; the rope side (dr = 64, ~1/9 of the bytes, P = 2 positions
    abreast in rows of the 128 lanes: `rope_abreast`) converts on the VPU and
    dots in f32 over whole rows, its scales folded post-dot too (`_rope_scores`).
    Position w's score and value come from the exact unquantized vectors,
    so the current token is attended at full precision whether or not the
    quantized row has been scattered yet.
    """
    b = pl.program_id(0)
    w = lengths_ref[b]
    S = lat_ref.shape[3]

    qt = qt_ref[0].astype(jnp.float32)  # [H, R]
    qr = qr_ref[0].astype(jnp.float32)  # [P*H, P*dr]
    H = qt.shape[0]
    nc = nc_ref[0, 0].astype(jnp.float32)  # [R]
    nr = nr_ref[0, 0].astype(jnp.float32)  # [P*dr]
    lats = lats_ref[0, 0, 0].astype(jnp.float32)  # [S]
    rops = rops_ref[0, 0, 0].astype(jnp.float32)  # [S]

    # latent scores on the MXU: quantize q̃ per head, fold scale post-dot
    qa = jnp.max(jnp.abs(qt), axis=-1)  # [H]
    qsc = jnp.maximum(qa / 127.0, 1e-30)
    qt8 = jnp.round(qt / qsc[:, None]).astype(jnp.int8)
    s_lat_i = jax.lax.dot_general(
        qt8,
        lat_ref[0, 0, 0],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [H, S]
    s = s_lat_i.astype(jnp.float32) * (scale * qsc)[:, None] * lats[None, :]

    # rope scores: S x dr is tiny — f32 dot over the rows as they lie
    s = s + _rope_scores(
        qr, lambda r0, m: rop_ref[0, 0, 0, r0:r0 + m, :], 0, S, rop_ref.shape[3], H
    ) * scale * rops[None, :]

    pos = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    s_new = (
        jnp.sum(qt * nc[None, :], axis=-1) + jnp.sum(qr[:H] * nr[None, :], axis=-1)
    ) * scale  # [H]
    s = jnp.where(pos == w, s_new[:, None], s)
    s = jnp.where(pos <= w, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p_w = jnp.sum(jnp.where(pos == w, p, 0.0), axis=-1, keepdims=True)  # [H, 1]
    # fold the latent dequant scales into the probs, quantize the prob rows,
    # and run the PV dot s8 x s8 too
    pv = jnp.where(pos == w, 0.0, p * lats[None, :])  # [H, S]
    pa = jnp.max(pv, axis=-1)
    psc = jnp.maximum(pa / 127.0, 1e-30)
    p8 = jnp.round(pv / psc[:, None]).astype(jnp.int8)
    ctx_i = jax.lax.dot_general(
        p8,
        lat_ref[0, 0, 0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [H, R]
    ctx = ctx_i.astype(jnp.float32) * psc[:, None] + p_w * nc[None, :]
    o_ref[0] = (ctx / l).astype(o_ref.dtype)


def mla_whole_s_fits(S: int, R: int, dr: int, H: int) -> bool:
    """Whole-S VMEM budget for `_attend_q8_mla_kernel`: int8 payloads + the
    f32 working set — three [H, S] score/prob arrays, the [S, dr]
    dequantized rope block, and the [H, R]-class query/context tiles —
    under ~8 MB headroom. Beyond it the BLOCKED variant streams from HBM."""
    return (
        S * (R + dr) + 4 * S * (3 * H + dr) + 4 * H * (2 * R + dr)
    ) <= 8 * 1024 * 1024


def _attend_q8_mla_blocked_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    ids_ref,  # [Ba] int32 (scalar prefetch) — cache row per batch position
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    qt_ref,  # [1, H, R] VMEM — absorbed queries (latent space)
    qr_ref,  # [1, P*H, P*dr] VMEM — rope queries (`rope_queries`)
    nc_ref,  # [1, 1, R] VMEM — this step's exact latent
    nr_ref,  # [1, 1, P*dr] VMEM — this step's exact rope key, lane group 0
    lat_hbm,  # [L, B, 1, S, R] int8 — latent payload, stays in HBM (ANY)
    lats_ref,  # [1, 1, 1, S] VMEM — latent scales (whole row via BlockSpec)
    rop_ref,  # [1, 1, 1, S/P, P*dr] VMEM — rope payload, P positions abreast
    #           (whole row: a block of positions is a run of rows in ONE lane
    #           group, which no manual DMA cuts out; the BlockSpec pipeline
    #           brings the row. Rope+scales are ≤1/8 of the latent bytes
    #           and the caller caps S//BS at 64, so whole-row VMEM is ≤3 MB)
    rops_ref,  # [1, 1, 1, S] VMEM — rope scales
    o_ref,  # [1, H, R] VMEM out — context in latent space
    lat_buf,  # VMEM scratch [2, BS, R] int8 (double buffer) — the latent
    #           payload is the real bandwidth and DOES stream blockwise
    sems,  # DMA semaphores [2]
    *,
    scale: float,
    block_s: int,
    seq_len: int,
):
    """Long-context MLA decode attention: the blocked-DMA analog of
    `_attend_q8_mla_kernel` (absorbed MQA-shaped form, second additive
    rope-score term) — the latent row stays in HBM and a double-buffered
    DMA loop streams the attended prefix [0, w], flash-style online softmax
    accumulating the latent-space context across blocks.

    The block loop is a STATIC python unroll over seq_len//BS with every
    DMA gated by `pl.when(j < nblk)`: static block indices keep every
    slice/index in the op classes the whole-S kernel already proves Mosaic
    accepts (dynamic slot/offset forms tripped a parade of tiling-alignment
    rejections: size-1 bf16 sublane slices, (2,128)-tiled f32 row DMA dsts,
    64-lane rope slices). Blocks past nblk skip their DMA; their compute
    runs on stale buffer contents and is a NATURAL no-op — every position
    masks to -inf, so the online-softmax update leaves (acc, m, l)
    unchanged. The caller bounds seq_len//BS (program size is linear in
    it) and falls back to exact math beyond the cap."""
    b = pl.program_id(0)
    li = li_ref[0]
    row = ids_ref[b]
    w = lengths_ref[b]
    BS = block_s
    nblk_max = seq_len // BS
    nblk = jnp.clip((w + BS) // BS, 1, nblk_max)
    # parked/free rows (w >= S) produce discarded output: stream one block
    nblk = jnp.where(w >= seq_len, 1, nblk)

    def copy(j: int, slot: int):
        return pltpu.make_async_copy(
            lat_hbm.at[li, row, 0, pl.ds(j * BS, BS), :], lat_buf.at[slot],
            sems.at[slot],
        )

    def start(j: int, slot: int):
        @pl.when(j < nblk)
        def _():
            copy(j, slot).start()

    def wait(j: int, slot: int):
        @pl.when(j < nblk)
        def _():
            copy(j, slot).wait()

    start(0, 0)

    qt = qt_ref[0].astype(jnp.float32)  # [H, R]
    qr = qr_ref[0].astype(jnp.float32)  # [P*H, P*dr]
    H, R = qt.shape
    nc = nc_ref[0, 0].astype(jnp.float32)  # [R]
    nr = nr_ref[0, 0].astype(jnp.float32)  # [P*dr]
    qa = jnp.max(jnp.abs(qt), axis=-1)
    qsc = jnp.maximum(qa / 127.0, 1e-30)
    qt8 = jnp.round(qt / qsc[:, None]).astype(jnp.int8)
    s_new = (
        jnp.sum(qt * nc[None, :], axis=-1) + jnp.sum(qr[:H] * nr[None, :], axis=-1)
    )[:, None] * scale  # [H, 1]

    acc = jnp.zeros((H, R), jnp.float32)
    m = jnp.full((H, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((H, 1), jnp.float32)

    for j in range(nblk_max):  # static unroll; see docstring
        slot = j % 2
        if j + 1 < nblk_max:
            start(j + 1, 1 - slot)
        wait(j, slot)
        lat = lat_buf[slot]  # [BS, R] int8
        # static block slices of the BlockSpec-delivered rows (j is a
        # python int: every start is a provable tile multiple)
        lats = lats_ref[0, 0, 0, j * BS:(j + 1) * BS].astype(jnp.float32)
        # latent scores: s8 x s8 -> s32 on the MXU, post-dot scale fold
        s_i = jax.lax.dot_general(
            qt8, lat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
        )  # [H, BS]
        s = s_i.astype(jnp.float32) * (scale * qsc)[:, None] * lats[None, :]
        # rope scores: BS x dr is tiny — f32 dot over the rows as they lie
        rops = rops_ref[0, 0, 0, j * BS:(j + 1) * BS].astype(jnp.float32)
        s = s + _rope_scores(
            qr, lambda r0, m: rop_ref[0, 0, 0, r0:r0 + m, :], j * BS, BS, rop_ref.shape[3], H
        ) * scale * rops[None, :]
        pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)
        # skipped blocks (j >= nblk) hold STALE buffer bytes — every mask
        # must also gate on the block being live, or a parked row (w >= S,
        # so pos <= w everywhere) would exponentiate garbage into NaN
        live = pos <= jnp.where(j < nblk, w, -1)
        cur = live & (pos == w)
        s = jnp.where(cur, s_new, s)
        s = jnp.where(live, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p_w = jnp.sum(jnp.where(cur, p, 0.0), axis=-1, keepdims=True)
        # fold latent dequant scales into the probs, requantize, PV on MXU.
        # Gate on `live`, not just ~cur: a skipped block's stale lats can be
        # NaN and 0 * NaN = NaN would poison the accumulator.
        pv = jnp.where(live & ~cur, p * lats[None, :], 0.0)  # [H, BS]
        pa = jnp.max(pv, axis=-1)
        psc = jnp.maximum(pa / 127.0, 1e-30)
        p8 = jnp.round(pv / psc[:, None]).astype(jnp.int8)
        ctx_i = jax.lax.dot_general(
            p8, lat, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )  # [H, R]
        acc = acc * alpha + ctx_i.astype(jnp.float32) * psc[:, None] + p_w * nc[None, :]
        m = m_new

    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _attend_q8_mla_paged_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    tbl_ref,  # [Ba * nbs] int32 (scalar prefetch) — flattened block tables
    qt_ref,  # [1, H, R] VMEM — absorbed queries (latent space)
    qr_ref,  # [1, P*H, P*dr] VMEM — rope queries (`rope_queries`)
    nc_ref,  # [1, 1, R] VMEM — this step's exact latent
    nr_ref,  # [1, 1, P*dr] VMEM — this step's exact rope key, lane group 0
    lat_hbm,  # [L, B, 1, S, R] int8 — latent arena (identity homes), HBM
    pool_lat_hbm,  # [L, PXB, 1, bt, R] int8 — latent prefix pool, HBM
    lats_ref,  # [1, S] VMEM — latent scales, PRE-GATHERED through the table
    rop_ref,  # [1, S/P, P*dr] VMEM — rope payload, PRE-GATHERED, laid abreast again
    rops_ref,  # [1, S] VMEM — rope scales, PRE-GATHERED
    o_ref,  # [1, H, R] VMEM out — context in latent space
    lat_buf,  # VMEM scratch [2, BS, R] int8 (double buffer)
    sems,  # DMA semaphores [2]
    *,
    scale: float,
    block_s: int,
    seq_len: int,
):
    """Block-indirect sibling of `_attend_q8_mla_blocked_kernel`: the
    latent payload — ~8/9 of the bytes — streams through the per-row block
    table (arena home vs. pool row, one DMA per block either way); the
    rope payload and both scale rows arrive PRE-GATHERED by the caller
    (`paged_gather` in XLA) because their whole-row BlockSpec rides index
    a single cache row and a [BS, dr]/[1, BS]-class manual DMA is exactly
    the op Mosaic rejected when the blocked kernel was built (see its
    docstring). Same static unroll + `pl.when`-gated DMAs + live-masked
    stale-block no-ops as the blocked variant; BS equals the ledger's
    block_tokens so table entry j covers kernel block j."""
    b = pl.program_id(0)
    li = li_ref[0]
    w = lengths_ref[b]
    BS = block_s
    nbs = seq_len // BS
    pool_base = lat_hbm.shape[1] * nbs
    nblk = jnp.clip((w + BS) // BS, 1, nbs)
    # parked/free rows (w >= S) stream one block; freed rows are identity
    nblk = jnp.where(w >= seq_len, 1, nblk)

    def issue(j: int, slot: int, op: str):
        phys = tbl_ref[b * nbs + j]
        ina = phys < pool_base

        @pl.when((j < nblk) & ina)
        def _arena():
            c = pltpu.make_async_copy(
                lat_hbm.at[li, phys // nbs, 0, pl.ds((phys % nbs) * BS, BS), :],
                lat_buf.at[slot],
                sems.at[slot],
            )
            getattr(c, op)()

        @pl.when((j < nblk) & jnp.logical_not(ina))
        def _pool():
            c = pltpu.make_async_copy(
                pool_lat_hbm.at[li, phys - pool_base, 0],
                lat_buf.at[slot],
                sems.at[slot],
            )
            getattr(c, op)()

    issue(0, 0, "start")

    qt = qt_ref[0].astype(jnp.float32)  # [H, R]
    qr = qr_ref[0].astype(jnp.float32)  # [P*H, P*dr]
    H, R = qt.shape
    nc = nc_ref[0, 0].astype(jnp.float32)  # [R]
    nr = nr_ref[0, 0].astype(jnp.float32)  # [P*dr]
    qa = jnp.max(jnp.abs(qt), axis=-1)
    qsc = jnp.maximum(qa / 127.0, 1e-30)
    qt8 = jnp.round(qt / qsc[:, None]).astype(jnp.int8)
    s_new = (
        jnp.sum(qt * nc[None, :], axis=-1) + jnp.sum(qr[:H] * nr[None, :], axis=-1)
    )[:, None] * scale  # [H, 1]

    acc = jnp.zeros((H, R), jnp.float32)
    m = jnp.full((H, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((H, 1), jnp.float32)

    for j in range(nbs):  # static unroll; see blocked kernel's docstring
        slot = j % 2
        if j + 1 < nbs:
            issue(j + 1, 1 - slot, "start")
        issue(j, slot, "wait")
        lat = lat_buf[slot]  # [BS, R] int8
        lats = lats_ref[0, j * BS:(j + 1) * BS].astype(jnp.float32)
        s_i = jax.lax.dot_general(
            qt8, lat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
        )  # [H, BS]
        s = s_i.astype(jnp.float32) * (scale * qsc)[:, None] * lats[None, :]
        rops = rops_ref[0, j * BS:(j + 1) * BS].astype(jnp.float32)
        s = s + _rope_scores(
            qr, lambda r0, m: rop_ref[0, r0:r0 + m, :], j * BS, BS, rop_ref.shape[1], H
        ) * scale * rops[None, :]
        pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)
        # skipped blocks (j >= nblk) hold STALE buffer bytes — gate every
        # mask on liveness (same invariant as the blocked kernel)
        live = pos <= jnp.where(j < nblk, w, -1)
        cur = live & (pos == w)
        s = jnp.where(cur, s_new, s)
        s = jnp.where(live, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p_w = jnp.sum(jnp.where(cur, p, 0.0), axis=-1, keepdims=True)
        pv = jnp.where(live & ~cur, p * lats[None, :], 0.0)  # [H, BS]
        pa = jnp.max(pv, axis=-1)
        psc = jnp.maximum(pa / 127.0, 1e-30)
        p8 = jnp.round(pv / psc[:, None]).astype(jnp.int8)
        ctx_i = jax.lax.dot_general(
            p8, lat, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )  # [H, R]
        acc = acc * alpha + ctx_i.astype(jnp.float32) * psc[:, None] + p_w * nc[None, :]
        m = m_new

    o_ref[0] = (acc / l).astype(o_ref.dtype)


def mla_block_size(seq_len: int) -> int:
    """Block size for `_attend_q8_mla_blocked_kernel`, 0 = no blocked arm.

    BS must divide S (a floored trip count would drop the tail — including
    the current position). The kernel's block loop is a STATIC python
    unroll (see its docstring), so program size is linear in S//BS: past 64
    blocks (S=32768 at BS=512 is exactly the boundary) compile time
    outgrows the win and `decode_attend_q8_mla` falls back to exact f32
    math instead."""
    bs = next((c for c in (512, 256, 128) if seq_len % c == 0), 0)
    if bs and seq_len // bs > 64:
        return 0
    return bs


def mla_stream_block(seq_len: int, R: int, dr: int, H: int) -> int:
    """Cache positions a block of the latent decode arm streams for a cache of
    this shape, as `decode_attend_q8_mla` chooses its arm: 0 where the whole-S
    arm fits (every row's `seq_len` positions a step, whatever its fill), else
    the blocked arm's block (the attended prefix in whole blocks)."""
    return 0 if mla_whole_s_fits(seq_len, R, dr, H) else mla_block_size(seq_len)


def _decode_attend_q8_mla_fallback(
    qt, qr, new_c, new_r, cache_c, cache_r, layer, lengths, scale, slot_ids,
    block_tables=None, pool_c=None, pool_r=None,
):
    """Exact f32 math of the MLA kernel (CPU / unfit shapes): pre-append
    semantics with the current position overridden by the exact vectors.
    With `block_tables` every cache read gathers block-indirectly."""
    Ba = qt.shape[0]

    def rowsel(x):
        return x if slot_ids is None else jnp.take(x, slot_ids, axis=0)

    if block_tables is not None:
        tbl = (
            block_tables
            if slot_ids is None
            else jnp.take(block_tables, slot_ids, 0)
        )

    def sel(entry, pool_entry=None):
        a = jax.lax.dynamic_index_in_dim(entry, layer, 0, keepdims=False)
        if block_tables is None:
            return rowsel(a[:, 0])
        p = jax.lax.dynamic_index_in_dim(pool_entry, layer, 0, keepdims=False)
        return paged_gather(a, p, tbl)[:, 0]

    lat = sel(cache_c["q"], pool_c and pool_c["q"]).astype(jnp.float32)  # [Ba,S,R]
    # the rope keys' bytes, pulled apart: the positions in order [Ba, S, dr]
    P = cache_c["q"].shape[3] // cache_r["q"].shape[3]
    rop_l = jax.lax.dynamic_index_in_dim(cache_r["q"], layer, 0, keepdims=False)
    if block_tables is None:
        rop = rope_rows(rop_l, P, slot_ids)
    else:
        pool_l = jax.lax.dynamic_index_in_dim(pool_r["q"], layer, 0, keepdims=False)
        rop = rope_rows(rop_l, P, tables=tbl, pool=pool_l)
    rop = rop.astype(jnp.float32)
    ls = sel(cache_c["s"], pool_c and pool_c["s"]).astype(jnp.float32)  # [Ba, S]
    rs = sel(cache_r["s"], pool_r and pool_r["s"]).astype(jnp.float32)
    S = lat.shape[1]
    qtf = qt.astype(jnp.float32)
    qrf = qr.astype(jnp.float32)
    s = (
        jnp.einsum("bhr,bsr->bhs", qtf, lat) * ls[:, None, :]
        + jnp.einsum("bhd,bsd->bhs", qrf, rop) * rs[:, None, :]
    ) * scale
    pos = jnp.arange(S)[None, None, :]
    w = lengths[:, None, None]
    s_new = (
        jnp.einsum("bhr,br->bh", qtf, new_c.astype(jnp.float32))
        + jnp.einsum("bhd,bd->bh", qrf, new_r.astype(jnp.float32))
    ) * scale
    s = jnp.where(pos == w, s_new[..., None], s)
    s = jnp.where(pos <= w, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p_w = jnp.sum(jnp.where(pos == w, p, 0.0), axis=-1)  # [Ba, H]
    pl_ = jnp.where(pos == w, 0.0, p * ls[:, None, :])
    ctx = jnp.einsum("bhs,bsr->bhr", pl_, lat) + p_w[..., None] * new_c.astype(
        jnp.float32
    )[:, None, :]
    return ctx.astype(qt.dtype)


def decode_attend_q8_mla(
    qt: jnp.ndarray,  # [Ba, H, R] — absorbed queries (latent space)
    qr: jnp.ndarray,  # [Ba, H, dr] — rope queries
    new_c: jnp.ndarray,  # [Ba, R] — this step's exact latent
    new_r: jnp.ndarray,  # [Ba, dr] — this step's exact rope key
    cache_c: dict,  # {"q": int8 [L,B,1,S,R], "s": [L,B,1,S]}
    cache_r: dict,  # {"q": int8 [L,B,1,S/P,P*dr] (`rope_abreast`), "s": [L,B,1,S]}
    layer: jnp.ndarray,  # scalar int32
    lengths: jnp.ndarray,  # [Ba] int32 — this step's position per row
    *,
    slot_ids: jnp.ndarray | None = None,
    block_tables: jnp.ndarray | None = None,  # [n_slots, nbs] int32 physical
    #   block tables (executor/physical.py); None = contiguous layout
    pool_c: dict | None = None,  # latent prefix pool mirroring cache_c
    pool_r: dict | None = None,  # rope prefix pool, its rows apart [L,PXB,1,bt,dr]
    scale: float,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Absorbed MLA decode attention over the int8 latent cache for one
    layer — the s8-MXU replacement for the XLA dequant-then-dot path
    (models/mla.py). Returns ctx in latent space [Ba, H, R]; the caller
    owns the cache append (the kernel overrides position w exactly).

    Falls back to exact f32 math off-TPU or when R isn't a 128-lane
    multiple (tiny test configs). Past the whole-S kernel's VMEM budget,
    the BLOCKED variant streams the latent row from HBM with a dynamic
    trip count (`_attend_q8_mla_blocked_kernel`) — int8-latent long
    context (S=32k) runs on the MXU too. With `block_tables`/pools the
    latent payload streams block-indirectly
    (`_attend_q8_mla_paged_kernel`, identity-table fast path as in
    `decode_attend_q8`; `LLM_MCP_TPU_Q8_DECODE=paged` forces the arm)."""
    Ba, H, R = qt.shape
    dr = qr.shape[-1]
    S = cache_c["q"].shape[3]
    # P positions abreast in a row of the rope keys, read off the pair's shapes
    Sr, W = cache_r["q"].shape[3:]
    P = S // Sr
    interp = _interpret() if interpret is None else interpret
    fits = mla_whole_s_fits(S, R, dr, H)
    BS = mla_block_size(S)
    if (not fits and BS == 0) or (not interp and R % 128 != 0):
        _note_fall(
            "decode_attend_q8_mla", f"S={S} R={R}: no whole-S fit or block size",
            interp,
        )
        return _decode_attend_q8_mla_fallback(
            qt, qr, new_c, new_r, cache_c, cache_r, layer, lengths, scale, slot_ids,
            block_tables, pool_c, pool_r,
        )

    ids = (
        jnp.arange(Ba, dtype=jnp.int32)
        if slot_ids is None
        else slot_ids.astype(jnp.int32)
    )
    # the rope queries a lane group a row group, and this step's exact rope key
    # in lane group 0 (the first H query rows' own): `_rope_scores`
    qr_ab = rope_queries(qr, P)  # [Ba, P*H, W]
    nr_ab = jnp.pad(new_r, ((0, 0), (0, W - dr))).reshape(Ba, 1, W)
    args = (
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        ids,
        lengths.astype(jnp.int32),
        qt,
        qr_ab,
        new_c.reshape(Ba, 1, R),
        nr_ab,
        cache_c["q"],
        cache_c["s"],
        cache_r["q"],
        cache_r["s"],
    )
    out_shape = jax.ShapeDtypeStruct((Ba, H, R), qt.dtype)

    def run_whole():
        kernel = functools.partial(_attend_q8_mla_kernel, scale=scale)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer [1], slot ids [Ba], lengths [Ba]
            grid=(Ba,),
            in_specs=[
                pl.BlockSpec((1, H, R), lambda b, li, ids, lens: (b, 0, 0)),
                pl.BlockSpec((1, P * H, W), lambda b, li, ids, lens: (b, 0, 0)),
                pl.BlockSpec((1, 1, R), lambda b, li, ids, lens: (b, 0, 0)),
                pl.BlockSpec((1, 1, W), lambda b, li, ids, lens: (b, 0, 0)),
                pl.BlockSpec(
                    (1, 1, 1, S, R), lambda b, li, ids, lens: (li[0], ids[b], 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, S), lambda b, li, ids, lens: (li[0], ids[b], 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, Sr, W), lambda b, li, ids, lens: (li[0], ids[b], 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, S), lambda b, li, ids, lens: (li[0], ids[b], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, H, R), lambda b, li, ids, lens: (b, 0, 0)),
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_mla_q8_whole",
        )(*args)

    def run_blocked():
        kernel = functools.partial(
            _attend_q8_mla_blocked_kernel, scale=scale, block_s=BS, seq_len=S
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer [1], slot ids [Ba], lengths [Ba]
            grid=(Ba,),
            in_specs=[
                pl.BlockSpec((1, H, R), lambda b, li, ids, lens: (b, 0, 0)),
                pl.BlockSpec((1, P * H, W), lambda b, li, ids, lens: (b, 0, 0)),
                pl.BlockSpec((1, 1, R), lambda b, li, ids, lens: (b, 0, 0)),
                pl.BlockSpec((1, 1, W), lambda b, li, ids, lens: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # latent payload (DMA'd)
                # scales + the (small) rope row ride the BlockSpec
                # pipeline — see kernel docstring
                pl.BlockSpec(
                    (1, 1, 1, S), lambda b, li, ids, lens: (li[0], ids[b], 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, Sr, W), lambda b, li, ids, lens: (li[0], ids[b], 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, S), lambda b, li, ids, lens: (li[0], ids[b], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, H, R), lambda b, li, ids, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, BS, R), jnp.int8),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_mla_q8_blocked",
        )(*args)

    def run_paged():
        # latent payload streams through the table; rope + scales are
        # PRE-GATHERED contiguous-equivalent rows (see the paged kernel's
        # docstring for why they can't ride a per-block DMA)
        nbs = block_tables.shape[1]
        bt = S // nbs
        tblc = jnp.take(block_tables, ids, 0).astype(jnp.int32)
        lat_a = jax.lax.dynamic_index_in_dim(cache_c["s"], layer, 0, keepdims=False)
        lat_p = jax.lax.dynamic_index_in_dim(pool_c["s"], layer, 0, keepdims=False)
        lats_g = paged_gather(lat_a, lat_p, tblc)[:, 0]  # [Ba, S]
        rop_a = jax.lax.dynamic_index_in_dim(cache_r["q"], layer, 0, keepdims=False)
        rop_p = jax.lax.dynamic_index_in_dim(pool_r["q"], layer, 0, keepdims=False)
        # gathered apart (a pool's rows are), laid abreast again for the kernel
        rop_g = rope_abreast(rope_rows(rop_a, P, tables=tblc, pool=rop_p), P)  # [Ba, S/P, W]
        rops_a = jax.lax.dynamic_index_in_dim(cache_r["s"], layer, 0, keepdims=False)
        rops_p = jax.lax.dynamic_index_in_dim(pool_r["s"], layer, 0, keepdims=False)
        rops_g = paged_gather(rops_a, rops_p, tblc)[:, 0]  # [Ba, S]
        kernel = functools.partial(
            _attend_q8_mla_paged_kernel, scale=scale, block_s=bt, seq_len=S
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer [1], lengths [Ba], tables [Ba*nbs]
            grid=(Ba,),
            in_specs=[
                pl.BlockSpec((1, H, R), lambda b, li, lens, tbl: (b, 0, 0)),
                pl.BlockSpec((1, P * H, W), lambda b, li, lens, tbl: (b, 0, 0)),
                pl.BlockSpec((1, 1, R), lambda b, li, lens, tbl: (b, 0, 0)),
                pl.BlockSpec((1, 1, W), lambda b, li, lens, tbl: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # latent arena (DMA'd)
                pl.BlockSpec(memory_space=pl.ANY),  # latent pool (DMA'd)
                pl.BlockSpec((1, S), lambda b, li, lens, tbl: (b, 0)),
                pl.BlockSpec((1, Sr, W), lambda b, li, lens, tbl: (b, 0, 0)),
                pl.BlockSpec((1, S), lambda b, li, lens, tbl: (b, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, R), lambda b, li, lens, tbl: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bt, R), jnp.int8),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interp,
            name="decode_attn_mla_q8_paged",
        )(
            jnp.reshape(layer, (1,)).astype(jnp.int32),
            lengths.astype(jnp.int32),
            tblc.reshape(-1),
            qt,
            qr_ab,
            new_c.reshape(Ba, 1, R),
            nr_ab,
            cache_c["q"],
            pool_c["q"],
            lats_g,
            rop_g,
            rops_g,
        )

    # STATIC selection (unlike decode_attend_q8's runtime hybrid): measured
    # at mla-8b kv8 B=32 S=2048, whole-S beats blocked even at low fill
    # (1845 vs 1653 tok/s — the absorbed form is MQA-shaped, so whole-S
    # cells amortize one huge row DMA over ALL heads and the traffic-ratio
    # trade that pays off for GQA does not appear). The blocked kernel's
    # job is S past the VMEM budget — int8-latent long context on the MXU
    # instead of the XLA dequant path — and it covers a BOUNDED window:
    # `mla_block_size` zeroes BS past 64 static-unroll blocks (S=32768 at
    # BS=512 is the last in-window size), after which the early fallback
    # above already returned exact f32 math. "Whole if it fits, else
    # blocked" below can therefore assume BS > 0.
    mode = os.environ.get("LLM_MCP_TPU_Q8_DECODE", "auto")

    def run_contig():
        if mode == "whole" and fits:
            return run_whole()
        if mode == "blocked" and BS:
            return run_blocked()
        return run_whole() if fits else run_blocked()

    if block_tables is None:
        return run_contig()
    nbs_t = block_tables.shape[1]
    # paged arm shares the blocked kernel's static-unroll budget (≤ 64
    # blocks) and needs an int8-tileable block size
    paged_ok = (
        pool_c is not None and nbs_t > 0 and S % nbs_t == 0
        and (S // nbs_t) >= 32 and nbs_t <= 64
    )
    if mode == "paged" and paged_ok:
        return run_paged()
    if interp or not paged_ok:
        # interpret runs keep a static arm choice (parity tests force the
        # paged kernel via LLM_MCP_TPU_Q8_DECODE=paged); unfit block sizes
        # take the exact gather math
        _note_fall("decode_attend_q8_mla", f"paged: {nbs_t} blocks of {S} unfit", interp)
        return _decode_attend_q8_mla_fallback(
            qt, qr, new_c, new_r, cache_c, cache_r, layer, lengths, scale, slot_ids,
            block_tables, pool_c, pool_r,
        )
    # identity tables keep the contiguous dispatch (see decode_attend_q8)
    n_slots = cache_c["q"].shape[1]
    ident = jnp.all(
        block_tables
        == jnp.arange(n_slots * nbs_t, dtype=block_tables.dtype).reshape(
            n_slots, nbs_t
        )
    )
    return jax.lax.cond(ident, run_contig, run_paged)


def _append_q8_kernel(
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    ids_ref,  # [Ba] int32 (scalar prefetch) — cache row per batch position
    #          (consumed by the BlockSpec index maps only: grid cell b's
    #          cache tiles are selected at row ids[b], the body never reads it)
    pay_ref,  # [L, 1, Hf, W] int8 — this step's FUSED row in the cache's own
    #           form (`_q8_step_rows`): quantized K heads, V heads, P abreast
    #           in rows of W lanes, packed-scale bytes (built by append_kv_q8
    #           in plain JAX — the kernel only selects, never quantizes)
    s_ref,  # [L, 1, 2*Hkv, BSS] — this step's plain dequant scales, already
    #         broadcast along the lane tile (a [L, 1, 2*Hkv] block has a
    #         second-to-last dim of 1 over Ba: no legal TPU tile)
    cq_ref,  # [L, 1, Hf, BSQ, W] int8 — payload tile containing position w
    cs_ref,  # [L, 1, 2*Hkv, BSS] — scales tile containing position w
    oq_ref,  # outputs — aliased to the cache operands
    os_ref,
    *,
    block_q: int,  # payload S-tile (32: int8 sublane height)
    block_s: int,  # scales S-tile (128: lane width)
    seq_len: int,
):
    b = pl.program_id(0)
    w = lengths_ref[b]
    live = w < seq_len  # parked rows (w >= S) must not write anywhere
    wq = jnp.minimum(w, seq_len - 1) % block_q  # payload row within its tile
    ws = jnp.minimum(w, seq_len - 1) % block_s  # scale lane within its tile

    rows = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_q, 1), 2)  # [1,1,BSQ,1]
    hit = live & (rows == wq)
    oq_ref[:, 0] = jnp.where(hit, pay_ref[:, 0][:, :, None, :], cq_ref[:, 0])
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_s), 2)  # [1,1,BSS]
    hit_s = live & (lanes == ws)
    os_ref[:, 0] = jnp.where(hit_s, s_ref[:, 0].astype(os_ref.dtype), cs_ref[:, 0])


def _down_the_tile(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """A step's rows [L, Ba, heads, hd] repeated down a cache tile's `block`
    positions, [L, Ba, heads, block, hd]: what `append_kv_bf16` selects from
    where a head is narrower than the 128 lanes. Mosaic has no form of the
    in-kernel broadcast [heads, hd] -> [heads, 1, hd] at 64 lanes ("unsupported
    shape cast", seen in the described-chip compile), so the repeat is made
    outside, a few MB a step at head size 64, and the kernel's select is
    between two tiles of one shape. (The int8 cache has no such rows: its heads
    lie abreast, `kv_heads_abreast`.)"""
    return jnp.broadcast_to(x[:, :, :, None, :], (*x.shape[:3], block, x.shape[-1]))


def _q8_step_rows(cache_k: dict, new_k, new_v):
    """One decode step's K/V [..., Ba, Hkv, hd] in the FUSED cache's own form:
    (payload [..., Ba, Hf, W] int8 — K heads | V heads, P abreast as the cache
    holds them, | packed-scale bytes; plain scales [..., Ba, 2*Hkv]). The same
    bytes `fuse_prompt_kv` makes of a prompt's rows."""
    from ..models.llama import quantize_kv  # local import: avoid cycle
    from ..models.quant import pack_scales

    W = cache_k["q"].shape[-1]
    _, p, P = fused_q8_heads(cache_k)
    sdt = cache_k["s"].dtype
    kq = quantize_kv(new_k, scale_dtype=sdt)
    vq = quantize_kv(new_v, scale_dtype=sdt)
    s_new = jnp.concatenate([kq["s"], vq["s"]], axis=-1)

    def rows(x):  # [..., Hkv, hd] -> [..., Hkv / P, W]
        return x if P == 1 else kv_abreast(x[..., None, :], P)[..., 0, :]

    pay = jnp.concatenate([rows(kq["q"]), rows(vq["q"])], axis=-2)
    if p:
        # the packed pseudo-head row for this position: [..., Ba, 1, W]
        pay = jnp.concatenate([pay, pack_scales(s_new[..., None], W)[..., 0, :]], -2)
    return pay, s_new


def _append_q8_scatter(cache_k: dict, pay, s_new, rows, lengths) -> dict:
    """The plain XLA scatter the append kernel is held to (and what
    unaligned test shapes take): OOB (parked) rows drop by scatter
    semantics. It copies the whole cache per call on the chip."""
    L, _, Hf = cache_k["q"].shape[:3]
    Hs = cache_k["s"].shape[2]
    l_idx = jnp.arange(L)[:, None, None]
    b_idx = rows[None, :, None]
    w_idx = lengths[None, :, None]
    return {
        "q": cache_k["q"].at[l_idx, b_idx, jnp.arange(Hf)[None, None, :], w_idx].set(pay),
        "s": cache_k["s"].at[l_idx, b_idx, jnp.arange(Hs)[None, None, :], w_idx].set(s_new),
    }


def append_kv_q8_reference(cache_k, cache_v, new_k, new_v, lengths, slot_ids=None):
    """`append_kv_q8` by plain XLA scatter: the kernel's parity reference
    (tests/test_kernel_parity.py, chip_smoke.py)."""
    Ba = new_k.shape[1]
    rows = jnp.arange(Ba, dtype=jnp.int32) if slot_ids is None else slot_ids
    pay, s_new = _q8_step_rows(cache_k, new_k, new_v)
    return _append_q8_scatter(cache_k, pay, s_new, rows, lengths), cache_v


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_kv_q8(
    cache_k: dict,  # FUSED: {"q": int8 [L,B,2*Hkv/P+p,S,P*hd], "s": [L,B,2*Hkv,S]}
    cache_v: dict,  # {} — passed through untouched
    new_k: jnp.ndarray,  # [L, Ba, Hkv, hd] — post-rope K for this step, all layers
    new_v: jnp.ndarray,
    lengths: jnp.ndarray,  # [Ba] int32 — write position per row (>= S: skip)
    *,
    slot_ids: jnp.ndarray | None = None,  # [Ba] int32 cache rows (None = 1:1)
    interpret: bool | None = None,
) -> tuple[dict, dict]:
    """Append one decode step's K/V (all layers at once) into the FUSED int8
    cache IN PLACE.

    The XLA scatter alternative (`.at[l_idx, b_idx, h_idx, w_idx].set`)
    copies the entire cache payload per call — measured 6.4 ms of a ~30 ms
    decode step at 8B B=112 S=1024, and 14.2 ms when issued per-layer inside
    the scan. This kernel aliases the cache operands to its outputs and
    rewrites only the 32-row (b, w-tile) block holding each row's position:
    ~0.5 GB of tile traffic instead of ~4 GB of full-buffer copies. Parked
    rows (lengths >= S, see executor/engine.py) write nothing.

    Quantization AND scale-packing happen outside the kernel in plain JAX
    on the tiny [L, Ba, Hkv, hd] step tensors (the bitcast lane-packing of
    `pack_scales` has no proven in-kernel store form; the kernel body only
    selects rows), producing one fused [L, Ba, Hf, W] row per slot whose
    bytes are written in a single aliased tile pass.
    """
    L, B, Hf, S, W = cache_k["q"].shape
    Hs = cache_k["s"].shape[2]
    Ba = new_k.shape[1]
    interp = _interpret() if interpret is None else interpret
    rows = (
        jnp.arange(Ba, dtype=jnp.int32)
        if slot_ids is None
        else slot_ids.astype(jnp.int32)
    )
    pay, s_new = _q8_step_rows(cache_k, new_k, new_v)

    # mosaic int8 stores want rows of whole 128-lane tiles, which every cache
    # of heads that divide the lanes has (`kv_heads_abreast`); narrower test
    # configs (hd 32 with two KV heads) take the scatter.
    if W % LANES != 0 or S % 128 != 0:
        _note_fall("append_kv_q8", f"row={W} S={S} not lane-aligned", interp)
        return _append_q8_scatter(cache_k, pay, s_new, rows, lengths), cache_v

    BSQ = 32  # int8 sublane tile height: smallest in-place payload rewrite
    BSS = 128  # lane width: smallest in-place scales rewrite
    assert S % BSQ == 0 and S % BSS == 0, (S, BSQ, BSS)
    kernel = functools.partial(_append_q8_kernel, block_q=BSQ, block_s=BSS, seq_len=S)

    def blkq(lens, b):
        # payload tile holding this row's write position (clamped if parked)
        return jnp.minimum(lens[b], S - 1) // BSQ

    def blks(lens, b):
        return jnp.minimum(lens[b], S - 1) // BSS

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # lengths [Ba], cache row ids [Ba]
        grid=(Ba,),
        in_specs=[
            pl.BlockSpec((L, 1, Hf, W), lambda b, lens, ids: (0, b, 0, 0)),
            pl.BlockSpec((L, 1, Hs, BSS), lambda b, lens, ids: (0, b, 0, 0)),
            pl.BlockSpec(
                (L, 1, Hf, BSQ, W), lambda b, lens, ids: (0, ids[b], 0, blkq(lens, b), 0)
            ),
            pl.BlockSpec(
                (L, 1, Hs, BSS), lambda b, lens, ids: (0, ids[b], 0, blks(lens, b))
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (L, 1, Hf, BSQ, W), lambda b, lens, ids: (0, ids[b], 0, blkq(lens, b), 0)
            ),
            pl.BlockSpec(
                (L, 1, Hs, BSS), lambda b, lens, ids: (0, ids[b], 0, blks(lens, b))
            ),
        ],
    )
    oq, os_ = pl.pallas_call(
        kernel,
        name="append_kv_q8",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(cache_k["q"].shape, cache_k["q"].dtype),
            jax.ShapeDtypeStruct(cache_k["s"].shape, cache_k["s"].dtype),
        ],
        # operand indices include the prefetch scalars: lengths=0, ids=1,
        # pay=2, s_new=3, cq=4, cs=5 → outputs 0..1
        input_output_aliases={4: 0, 5: 1},
        interpret=interp,
    )(
        lengths.astype(jnp.int32),
        rows,
        pay,
        jnp.broadcast_to(s_new[..., None], (L, Ba, Hs, BSS)),
        cache_k["q"],
        cache_k["s"],
    )
    return {"q": oq, "s": os_}, cache_v


def _append_bf16_kernel(
    lengths_ref,  # [Ba] int32 (scalar prefetch) — this step's position per row
    ids_ref,  # [Ba] int32 (scalar prefetch) — cache row per batch position
    nk_ref,  # [L, 1, Hkv, hd] — this step's K vectors (post-rope);
    nv_ref,  # [L, 1, Hkv, BQ, hd] where a head is narrower than the 128 lanes
    ck_ref,  # [L, 1, Hkv, BQ, hd] — K tile containing position w
    cv_ref,  # [L, 1, Hkv, BQ, hd]
    ok_ref,  # outputs — aliased to the cache operands
    ov_ref,
    *,
    block_q: int,  # S-tile (16: bf16 sublane height; also divides f32's 8)
    seq_len: int,
):
    b = pl.program_id(0)
    w = lengths_ref[b]
    live = w < seq_len  # parked rows (w >= S) must not write anywhere
    wq = jnp.minimum(w, seq_len - 1) % block_q

    rows = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_q, 1), 2)  # [1,1,BQ,1]
    hit = live & (rows == wq)

    def tile(ref):
        x = ref[:, 0]
        return (x if x.ndim == 4 else x[:, :, None, :]).astype(ok_ref.dtype)

    ok_ref[:, 0] = jnp.where(hit, tile(nk_ref), ck_ref[:, 0])
    ov_ref[:, 0] = jnp.where(hit, tile(nv_ref), cv_ref[:, 0])


def append_kv_bf16_reference(cache_k, cache_v, new_k, new_v, lengths, slot_ids=None):
    """`append_kv_bf16` by plain XLA scatter, OOB (parked) rows dropped: the
    kernel's parity reference and what unaligned test shapes take."""
    L, _, Hkv = cache_k.shape[:3]
    Ba = new_k.shape[1]
    rows = jnp.arange(Ba, dtype=jnp.int32) if slot_ids is None else slot_ids
    l_idx = jnp.arange(L)[:, None, None]
    b_idx = rows[None, :, None]
    h_idx = jnp.arange(Hkv)[None, None, :]
    w_idx = lengths[None, :, None]
    ck = cache_k.at[l_idx, b_idx, h_idx, w_idx].set(new_k.astype(cache_k.dtype))
    cv = cache_v.at[l_idx, b_idx, h_idx, w_idx].set(new_v.astype(cache_v.dtype))
    return ck, cv


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_kv_bf16(
    cache_k: jnp.ndarray,  # [L, B, Hkv, S, hd] bf16/f32
    cache_v: jnp.ndarray,
    new_k: jnp.ndarray,  # [L, Ba, Hkv, hd] — post-rope K for this step, all layers
    new_v: jnp.ndarray,
    lengths: jnp.ndarray,  # [Ba] int32 — write position per row (>= S: skip)
    *,
    slot_ids: jnp.ndarray | None = None,  # [Ba] int32 cache rows (None = 1:1)
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Append one decode step's K/V (all layers at once) into the bf16 cache
    IN PLACE — the bf16 twin of `append_kv_q8`: aliased cache operands,
    only the 16-row (b, w-tile) block holding each row's position is
    rewritten, parked rows (lengths >= S) write nothing. This is what lets
    `_decode_step_bf16` keep the cache scan-invariant (no per-layer
    dynamic_update_slice copies inside the scan) and batch the whole
    append into one pass after the layer scan."""
    L, B, Hkv, S, hd = cache_k.shape
    Ba = new_k.shape[1]
    interp = _interpret() if interpret is None else interpret
    rows = (
        jnp.arange(Ba, dtype=jnp.int32)
        if slot_ids is None
        else slot_ids.astype(jnp.int32)
    )

    BQ = 16  # bf16 sublane tile height (f32 needs 8 — 16 covers both)
    # mosaic stores want rows of 128 lanes, or of 64 (`append_kv_q8` says
    # how); smaller-head test configs take the scatter fallback. Interpret
    # mode keeps the kernel path at those shapes so parity tests cover the
    # real tile-rewrite body.
    if hd % 64 != 0 or S % BQ != 0:
        _note_fall("append_kv_bf16", f"hd={hd} S={S} not tile-aligned", interp)
        return append_kv_bf16_reference(
            cache_k, cache_v, new_k, new_v, lengths, slot_ids=rows
        )

    kernel = functools.partial(_append_bf16_kernel, block_q=BQ, seq_len=S)
    narrow = hd % 128 != 0  # the step's rows come repeated down the tile
    new_spec = (pl.BlockSpec((L, 1, Hkv, BQ, hd), lambda b, lens, ids: (0, b, 0, 0, 0)) if narrow
                else pl.BlockSpec((L, 1, Hkv, hd), lambda b, lens, ids: (0, b, 0, 0)))
    if narrow:
        new_k, new_v = _down_the_tile(new_k, BQ), _down_the_tile(new_v, BQ)

    def blkq(lens, b):
        # tile holding this row's write position (clamped if parked)
        return jnp.minimum(lens[b], S - 1) // BQ

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # lengths [Ba], cache row ids [Ba]
        grid=(Ba,),
        in_specs=[
            new_spec,
            new_spec,
            pl.BlockSpec(
                (L, 1, Hkv, BQ, hd), lambda b, lens, ids: (0, ids[b], 0, blkq(lens, b), 0)
            ),
            pl.BlockSpec(
                (L, 1, Hkv, BQ, hd), lambda b, lens, ids: (0, ids[b], 0, blkq(lens, b), 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (L, 1, Hkv, BQ, hd), lambda b, lens, ids: (0, ids[b], 0, blkq(lens, b), 0)
            ),
            pl.BlockSpec(
                (L, 1, Hkv, BQ, hd), lambda b, lens, ids: (0, ids[b], 0, blkq(lens, b), 0)
            ),
        ],
    )
    ok, ov = pl.pallas_call(
        kernel,
        name="append_kv_bf16",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
            jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
        ],
        # operand indices include the prefetch scalars: lengths=0, ids=1,
        # nk=2, nv=3, ck=4, cv=5 → outputs 0..1
        input_output_aliases={4: 0, 5: 1},
        interpret=interp,
    )(
        lengths.astype(jnp.int32),
        rows,
        new_k,
        new_v,
        cache_k,
        cache_v,
    )
    return ok, ov


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(
    q: jnp.ndarray,  # [B, Hkv, G, hd]
    cache_k: jnp.ndarray,  # [B, Hkv, S, hd]
    cache_v: jnp.ndarray,  # [B, Hkv, S, hd]
    lengths: jnp.ndarray,  # [B] int32 — current write position (inclusive)
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched single-step attention. Returns [B, Hkv, G, hd].

    The caller has already written this step's K/V at `lengths[b]`; the
    kernel attends over positions ≤ lengths[b]. Whole-S tiles stream through
    VMEM once; for cache capacities beyond VMEM (≳16K positions at hd=128)
    the sequence-parallel ring path (parallel/ring.py) shards S instead.
    """
    B, Hkv, G, hd = q.shape
    S = cache_k.shape[2]
    interp = _interpret() if interpret is None else interpret

    kernel = functools.partial(_decode_attn_kernel, scale=hd**-0.5)
    return pl.pallas_call(
        kernel,
        name="decode_attn_dense",
        grid=(B, Hkv),
        in_specs=[
            _smem_spec(),  # lengths [B]
            pl.BlockSpec((1, 1, G, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interp,
    )(lengths.astype(jnp.int32), q, cache_k, cache_v)


# ---------------------------------------------------------------------------
# Ragged paged-native flash prefill (the chunked-prefill path of record)
# ---------------------------------------------------------------------------
#
# One fixed-shape packed dispatch replaces the bucketed path's per-(bucket,
# skey) executable zoo. A [T]-token buffer carries up to R rows' chunks
# back-to-back: row r occupies packed positions [offsets[r], offsets[r+1]);
# pads sit past offsets[R] with rowid == R and write position == S, so their
# cache scatters DROP (the engine's parked-slot OOB convention). Each kernel
# tiles q-blocks against
#
#   (a) the row's already-cached prefix, streamed block-indirect through the
#       PR 10 per-slot tables (arena identity homes < pool_base, shared
#       prefix pool rows >= pool_base — the same two-way `pl.when` descriptor
#       resolution as `_attend_q8_paged_kernel`), masked `k_pos < starts[r]`;
#   (b) the packed SELF segment from in-register K/V (exact bf16, even over
#       an int8 cache — the chunk path's current-token override generalized),
#       masked by segment equality + packed-index causal order.
#
# T and R are static; every descriptor (offsets, starts, tables) is data —
# one executable per (T, layout) serves every fill mix. Masks come from the
# row's segment BOUNDARIES (scalar-prefetch `offsets`, rows packed in
# ascending order), not per-token rowid vectors: boundary compares are plain
# 2-D iota-vs-scalar ops, which Mosaic vectorizes with no gather/relayout.
#
# Numerics mirror `llama_prefill_chunk_batch` / `mla_prefill_chunk_batch`:
# raw dots accumulate in f32 (int8 values are exact in every wider dtype),
# per-position dequant scales fold post-dot on the score AND value sides, and
# the attn scale applies to scores after dequant. The kernels use online
# softmax where the bucketed path takes one joint softmax — reductions
# associate differently, so outputs agree to bf16 rounding, not bitwise; the
# acceptance bar is greedy token identity (tests/test_kernel_parity.py).
#
# Sliding-window and softcap families are NOT covered — the engine gates
# those to the bucketed path (`GenerationEngine._ragged` eligibility).


def resolve_ragged_impl() -> str:
    """Implementation for the ragged chunked-prefill attention.

    env LLM_MCP_TPU_RAGGED_IMPL: auto (default) | kernel | xla.
    auto → the Pallas kernels on a TPU chip, the exact packed XLA fallback
    elsewhere (CPU serve; parity tests force `kernel` to exercise the
    kernels in interpret mode). This only picks HOW a ragged dispatch
    computes attention — whether ragged dispatch happens at all is the
    engine's TPU_RAGGED_PREFILL gate."""
    mode = os.environ.get("LLM_MCP_TPU_RAGGED_IMPL", "auto")
    if mode in ("kernel", "xla"):
        return mode
    return "kernel" if _on_tpu() else "xla"


def _ragged_kernel_asked(impl: str | None) -> bool:
    """An explicit `impl=` outranks the resolver; anything but the two
    names is a caller's bug, not a request for the reference."""
    impl = impl or resolve_ragged_impl()
    if impl not in ("kernel", "xla"):
        raise ValueError(f"unknown ragged prefill impl {impl!r} (kernel | xla)")
    return impl == "kernel"


def ragged_block_size(seq_len: int, block_tokens: int | None = None) -> int:
    """KV block size for the ragged kernels' past streams. Under physical
    paging it MUST equal the ledger's block_tokens (logical block j covers
    exactly table entry j); unpaged identity tables pick the largest
    MXU-friendly divisor of S."""
    if block_tokens:
        return block_tokens
    for bs in (256, 128, 64, 32):
        if seq_len % bs == 0 and bs <= seq_len:
            return bs
    return seq_len


def ragged_prefill_max_tokens(
    head_dim: int, n_kv_heads: int, *, latent: int = 0, rope_dim: int = 0
) -> int:
    """Largest packed-token capacity T the ragged kernels can hold in VMEM.

    The self segment keeps the whole chunk's K/V (GQA: 2·Hkv·hd bf16 per
    token; MLA: latent+rope bf16 per token) resident across q-tiles; the
    past stream is double-buffered blocks (T-independent). 10 MB of the
    ~16 MB budget bounds T, leaving headroom for q/out tiles, f32 score
    tiles, and the MLA pre-gathered rope/scale rows."""
    budget = 10 * 1024 * 1024
    if latent:
        per_tok = 2 * (latent + rope_dim)
    else:
        per_tok = 2 * 2 * n_kv_heads * head_dim
    return max(256, budget // per_tok)


def _seg_of(offs_ref, idx, n_rows: int):
    """Descriptor row of packed index `idx` by counting crossed boundaries
    (rows are packed contiguously ascending; pads land in segment n_rows)."""
    seg = jnp.zeros(idx.shape, jnp.int32)
    for r in range(1, n_rows + 1):
        seg = seg + (idx >= offs_ref[r]).astype(jnp.int32)
    return seg


def _tile_token_index(rows: int, block_q: int, t0):
    """[rows, 1] packed token index of each query row of a group-major tile
    (row g*BQ + t is token t0 + t): row mod BQ by compare-and-subtract, which
    lowers where a vector integer remainder may not."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    t_loc = row
    for g in range(1, rows // block_q):
        t_loc = t_loc - jnp.where(row >= g * block_q, block_q, 0)
    return t0 + t_loc


def _vmem_nbytes(shape, dtype) -> int:
    """Bytes a block of `shape` takes in VMEM: the lane axis pads to 128."""
    lanes = -(-shape[-1] // 128) * 128
    n = 1
    for d in shape[:-1]:
        n *= d
    return n * lanes * jnp.dtype(dtype).itemsize


def ragged_q_block(T: int, groups: int, block_q: int = 128) -> int:
    """Packed tokens per q-tile of the ragged GQA kernel. One tile holds
    groups * BQ query rows per KV head; 256 rows keep the f32 score tile and
    the online-softmax state a few dozen vregs per head (the first form of
    this kernel held every head of a 128-token tile live at once — 2 MB
    values the compiler spent minutes spilling). Power of two so it divides
    the pow2 packed length."""
    cap = max(8, 256 // max(1, groups))
    cap = 1 << (cap.bit_length() - 1)
    return max(1, min(block_q, T, cap))


def _ragged_prefill_gqa_kernel(
    li_ref,  # [1] int32 (scalar prefetch) — layer index
    offs_ref,  # [R+1] int32 (scalar prefetch) — packed row boundaries
    starts_ref,  # [R] int32 (scalar prefetch) — cached-prefix length per row
    tbl_ref,  # [R * nbs] int32 (scalar prefetch) — flattened block tables
    q_ref,  # [1, Hkv, G*BQ, hd] VMEM — this tile's post-rope queries, row
    #         g*BQ + t (group-major, so one head's rows are one 2-D matmul
    #         operand and the packed index of a row is t0 + row mod BQ)
    ks_ref,  # [Hkv, T, hd] VMEM — the chunk's own post-rope keys (packed)
    vs_ref,  # [Hkv, T, hd] VMEM
    *rest,
    scale: float,
    block_s: int,
    seq_len: int,
    n_rows: int,
    block_q: int,
    quantized: bool,
):
    """Ragged flash prefill over the GQA cache, both layouts: per packed
    q-tile, one double-buffered block-indirect stream per descriptor row
    (past), then causal packed self tiles, all folded into one online
    softmax whose state (acc, m, l) lives in VMEM scratch per KV head.

    quantized=False — split bf16 cache: K and V blocks ride two DMAs.
    quantized=True — FUSED int8 cache: ONE payload DMA per past block (K and
      V heads ride the same copy — the PR 7 one-DMA property); the packed-
      scale pseudo-head is never streamed — per-row plain scales arrive
      PRE-GATHERED in VMEM as [R, nbs, 2*Hkv, BS] f32, one leading-dim index
      per block (a lane slice at a 64-token offset is not 128-aligned and
      Mosaic refuses it). Dequant folds post-dot on score and value sides;
      the self segment stays exact.

    Every loop that multiplies program size is a `fori_loop` (descriptor
    rows, blocks, KV heads): the body is compiled once, so compile time does
    not grow with R, S or Hkv."""
    if quantized:
        (srow_ref, pay_hbm, pool_hbm, o_ref, pay_buf, sems,
         acc_ref, m_ref, l_ref) = rest
        n_arena = pay_hbm.shape[1]
    else:
        (ck_hbm, cv_hbm, pk_hbm, pv_hbm, o_ref, kbuf, vbuf, sems,
         acc_ref, m_ref, l_ref) = rest
        n_arena = ck_hbm.shape[1]
    qi = pl.program_id(0)
    li = li_ref[0]
    BS = block_s
    BQ = block_q
    Hkv, RQ, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    G = RQ // BQ
    nbs = seq_len // BS
    pool_base = n_arena * nbs
    t0 = qi * BQ
    cdt = q_ref.dtype  # matmul operand dtype (bf16 on the chip)

    t_idx = _tile_token_index(RQ, BQ, t0)  # [RQ, 1] packed index of each query row

    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def fold(h, s, mask, v, vss):
        """One online-softmax update of head h with scores s [RQ, N]."""
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if vss is not None:
            p = p * vss
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[h] = m_new

    def scores(h, k):
        return jax.lax.dot_general(
            q_ref[0, h], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [RQ, N]

    # ---- past segment: block-indirect stream per row with cached prefix
    def copies(phys, slot, arena):
        if arena:
            arow = phys // nbs
            blk = pl.ds((phys % nbs) * BS, BS)
            if quantized:
                srcs = (pay_hbm.at[li, arow, pl.ds(0, 2 * Hkv), blk, :],)
            else:
                srcs = (ck_hbm.at[li, arow, :, blk, :],
                        cv_hbm.at[li, arow, :, blk, :])
        else:
            prow = phys - pool_base
            if quantized:
                srcs = (pool_hbm.at[li, prow, pl.ds(0, 2 * Hkv)],)
            else:
                srcs = (pk_hbm.at[li, prow], pv_hbm.at[li, prow])
        dsts = (pay_buf,) if quantized else (kbuf, vbuf)
        return [
            pltpu.make_async_copy(src, dst.at[slot], sems.at[slot, i])
            for i, (src, dst) in enumerate(zip(srcs, dsts))
        ]

    def issue(r, j, slot, op):
        phys = tbl_ref[r * nbs + j]
        ina = phys < pool_base

        @pl.when(ina)
        def _arena():
            for c in copies(phys, slot, True):
                getattr(c, op)()

        @pl.when(jnp.logical_not(ina))
        def _pool():
            for c in copies(phys, slot, False):
                getattr(c, op)()

    def past_row(r, carry):
        w = starts_ref[r]
        lo = offs_ref[r]
        hi = offs_ref[r + 1]
        # skip rows with no tokens in this tile or no cached prefix
        use = (hi > lo) & (lo < t0 + BQ) & (hi > t0) & (w > 0)
        nblk = jnp.where(use, jnp.minimum((w + BS - 1) // BS, nbs), 0)
        sel_q = (t_idx >= lo) & (t_idx < hi)  # [RQ, 1]

        @pl.when(nblk > 0)
        def _warm():
            issue(r, 0, 0, "start")

        def block(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < nblk)
            def _prefetch():
                issue(r, j + 1, 1 - slot, "start")

            issue(r, j, slot, "wait")
            k_pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)
            mask = sel_q & (k_pos < w)  # [RQ, BS]

            def head(h, carry):
                if quantized:
                    k = pay_buf[slot, h].astype(jnp.float32).astype(cdt)
                    v = pay_buf[slot, Hkv + h].astype(jnp.float32).astype(cdt)
                    kss = srow_ref[r, j, pl.ds(h, 1), :]  # [1, BS] f32
                    vss = srow_ref[r, j, pl.ds(Hkv + h, 1), :]
                    fold(h, scores(h, k) * kss, mask, v, vss)
                else:
                    k = kbuf[slot, h].astype(cdt)
                    v = vbuf[slot, h].astype(cdt)
                    fold(h, scores(h, k), mask, v, None)
                return carry

            return jax.lax.fori_loop(0, Hkv, head, carry)

        return jax.lax.fori_loop(0, nblk, block, carry)

    jax.lax.fori_loop(0, n_rows, past_row, 0)

    # ---- self segment: causal packed tiles, segment-equality masked
    seg_q = _seg_of(offs_ref, t_idx, n_rows)  # [RQ, 1]

    def self_tile(tb, carry):
        u0 = pl.multiple_of(tb * BQ, BQ)
        u_idx = u0 + jax.lax.broadcasted_iota(jnp.int32, (1, BQ), 1)
        seg_k = _seg_of(offs_ref, u_idx, n_rows)  # [1, BQ]
        mask = (seg_q == seg_k) & (u_idx <= t_idx)  # [RQ, BQ]

        def head(h, carry):
            k = ks_ref[h, pl.ds(u0, BQ), :].astype(cdt)
            v = vs_ref[h, pl.ds(u0, BQ), :].astype(cdt)
            fold(h, scores(h, k), mask, v, None)
            return carry

        return jax.lax.fori_loop(0, Hkv, head, carry)

    jax.lax.fori_loop(0, qi + 1, self_tile, 0)

    def finish(h, carry):
        l = l_ref[h]
        out = jnp.where(l > 0, acc_ref[h] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0, h] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, Hkv, finish, 0)


def _ragged_gqa_call(
    kernel_kw, q, k_self, v_self, layer, offsets, starts, tbl, extra_in,
    extra_specs, scratch, stream_bytes, interp, block_q,
):
    """Shared pallas_call plumbing of the two GQA ragged dispatchers: tile
    the packed queries group-major, run `_ragged_prefill_gqa_kernel`, undo
    the tiling. `extra_in`/`extra_specs` are the layout's cache operands,
    `scratch` its stream buffers + semaphores (`stream_bytes` of VMEM)."""
    T, Hkv, G, hd = q.shape
    BQ = ragged_q_block(T, G, block_q)
    assert T % BQ == 0, (T, BQ)
    nQ, RQ = T // BQ, G * BQ
    q_t = (
        q.reshape(nQ, BQ, Hkv, G, hd).transpose(0, 2, 3, 1, 4).reshape(nQ, Hkv, RQ, hd)
    )
    kernel = functools.partial(_ragged_prefill_gqa_kernel, block_q=BQ, **kernel_kw)
    scratch = list(scratch) + [
        pltpu.VMEM((Hkv, RQ, hd), jnp.float32),  # acc
        pltpu.VMEM((Hkv, RQ, 1), jnp.float32),  # m
        pltpu.VMEM((Hkv, RQ, 1), jnp.float32),  # l
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # li [1], offsets [R+1], starts [R], tbl [R*nbs]
        grid=(nQ,),
        in_specs=[
            pl.BlockSpec((1, Hkv, RQ, hd), lambda qi, li, of, st, tb: (qi, 0, 0, 0)),
            pl.BlockSpec((Hkv, T, hd), lambda qi, li, of, st, tb: (0, 0, 0)),
            pl.BlockSpec((Hkv, T, hd), lambda qi, li, of, st, tb: (0, 0, 0)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec(
            (1, Hkv, RQ, hd), lambda qi, li, of, st, tb: (qi, 0, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    # VMEM the kernel asks the compiler for, from its own shapes: the
    # pipeline double-buffers every blocked operand (the resident self K/V
    # included), scratch is single. The default scoped limit (16 MB) is
    # below what the largest packed length needs; a v5e core has 128 MiB.
    blocked = 2 * _vmem_nbytes((Hkv, RQ, hd), q.dtype)  # q, out
    blocked += 2 * _vmem_nbytes((Hkv, T, hd), q.dtype)  # self K, V
    blocked += sum(
        _vmem_nbytes(x.shape, x.dtype)
        for x, sp in zip(extra_in, extra_specs) if sp.memory_space is None
    )
    state = _vmem_nbytes((Hkv, RQ, hd), jnp.float32) + 2 * _vmem_nbytes(
        (Hkv, RQ, 1), jnp.float32
    )  # acc, m, l
    vmem = 2 * blocked + state + stream_bytes + (8 << 20)
    out = pl.pallas_call(
        kernel,
        name=f"ragged_prefill_attn_{'q8' if kernel_kw['quantized'] else 'bf16'}_gqa",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nQ, Hkv, RQ, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=int(vmem)
        ),
        interpret=interp,
    )(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        jnp.asarray(offsets, jnp.int32),
        starts,
        tbl.reshape(-1).astype(jnp.int32),
        q_t,
        k_self.transpose(1, 0, 2),
        v_self.transpose(1, 0, 2),
        *extra_in,
    )
    return (
        out.reshape(nQ, Hkv, G, BQ, hd).transpose(0, 3, 1, 2, 4).reshape(T, Hkv, G, hd)
    )


def _ragged_prefill_mla_kernel(
    li_ref,  # [1] int32 (scalar prefetch)
    offs_ref,  # [R+1] int32 (scalar prefetch)
    starts_ref,  # [R] int32 (scalar prefetch)
    tbl_ref,  # [R * nbs] int32 (scalar prefetch)
    qt_ref,  # [1, H*BQ, Rl] VMEM — absorbed latent queries (q_nope @ W_uk),
    #          row h*BQ + t (head-major, as the GQA kernel tiles its groups)
    qr_ref,  # [1, H*BQ, dr] VMEM — post-rope rope queries, same rows
    cs_ref,  # [T, Rl] VMEM — the chunk's own latents, exact
    krs_ref,  # [T, dr] VMEM — the chunk's own post-rope rope keys
    rop_ref,  # [R, S, dr] VMEM — pre-gathered cached rope rows (native dtype)
    ls_ref,  # [R, nbs, 1, BS] f32 VMEM — latent dequant scales (ones when bf16)
    rs_ref,  # [R, nbs, 1, BS] f32 VMEM — rope dequant scales (ones when bf16)
    lat_hbm,  # [L, B, 1, S, Rl] ANY — latent arena (int8 or bf16)
    pool_lat,  # [L, PXB, 1, bt, Rl] ANY — latent prefix pool
    o_ref,  # [1, H*BQ, Rl] VMEM out — attended latent context
    lbuf,  # VMEM scratch [2, BS, Rl] (double buffer)
    sems,  # DMA semaphores [2, 1]
    acc_ref,  # VMEM scratch [H*BQ, Rl] f32
    m_ref,  # [H*BQ, 1] f32
    l_ref,  # [H*BQ, 1] f32
    *,
    scale: float,
    block_s: int,
    seq_len: int,
    n_rows: int,
    block_q: int,
):
    """Ragged flash prefill over the MLA latent cache, absorbed form: scores
    land directly on cached latents (q_nope pre-folded through W_uk), the
    value side re-expands outside the kernel. One body covers bf16 AND int8
    latents: blocks stream in the cache's native dtype and dequant scales
    (ones for bf16 — exact multiply) fold post-dot. Every head attends the
    SAME latent rows, so one tile is one [H*BQ, Rl] x [Rl, BS] matmul.
    Rope rows + scales arrive PRE-GATHERED in VMEM (`paged_gather`): the
    per-block [BS, dr] rope slices are exactly the narrow DMAs Mosaic
    rejects in the MLA decode kernels, so only the [BS, Rl] latent payload
    streams block-indirect; the scales are laid out one leading index per
    block (a lane slice at a 64-token offset is refused). Structure as
    `_ragged_prefill_gqa_kernel`: softmax state in VMEM scratch, `fori_loop`
    over descriptor rows and blocks."""
    qi = pl.program_id(0)
    li = li_ref[0]
    BS = block_s
    BQ = block_q
    RQ, Rl = qt_ref.shape[1], qt_ref.shape[2]
    H = RQ // BQ
    nbs = seq_len // BS
    pool_base = lat_hbm.shape[1] * nbs
    t0 = qi * BQ
    cdt = qt_ref.dtype

    t_idx = _tile_token_index(RQ, BQ, t0)  # [RQ, 1] packed index of each query row

    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def nt(a, b):  # [M, K] x [N, K] -> [M, N] f32
        return jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )

    def fold(s, mask, v, vscale):
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if vscale is not None:
            p = p * vscale
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    def issue(r, j, slot, op):
        phys = tbl_ref[r * nbs + j]
        ina = phys < pool_base

        @pl.when(ina)
        def _arena():
            getattr(
                pltpu.make_async_copy(
                    lat_hbm.at[li, phys // nbs, 0, pl.ds((phys % nbs) * BS, BS), :],
                    lbuf.at[slot],
                    sems.at[slot, 0],
                ),
                op,
            )()

        @pl.when(jnp.logical_not(ina))
        def _pool():
            getattr(
                pltpu.make_async_copy(
                    pool_lat.at[li, phys - pool_base, 0], lbuf.at[slot], sems.at[slot, 0]
                ),
                op,
            )()

    def past_row(r, carry):
        w = starts_ref[r]
        lo = offs_ref[r]
        hi = offs_ref[r + 1]
        use = (hi > lo) & (lo < t0 + BQ) & (hi > t0) & (w > 0)
        nblk = jnp.where(use, jnp.minimum((w + BS - 1) // BS, nbs), 0)
        sel_q = (t_idx >= lo) & (t_idx < hi)  # [RQ, 1]

        @pl.when(nblk > 0)
        def _warm():
            issue(r, 0, 0, "start")

        def block(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < nblk)
            def _prefetch():
                issue(r, j + 1, 1 - slot, "start")

            issue(r, j, slot, "wait")
            lat = lbuf[slot].astype(jnp.float32).astype(cdt)  # [BS, Rl]
            rop = rop_ref[
                r, pl.ds(pl.multiple_of(j * BS, BS), BS), :
            ].astype(jnp.float32).astype(cdt)  # [BS, dr]
            lsb = ls_ref[r, j]  # [1, BS] f32
            rsb = rs_ref[r, j]
            s = (nt(qt_ref[0], lat) * lsb + nt(qr_ref[0], rop) * rsb) * scale
            k_pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)
            fold(s, sel_q & (k_pos < w), lat, lsb)
            return carry

        return jax.lax.fori_loop(0, nblk, block, carry)

    jax.lax.fori_loop(0, n_rows, past_row, 0)

    seg_q = _seg_of(offs_ref, t_idx, n_rows)  # [RQ, 1]

    def self_tile(tb, carry):
        u0 = pl.multiple_of(tb * BQ, BQ)
        c = cs_ref[pl.ds(u0, BQ), :].astype(cdt)  # [BQ, Rl]
        kr = krs_ref[pl.ds(u0, BQ), :].astype(cdt)  # [BQ, dr]
        s = (nt(qt_ref[0], c) + nt(qr_ref[0], kr)) * scale
        u_idx = u0 + jax.lax.broadcasted_iota(jnp.int32, (1, BQ), 1)
        seg_k = _seg_of(offs_ref, u_idx, n_rows)
        fold(s, (seg_q == seg_k) & (u_idx <= t_idx), c, None)
        return carry

    jax.lax.fori_loop(0, qi + 1, self_tile, 0)
    l = l_ref[...]
    out = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def _ragged_attend_gqa_fallback(
    q, k_self, v_self, krows, vrows, ksr, vsr, rowids, starts, scale
):
    """Exact packed mirror of `llama_prefill_chunk_batch`'s attention math
    (joint softmax over [past | self], bf16 dots, post-dot dequant) — the
    CPU/XLA arm of the ragged dispatchers and the reference the kernels are
    parity-tested against. Past rows arrive pre-gathered per descriptor row
    ([R, Hkv, Sk, hd]); a static loop over the R rows selects each token's
    row without a [T, Sk, hd] gather (memory mirrors the bucketed form).

    q [T, Hkv, G, hd] · k_self/v_self [T, Hkv, hd] · ksr/vsr [R, Hkv, Sk]
    (None for bf16) · rowids [T] (pads = R) · starts [R] → [T, Hkv, G, hd].
    """
    T, Hkv, G, hd = q.shape
    R, _, Sk, _ = krows.shape
    neg = jnp.float32(NEG_INF)
    rid = rowids.astype(jnp.int32)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    key_pos = jnp.arange(Sk, dtype=jnp.int32)

    s_past = jnp.full((Hkv, G, T, Sk), neg, jnp.float32)
    for r in range(R):
        sr = jnp.einsum(
            "thgd,hsd->hgts", q, krows[r].astype(q.dtype)
        ).astype(jnp.float32)
        if ksr is not None:
            sr = sr * ksr[r].astype(jnp.float32)[:, None, None, :]
        s_past = jnp.where((rid == r)[None, None, :, None], sr, s_past)
    s_past = s_past * scale
    start_t = starts[jnp.clip(rid, 0, R - 1)]  # [T]
    pm = (key_pos[None, :] < start_t[:, None]) & (rid < R)[:, None]
    s_past = jnp.where(pm[None, None], s_past, neg)

    s_self = jnp.einsum("thgd,uhd->hgtu", q, k_self).astype(jnp.float32) * scale
    sm = (rid[None, :] == rid[:, None]) & (t_idx[None, :] <= t_idx[:, None])
    s_self = jnp.where(sm[None, None], s_self, neg)

    s = jnp.concatenate([s_past, s_self], axis=-1)  # [Hkv, G, T, Sk+T]
    probs = jax.nn.softmax(s, axis=-1)
    p_past, p_self = probs[..., :Sk], probs[..., Sk:]
    ctx = jnp.einsum("hgtu,uhd->thgd", p_self.astype(q.dtype), v_self)
    for r in range(R):
        pr = p_past
        if vsr is not None:
            pr = pr * vsr[r].astype(jnp.float32)[:, None, None, :]
        cr = jnp.einsum("hgts,hsd->thgd", pr.astype(q.dtype), vrows[r].astype(q.dtype))
        ctx = ctx + jnp.where((rid == r)[:, None, None, None], cr, jnp.zeros_like(cr))
    return ctx.astype(q.dtype)


def _ragged_attend_mla_fallback(
    qt, qr, c_self, kr_self, lat, rop, ls, rs, rowids, starts, scale
):
    """Exact packed mirror of `mla_prefill_chunk_batch`'s attention math —
    the XLA arm of `ragged_prefill_attend_mla` and the kernels' parity
    reference. lat/rop [R, Sk, ·] pre-gathered; ls/rs [R, Sk] f32 dequant
    scales or None (bf16). Returns attended latent context [T, H, Rl]."""
    T, H, Rl = qt.shape
    R, Sk, _ = lat.shape
    neg = jnp.float32(NEG_INF)
    rid = rowids.astype(jnp.int32)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    key_pos = jnp.arange(Sk, dtype=jnp.int32)

    s_past = jnp.full((H, T, Sk), neg, jnp.float32)
    for r in range(R):
        sr = jnp.einsum("thr,sr->hts", qt, lat[r].astype(qt.dtype)).astype(
            jnp.float32
        )
        rr = jnp.einsum("thd,sd->hts", qr, rop[r].astype(qr.dtype)).astype(
            jnp.float32
        )
        if ls is not None:
            sr = sr * ls[r][None, None, :]
            rr = rr * rs[r][None, None, :]
        s_past = jnp.where((rid == r)[None, :, None], sr + rr, s_past)
    s_past = s_past * scale
    start_t = starts[jnp.clip(rid, 0, R - 1)]
    pm = (key_pos[None, :] < start_t[:, None]) & (rid < R)[:, None]
    s_past = jnp.where(pm[None], s_past, neg)

    s_self = (
        jnp.einsum("thr,ur->htu", qt, c_self)
        + jnp.einsum("thd,ud->htu", qr, kr_self)
    ).astype(jnp.float32) * scale
    sm = (rid[None, :] == rid[:, None]) & (t_idx[None, :] <= t_idx[:, None])
    s_self = jnp.where(sm[None], s_self, neg)

    s = jnp.concatenate([s_past, s_self], axis=-1)  # [H, T, Sk+T]
    probs = jax.nn.softmax(s, axis=-1)
    p_past, p_self = probs[..., :Sk], probs[..., Sk:]
    ctx = jnp.einsum("htu,ur->thr", p_self.astype(qt.dtype), c_self)
    for r in range(R):
        pr = p_past * ls[r][None, None, :] if ls is not None else p_past
        cr = jnp.einsum("hts,sr->thr", pr.astype(qt.dtype), lat[r].astype(qt.dtype))
        ctx = ctx + jnp.where((rid == r)[:, None, None], cr, jnp.zeros_like(cr))
    return ctx.astype(qt.dtype)


def _ragged_tables(slots, S, BS, block_tables):
    """(tbl [R, nbs], nbs, paged?) — the per-row block tables the kernels
    stream through: the PR 10 ledger tables gathered to the descriptor rows,
    or identity tables (phys = slot·nbs + j, always arena) when unpaged."""
    slots = jnp.asarray(slots, jnp.int32)
    if block_tables is not None:
        return jnp.take(block_tables, slots, axis=0), block_tables.shape[1], True
    nbs = S // BS
    tbl = slots[:, None] * nbs + jnp.arange(nbs, dtype=jnp.int32)[None, :]
    return tbl, nbs, False


def ragged_prefill_attend_bf16(
    q: jnp.ndarray,  # [T, Hkv, G, hd] post-rope queries (packed)
    k_self: jnp.ndarray,  # [T, Hkv, hd] the chunk's own post-rope keys
    v_self: jnp.ndarray,  # [T, Hkv, hd]
    cache_k: jnp.ndarray,  # [L, B, Hkv, S, hd]
    cache_v: jnp.ndarray,
    layer,  # traced int32 scalar
    rowids: jnp.ndarray,  # [T] int32 — descriptor row per token (pads = R)
    offsets: jnp.ndarray,  # [R+1] int32 — packed row boundaries
    slots: jnp.ndarray,  # [R] int32
    starts: jnp.ndarray,  # [R] int32 — cached-prefix length per row
    *,
    scale: float = 0.0,
    skey: int = 0,  # STATIC past bound for the XLA arm (0 = whole S)
    block_tables=None,  # [max_slots, nbs] ledger tables (None = unpaged)
    pool_k=None,  # [L, PXB, Hkv, bt, hd] prefix pool
    pool_v=None,
    impl: str | None = None,
    interpret: bool | None = None,
    block_q: int = 128,
) -> jnp.ndarray:
    """Ragged chunked-prefill attention over the split bf16 GQA cache.
    Returns [T, Hkv, G, hd] attended context for the packed chunk."""
    T, Hkv, G, hd = q.shape
    L, B, _, S, _ = cache_k.shape
    R = slots.shape[0]
    sc = scale or hd**-0.5
    starts = jnp.asarray(starts, jnp.int32)
    use_kernel = _ragged_kernel_asked(impl)

    if not use_kernel:
        Sk = min(skey, S) if skey else S
        ck_l = jax.lax.dynamic_index_in_dim(cache_k, layer, 0, keepdims=False)
        cv_l = jax.lax.dynamic_index_in_dim(cache_v, layer, 0, keepdims=False)
        slots_i = jnp.asarray(slots, jnp.int32)
        if block_tables is not None:
            nbs_full = block_tables.shape[1]
            bt = S // nbs_full
            nsel = max(1, -(-Sk // bt))
            tbl = jnp.take(block_tables, slots_i, axis=0)[:, :nsel]
            pk_l = jax.lax.dynamic_index_in_dim(pool_k, layer, 0, keepdims=False)
            pv_l = jax.lax.dynamic_index_in_dim(pool_v, layer, 0, keepdims=False)
            krows = paged_gather(ck_l, pk_l, tbl, nbs=nbs_full)[:, :, :Sk]
            vrows = paged_gather(cv_l, pv_l, tbl, nbs=nbs_full)[:, :, :Sk]
        else:
            krows = jnp.take(ck_l, slots_i, axis=0)[:, :, :Sk]
            vrows = jnp.take(cv_l, slots_i, axis=0)[:, :, :Sk]
        return _ragged_attend_gqa_fallback(
            q, k_self, v_self, krows, vrows, None, None, rowids, starts, sc
        )

    interp = _interpret() if interpret is None else interpret
    bt = None if block_tables is None else S // block_tables.shape[1]
    BS = ragged_block_size(S, bt)
    tbl, nbs, paged = _ragged_tables(slots, S, BS, block_tables)
    if paged:
        pk, pv = pool_k, pool_v
    else:
        pk = jnp.zeros((L, 1, Hkv, BS, hd), cache_k.dtype)
        pv = jnp.zeros((L, 1, Hkv, BS, hd), cache_v.dtype)
    return _ragged_gqa_call(
        dict(scale=sc, block_s=BS, seq_len=S, n_rows=R, quantized=False),
        q, k_self, v_self, layer, offsets, starts, tbl,
        (cache_k, cache_v, pk, pv),
        [pl.BlockSpec(memory_space=pl.ANY)] * 4,  # arena K, V; pool K, V
        [
            pltpu.VMEM((2, Hkv, BS, hd), cache_k.dtype),
            pltpu.VMEM((2, Hkv, BS, hd), cache_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        4 * Hkv * BS * hd * cache_k.dtype.itemsize,
        interp, block_q,
    )


def ragged_prefill_attend_q8(
    q: jnp.ndarray,  # [T, Hkv, G, hd] post-rope queries (packed)
    k_self: jnp.ndarray,  # [T, Hkv, hd] exact bf16 self keys
    v_self: jnp.ndarray,
    cache_k: dict,  # FUSED int8 cache {"q": [L,B,2Hkv/P+p,S,P*hd], "s": [L,B,2Hkv,S]}
    layer,
    rowids: jnp.ndarray,
    offsets: jnp.ndarray,
    slots: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    scale: float = 0.0,
    skey: int = 0,
    block_tables=None,
    pool=None,  # {"q", "s"} prefix pool (paged["k"])
    impl: str | None = None,
    interpret: bool | None = None,
    block_q: int = 128,
) -> jnp.ndarray:
    """Ragged chunked-prefill attention over the FUSED int8 GQA cache.
    Returns [T, Hkv, G, hd]."""
    T, Hkv, G, hd = q.shape
    L, B, _, S, _ = cache_k["q"].shape
    R = slots.shape[0]
    sc = scale or hd**-0.5
    starts = jnp.asarray(starts, jnp.int32)
    slots_i = jnp.asarray(slots, jnp.int32)
    use_kernel = _ragged_kernel_asked(impl)
    _, _, P = fused_q8_heads(cache_k)
    if use_kernel and P > 1:
        # the ragged kernel walks the heads one by one under a `fori_loop` and
        # has no form of a head's lanes of a row at a traced index
        _note_fall("ragged_prefill_attend_q8", f"{P} heads abreast", _interpret())
        use_kernel = False

    if not use_kernel:
        Sk = min(skey, S) if skey else S
        pay_l = jax.lax.dynamic_index_in_dim(cache_k["q"], layer, 0, keepdims=False)
        ss_l = jax.lax.dynamic_index_in_dim(cache_k["s"], layer, 0, keepdims=False)
        if block_tables is not None:
            nbs_full = block_tables.shape[1]
            bt = S // nbs_full
            nsel = max(1, -(-Sk // bt))
            tbl = jnp.take(block_tables, slots_i, axis=0)[:, :nsel]
            pp_l = jax.lax.dynamic_index_in_dim(pool["q"], layer, 0, keepdims=False)
            ps_l = jax.lax.dynamic_index_in_dim(pool["s"], layer, 0, keepdims=False)
            pays = paged_gather(pay_l, pp_l, tbl, nbs=nbs_full)[:, : 2 * Hkv // P, :Sk]
            srows = paged_gather(ss_l, ps_l, tbl, nbs=nbs_full)[:, : 2 * Hkv, :Sk]
        else:
            pays = jnp.take(pay_l, slots_i, axis=0)[:, : 2 * Hkv // P, :Sk]
            srows = jnp.take(ss_l, slots_i, axis=0)[:, : 2 * Hkv, :Sk]
        return _ragged_attend_gqa_fallback(
            q,
            k_self,
            v_self,
            *fused_kv(pays, Hkv, P),
            srows[:, :Hkv],
            srows[:, Hkv:],
            rowids,
            starts,
            sc,
        )

    interp = _interpret() if interpret is None else interpret
    bt = None if block_tables is None else S // block_tables.shape[1]
    BS = ragged_block_size(S, bt)
    tbl, nbs, paged_ = _ragged_tables(slots, S, BS, block_tables)
    # plain scales pre-gathered whole-S through the same tables the payload
    # streams through — the scale rows must come from the SAME physical
    # blocks (pool rows for a pinned prefix), not the arena slot rows
    ss_l = jax.lax.dynamic_index_in_dim(cache_k["s"], layer, 0, keepdims=False)
    if paged_:
        ps_l = jax.lax.dynamic_index_in_dim(pool["s"], layer, 0, keepdims=False)
        srows = paged_gather(ss_l, ps_l, jnp.take(block_tables, slots_i, 0))
        pp = pool["q"]
    else:
        srows = jnp.take(ss_l, slots_i, axis=0)
        pp = jnp.zeros((L, 1, cache_k["q"].shape[2], BS, hd), jnp.int8)
    # [R, 2*Hkv, S] -> [R, nbs, 2*Hkv, BS] f32: block j is a leading index
    srows = (
        srows.astype(jnp.float32).reshape(R, 2 * Hkv, nbs, BS).transpose(0, 2, 1, 3)
    )
    return _ragged_gqa_call(
        dict(scale=sc, block_s=BS, seq_len=S, n_rows=R, quantized=True),
        q, k_self, v_self, layer, offsets, starts, tbl,
        (srows, cache_k["q"], pp),
        [
            pl.BlockSpec(
                (R, nbs, 2 * Hkv, BS), lambda qi, li, of, st, tb: (0, 0, 0, 0)
            ),  # scales
            pl.BlockSpec(memory_space=pl.ANY),  # fused arena payload
            pl.BlockSpec(memory_space=pl.ANY),  # fused pool payload
        ],
        [
            pltpu.VMEM((2, 2 * Hkv, BS, hd), jnp.int8),
            pltpu.SemaphoreType.DMA((2, 1)),
        ],
        4 * Hkv * BS * hd,
        interp, block_q,
    )


def ragged_prefill_attend_mla(
    qt: jnp.ndarray,  # [T, H, Rl] absorbed latent queries
    qr: jnp.ndarray,  # [T, H, dr] post-rope rope queries
    c_self: jnp.ndarray,  # [T, Rl] the chunk's own latents (exact bf16)
    kr_self: jnp.ndarray,  # [T, dr] the chunk's own post-rope rope keys
    cache_c,  # [L, B, 1, S, Rl] latents or int8 {"q","s"}
    cache_r,  # [L, B, 1, S, dr] rope keys or int8 {"q","s"}
    layer,
    rowids: jnp.ndarray,
    offsets: jnp.ndarray,
    slots: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    scale: float,
    skey: int = 0,
    block_tables=None,
    pool_c=None,  # paged["k"] — latent prefix pool (array or {"q","s"})
    pool_r=None,  # paged["v"] — rope prefix pool
    impl: str | None = None,
    interpret: bool | None = None,
    block_q: int = 128,
) -> jnp.ndarray:
    """Ragged chunked-prefill attention over the MLA latent cache (absorbed
    form, bf16 or int8). Returns attended latent context [T, H, Rl] — the
    caller re-expands through W_uv."""
    quantized = isinstance(cache_c, dict)
    lat_all = cache_c["q"] if quantized else cache_c
    rop_all = cache_r["q"] if quantized else cache_r
    L, B, _, S, Rl = lat_all.shape
    dr = qr.shape[-1]
    P = S // rop_all.shape[3]  # positions abreast in a row of the rope keys
    T = qt.shape[0]
    R = slots.shape[0]
    starts = jnp.asarray(starts, jnp.int32)
    slots_i = jnp.asarray(slots, jnp.int32)
    use_kernel = _ragged_kernel_asked(impl)

    def rows_of(cache_full, pool_full, bound, abreast=1):
        """Layer-select + per-row gather of a cache plane, bounded to the
        first `bound` positions (block-rounded under paging). The rope keys
        that lie `abreast` are pulled apart once gathered ([R, S, dr], a quarter
        of a MB a layer: the cache itself is read as it lies)."""
        plane = jax.lax.dynamic_index_in_dim(cache_full, layer, 0, keepdims=False)
        if abreast > 1:
            tbl = None if block_tables is None else jnp.take(block_tables, slots_i, axis=0)
            return rope_rows(plane, abreast, slots_i, tbl, None if tbl is None else jax.lax.dynamic_index_in_dim(
                pool_full, layer, 0, keepdims=False))[:, :bound]
        if block_tables is not None:
            nbs_full = block_tables.shape[1]
            bt = S // nbs_full
            nsel = max(1, -(-bound // bt))
            pool_plane = jax.lax.dynamic_index_in_dim(
                pool_full, layer, 0, keepdims=False
            )
            tbl = jnp.take(block_tables, slots_i, axis=0)[:, :nsel]
            g = paged_gather(plane, pool_plane, tbl, nbs=nbs_full)
        else:
            g = jnp.take(plane, slots_i, axis=0)
        return g[:, 0, :bound]  # drop the fake head axis

    if not use_kernel:
        Sk = min(skey, S) if skey else S
        if quantized:
            lat = rows_of(cache_c["q"], pool_c and pool_c["q"], Sk)
            rop = rows_of(cache_r["q"], pool_r and pool_r["q"], Sk, P)
            ls = rows_of(cache_c["s"], pool_c and pool_c["s"], Sk).astype(jnp.float32)
            rs = rows_of(cache_r["s"], pool_r and pool_r["s"], Sk).astype(jnp.float32)
        else:
            lat = rows_of(cache_c, pool_c, Sk)
            rop = rows_of(cache_r, pool_r, Sk)
            ls = rs = None
        return _ragged_attend_mla_fallback(
            qt, qr, c_self, kr_self, lat, rop, ls, rs, rowids, starts, scale
        )

    interp = _interpret() if interpret is None else interpret
    bt = None if block_tables is None else S // block_tables.shape[1]
    BS = ragged_block_size(S, bt)
    tbl, nbs, paged_ = _ragged_tables(slots, S, BS, block_tables)
    # rope rows + dequant scales pre-gathered whole-S (per-block rope/scale
    # slices are the narrow DMAs Mosaic rejects); latent payload streams
    rop_g = rows_of(rop_all, pool_r["q"] if (paged_ and quantized) else pool_r, S, P)
    if quantized:
        ls_g = rows_of(cache_c["s"], pool_c and pool_c["s"], S).astype(jnp.float32)
        rs_g = rows_of(cache_r["s"], pool_r and pool_r["s"], S).astype(jnp.float32)
    else:
        ls_g = rs_g = jnp.ones((R, S), jnp.float32)
    # [R, S] -> [R, nbs, 1, BS]: block j is a leading index in the kernel
    ls_g = ls_g.reshape(R, nbs, 1, BS)
    rs_g = rs_g.reshape(R, nbs, 1, BS)
    pl_pool = (
        (pool_c["q"] if quantized else pool_c)
        if paged_
        else jnp.zeros((L, 1, 1, BS, Rl), lat_all.dtype)
    )
    H = qt.shape[1]
    BQ = ragged_q_block(T, H, block_q)
    assert T % BQ == 0, (T, BQ)
    nQ, RQ = T // BQ, H * BQ

    def tile(x):  # [T, H, d] -> [nQ, H*BQ, d], row h*BQ + t
        return x.reshape(nQ, BQ, H, -1).transpose(0, 2, 1, 3).reshape(nQ, RQ, -1)

    kernel = functools.partial(
        _ragged_prefill_mla_kernel, scale=scale, block_s=BS, seq_len=S,
        n_rows=R, block_q=BQ,
    )
    const = lambda *dims: (lambda qi, li, of, st, tb: (0,) * len(dims))  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nQ,),
        in_specs=[
            pl.BlockSpec((1, RQ, Rl), lambda qi, li, of, st, tb: (qi, 0, 0)),
            pl.BlockSpec((1, RQ, dr), lambda qi, li, of, st, tb: (qi, 0, 0)),
            pl.BlockSpec((T, Rl), const(T, Rl)),
            pl.BlockSpec((T, dr), const(T, dr)),
            pl.BlockSpec((R, S, dr), const(R, S, dr)),
            pl.BlockSpec((R, nbs, 1, BS), const(R, nbs, 1, BS)),
            pl.BlockSpec((R, nbs, 1, BS), const(R, nbs, 1, BS)),
            pl.BlockSpec(memory_space=pl.ANY),  # latent arena
            pl.BlockSpec(memory_space=pl.ANY),  # latent pool
        ],
        out_specs=pl.BlockSpec((1, RQ, Rl), lambda qi, li, of, st, tb: (qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, BS, Rl), lat_all.dtype),
            pltpu.SemaphoreType.DMA((2, 1)),
            pltpu.VMEM((RQ, Rl), jnp.float32),
            pltpu.VMEM((RQ, 1), jnp.float32),
            pltpu.VMEM((RQ, 1), jnp.float32),
        ],
    )
    # VMEM from the kernel's own shapes (blocked operands double-buffered),
    # as `_ragged_gqa_call` does
    blocked = sum(
        _vmem_nbytes(shape, dt)
        for shape, dt in (
            ((RQ, Rl), qt.dtype), ((RQ, Rl), qt.dtype), ((RQ, dr), qt.dtype),
            ((T, Rl), c_self.dtype), ((T, dr), kr_self.dtype),
            (rop_g.shape, rop_g.dtype), (ls_g.shape, ls_g.dtype),
            (rs_g.shape, rs_g.dtype),
        )
    )
    state = (
        _vmem_nbytes((RQ, Rl), jnp.float32) + 2 * _vmem_nbytes((RQ, 1), jnp.float32)
        + _vmem_nbytes((2, BS, Rl), lat_all.dtype)
    )
    out = pl.pallas_call(
        kernel,
        name="ragged_prefill_attn_mla",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nQ, RQ, Rl), qt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(2 * blocked + state + (8 << 20)),
        ),
        interpret=interp,
    )(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        jnp.asarray(offsets, jnp.int32),
        starts,
        tbl.reshape(-1).astype(jnp.int32),
        tile(qt),
        tile(qr),
        c_self,
        kr_self,
        rop_g,
        ls_g,
        rs_g,
        lat_all,
        pl_pool,
    )
    return out.reshape(nQ, H, BQ, Rl).transpose(0, 2, 1, 3).reshape(T, H, Rl)
