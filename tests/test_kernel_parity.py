"""Interpret-mode parity suite for every Pallas kernel in
kernels/attention.py, plus the guard that keeps it exhaustive.

The fused-layout decode kernels rewrote the highest-traffic code in the
repo; each kernel here is pinned against exact-f32 fallback math (or the
XLA scatter, for the append kernels) across the regimes that have bitten
before: empty rows, block-boundary fills, deep fills, batch sizes that
don't divide the block shapes, the slot_ids compaction indirection, and
parked rows. `KERNEL_PARITY` at the bottom maps every `_*_kernel`
function in the module to the test that exercises its body — the guard
test fails when a new kernel lands without registering coverage.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import llm_mcp_tpu.kernels.attention as A
from family import compiled_once
from llm_mcp_tpu.models.quant import pack_scales, scale_pack_width

FILLS = (0.0, 0.4, 0.9)
# ragged tier-1 keeps the boundary fills; the interior fill rides -m slow
RAGGED_FILLS = (0.0, pytest.param(0.4, marks=pytest.mark.slow), 0.9)


def _fused_q8_cache(rng, L, B, Hkv, S, hd, dtype=jnp.float32, abreast=False):
    """A random fused cache: a head a row (the form of heads of 128, which the
    kernels read at any head size), or with `abreast` in the form
    `init_kv_cache` gives this shape, P = `kv_heads_abreast` heads side by side
    in rows of P*hd lanes."""
    P = A.kv_heads_abreast(Hkv, hd) if abreast else 1
    pay = jnp.asarray(rng.integers(-127, 128, (L, B, 2 * Hkv, S, hd), dtype="int8"))
    s = jnp.asarray(rng.random((L, B, 2 * Hkv, S), dtype="float32") * 0.02).astype(
        dtype
    )
    pay = jnp.concatenate(
        [A.kv_abreast(pay[:, :, :Hkv], P), A.kv_abreast(pay[:, :, Hkv:], P)], axis=2)
    if scale_pack_width(Hkv, P * hd, dtype):
        pay = jnp.concatenate([pay, pack_scales(s, P * hd)], axis=2)
    return {"q": pay, "s": s}, {}


def _heads_apart(ck):
    """The same bytes with a head a row: what the cache of this shape was
    before its heads lay abreast (the kernels read either off the shapes)."""
    Hkv, p, P = A.fused_q8_heads(ck)
    k, v = A.fused_kv(ck["q"], Hkv, P)
    pay = jnp.concatenate([k, v], axis=2)
    if p:
        pay = jnp.concatenate([pay, pack_scales(ck["s"], k.shape[-1])], axis=2)
    return {"q": pay, "s": ck["s"]}


def _lens_for(fill: float, B: int, S: int, rng) -> jnp.ndarray:
    """Per-row fills scattered around the target: exercises rows in
    different blocks of the same grid, not one uniform trip count."""
    base = int(fill * (S - 2))
    lens = (base + rng.integers(0, max(S // 8, 2), B)) % (S - 1)
    return jnp.asarray(lens, jnp.int32)


# -- GQA int8 (fused layout) -------------------------------------------------


# What the batch-wide pipeline of the blocked arm adds (PR 36): a cell's copy
# is started a cell ahead, across a row's edge too, so every way one row's
# cells can end and the next row's begin gets a case. `w` is given in blocks
# (a pair (a, b) is a * BS + b), S is 4 blocks, "parked" is w >= S; ids None
# is the full batch, "perm" a permuted compaction.
PARKED = (4, 0)
PIPELINE_CASES = {
    "fill_0.0": None, "fill_0.4": None, "fill_0.9": None,  # scattered fills, B=3
    "unlike_lengths": [(1, -1), (1, 0), (2, -1), (0, 0)],
    "unlike_lengths_reversed": [(0, 0), (2, -1), (1, 0), (1, -1)],
    "every_row_one_block": [(0, 5), (0, 0), (1, -1), (0, 17)],
    "every_row_whole": [(4, -1), (4, -1), (4, -1)],
    "parked_first": [PARKED, (1, 3), (0, 9)],
    "parked_middle": [(2, 3), PARKED, (0, 9)],
    "parked_last": [(2, 3), (0, 9), PARKED],
    "parked_pair_between": [(1, 0), PARKED, PARKED, (2, -1)],
    "all_parked": [PARKED, PARKED, PARKED],
    "one_row": [(2, 5)],
    "one_row_one_block": [(0, 0)],
    "one_row_parked": [PARKED],
}


def _pipeline_params():
    """(case, pack, block, ids): the scattered fills at the block their shape's
    rule gives, both scale modes; every row-edge case packed at 128 and
    unpacked at 64 under a permuted compaction; four of them at the outer block
    sizes, and as a full batch (ids None)."""
    out = []
    for case, rows in PIPELINE_CASES.items():
        if rows is None:
            out += [(case, pack, 0, "perm", form) for pack in ("0", "1")
                    for form in ("apart", "abreast")]
            continue
        out += [(case, "1", 128, "perm", "apart"), (case, "0", 64, "perm", "apart")]
        if case in ("unlike_lengths", "parked_first", "parked_middle", "one_row"):
            out += [(case, "1", 32, "perm", "apart"), (case, "1", 256, "perm", "apart"),
                    (case, "1", 128, None, "apart"), (case, "1", 128, "perm", "abreast")]
    return [pytest.param(*c, id="-".join(map(str, c))) for c in out]


@pytest.mark.parametrize("case,pack,block,ids_kind,form", _pipeline_params())
def test_q8_gqa_blocked_parity(monkeypatch, case, pack, block, ids_kind, form):
    """Fused blocked q8 kernel (packed 1-DMA and unpacked 2-DMA modes) vs
    the exact-f32 fallback: odd batch (B=3, a remainder against every
    block shape), scattered fills, compaction ids; and since the batch's
    cells are one pipeline, the row edges of PIPELINE_CASES at each block
    size `q8_block_tokens` can return. `abreast`: four KV heads of 64 two to a
    row of 128 lanes, the form `init_kv_cache` gives that shape, against the
    fallback on the same cache and the kernel on the same bytes a head a row."""
    rows = PIPELINE_CASES[case]
    monkeypatch.setenv("LLM_MCP_TPU_Q8_DECODE", "blocked")
    monkeypatch.setenv("LLM_MCP_TPU_Q8_SCALE_PACK", pack)
    rng = np.random.default_rng(7)
    abreast = form == "abreast"
    if rows is None:
        L, B, Hkv, S, hd, G = 2, 3, 4 if abreast else 2, 256, 64, 2
        lens, kw = _lens_for(float(case.split("_")[1]), B, S, rng), {}
    else:
        L, B, Hkv, S, hd, G = 2, len(rows), 4 if abreast else 2, 4 * block, 64, 2
        lens = jnp.asarray([a * block + b for a, b in rows], jnp.int32)
        kw = {"block_s": block}
    ck, cv = _fused_q8_cache(rng, L, B, Hkv, S, hd, abreast=abreast)
    assert A.fused_q8_heads(ck)[2] == (2 if abreast else 1)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    nk = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    ids = jnp.asarray(rng.permutation(B), jnp.int32) if ids_kind else None

    def kernel(q, nk, nv, ck, lens, ids):
        A.decode_attend_q8.clear_cache()  # env knobs are read at trace time
        return A.decode_attend_q8(
            q, nk, nv, ck, {}, jnp.int32(1), lens, slot_ids=ids, interpret=True, **kw)

    # the cases of one (knobs, shapes) differ in the lengths alone: one executable
    key = ("q8_blocked", pack, block, ids_kind, form, B, S)
    out = compiled_once(key, kernel, q, nk, nv, ck, lens, ids)
    ref = compiled_once(
        key + ("fallback",),
        lambda q, nk, nv, ck, lens, ids: A._decode_attend_q8_fallback(
            q, nk, nv, ck, {}, jnp.int32(1), lens, hd**-0.5, ids),
        q, nk, nv, ck, lens, ids)
    seated = (lens < S)[:, None, None, None]  # a parked row's output is discarded
    # tolerance covers the kernel's q/prob int8 requantization
    assert float(jnp.max(jnp.abs(jnp.where(seated, out - ref, 0.0)))) < 0.05
    assert not bool(jnp.isnan(jnp.where(seated, out, 0.0)).any())
    if abreast:  # a row of two heads gives each head what its own row gave it
        apart = compiled_once(key + ("apart",), kernel, q, nk, nv, _heads_apart(ck), lens, ids)
        np.testing.assert_allclose(
            np.asarray(jnp.where(seated, out, 0.0)), np.asarray(jnp.where(seated, apart, 0.0)),
            rtol=0, atol=1e-6)


# -- a block pass's attention (`cfg.block_len`) over the fused int8 cache ----------


def _block_attend_reference(q, ks, vs, ck, layer, starts, sc, ids):
    """The XLA arm's arithmetic (`models/llama.py:_chunk_attention` for a block
    pass) in float32 on the same fused cache: the past [0, start) with the K
    scales on the scores and the V scales on the probabilities, the block's own
    L keys whole, one softmax over both. q [A, L, Hkv, G, hd], ks / vs
    [A, Hkv, L, hd] -> [A, L, Hkv, G, hd]."""
    Hkv, _, P = A.fused_q8_heads(ck)
    S = ck["q"].shape[3]
    pay, ss = ck["q"][layer], ck["s"][layer].astype(jnp.float32)
    if ids is not None:
        pay, ss = pay[ids], ss[ids]
    kf, vf = A.fused_kv(pay, Hkv, P)
    kss, vss = ss[:, :Hkv, None, None, :], ss[:, Hkv:, None, None, :]
    past = jnp.einsum("alhgd,ahsd->ahgls", q, kf.astype(jnp.float32)) * kss * sc
    seen = (jnp.arange(S)[None, :] < starts[:, None])[:, None, None, None, :]
    own = jnp.einsum("alhgd,ahtd->ahglt", q, ks) * sc
    p = jax.nn.softmax(jnp.concatenate([jnp.where(seen, past, A.NEG_INF), own], -1), -1)
    return (jnp.einsum("ahgls,ahsd->alhgd", p[..., :S] * vss, vf.astype(jnp.float32))
            + jnp.einsum("ahglt,ahtd->alhgd", p[..., S:], vs))


# a block's first position by (blocks, offset), S four blocks; the offsets are
# multiples of L = 4 as a block's start is. "parked" is start >= S.
BLOCK_STARTS = {
    "edges": [(0, 0), (0, 4), (1, -4), (1, 0), (4, -4)],  # no past block, L, one short
    #   of a block edge, a block edge, the last block of the row
    "parked_beside_live": [PARKED, (2, 8), PARKED, (0, 0), (0, 12)],
    "parked_last": [(1, 4), (3, 0), PARKED],
    "no_row_has_a_past": [(0, 0), PARKED, (0, 0)],
    "one_row": [(2, 4)],
}


@pytest.mark.parametrize("pack", ["1", "0"], ids=["packed_scales", "plain_scales"])
@pytest.mark.parametrize("form", ["heads_of_128", "heads_of_64_abreast"])
@pytest.mark.parametrize("ids_kind", [None, "perm"], ids=["in_order", "compact_permuted"])
@pytest.mark.parametrize("case", sorted(BLOCK_STARTS))
def test_block_attend_q8_parity(monkeypatch, case, ids_kind, form, pack):
    """`block_attend_q8` in interpret mode against the XLA arm's arithmetic on
    the same fused cache: a row's L = 4 positions x G query heads against its
    past in blocks (none for a first block at 0 and for a parked row, whose
    cells the batch's one pipeline steps over) and its own L keys whole; the
    whole batch in order and a permuted compaction; a head a row (P = 1) and
    two heads of 64 abreast (P = 2); the scales packed beside the payload and
    copied apart."""
    monkeypatch.setenv("LLM_MCP_TPU_Q8_SCALE_PACK", pack)
    rng = np.random.default_rng(11)
    rows = BLOCK_STARTS[case]
    abreast = form == "heads_of_64_abreast"
    Lyr, B, Hkv, hd, G, L, BS = 2, len(rows), 4 if abreast else 2, 64 if abreast else 128, 2, 4, 32
    S = 4 * BS
    ck, _ = _fused_q8_cache(rng, Lyr, B, Hkv, S, hd, abreast=abreast)
    assert A.fused_q8_heads(ck)[1:] == (1, 2 if abreast else 1)  # the pseudo-head is there
    starts = jnp.asarray([a * BS + b for a, b in rows], jnp.int32)
    ids = jnp.asarray(rng.permutation(B), jnp.int32) if ids_kind else None
    q = jnp.asarray(rng.standard_normal((B, L, Hkv, G, hd)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((B, Hkv, L, hd)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((B, Hkv, L, hd)), jnp.float32)

    def kernel(q, ks, vs, ck, starts, ids):
        A.block_attend_q8.clear_cache()  # the knob is read at trace time
        return A.block_attend_q8(
            q, ks, vs, ck, jnp.int32(1), starts, slot_ids=ids, interpret=True, block_s=BS)

    with jax.default_matmul_precision("highest"):
        # the cases of one (knob, form, batch) differ in the starts alone: one executable
        out = compiled_once(("block_attend", pack, form, ids_kind, B), kernel, q, ks, vs, ck, starts, ids)
        want = compiled_once(
            ("block_attend_reference", form, ids_kind, B),
            lambda q, ks, vs, ck, starts, ids: _block_attend_reference(q, ks, vs, ck, 1, starts, hd**-0.5, ids),
            q, ks, vs, ck, starts, ids)
    assert out.shape == want.shape and not bool(jnp.isnan(out).any())
    seated = (starts < S)[:, None, None, None, None]  # a parked row's output is discarded
    # float32 on both sides: the blocks' online softmax against the whole one
    assert float(jnp.max(jnp.abs(jnp.where(seated, out - want, 0.0)))) < 1e-5


def test_block_row_blocks_counts_what_the_arm_streams():
    """ceil(start / BS) blocks of the past; none at 0 and none for a parked row."""
    starts = np.asarray([0, 4, 252, 256, 260, 1020, 1024, 2000])
    assert list(A.block_row_blocks(starts, 1024, 256, xp=np)) == [0, 1, 1, 1, 2, 4, 0, 0]
    assert list(np.asarray(A.block_row_blocks(jnp.asarray(starts), 1024, 256))) == [0, 1, 1, 1, 2, 4, 0, 0]


@pytest.mark.parametrize("arm,block,streamed", [("pallas", 256, 3 * (256 + 512)), ("xla", 0, 3 * 4 * 1024)])
def test_block_attn_stream_counts_a_rounds_passes(arm, block, streamed):
    """The host's book of a block round (`perf_stats()["blocks"]["attn"]`): two
    seated rows at 100 and 300 of a batch of four, two denoising passes and the
    commit. The kernel's arm streams whole blocks of the seated rows' pasts,
    the XLA arm every row of the batch whole; live is the pasts' positions."""
    book = A.BlockAttnStream(arm, (48, 64, 9, 1024, 128))
    book.fetched([100, 300], 4, 3)
    st = book.stats()
    assert st["arm"] == arm and st["block_tokens"] == block and st["passes"] == 3
    assert st["tokens_live"] == 3 * 400 and st["tokens_streamed"] == streamed
    assert st["live_over_streamed"] == round(1200 / streamed, 4)


def test_q8_block_tokens_is_a_function_of_the_caches_shape():
    """The three shapes the benchmark's cells run (PERF.md section 6, PR 36):
    17 payload heads take the coarse block, 61 the finer one; a block always
    divides the row; 0 where nothing int8-tileable does."""
    assert A.q8_block_tokens(17, 2048, 128) == 256  # decode_closed
    assert A.q8_block_tokens(17, 1024, 128) == 256  # solar_decode_closed
    assert A.q8_block_tokens(9, 1024, 128) == 256  # granite_ / lfm2_decode_closed: heads of 64 abreast
    assert A.q8_block_tokens(61, 1024, 128) == 128  # olmo_hybrid_decode_closed
    assert A.q8_block_tokens(61, 1024 + 64, 128) == 64
    assert A.q8_block_tokens(400, 1024, 128) == 32  # none within the bound: the smallest
    assert A.q8_block_tokens(17, 1000, 128) == 0
    for heads, seq in [(17, 2048), (61, 1024), (5, 96), (33, 640)]:
        assert seq % A.q8_block_tokens(heads, seq, 128) == 0


def test_blocked_row_blocks_counts_what_the_arm_streams():
    w = np.array([0, 63, 64, 127, 128, 255, 256, 1023, 1024, 5000])
    assert A.blocked_row_blocks(w, 1024, 64, xp=np).tolist() == [1, 1, 2, 2, 3, 4, 5, 16, 1, 1]
    assert A.blocked_row_blocks(w, 1024, 256, xp=np).tolist() == [1, 1, 1, 1, 1, 1, 2, 4, 1, 1]
    np.testing.assert_array_equal(
        np.asarray(A.blocked_row_blocks(jnp.asarray(w), 1024, 128)),
        A.blocked_row_blocks(w, 1024, 128, xp=np))


@pytest.mark.parametrize("heads,block", [(17, 256), (61, 128)])
def test_attn_stream_counts_a_rounds_steps(heads, block):
    """`perf_stats()["decode_attn"]`: positions fetched and positions live over
    the steps of the rounds dispatched, at the block size the cache's shape
    gives; a parked row one block and no live position, a row that reaches the
    cache's end mid-round parked from there."""
    book = A.AttnStream((4, 8, heads, 1024, 128))
    assert book.block_tokens == block and book.stats()["live_over_streamed"] is None
    lens = np.array([0, block - 2, 1022, 1024, 3000], np.int32)
    book.dispatched(lens, 4)
    # row 0: positions 0..3, one block a step; row 1 crosses into its second
    # block at step 2; row 2 holds 1023 and 1024 positions, then is parked
    blocks = 4 + (1 + 1 + 2 + 2) + (1024 // block) * 2 + 2 + 4 + 4
    live = (1 + 2 + 3 + 4) + (4 * block + 2) + (1023 + 1024)
    got = book.stats()
    assert got["steps"] == 4 and got["block_tokens"] == block
    assert got["tokens_streamed"] == blocks * block and got["tokens_live"] == live
    assert got["live_over_streamed"] == round(live / (blocks * block), 4)
    book.dispatched(lens[:1], 2)
    assert book.stats()["steps"] == 6 and book.stats()["tokens_live"] == live + 1 + 2


@pytest.mark.parametrize("shape,kv_heads,block,abreast", [
    ((4, 64, 9, 1024, 128), 8, 256, 2),  # Granite-4.0-H: 8 KV heads of 64, two abreast
    ((36, 32, 17, 2048, 128), 8, 256, 1),  # Qwen3-8B: 8 KV heads of 128
    ((5, 64, 61, 1024, 128), 30, 128, 1),  # Olmo-Hybrid
    ((4, 64, 9, 1024, 128), 0, 256, 1),  # without the configuration's heads: a head a row
], ids=["granite", "qwen3_8b", "olmo_hybrid", "heads_not_given"])
def test_attn_stream_says_how_many_heads_lie_abreast(shape, kv_heads, block, abreast):
    """`perf_stats()["decode_attn"]["heads_abreast"]`: P of the cache the arm
    streams, by `fused_q8_heads`' rule from the payload's rows and the
    configuration's KV heads, beside the block of that shape."""
    book = A.AttnStream(shape, kv_heads=kv_heads)
    assert book.block_tokens == block and book.heads_abreast == abreast
    got = book.stats()
    assert got["heads_abreast"] == abreast and got["block_tokens"] == block
    if kv_heads:
        ck = {"q": jax.ShapeDtypeStruct(shape, jnp.int8),
              "s": jax.ShapeDtypeStruct((*shape[:2], 2 * kv_heads, shape[3]), jnp.bfloat16)}
        assert A.fused_q8_heads(ck) == (kv_heads, 1, abreast)


@pytest.mark.parametrize("Hkv,hd", [(2, 32), (4, 64), (2, 128)], ids=["hd32", "hd64_abreast", "hd128"])
@pytest.mark.parametrize("fill", FILLS)
def test_q8_gqa_whole_parity(monkeypatch, fill, Hkv, hd):
    """Fused whole-S q8 kernel (payload head-block + plain-scales DMA) vs
    the exact-f32 fallback at the same fills; a head of 64 beside one of 128:
    four KV heads of 64 lie two to a row, and since the int8 products of a row
    of two heads are each head's own exactly, the output is the same bytes' a
    head a row BIT FOR BIT."""
    monkeypatch.setenv("LLM_MCP_TPU_Q8_DECODE", "whole")
    A.decode_attend_q8.clear_cache()
    rng = np.random.default_rng(8)
    L, B, S, G = 2, 3, 64, 2
    ck, cv = _fused_q8_cache(rng, L, B, Hkv, S, hd, abreast=True)
    assert A.fused_q8_heads(ck)[2] == (2 if hd == 64 else 1)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    nk = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    lens = _lens_for(fill, B, S, rng)
    out = A.decode_attend_q8(q, nk, nv, ck, cv, jnp.int32(0), lens, interpret=True)
    ref = A._decode_attend_q8_fallback(
        q, nk, nv, ck, cv, jnp.int32(0), lens, hd**-0.5, None
    )
    assert float(jnp.max(jnp.abs(out - ref))) < 0.05
    apart = _heads_apart(ck)
    np.testing.assert_array_equal(
        np.asarray(ref), np.asarray(A._decode_attend_q8_fallback(
            q, nk, nv, apart, cv, jnp.int32(0), lens, hd**-0.5, None)))
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(A.decode_attend_q8(q, nk, nv, apart, cv, jnp.int32(0), lens, interpret=True)))


# -- GQA bf16 (split arrays) -------------------------------------------------


@pytest.mark.parametrize("arm", ["whole", "blocked"])
@pytest.mark.parametrize("fill", FILLS)
def test_bf16_gqa_parity(monkeypatch, fill, arm):
    """Both arms of the bf16 hybrid vs the exact-f32 fallback — the new
    dispatch that replaced the XLA demotion past the VMEM cap."""
    monkeypatch.setenv("LLM_MCP_TPU_BF16_DECODE", arm)
    A.decode_attend_bf16.clear_cache()
    rng = np.random.default_rng(9)
    L, B, Hkv, S, hd, G = 2, 3, 2, 256, 64, 2
    ck = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    nk = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    lens = _lens_for(fill, B, S, rng)
    ids = jnp.asarray(rng.permutation(B), jnp.int32)
    out = A.decode_attend_bf16(
        q, nk, nv, ck, cv, jnp.int32(1), lens, slot_ids=ids, interpret=True
    )
    ref = A._decode_attend_bf16_fallback(
        q, nk, nv, ck, cv, jnp.int32(1), lens, hd**-0.5, ids
    )
    # f32 caches on CPU: both sides run the same exact math
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_bf16_gqa_blocked_parked_rows(monkeypatch):
    monkeypatch.setenv("LLM_MCP_TPU_BF16_DECODE", "blocked")
    A.decode_attend_bf16.clear_cache()
    rng = np.random.default_rng(10)
    L, B, Hkv, S, hd, G = 1, 2, 2, 128, 64, 2
    ck = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    nk = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    lens = jnp.asarray([S, 17], jnp.int32)  # row 0 parked
    out = A.decode_attend_bf16(q, nk, nv, ck, cv, jnp.int32(0), lens, interpret=True)
    assert not bool(jnp.isnan(out).any())
    ref = A._decode_attend_bf16_fallback(
        q, nk, nv, ck, cv, jnp.int32(0), lens, hd**-0.5, None
    )
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]), atol=2e-5)


# -- MLA int8 latents --------------------------------------------------------


# The rope keys' width and how the int8 cache holds them: (dr, P). P positions
# lie abreast in a row of whole lanes (`A.positions_abreast`: 2 at the
# published 64, 8 at the tiny twins' 16); at 32 the rows are laid APART, a
# position a row, the form every cache had and a shape the rule leaves alone
# where P does not divide the positions.
MLA_FORMS = [(32, 1), (64, 2), (16, 8)]
MLA_FORM_IDS = ["dr32_apart", "dr64_two_abreast", "dr16_eight_abreast"]


def _mla_pair(rng, L, B, S, R, dr, H, P):
    """`_mla_args` with the rope keys' int8 payload P positions abreast, and
    the SAME bytes laid apart for the reference."""
    cc, cr, qt, qr, nc, nr = _mla_args(rng, L, B, S, R, dr, H)
    if P > 1:
        assert A.positions_abreast(S, dr) == P
    return cc, {"q": A.rope_abreast(cr["q"], P), "s": cr["s"]}, cr, qt, qr, nc, nr


def _mla_args(rng, L, B, S, R, dr, H):
    cc = {
        "q": jnp.asarray(rng.integers(-127, 128, (L, B, 1, S, R), dtype="int8")),
        "s": jnp.asarray(rng.random((L, B, 1, S), dtype="float32") * 0.02),
    }
    cr = {
        "q": jnp.asarray(rng.integers(-127, 128, (L, B, 1, S, dr), dtype="int8")),
        "s": jnp.asarray(rng.random((L, B, 1, S), dtype="float32") * 0.02),
    }
    qt = jnp.asarray(rng.standard_normal((B, H, R)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((B, H, dr)), jnp.float32)
    nc = jnp.asarray(rng.standard_normal((B, R)), jnp.float32)
    nr = jnp.asarray(rng.standard_normal((B, dr)), jnp.float32)
    return cc, cr, qt, qr, nc, nr


def _same_as_apart(out, apart):
    """The kernel over rows laid abreast against ITSELF over the same bytes laid
    apart: the int8 products are the same numbers and the rope product only
    adds zeros, so the contexts agree to the last bits of a float32 sum."""
    np.testing.assert_allclose(np.asarray(out), np.asarray(apart), atol=2e-6, rtol=0)


@pytest.mark.parametrize("dr,P", MLA_FORMS, ids=MLA_FORM_IDS)
@pytest.mark.parametrize("fill", FILLS)
def test_mla_whole_s_parity(fill, dr, P):
    rng = np.random.default_rng(11)
    L, B, S, R, H = 2, 3, 128, 64, 4
    cc, cr, apart, qt, qr, nc, nr = _mla_pair(rng, L, B, S, R, dr, H, P)
    lens = _lens_for(fill, B, S, rng)
    sc = (R + dr) ** -0.5
    out = A.decode_attend_q8_mla(
        qt, qr, nc, nr, cc, cr, jnp.int32(1), lens, scale=sc, interpret=True
    )
    ref = A._decode_attend_q8_mla_fallback(
        qt, qr, nc, nr, cc, apart, jnp.int32(1), lens, sc, None
    )
    assert float(jnp.max(jnp.abs(out - ref))) < 0.05
    # the fallback pulls the rows apart itself: bit for bit what it reads apart
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(A._decode_attend_q8_mla_fallback(
        qt, qr, nc, nr, cc, cr, jnp.int32(1), lens, sc, None)))
    _same_as_apart(out, A.decode_attend_q8_mla(
        qt, qr, nc, nr, cc, apart, jnp.int32(1), lens, scale=sc, interpret=True))


@pytest.mark.parametrize("dr,P", MLA_FORMS, ids=MLA_FORM_IDS)
@pytest.mark.parametrize("fill", FILLS)
def test_mla_blocked_parity(monkeypatch, fill, dr, P):
    """The blocked MLA kernel (whole-S arm disabled via the VMEM-fit
    probe): S=1024 runs 2 blocks of 512 — the same static-unroll dispatch
    the S=32k sweep uses at the 64-block cap. Two positions abreast a block is
    one lane group of every row; eight abreast it is four groups of 128 rows."""
    monkeypatch.setattr(A, "mla_whole_s_fits", lambda *a, **k: False)
    rng = np.random.default_rng(12)
    L, B, S, R, H = 1, 3, 1024, 64, 4
    cc, cr, apart, qt, qr, nc, nr = _mla_pair(rng, L, B, S, R, dr, H, P)
    lens = _lens_for(fill, B, S, rng)
    ids = jnp.asarray(rng.permutation(B), jnp.int32)
    sc = (R + dr) ** -0.5
    out = A.decode_attend_q8_mla(
        qt, qr, nc, nr, cc, cr, jnp.int32(0), lens,
        slot_ids=ids, scale=sc, interpret=True,
    )
    ref = A._decode_attend_q8_mla_fallback(
        qt, qr, nc, nr, cc, apart, jnp.int32(0), lens, sc, ids
    )
    assert float(jnp.max(jnp.abs(out - ref))) < 0.05
    _same_as_apart(out, A.decode_attend_q8_mla(
        qt, qr, nc, nr, cc, apart, jnp.int32(0), lens, slot_ids=ids, scale=sc, interpret=True))


def test_mla_block_cap_boundary(monkeypatch):
    """The blocked MLA kernel statically unrolls its DMA loop, capped at 64
    blocks: S=32768 @ BS=512 is EXACTLY 64 and must stay on the kernel
    (S=32k is the cap boundary); S=65536
    exceeds the cap for every tileable block size and must fall back to
    the exact-f32 path, not compile a 128-way unroll."""
    assert A.mla_block_size(1024) == 512
    assert A.mla_block_size(32_768) == 512  # 64 blocks: the allowed boundary
    assert A.mla_block_size(65_536) == 0  # past the cap: no tileable BS
    # past-cap dispatch equals the fallback bit-for-bit (it IS the fallback)
    monkeypatch.setattr(A, "mla_whole_s_fits", lambda *a, **k: False)
    rng = np.random.default_rng(13)
    L, B, S, R, dr, H = 1, 1, 65_536, 16, 8, 2
    cc, cr, qt, qr, nc, nr = _mla_args(rng, L, B, S, R, dr, H)
    lens = jnp.asarray([40], jnp.int32)
    sc = (R + dr) ** -0.5
    out = A.decode_attend_q8_mla(
        qt, qr, nc, nr, cc, cr, jnp.int32(0), lens, scale=sc, interpret=True
    )
    ref = A._decode_attend_q8_mla_fallback(
        qt, qr, nc, nr, cc, cr, jnp.int32(0), lens, sc, None
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# -- block-indirect (paged) arms ---------------------------------------------
#
# Construction: start from a CONTIGUOUS reference cache, force blocks
# [0, nshared) of every slot to identical bytes (the shared prefix), move
# ONE copy of those blocks into the pool, point every slot's table at the
# pool rows, and scramble the arena's donor region with garbage. The paged
# arm on (scrambled arena + table + pool) must match the plain contiguous
# fallback on the reference — proving every shared read really goes
# through the table. nshared tracks the fill level, so 0% runs the
# identity-table case and 90% redirects every block including the one
# holding the write position (the kernels' exact current-row override).


def _paged_split(tree, bt, nshared, pxb, rng):
    """(ref, arena, pool, tables) for a cache pytree of [L,B,H,S,...]
    leaves. Physical ids < B*nbs are arena homes (slot p//nbs, block
    p%nbs); ids >= B*nbs index pool rows — the same mapping
    executor/physical.py maintains."""
    if isinstance(tree, dict):
        parts = {k: _paged_split(v, bt, nshared, pxb, rng) for k, v in tree.items()}
        return tuple({k: v[i] for k, v in parts.items()} for i in range(3))
    x = np.array(tree)
    L, B, H, S = x.shape[:4]
    for j in range(nshared):  # shared prefix: one content for every slot
        x[:, :, :, j * bt:(j + 1) * bt] = x[:, :1, :, j * bt:(j + 1) * bt]
    ref = x.copy()
    pool = np.zeros((L, pxb, H, bt) + x.shape[4:], x.dtype)
    for j in range(nshared):
        pool[:, j] = x[:, 0, :, j * bt:(j + 1) * bt]
        blk = x[:, :, :, j * bt:(j + 1) * bt]
        junk = (
            rng.integers(-127, 128, blk.shape)
            if np.issubdtype(x.dtype, np.integer)
            else rng.standard_normal(blk.shape)
        )
        x[:, :, :, j * bt:(j + 1) * bt] = junk.astype(x.dtype)
    return jnp.asarray(ref), jnp.asarray(x), jnp.asarray(pool)


def _paged_tables(B, nbs, nshared):
    tbl = np.arange(B * nbs, dtype=np.int32).reshape(B, nbs)
    tbl[:, :nshared] = B * nbs + np.arange(nshared, dtype=np.int32)
    return jnp.asarray(tbl)


# The paged arms follow the leak-soak precedent: the production
# configuration (packed scales; and for bf16/MLA the mid-fill case that
# exercises both shared and private blocks) runs in tier-1, the rest of
# the fill x pack grid is slow-marked and covered by `-m slow` runs.
@pytest.mark.parametrize("form", ["apart", "abreast"])
@pytest.mark.parametrize(
    "pack", [pytest.param("0", marks=pytest.mark.slow), "1"])
@pytest.mark.parametrize("fill", FILLS)
def test_q8_gqa_paged_parity(monkeypatch, fill, pack, form):
    """Block-indirect fused-q8 kernel (packed and unpacked) vs the plain
    contiguous fallback on the pre-split reference cache; `abreast`: arena and
    pool with four KV heads of 64 two to a row."""
    monkeypatch.setenv("LLM_MCP_TPU_Q8_DECODE", "paged")
    monkeypatch.setenv("LLM_MCP_TPU_Q8_SCALE_PACK", pack)
    A.decode_attend_q8.clear_cache()
    rng = np.random.default_rng(21)
    L, B, Hkv, S, hd, G, bt = 2, 3, 4 if form == "abreast" else 2, 256, 64, 2, 64
    nbs = S // bt
    nshared = min(nbs, round(fill * nbs))
    ck, cv = _fused_q8_cache(rng, L, B, Hkv, S, hd, abreast=form == "abreast")
    assert ck["q"].shape == (L, B, 5, S, 128 if form == "abreast" else 64)
    ref, arena, pool = _paged_split(ck, bt, nshared, nbs, rng)
    tbl = _paged_tables(B, nbs, nshared)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    nk = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    lens = _lens_for(fill, B, S, rng)
    ids = jnp.asarray(rng.permutation(B), jnp.int32)
    out = A.decode_attend_q8(
        q, nk, nv, arena, cv, jnp.int32(1), lens, slot_ids=ids,
        block_tables=tbl, pool_k=pool, interpret=True,
    )
    want = A._decode_attend_q8_fallback(
        q, nk, nv, ref, cv, jnp.int32(1), lens, hd**-0.5, ids
    )
    assert float(jnp.max(jnp.abs(out - want))) < 0.05
    assert not bool(jnp.isnan(out).any())


@pytest.mark.parametrize(
    "fill", [pytest.param(0.0, marks=pytest.mark.slow), 0.4,
             pytest.param(0.9, marks=pytest.mark.slow)])
def test_bf16_gqa_paged_parity(monkeypatch, fill):
    """Block-indirect bf16 kernel vs the contiguous fallback on the
    reference: f32 caches on CPU, so both sides run exact math."""
    monkeypatch.setenv("LLM_MCP_TPU_BF16_DECODE", "paged")
    A.decode_attend_bf16.clear_cache()
    rng = np.random.default_rng(22)
    L, B, Hkv, S, hd, G, bt = 2, 3, 2, 256, 64, 2, 64
    nbs = S // bt
    nshared = min(nbs, round(fill * nbs))
    ck = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    ref_k, arena_k, pool_k = _paged_split(ck, bt, nshared, nbs, rng)
    ref_v, arena_v, pool_v = _paged_split(cv, bt, nshared, nbs, rng)
    tbl = _paged_tables(B, nbs, nshared)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    nk = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    lens = _lens_for(fill, B, S, rng)
    ids = jnp.asarray(rng.permutation(B), jnp.int32)
    out = A.decode_attend_bf16(
        q, nk, nv, arena_k, arena_v, jnp.int32(1), lens, slot_ids=ids,
        block_tables=tbl, pool_k=pool_k, pool_v=pool_v, interpret=True,
    )
    want = A._decode_attend_bf16_fallback(
        q, nk, nv, ref_k, ref_v, jnp.int32(1), lens, hd**-0.5, ids
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dr,P", MLA_FORMS, ids=MLA_FORM_IDS)
@pytest.mark.parametrize(
    "fill", [pytest.param(0.0, marks=pytest.mark.slow), 0.4,
             pytest.param(0.9, marks=pytest.mark.slow)])
def test_mla_paged_parity(monkeypatch, fill, dr, P):
    """Block-indirect MLA latent kernel vs the contiguous fallback: one
    table drives BOTH the latent and rope pools. The rope keys' ARENA lies P
    positions abreast; its pool, as every row cut out of the cache, apart."""
    monkeypatch.setenv("LLM_MCP_TPU_Q8_DECODE", "paged")
    rng = np.random.default_rng(23)
    L, B, S, R, H, bt = 2, 3, 256, 64, 4, 64
    nbs = S // bt
    nshared = min(nbs, round(fill * nbs))
    cc, cr, qt, qr, nc, nr = _mla_args(rng, L, B, S, R, dr, H)
    ref_c, arena_c, pool_c = _paged_split(cc, bt, nshared, nbs, rng)
    ref_r, arena_r, pool_r = _paged_split(cr, bt, nshared, nbs, rng)
    arena_r = {"q": A.rope_abreast(arena_r["q"], P), "s": arena_r["s"]}
    tbl = _paged_tables(B, nbs, nshared)
    lens = _lens_for(fill, B, S, rng)
    ids = jnp.asarray(rng.permutation(B), jnp.int32)
    sc = (R + dr) ** -0.5
    out = A.decode_attend_q8_mla(
        qt, qr, nc, nr, arena_c, arena_r, jnp.int32(1), lens, slot_ids=ids,
        block_tables=tbl, pool_c=pool_c, pool_r=pool_r, scale=sc,
        interpret=True,
    )
    want = A._decode_attend_q8_mla_fallback(
        qt, qr, nc, nr, ref_c, ref_r, jnp.int32(1), lens, sc, ids
    )
    assert float(jnp.max(jnp.abs(out - want))) < 0.05


def test_paged_fallback_gather_matches_contiguous():
    """`paged_gather` (the exact XLA gather every serve path uses on CPU)
    reassembles the reference bit-for-bit from (arena, pool, table) — the
    foundation the engine's greedy-identity guarantees rest on."""
    rng = np.random.default_rng(24)
    L, B, H, S, hd, bt = 2, 3, 2, 256, 16, 64
    nbs = S // bt
    x = jnp.asarray(rng.standard_normal((L, B, H, S, hd)), jnp.float32)
    ref, arena, pool = _paged_split(x, bt, 2, nbs, rng)
    tbl = _paged_tables(B, nbs, 2)
    for layer in range(L):
        got = A.paged_gather(arena[layer], pool[layer], tbl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref[layer]))
    # narrow-table prefix (the chunked-prefill read): first 2 blocks only,
    # with nbs naming the FULL blocks-per-slot so physical ids decode right
    got = A.paged_gather(arena[0], pool[0], tbl[:, :2], nbs=nbs)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref[0][:, :, : 2 * bt])
    )


# -- append kernels ----------------------------------------------------------


@pytest.mark.parametrize("Hkv,hd", [(2, 128), (4, 64), (4, 32)],
                         ids=["hd128", "hd64_two_abreast", "hd32_four_abreast"])
def test_append_q8_kernel_parity(monkeypatch, Hkv, hd):
    """The aliased tile-rewrite append vs the XLA scatter at a lane-aligned
    shape (rows of 128 lanes, S=128 — the kernel path): identical bytes,
    including the packed pseudo-head, with parked rows and compaction ids;
    heads of 128, and heads of 64 and of 32 abreast in rows of 128 lanes (the
    kernel selects a full-lane row whatever lies in it)."""
    rng = np.random.default_rng(14)
    L, B, S = 2, 3, 128
    ck, cv = _fused_q8_cache(rng, L, B, Hkv, S, hd, abreast=True)
    assert ck["q"].shape[-1] == 128 and A.fused_q8_heads(ck) == (Hkv, 1, 128 // hd)
    falls = dict(A.reference_falls)
    nk = jnp.asarray(rng.standard_normal((L, B, Hkv, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((L, B, Hkv, hd)), jnp.float32)
    lens = jnp.asarray([0, S, 100], jnp.int32)  # row 1 parked: writes nothing
    ids = jnp.asarray([2, 0, 1], jnp.int32)
    out_k, out_v = A.append_kv_q8(
        ck, cv, nk, nv, lens, slot_ids=ids, interpret=True
    )
    # jitted like the kernel wrapper: eager quantization rounds a value or
    # two differently than the fused program does
    ref_k, ref_v = jax.jit(A.append_kv_q8_reference)(ck, cv, nk, nv, lens, slot_ids=ids)
    np.testing.assert_array_equal(np.asarray(out_k["q"]), np.asarray(ref_k["q"]))
    np.testing.assert_array_equal(np.asarray(out_k["s"]), np.asarray(ref_k["s"]))
    assert out_v == ref_v == {}
    # the kernel ran: with interpret off this shape would not have fallen either
    jax.eval_shape(lambda *a: A.append_kv_q8(*a, slot_ids=ids, interpret=False), ck, cv, nk, nv, lens)
    assert A.reference_falls == falls
    # and what it wrote is the step's K/V: the row read back a head at a time
    k_rows, v_rows = A.fused_kv(out_k["q"], Hkv, 128 // hd)
    kq = jax.jit(lambda x: A._q8_step_rows(_heads_apart(ck), x, x)[0])(nk)[:, :, :Hkv]
    np.testing.assert_array_equal(np.asarray(k_rows[:, 2, :, 0]), np.asarray(kq[:, 0]))


@pytest.mark.parametrize("Hkv,hd,P", [(4, 64, 2), (8, 64, 2), (4, 32, 4), (2, 128, 1), (2, 32, 1), (3, 64, 1)])
def test_kv_heads_abreast_round_trip(Hkv, hd, P):
    """`kv_heads_abreast`'s rule and the two directions of the form: heads
    narrower than the 128 lanes that divide them lie P = 128 // hd to a row
    where P divides the heads; head p*R + r in lanes [p*hd, (p+1)*hd) of row r;
    `kv_apart` undoes `kv_abreast`, `ctx_apart` undoes `q_abreast`, and a
    query's row holds zeros outside its own head's lanes."""
    assert A.kv_heads_abreast(Hkv, hd) == P
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-127, 128, (2, 3, Hkv, 8, hd), dtype="int8"))
    rows = A.kv_abreast(x, P)
    R = Hkv // P
    assert rows.shape == (2, 3, R, 8, P * hd)
    for h in range(Hkv):
        p, r = divmod(h, R)
        np.testing.assert_array_equal(
            np.asarray(rows[:, :, r, :, p * hd:(p + 1) * hd]), np.asarray(x[:, :, h]))
    np.testing.assert_array_equal(np.asarray(A.kv_apart(rows, P)), np.asarray(x))
    k, v = A.fused_kv(jnp.concatenate([rows, rows + 0, rows[:, :, :1]], axis=2), Hkv, P)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(x))
    G = 3
    q = jnp.asarray(rng.integers(-9, 10, (5, Hkv, G, hd)), jnp.float32)  # whole numbers: exact sums
    qw = A.q_abreast(q, P)
    assert qw.shape == (5, R, P * G, P * hd)
    np.testing.assert_array_equal(np.asarray(A.ctx_apart(qw, P)), np.asarray(q))
    assert int(jnp.sum(qw != 0)) == int(jnp.sum(q != 0))  # zeros everywhere else
    # a product over all of a row's lanes is each head's own
    kf = x[0, 0].astype(jnp.float32)  # [Hkv, 8, hd]
    want = jnp.einsum("bhgd,hsd->bhgs", q, kf)
    got = jnp.einsum("brgw,rsw->brgs", qw, rows[0, 0].astype(jnp.float32))  # [5, R, P*G, 8]
    got = got.reshape(5, R, P, G, 8).transpose(0, 2, 1, 3, 4).reshape(5, Hkv, G, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("S,dr,P", [(64, 64, 2), (64, 16, 8), (48, 32, 4), (64, 128, 1), (64, 96, 1), (63, 64, 1)])
def test_positions_abreast_round_trip(S, dr, P):
    """`positions_abreast`'s rule and the two directions of the latent pair's
    form: position s of the rope keys lies in row s mod S/P, lanes [(s div S/P)
    dr, +dr), `rope_apart` undoes `rope_abreast`, and a product of
    `rope_queries`' rows over whole rows is each position's own score, the lane
    groups' scores side by side the positions in order."""
    assert A.positions_abreast(S, dr) == P
    rng = np.random.default_rng(58)
    x = jnp.asarray(rng.integers(-127, 128, (2, 3, 1, S, dr)), jnp.int8)
    rows = A.rope_abreast(x, P)
    half = S // P
    assert rows.shape == (2, 3, 1, half, P * dr)
    for s_ in range(S):
        g, r = divmod(s_, half)
        np.testing.assert_array_equal(
            np.asarray(rows[:, :, 0, r, g * dr:(g + 1) * dr]), np.asarray(x[:, :, 0, s_]))
    np.testing.assert_array_equal(np.asarray(A.rope_apart(rows, P)), np.asarray(x))
    H = 3
    q = jnp.asarray(rng.integers(-9, 10, (5, H, dr)), jnp.float32)  # whole numbers: exact sums
    qw = A.rope_queries(q, P)
    assert qw.shape == (5, P * H, P * dr) and int(jnp.sum(qw != 0)) == P * int(jnp.sum(q != 0))
    want = jnp.einsum("bhd,sd->bhs", q, x[0, 0, 0].astype(jnp.float32))
    got = jnp.einsum("bgw,rw->bgr", qw, rows[0, 0, 0].astype(jnp.float32))  # [5, P*H, half]
    got = got.reshape(5, P, H, half).transpose(0, 2, 1, 3).reshape(5, H, S)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dr,P", [(64, 2), (16, 8), (32, 1)], ids=["two_abreast", "eight_abreast", "apart"])
@pytest.mark.parametrize("w", [7, 8, 31, 32, 33, 63])
def test_rope_append_leaves_the_neighbours_bytes(dr, P, w):
    """A decode step's append at an odd and at an even position, inside and at
    the ends of a lane group (S / P = 32 rows at two abreast): the one position
    changes and every other byte of the cache stays, and a parked row (w >= S)
    writes nothing. Batched over the layers (the Pallas path's one scatter) and
    a layer at a time (the XLA path's)."""
    rng = np.random.default_rng(w)
    L, B, S = 2, 3, 64
    P = P if dr != 32 else 1
    apart = jnp.asarray(rng.integers(-127, 128, (L, B, 1, S, dr)), jnp.int8)
    cache = A.rope_abreast(apart, P)
    new = jnp.asarray(rng.integers(-127, 128, (L, 2, dr)), jnp.int8)
    rows, ws = jnp.asarray([2, 0], jnp.int32), jnp.asarray([w, S], jnp.int32)  # row 0 parked
    want = np.array(apart)
    want[:, 2, 0, w] = np.asarray(new[:, 0])
    got = A.rope_append(cache, new, jnp.arange(L)[:, None], rows[None, :], ws[None, :])
    np.testing.assert_array_equal(np.asarray(A.rope_apart(got, P)), want)
    one = cache
    for l in range(L):
        one = A.rope_append(one, new[l], jnp.int32(l), rows, ws)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(got))


@pytest.mark.parametrize("dr,P", [(64, 2), (16, 8), (32, 1)], ids=["two_abreast", "eight_abreast", "apart"])
@pytest.mark.parametrize("start,n", [(0, 16), (0, 24), (0, 40), (0, 64), (5, 16), (27, 10), (60, 16)])
def test_rope_put_leaves_the_neighbours_bytes(dr, P, start, n):
    """A prompt's rows into a cache row: a bucket that ends inside a row's lane
    group (16 and 24 of 32), one that runs over into the next group (40), the
    whole row, a chunk that starts inside a group and one that crosses from one
    group into the next (27..36), and a start that dynamic_update_slice clamps
    back (60 + 16 > 64): the positions written are the rows, every other byte of
    the cache as it was, for all layers at once (admit, a prefix entry, a pool's
    block) and a layer at a time (the bucketed chunk)."""
    rng = np.random.default_rng(n + start)
    L, B, S = 2, 3, 64
    P = P if dr != 32 else 1
    apart = jnp.asarray(rng.integers(-127, 128, (L, B, 1, S, dr)), jnp.int8)
    cache = A.rope_abreast(apart, P)
    rows = jnp.asarray(rng.integers(-127, 128, (L, 1, 1, n, dr)), jnp.int8)
    want = jax.lax.dynamic_update_slice(apart, rows, (0, 1, 0, start, 0))
    got = jax.jit(lambda c, r, b, s_: A.rope_put(c, r, (0, b), s_))(cache, rows, jnp.int32(1), jnp.int32(start))
    assert got.shape == cache.shape and got.dtype == cache.dtype
    np.testing.assert_array_equal(np.asarray(A.rope_apart(got, P)), np.asarray(want))
    one = cache
    for l in range(L):
        one = A.rope_put(one, rows[l:l + 1], (jnp.int32(l), jnp.int32(1)), jnp.int32(start))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(got))
    # `keep`: the packed chunk's window, of which only some rows land
    keep = jnp.asarray(rng.integers(0, 2, (n,)), bool)
    kept = A.rope_put(cache, rows, (0, 1), jnp.int32(start), keep=keep)
    at = np.clip(start, 0, S - n)
    want_k = np.array(apart)
    want_k[:, 1, 0, at:at + n] = np.where(np.asarray(keep)[:, None], np.asarray(rows[:, 0, 0]), want_k[:, 1, 0, at:at + n])
    np.testing.assert_array_equal(np.asarray(A.rope_apart(kept, P)), want_k)


@pytest.mark.parametrize("Hkv,hd", [(4, 64), (8, 64), (2, 128), (4, 32)])
def test_fuse_prompt_kv_and_step_rows_pack_the_same_bytes(Hkv, hd):
    """A prompt's rows (`fuse_prompt_kv`: admit, chunk and mixed programs) and
    a decode step's row (`_q8_step_rows`: the append) land in one form, the
    cache's own (`init_kv_cache`): position t of the prompt's entry is the
    step's row of the same vectors, byte for byte, pseudo-head included, and
    the packed scales read back (`unpack_scales`) are the plain ones."""
    from llm_mcp_tpu.models.configs import ModelConfig
    from llm_mcp_tpu.models.llama import fuse_prompt_kv, init_kv_cache
    from llm_mcp_tpu.models.quant import unpack_scales

    rng = np.random.default_rng(5)
    L, B, S = 2, 3, 16
    k = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    entry = jax.jit(lambda k, v: fuse_prompt_kv(k, v, scale_dtype=jnp.float32))(k, v)
    P = A.kv_heads_abreast(Hkv, hd)
    cfg = ModelConfig(name="t", vocab_size=8, dim=Hkv * hd, n_layers=L, n_heads=Hkv,
                      n_kv_heads=Hkv, ffn_hidden=8, head_dim=hd)
    made = jax.eval_shape(lambda: init_kv_cache(cfg, B, S, dtype=jnp.float32, quantized=True))
    assert made["k"]["q"].shape == entry["q"].shape == (L, B, 2 * Hkv // P + 1, S, P * hd)
    assert made["k"]["s"].shape == entry["s"].shape == (L, B, 2 * Hkv, S)
    assert A.fused_q8_heads(entry) == (Hkv, 1, P)
    for t in (0, 7, S - 1):
        pay, s_new = jax.jit(lambda k, v: A._q8_step_rows(entry, k, v))(
            k[:, :, :, t], v[:, :, :, t])
        np.testing.assert_array_equal(np.asarray(pay), np.asarray(entry["q"][:, :, :, t]))
        np.testing.assert_array_equal(np.asarray(s_new), np.asarray(entry["s"][:, :, :, t]))
    np.testing.assert_array_equal(
        np.asarray(unpack_scales(entry["q"][:, :, -1], 2 * Hkv, jnp.float32)),
        np.asarray(entry["s"]))
    # and the heads read back a row at a time are the quantised K and V
    kq, vq = A.fused_kv(entry["q"], Hkv, P)
    deq = kq.astype(jnp.float32) * entry["s"][:, :, :Hkv, :, None]
    assert float(jnp.max(jnp.abs(deq - k))) < float(jnp.max(jnp.abs(k))) / 127 * 0.51
    deq = vq.astype(jnp.float32) * entry["s"][:, :, Hkv:, :, None]
    assert float(jnp.max(jnp.abs(deq - v))) < float(jnp.max(jnp.abs(v))) / 127 * 0.51


def test_append_bf16_kernel_parity(monkeypatch):
    rng = np.random.default_rng(15)
    L, B, Hkv, S, hd = 2, 3, 2, 32, 128
    ck = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    nk = jnp.asarray(rng.standard_normal((L, B, Hkv, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((L, B, Hkv, hd)), jnp.float32)
    lens = jnp.asarray([15, S, 16], jnp.int32)  # tile boundary + parked row
    ids = jnp.asarray([1, 2, 0], jnp.int32)
    out_k, out_v = A.append_kv_bf16(ck, cv, nk, nv, lens, slot_ids=ids, interpret=True)
    ref_k, ref_v = A.append_kv_bf16_reference(ck, cv, nk, nv, lens, slot_ids=ids)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(out_v), np.asarray(ref_v))


# -- ragged packed prefill ---------------------------------------------------
#
# The chunked-prefill tentpole (kernels/attention.py ragged_* family): a
# packed [T] token buffer with per-row (slot, start, len) descriptors, the
# cached prefix streamed block-indirect through per-slot tables. Parity is
# kernel-in-interpret vs the module's own exact XLA arm (`impl="xla"`) —
# the arm that mirrors the bucketed chunk math the engine's greedy-identity
# acceptance pins end-to-end (tests/test_engine.py ragged toggle tests).
# Construction per the paged-decode precedent: identity tables scrambled so
# prefix blocks resolve through donor pool rows and foreign arena homes in
# shuffled order; the packed buffer carries a batch remainder (pads past the
# last row) and an EMPTY row (a budget-starved descriptor). The fill level
# drives the cached-prefix depth (`starts`), covering no-past, mid-block,
# and deep multi-block streaming.


def _ragged_case(fill, S, bt, B=6, pxb=4):
    R, T = 3, 32
    lens = [10, 0, 14]  # row 1 empty; total 24 < T = 32: remainder pads
    total = sum(lens)
    offsets = np.zeros(R + 1, np.int32)
    offsets[1:] = np.cumsum(lens)
    rowids = np.concatenate(
        [np.full(n, r, np.int32) for r, n in enumerate(lens)]
        + [np.full(T - total, R, np.int32)]
    )
    base = int(fill * (S - 16))
    starts = np.asarray(
        [base + 5 if base else 0, 0, max(1, base) if base else 0], np.int32
    )
    slots = np.asarray([4, 2, 0], np.int32)
    nbs = S // bt
    tbl = np.arange(B * nbs, dtype=np.int32).reshape(B, nbs)
    # scrambled donors: slot 4's prefix resolves through pool rows 1, 3 and
    # slot 2's arena home; slot 0's through pool 0 and slot 5's home
    tbl[4, 0] = B * nbs + 1
    if nbs > 1:
        tbl[4, 1] = 2 * nbs + 1
    if nbs > 2:
        tbl[4, 2] = B * nbs + 3
    tbl[0, 0] = B * nbs + 0
    if nbs > 1:
        tbl[0, 1] = 5 * nbs + 1
    return R, T, total, rowids, offsets, slots, starts, tbl, nbs, pxb


@pytest.mark.parametrize(
    "paged", [pytest.param(False, marks=pytest.mark.slow), True])
@pytest.mark.parametrize("fill", RAGGED_FILLS)
def test_ragged_prefill_bf16_parity(fill, paged):
    rng = np.random.default_rng(31)
    L, Hkv, G, hd, S, bt, B = 2, 2, 2, 64, 128, 32, 6
    R, T, total, rowids, offsets, slots, starts, tbl, nbs, pxb = _ragged_case(
        fill, S, bt, B
    )
    ck = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((L, B, Hkv, S, hd)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((L, pxb, Hkv, bt, hd)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((L, pxb, Hkv, bt, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((T, Hkv, G, hd)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((T, Hkv, hd)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((T, Hkv, hd)), jnp.float32)
    kw = dict(
        scale=hd**-0.5, skey=0, block_q=16,
        block_tables=jnp.asarray(tbl) if paged else None,
        pool_k=pk if paged else None, pool_v=pv if paged else None,
    )
    args = (q, ks, vs, ck, cv, 1, rowids, offsets, slots, starts)
    ref = A.ragged_prefill_attend_bf16(*args, impl="xla", **kw)
    out = A.ragged_prefill_attend_bf16(
        *args, impl="kernel", interpret=True, **kw
    )
    np.testing.assert_allclose(
        np.asarray(out[:total]), np.asarray(ref[:total]), atol=2e-5
    )
    assert not bool(jnp.isnan(out).any())


@pytest.mark.parametrize(
    "paged", [pytest.param(False, marks=pytest.mark.slow), True])
@pytest.mark.parametrize("fill", RAGGED_FILLS)
def test_ragged_prefill_q8_parity(fill, paged):
    """Fused int8 layout incl. the bit-packed scale pseudo-head riding the
    payload DMA; plain scales pre-gathered through the SAME scrambled
    tables as the payload blocks."""
    rng = np.random.default_rng(32)
    L, Hkv, G, hd, S, bt, B = 2, 2, 2, 64, 128, 32, 6
    R, T, total, rowids, offsets, slots, starts, tbl, nbs, pxb = _ragged_case(
        fill, S, bt, B
    )
    ck, _ = _fused_q8_cache(rng, L, B, Hkv, S, hd)
    p = ck["q"].shape[2] - 2 * Hkv
    pool = {
        "q": jnp.asarray(
            rng.integers(-127, 128, (L, pxb, 2 * Hkv + p, bt, hd), dtype="int8")
        ),
        "s": jnp.asarray(rng.random((L, pxb, 2 * Hkv, bt), dtype="float32") * 0.02),
    }
    q = jnp.asarray(rng.standard_normal((T, Hkv, G, hd)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((T, Hkv, hd)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((T, Hkv, hd)), jnp.float32)
    kw = dict(
        scale=hd**-0.5, skey=0, block_q=16,
        block_tables=jnp.asarray(tbl) if paged else None,
        pool=pool if paged else None,
    )
    args = (q, ks, vs, ck, 1, rowids, offsets, slots, starts)
    ref = A.ragged_prefill_attend_q8(*args, impl="xla", **kw)
    out = A.ragged_prefill_attend_q8(*args, impl="kernel", interpret=True, **kw)
    assert float(jnp.max(jnp.abs(out[:total] - ref[:total]))) < 1e-4
    assert not bool(jnp.isnan(out).any())


@pytest.mark.parametrize("paged", [False, True])
def test_ragged_prefill_q8_reads_heads_abreast(paged):
    """Over a cache of four KV heads of 64 two to a row the ragged prefill's
    past rows are read through `fused_kv` by the XLA arm, whichever arm is asked
    for (the kernel walks the heads under a `fori_loop` and has no form of a
    head's lanes at a traced index): what it returns is what the same bytes a
    head a row give."""
    rng = np.random.default_rng(33)
    L, Hkv, G, hd, S, bt, B = 2, 4, 2, 64, 128, 32, 6
    R, T, total, rowids, offsets, slots, starts, tbl, nbs, pxb = _ragged_case(0.4, S, bt, B)
    ck, _ = _fused_q8_cache(rng, L, B, Hkv, S, hd, abreast=True)
    pool, _ = _fused_q8_cache(rng, L, pxb, Hkv, bt, hd, abreast=True)
    assert ck["q"].shape == (L, B, 5, S, 128) and pool["q"].shape == (L, pxb, 5, bt, 128)
    q = jnp.asarray(rng.standard_normal((T, Hkv, G, hd)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((T, Hkv, hd)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((T, Hkv, hd)), jnp.float32)

    def run(ck, pool, impl):
        return A.ragged_prefill_attend_q8(
            q, ks, vs, ck, 1, rowids, offsets, slots, starts, scale=hd**-0.5, skey=0,
            block_q=16, block_tables=jnp.asarray(tbl) if paged else None,
            pool=pool if paged else None, impl=impl, interpret=True)

    want = run(_heads_apart(ck), _heads_apart(pool), "xla")
    for impl in ("xla", "kernel"):
        got = run(ck, pool, impl)
        np.testing.assert_allclose(np.asarray(got[:total]), np.asarray(want[:total]), atol=1e-5)


@pytest.mark.parametrize(
    "quant", [pytest.param(False, marks=pytest.mark.slow), True, "eight_abreast"])
@pytest.mark.parametrize(
    "paged", [pytest.param(False, marks=pytest.mark.slow), True])
@pytest.mark.parametrize("fill", RAGGED_FILLS)
def test_ragged_prefill_mla_parity(fill, paged, quant):
    """One ragged MLA body covers bf16 and int8 latents (ones-scales when
    bf16); rope and per-token scales ride pre-gathered VMEM operands while
    the latent payload streams block-indirect. `eight_abreast`: the int8 rope
    keys as `init_mla_cache` lays them, eight positions of 16 lanes a row."""
    abreast = quant == "eight_abreast"
    rng = np.random.default_rng(33)
    L, S, bt, B, Rl, dr, H = 2, 128, 32, 6, 32, 16, 4
    R, T, total, rowids, offsets, slots, starts, tbl, nbs, pxb = _ragged_case(
        fill, S, bt, B
    )
    if quant:
        cc = {
            "q": jnp.asarray(rng.integers(-127, 128, (L, B, 1, S, Rl), dtype="int8")),
            "s": jnp.asarray(rng.random((L, B, 1, S), dtype="float32") * 0.02),
        }
        cr = {
            "q": jnp.asarray(rng.integers(-127, 128, (L, B, 1, S, dr), dtype="int8")),
            "s": jnp.asarray(rng.random((L, B, 1, S), dtype="float32") * 0.02),
        }
        pc = {
            "q": jnp.asarray(rng.integers(-127, 128, (L, pxb, 1, bt, Rl), dtype="int8")),
            "s": jnp.asarray(rng.random((L, pxb, 1, bt), dtype="float32") * 0.02),
        }
        pr = {
            "q": jnp.asarray(rng.integers(-127, 128, (L, pxb, 1, bt, dr), dtype="int8")),
            "s": jnp.asarray(rng.random((L, pxb, 1, bt), dtype="float32") * 0.02),
        }
    else:
        cc = jnp.asarray(rng.standard_normal((L, B, 1, S, Rl)), jnp.float32)
        cr = jnp.asarray(rng.standard_normal((L, B, 1, S, dr)), jnp.float32)
        pc = jnp.asarray(rng.standard_normal((L, pxb, 1, bt, Rl)), jnp.float32)
        pr = jnp.asarray(rng.standard_normal((L, pxb, 1, bt, dr)), jnp.float32)
    qt = jnp.asarray(rng.standard_normal((T, H, Rl)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((T, H, dr)), jnp.float32)
    cs = jnp.asarray(rng.standard_normal((T, Rl)), jnp.float32)
    krs = jnp.asarray(rng.standard_normal((T, dr)), jnp.float32)
    kw = dict(
        scale=(Rl + dr) ** -0.5, skey=0, block_q=16,
        block_tables=jnp.asarray(tbl) if paged else None,
        pool_c=pc if paged else None, pool_r=pr if paged else None,
    )
    args = (qt, qr, cs, krs, cc, cr, 1, rowids, offsets, slots, starts)
    ref = A.ragged_prefill_attend_mla(*args, impl="xla", **kw)
    if abreast:  # the same bytes, eight positions a row: what both arms read of them is the same
        cr = {"q": A.rope_abreast(cr["q"], A.positions_abreast(S, dr)), "s": cr["s"]}
        assert cr["q"].shape == (L, B, 1, S // 8, 128)
        args = (qt, qr, cs, krs, cc, cr, 1, rowids, offsets, slots, starts)
        np.testing.assert_array_equal(
            np.asarray(ref), np.asarray(A.ragged_prefill_attend_mla(*args, impl="xla", **kw)))
    out = A.ragged_prefill_attend_mla(
        *args, impl="kernel", interpret=True, **kw
    )
    assert float(jnp.max(jnp.abs(out[:total] - ref[:total]))) < 1e-4
    assert not bool(jnp.isnan(out).any())


# -- the guard ---------------------------------------------------------------

# Every Pallas kernel body in kernels/attention.py and the test that pins
# it against reference math. (module, test name) — the module string keeps
# cross-file coverage honest without importing test files into each other.
KERNEL_PARITY = {
    "_flash_prefill_kernel": ("tests/test_kernels.py", "test_flash_prefill_matches_reference"),
    "_decode_attn_kernel": ("tests/test_kernels.py", "test_decode_attention_matches_reference"),
    "_attend_q8_kernel": ("tests/test_kernel_parity.py", "test_q8_gqa_whole_parity"),
    "_attend_q8_blocked_kernel": ("tests/test_kernel_parity.py", "test_q8_gqa_blocked_parity"),
    "_block_attend_q8_kernel": ("tests/test_kernel_parity.py", "test_block_attend_q8_parity"),
    "_attend_bf16_kernel": ("tests/test_kernel_parity.py", "test_bf16_gqa_parity"),
    "_attend_bf16_blocked_kernel": ("tests/test_kernel_parity.py", "test_bf16_gqa_parity"),
    "_attend_q8_mla_kernel": ("tests/test_kernel_parity.py", "test_mla_whole_s_parity"),
    "_attend_q8_mla_blocked_kernel": ("tests/test_kernel_parity.py", "test_mla_blocked_parity"),
    "_append_q8_kernel": ("tests/test_kernel_parity.py", "test_append_q8_kernel_parity"),
    "_append_bf16_kernel": ("tests/test_kernel_parity.py", "test_append_bf16_kernel_parity"),
    "_attend_q8_paged_kernel": ("tests/test_kernel_parity.py", "test_q8_gqa_paged_parity"),
    "_attend_bf16_paged_kernel": ("tests/test_kernel_parity.py", "test_bf16_gqa_paged_parity"),
    "_attend_q8_mla_paged_kernel": ("tests/test_kernel_parity.py", "test_mla_paged_parity"),
    "_ragged_prefill_gqa_kernel": ("tests/test_kernel_parity.py", "test_ragged_prefill_q8_parity"),
    "_ragged_prefill_mla_kernel": ("tests/test_kernel_parity.py", "test_ragged_prefill_mla_parity"),
}


def test_every_pallas_kernel_has_parity_coverage():
    """Every `_*_kernel` function in kernels/attention.py must appear in
    KERNEL_PARITY with a test that actually exists. A new kernel without
    registered interpret-mode parity coverage fails here — the blocked q8
    kernel shipped with zero coverage once (VERDICT r2 weak #4) and this
    guard is what keeps that from recurring. The AST walk now lives in
    the registry-census pass (llm_mcp_tpu/analysis/census.py), which
    reads the KERNEL_PARITY dict above without importing this module."""
    import os

    from llm_mcp_tpu.analysis.census import RegistryCensusPass
    from llm_mcp_tpu.analysis.core import RepoIndex

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = RegistryCensusPass().run(RepoIndex(repo))
    parity = [
        f"{f.key}: {f.message}" for f in found
        if f.key.startswith(("kernel-", "parity-", "no-kernels"))
    ]
    assert not parity, parity
