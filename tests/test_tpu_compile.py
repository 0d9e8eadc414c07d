"""The main-path Pallas kernels meet the TPU's compiler, without a chip.

Interpret mode enforces none of Mosaic's rules: a kernel that passes every
interpret-mode parity test can still be refused on the chip (a width-changing
bitcast, a block whose last two dims are no legal tile, a lane slice at a
64-token offset, more VMEM than a kernel may use) or sit in the compiler for
minutes. libtpu is installed here and compiles for a chip that is *described*,
not attached, so each kernel is lowered with `interpret=False` at the widths
of Llama-3.1-8B (Hkv=8, G=4, hd=128, L=32, 32 slots, S=2048, 64-token blocks)
and compiled for one chip of a `v5e:2x2`. Nothing runs: this says the compiler
accepts the kernel, nothing about results (tests/test_kernel_parity.py) or
times (a chip run).

Rules this file keeps (guide `on-chip-measurement` §2): the topology is
described inside a module-scoped fixture, never at import; everything built
from it is built in a fixture or a test; all of it lives in this ONE file (a
process that loaded libtpu keeps its lock until it exits); the persistent
compile cache is off around the compiles, so they neither read the CPU tests'
entries nor leave entries no chip can load.
"""

import os

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import cell_programs
from llm_mcp_tpu.kernels import attention as A

L, B, HKV, G, HD, S, BT = 32, 32, 8, 4, 128, 2048, 64
BF, I8, I32 = jnp.bfloat16, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or its lock is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sd(one_chip):
    """ShapeDtypeStruct on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def compile_for_chip(fn, *args, **jit_kw) -> str:
    """Lower and compile for the described chip; the compiled module's text.
    The dispatchers read their mode knobs at trace time and are themselves
    jitted, so a stale trace from another mode must not answer."""
    jax.clear_caches()
    falls = dict(A.reference_falls)
    text = jax.jit(fn, **jit_kw).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled module"
    # a shape gate that fails with interpret=False answers with the XLA
    # reference math and counts it: THIS compile must not have
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    return text


def q8_cache(sd, n=B):
    return {"q": sd((L, n, 2 * HKV + 1, S, HD), I8), "s": sd((L, n, 2 * HKV, S), BF)}


def q8_pool(sd, rows=64):
    return {"q": sd((L, rows, 2 * HKV + 1, BT, HD), I8), "s": sd((L, rows, 2 * HKV, BT), BF)}


def decode_operands(sd, ba):
    return (sd((ba, HKV, G, HD), BF), sd((ba, HKV, HD), BF), sd((ba, HKV, HD), BF))


# [B, H, S, hd], KV heads, window (None: a traced scalar, as a scanned layer
# stack hands it over): the prompt attention as the admit programs of the
# benchmark's cells hold it (PERF.md section 4), and the smallest bucket
FLASH_PREFILL_SHAPES = {
    "llama_4x512": ((4, HKV * G, 512, HD), HKV, 0),
    "kexaone_1024_win": ((1, 64, 1024, 128), 8, 128),
    "kexaone_1024_global": ((1, 64, 1024, 128), 8, 0),
    "kexaone_768_win": ((1, 64, 768, 128), 8, 128),
    "kexaone_768_global": ((1, 64, 768, 128), 8, 0),
    "kexaone_1024_traced": ((1, 64, 1024, 128), 8, None),
    "granite_1024": ((1, 32, 1024, 64), 8, 0),
    "granite_768": ((1, 32, 768, 64), 8, 0),
    "olmo_hybrid_1024": ((1, 30, 1024, 128), 30, 0),
    "olmo_hybrid_768": ((1, 30, 768, 128), 30, 0),
    "olmo_hybrid_32": ((1, 30, 32, 128), 30, 0),
    "qwen3_64_traced": ((1, 32, 64, 128), 8, None),
}


@pytest.mark.parametrize("case", list(FLASH_PREFILL_SHAPES))
def test_flash_prefill_attention(sd, case):
    """The grouped, transposed-score cell at every group size, head size and
    bucket the cells give it, with the block its rule gives there: a Mosaic
    call under the name the trace readers look for, and no fall."""
    (b, h, s, hd), hkv, window = FLASH_PREFILL_SHAPES[case]
    traced = [sd((), I32)] if window is None else []
    text = compile_for_chip(
        lambda q, k, v, n, *w: A.flash_prefill_attention(
            q, k, v, n, window=w[0] if w else window, interpret=False),
        sd((b, h, s, hd), BF), sd((b, hkv, s, hd), BF), sd((b, hkv, s, hd), BF), sd((b,), I32),
        *traced)
    assert "flash_prefill_attn" in text


@pytest.mark.parametrize("mode", ["whole", "blocked", "auto"])
def test_decode_attend_q8(sd, monkeypatch, mode):
    """`auto` is the default and builds BOTH arms under one lax.cond: an arm
    the compiler refuses takes the default int8 decode step down with it (the
    packed-scale unpack's int8->bf16 bitcast did exactly that)."""
    monkeypatch.setenv("LLM_MCP_TPU_Q8_DECODE", mode)
    ba = 8  # the compact ladder's first rung
    compile_for_chip(
        lambda q, nk, nv, ck, li, n, ids: A.decode_attend_q8(
            q, nk, nv, ck, {}, li, n, slot_ids=ids, interpret=False),
        *decode_operands(sd, ba), q8_cache(sd), sd((), I32), sd((ba,), I32), sd((ba,), I32),
    )


# [rows, KV heads, group, head size], cache length, layers: the blocked arm as
# each generation cell of BENCHMARK.json runs it (PERF.md section 4); the heads
# of 64 lie two abreast in rows of 128 lanes (`kv_heads_abreast`)
CELL_SHAPES = {
    "decode_closed": ((32, 8, 4, HD), 2048, 36),
    "solar_decode_closed": ((64, 8, 8, HD), 1024, 1),
    "olmo_hybrid_decode_closed": ((64, 30, 1, HD), 1024, 5),
    "granite_decode_closed": ((64, 8, 4, 64), 1024, 4),
    "lfm2_decode_closed": ((64, 8, 4, 64), 1024, 3),
}


@pytest.mark.parametrize("mode", ["blocked", "auto"])
@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_decode_attend_q8_blocked_at_the_cells_shapes(sd, monkeypatch, cell, mode):
    """The batch-wide pipeline of the blocked arm at the shapes the benchmark
    runs it at, with the block size its rule gives there: a Mosaic call under
    the name the trace readers look for, and no fall. At heads of 64 the rows
    are two heads wide, P*hd = 128, and the arm's copies cut whole rows."""
    monkeypatch.setenv("LLM_MCP_TPU_Q8_DECODE", mode)
    (ba, hkv, g, hd), seq, layers = CELL_SHAPES[cell]
    P = A.kv_heads_abreast(hkv, hd)
    assert P * hd == 128
    cache = {"q": sd((layers, ba, 2 * hkv // P + 1, seq, P * hd), I8),
             "s": sd((layers, ba, 2 * hkv, seq), BF)}
    assert A.fused_q8_heads(cache) == (hkv, 1, P)
    assert A.q8_block_tokens(cache["q"].shape[2], seq, P * hd) == (128 if hkv == 30 else 256)
    text = compile_for_chip(
        lambda q, nk, nv, ck, li, n, ids: A.decode_attend_q8(
            q, nk, nv, ck, {}, li, n, slot_ids=ids, interpret=False),
        sd((ba, hkv, g, hd), BF), sd((ba, hkv, hd), BF), sd((ba, hkv, hd), BF),
        cache, sd((), I32), sd((ba,), I32), sd((ba,), I32),
    )
    assert "decode_attn_q8_blocked" in text


def test_decode_attend_q8_paged(sd, monkeypatch):
    """The engine's default: physical paging on, so the decode step holds the
    contiguous hybrid AND the block-indirect arm under the identity cond."""
    monkeypatch.delenv("LLM_MCP_TPU_Q8_DECODE", raising=False)
    ba = 32
    compile_for_chip(
        lambda q, nk, nv, ck, li, n, ids, tbl, pool: A.decode_attend_q8(
            q, nk, nv, ck, {}, li, n, slot_ids=ids, block_tables=tbl, pool_k=pool,
            interpret=False),
        *decode_operands(sd, ba), q8_cache(sd), sd((), I32), sd((ba,), I32), sd((ba,), I32),
        sd((B, S // BT), I32), q8_pool(sd),
    )


def test_append_kv_q8(sd):
    ba = 8
    compile_for_chip(
        lambda ck, nk, nv, n, ids: A.append_kv_q8(ck, {}, nk, nv, n, slot_ids=ids, interpret=False),
        q8_cache(sd), sd((L, ba, HKV, HD), BF), sd((L, ba, HKV, HD), BF),
        sd((ba,), I32), sd((ba,), I32), donate_argnums=(0,),
    )


@pytest.mark.parametrize("T", [32, 2048])
def test_ragged_prefill_attend_q8(sd, T):
    """Both ends of the packed-length ladder, paged (64-token blocks), R = the
    default admit batch. Compile time must not grow with T or R: the first
    form of this kernel took 18 s at T=32 and never finished at T=512."""
    R = 4
    compile_for_chip(
        lambda q, ks, vs, ck, li, rid, off, sl, st, tbl, pool: A.ragged_prefill_attend_q8(
            q, ks, vs, ck, li, rid, off, sl, st, block_tables=tbl, pool=pool,
            impl="kernel", interpret=False),
        sd((T, HKV, G, HD), BF), sd((T, HKV, HD), BF), sd((T, HKV, HD), BF), q8_cache(sd),
        sd((), I32), sd((T,), I32), sd((R + 1,), I32), sd((R,), I32), sd((R,), I32),
        sd((B, S // BT), I32), q8_pool(sd),
    )


def test_ragged_prefill_attend_bf16(sd):
    T, R, n = 512, 4, 8
    kv = sd((L, n, HKV, S, HD), BF)
    pool = sd((L, 16, HKV, BT, HD), BF)
    compile_for_chip(
        lambda q, ks, vs, ck, cv, li, rid, off, sl, st, tbl, pk, pv: A.ragged_prefill_attend_bf16(
            q, ks, vs, ck, cv, li, rid, off, sl, st, block_tables=tbl, pool_k=pk,
            pool_v=pv, impl="kernel", interpret=False),
        sd((T, HKV, G, HD), BF), sd((T, HKV, HD), BF), sd((T, HKV, HD), BF), kv, kv,
        sd((), I32), sd((T,), I32), sd((R + 1,), I32), sd((R,), I32), sd((R,), I32),
        sd((n, S // BT), I32), pool, pool,
    )


def test_decode_attend_bf16(sd, monkeypatch):
    monkeypatch.delenv("LLM_MCP_TPU_Q8_DECODE", raising=False)
    ba, n = 8, 8  # a bf16 cache at these widths holds fewer slots
    kv = sd((L, n, HKV, S, HD), BF)
    compile_for_chip(
        lambda q, nk, nv, ck, cv, li, m, ids: A.decode_attend_bf16(
            q, nk, nv, ck, cv, li, m, slot_ids=ids, interpret=False),
        *decode_operands(sd, ba), kv, kv, sd((), I32), sd((ba,), I32), sd((ba,), I32),
    )


def test_append_kv_bf16(sd):
    ba, n = 8, 8
    kv = sd((L, n, HKV, S, HD), BF)
    compile_for_chip(
        lambda ck, cv, nk, nv, m, ids: A.append_kv_bf16(ck, cv, nk, nv, m, slot_ids=ids, interpret=False),
        kv, kv, sd((L, ba, HKV, HD), BF), sd((L, ba, HKV, HD), BF),
        sd((ba,), I32), sd((ba,), I32), donate_argnums=(0, 1),
    )


def test_decode_attend_q8_mla(sd, monkeypatch):
    """Latent attention at DeepSeek-V2-Lite's widths (kv_lora_rank 512, rope
    64, 16 heads), default mode."""
    monkeypatch.delenv("LLM_MCP_TPU_Q8_DECODE", raising=False)
    ba, n, H, R, dr, Lm = 8, 32, 16, 512, 64, 27
    cc = {"q": sd((Lm, n, 1, S, R), I8), "s": sd((Lm, n, 1, S), BF)}
    cr = {"q": sd((Lm, n, 1, S, dr), I8), "s": sd((Lm, n, 1, S), BF)}
    compile_for_chip(
        lambda qt, qr, nc, nr, cc, cr, li, m, ids: A.decode_attend_q8_mla(
            qt, qr, nc, nr, cc, cr, li, m, slot_ids=ids, scale=(128 + dr) ** -0.5,
            interpret=False),
        sd((ba, H, R), BF), sd((ba, H, dr), BF), sd((ba, R), BF), sd((ba, dr), BF),
        cc, cr, sd((), I32), sd((ba,), I32), sd((ba,), I32),
    )


def test_ragged_prefill_attend_mla(sd):
    """Ragged latent-attention prefill, int8 latents, paged, V2-Lite widths."""
    T, Rn, n, H, R, dr, Lm = 512, 4, 32, 16, 512, 64, 27
    cc = {"q": sd((Lm, n, 1, S, R), I8), "s": sd((Lm, n, 1, S), BF)}
    cr = {"q": sd((Lm, n, 1, S, dr), I8), "s": sd((Lm, n, 1, S), BF)}
    pc = {"q": sd((Lm, 16, 1, BT, R), I8), "s": sd((Lm, 16, 1, BT), BF)}
    pr = {"q": sd((Lm, 16, 1, BT, dr), I8), "s": sd((Lm, 16, 1, BT), BF)}
    compile_for_chip(
        lambda qt, qr, c, kr, cc, cr, li, rid, off, sl, st, tbl, pc, pr:
        A.ragged_prefill_attend_mla(
            qt, qr, c, kr, cc, cr, li, rid, off, sl, st, scale=(128 + dr) ** -0.5,
            block_tables=tbl, pool_c=pc, pool_r=pr, impl="kernel", interpret=False),
        sd((T, H, R), BF), sd((T, H, dr), BF), sd((T, R), BF), sd((T, dr), BF), cc, cr,
        sd((), I32), sd((T,), I32), sd((Rn + 1,), I32), sd((Rn,), I32), sd((Rn,), I32),
        sd((n, S // BT), I32), pc, pr,
    )


@pytest.fixture
def chip_kernels(monkeypatch):
    """The program's one platform question answered "tpu", so its dispatchers
    take the kernels with interpret mode off. They are jitted and ask at trace
    time: no trace may cross this fixture's edges."""
    from llm_mcp_tpu.utils import platform

    monkeypatch.setattr(platform, "device_platform", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


# -- the hybrid decoder's step programs at the published Solar-Open2 widths ---------

SOLAR_SLOTS, SOLAR_S = 64, 1024


@pytest.mark.parametrize("name,H,dk,dv,head_decay", [
    ("kda_decode_step", 64, 128, 128, False),  # Solar-Open2: a decay a key channel
    ("gdn_decode_step", 30, 96, 192, True),  # Olmo-Hybrid: one a head, two heads abreast
], ids=["kda_64x128x128", "gdn_30x96x192"])
def test_kda_decode_step(sd, name, H, dk, dv, head_decay):
    """The one-step state kernel alone: 64 rows, three layers in the pool, rows
    found through the slot ids; 64 heads of [128, 128] float32, and 30 heads
    of [96, 192] in a pool of 15 x [96, 384], whose bytes as the compiler lays
    it out are its logical bytes (a [.., 96, 192] pool would pad to 256)."""
    import functools

    from llm_mcp_tpu.kernels.kda import heads_abreast, kda_decode_step

    F32, rows, P = jnp.float32, 64, heads_abreast(H, dv)
    pool = sd((3, SOLAR_SLOTS, H // P, dk, P * dv), F32)
    keys = sd((rows, H, dk), F32)
    text = compile_for_chip(
        functools.partial(kda_decode_step, name=name, interpret=False), pool, sd((), I32),
        sd((rows,), I32), sd((rows,), jnp.bool_), keys, keys, sd((rows, H, dv), F32),
        sd((rows, H), F32) if head_decay else keys, sd((rows, H), F32), donate_argnums=(0,))
    assert name in text
    laid_out = jax.jit(lambda s: s + 1.0).lower(pool).compile().memory_analysis()
    assert laid_out.argument_size_in_bytes == 3 * SOLAR_SLOTS * H * dk * dv * 4


@pytest.mark.parametrize("rows,T,packed", [(1, 256, True), (4, 128, False)],
                         ids=["a_mixed_rounds_row", "an_admit_programs_rows"])
@pytest.mark.parametrize("name,H,dk,dv,one_group", [
    ("ssd_chunk_scan", 64, 128, 64, True),  # Granite-4.0-H: no delta rule, B and C one group
    ("gdn_chunk_scan", 30, 96, 192, False),  # Olmo-Hybrid: the delta rule, q and k a head each
], ids=["ssd_64x128x64", "gdn_30x96x192"])
def test_chunk_scan(sd, name, H, dk, dv, one_group, rows, T, packed):
    """The chunk kernel alone at the two configurations' widths: a mixed
    round's one row of 256 packed positions from zero state, a state by chunk
    out, and an admit program's four rows of 128 from their states before; as
    a Mosaic call with no fall, and for Granite with no [.., 64, 128] copy of
    the one group's keys a head."""
    import functools

    from llm_mcp_tpu.kernels.kda import chunk_scan, heads_abreast

    F32, N, P = jnp.float32, T // 32, heads_abreast(H, dv)
    qk = sd((rows, T, 1 if one_group else H, dk), F32)
    text = compile_for_chip(
        functools.partial(chunk_scan, chunk=32, rows=rows * N if packed else rows, name=name,
                          interpret=False),
        qk, qk, sd((rows, T, H, dv), F32), sd((rows, T, H), F32),
        None if name.startswith("ssd") else sd((rows, T, H), F32),
        None if packed else sd((rows, H // P, dk, P * dv), F32),
        sd((N,), jnp.bool_), sd((), I32), sd((rows, N), I32))
    assert name in text
    if one_group:
        assert f"f32[{rows},{T},{H},{dk}]" not in text and f",{H},32,{dk}]" not in text


def cache_relayouts(text: str, cache_q_shape) -> list[str]:
    """The compiled module's lines that COPY an int8 array of the KV cache's
    size: a `copy` to another layout, or the `remat_compressed` /
    `_uncompressed` pair the compiler makes of one to save memory. (An update
    in place, `dynamic-update-slice` or a fusion of one, has the cache's shape
    too and is no copy.) A cache whose minor dimension was a head of 64 was
    laid out with positions minor and copied to the kernels' layout and back in
    every step program (PERF.md section 6, PR 55)."""
    import re

    made = re.compile(r"= s8\[" + ",".join(map(str, cache_q_shape)) + r"\]\{[^}]*\} copy\(")
    return [line.strip()[:160] for line in text.splitlines() if made.search(line)]


def grouped_kernels_in(text: str) -> bool:
    """Both of `kernels/grouped.py`'s calls, and no product over all the pairs."""
    assert "ragged-dot" not in text
    return "%grouped_swiglu" in text and "%grouped_down" in text


def test_a_fall_to_the_reference_is_counted(tmp_path):
    """What `compile_for_chip` and chip_smoke.py's zero-fall check stand on: a
    shape gate that fails with interpret=False is counted and lands in the
    flight recorder; interpret mode, which takes the exact math by design, is
    not. hd=32 is no row the append kernel can store, so append_kv_q8 takes its scatter. The
    fall is made on purpose, into a recorder of the test's own: the process's
    ring keeps no `kernel_fall` for chip_smoke's check to find when one worker
    runs both files."""
    from llm_mcp_tpu.telemetry import recorder as flight

    n, hd = 4, 32
    ck = {"q": jax.ShapeDtypeStruct((2, n, 2 * HKV + 1, 128, hd), I8),
          "s": jax.ShapeDtypeStruct((2, n, 2 * HKV, 128), BF)}
    new = jax.ShapeDtypeStruct((2, n, HKV, hd), BF)
    lens = jax.ShapeDtypeStruct((n,), I32)

    def trace(interpret):
        jax.eval_shape(
            lambda ck, nk, nv, n: A.append_kv_q8(ck, {}, nk, nv, n, interpret=interpret),
            ck, new, new, lens)

    falls = dict(A.reference_falls)
    own = flight.FlightRecorder(capacity=64, dump_dir=str(tmp_path))
    before = len(flight.get_recorder().snapshot(etype="kernel_fall"))  # (makes the process's ring, if none was)
    prev = flight.set_recorder(own)
    try:
        trace(True)
        assert A.reference_falls == falls
        trace(False)
        assert A.reference_falls == {**falls, "append_kv_q8": falls.get("append_kv_q8", 0) + 1}
        assert [e["fields"]["kernel"] for e in own.snapshot(etype="kernel_fall")] == ["append_kv_q8"]
    finally:  # table and ring are the process's: leave them as the other tests expect them
        flight.set_recorder(prev)
        A.reference_falls.clear()
        A.reference_falls.update(falls)
    assert len(flight.get_recorder().snapshot(etype="kernel_fall")) == before


# -- every cell's step programs, whole, at the published widths ---------------------
#
# The programs and their operand shapes are tests/cell_programs.py's rows; what
# each cell's programs are held to is a row of HELD beside them. A rule says in
# which of a cell's programs a kernel's name must stand in the compiled text.

GiB = 2**30


def only(*programs):
    return lambda which, found: found == (which in programs)


def within(*programs):  # there at least (nothing is said of the others)
    return lambda which, found: found or which not in programs


def absent_in(*programs):
    return lambda which, found: not found or which not in programs


def everywhere(which, found):
    return found


def nowhere(which, found):
    return not found


def nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _rehearse():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "rehearse_tpu_compile",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "rehearse_tpu_compile.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def qwen3_also(cell, which, operands, cfg, params, cache, text, mem):
    """Qwen3-8B, `decode_closed`'s widths. The decode round (int8 weights + int8
    KV, `_decode_step_q8`, the whole model; bf16 weights + bf16 KV,
    `_decode_step_bf16`, 16 layers and 8 slots so that bf16 fits the chip; the
    XLA-attention scan of `llama_decode_step` that windows, softcaps and meshes
    take): no loop body may make a weight-sized array in HBM (with `unroll=4` on
    the layer scans each round copied every group of four layers' weights out of
    the stacked tree: six instructions, 0.74 GiB of temporaries, more than half
    of a round's device time on the chip). What the bf16 program still does,
    ONCE a round and outside both loops: the compiler re-lays out the whole `wq`
    and `wk` stacks for the slices it prefetches into on-chip memory (PERF.md
    section 7); the case holds it to that. The mixed round (`mixed_step_q8`, then 3
    plain steps, 4 prompt rows, one rung of the packed buffer): the cache is
    updated in place (the prompts' rows go in through `write_prompt_rows` after
    the decode rows' append), no loop body copies weights, and the program stays
    inside what the cell has left: its peak is 13.77 GB of the chip's 15.75 GiB
    (PERF.md section 4), the plain round compiles to 12.74 GiB and the ragged
    chunk programs to 13.34."""
    rehearse = _rehearse()
    made = rehearse.stacked_weight_producers(text, rehearse.stacked_weight_dims(params["layers"]))
    if which == "mixed":
        assert made == [], "the mixed round copies weights"
        # the cache and nothing weight-sized beside it in temporaries; the whole
        # under the 13.34 GiB of the ragged programs the cell already holds
        assert mem.temp_size_in_bytes < (512 << 20), mem.temp_size_in_bytes
        total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert total < int(13.34 * GiB), total
        return
    assert [m for m in made if m[0] != "ENTRY"] == [], "a loop body copies weights"
    whole = [params["layers"][k] for k in (("wq", "wk") if cell == "qwen3_bf16" else ())]
    assert sorted(made) == sorted(
        ("ENTRY", "copy", f"bf16[{','.join(map(str, w.shape))}]") for w in whole)
    assert mem.temp_size_in_bytes < (256 << 20) + sum(w.size * w.dtype.itemsize for w in whole)


def olmo_also(cell, which, operands, cfg, params, cache, text, mem):
    """The argument bytes hold the pool at its logical size: nothing pads, the
    pool (15 x [96, 384]: 30 heads of [96, 192] two abreast) least of all."""
    from llm_mcp_tpu.models import kda

    assert cache["v"]["state"]["S"].shape == (15, 64, 15, 96, 384) and kda.state_abreast(cfg) == 2
    if which != "mixed":
        logical = nbytes((params, cache)) + sum(int(np.prod(shape)) * 4 for shape in operands)
        assert mem.argument_size_in_bytes < logical * 1.002


def granite_also(cell, which, operands, cfg, params, cache, text, mem):
    """The pool's [36, 64, 32, 128, 128] (two heads of 64 values abreast), whose
    bytes as the compiler lays it out are its logical bytes; the cache's heads
    of 64 two abreast in rows of 128 lanes."""
    from llm_mcp_tpu.models import ssm

    S = cache["v"]["state"]["S"]
    assert S.shape == (36, 64, 32, 128, 128) and ssm.state_abreast(cfg) == 2
    assert cache["k"]["q"].shape == (4, 64, 9, 1024, 128)
    if which != "mixed":
        pool = jax.jit(lambda s: s + 1.0).lower(S).compile().memory_analysis()
        assert pool.argument_size_in_bytes == 36 * 64 * 64 * 128 * 64 * 4


def kexaone_also(cell, which, operands, cfg, params, cache, text, mem):
    """Window layers hold a ring of 128 positions a slot and not 4096: the
    arguments are the weights, 0.58 GB of the global layer's cache and 0.07 GB
    of rings."""
    ring, full = cache["v"]["win"]["k"], cache["k"]
    assert full["q"].shape == (1, 64, 17, 4096, 128) and ring["q"].shape == (4, 64, 17, 128, 128)
    assert ring["s"].shape == (4, 64, 16, 128) and cache["v"]["win"]["v"] == {}
    assert cache["v"]["moe"].shape == (2, 4, 5) and "state" not in cache["v"]
    if which == "decode":
        assert text.count("append_kv_q8") >= 2  # the cache, the rings
    caches, weights = nbytes(cache), nbytes(params)
    assert round(weights / 1e9, 2) == 7.42 and 0.64e9 < caches < 0.66e9
    assert mem.argument_size_in_bytes < weights + caches + 2**20  # rings, not 4 x 4096 positions


def lfm2_also(cell, which, operands, cfg, params, cache, text, mem):
    """The tails (5.5 MiB: a pool with no matrix state), the cache's heads of 64
    WITH rotation two abreast, all 32 experts of 2048 x 1792 a layer, the two
    leading dense layers unstacked."""
    state = cache["v"]["state"]
    assert set(state) == {"conv"} and state["conv"].shape == (11, 64, 2 * 2048)
    assert cache["k"]["q"].shape == (3, 64, 9, 1024, 128)
    assert params["layers"]["w1e"].shape == (12, 32, 2048, 1792) and len(params["first"]) == 2
    # bfloat16 but for the twelve selection biases [32], float32
    assert nbytes(params) == 2 * cfg.param_count() + 2 * 12 * 32 == 9_334_155_520
    assert nbytes(state) == 64 * 90_112


def joyai_also(cell, which, operands, cfg, params, cache, text, mem):
    """The latent pair: the rope keys two positions abreast in rows of the 128
    lanes (`positions_abreast`); 580 bytes a position and layer."""
    assert cache["k"]["q"].shape == (40, 64, 1, 1024, 512) and cache["v"]["v"]["q"].shape == (40, 64, 1, 512, 128)
    assert cache["v"]["moe"].shape == (2, 39, 5)
    assert cache_relayouts(text, cache["v"]["v"]["q"].shape) == []  # nor of the other member
    assert params["layers"]["w1e"].shape == (39, 16, 2048, 768) and params["dense_layers"]["w1"].shape == (1, 2048, 7168)
    # bfloat16 but for the 39 selection biases [256], float32
    assert nbytes(params) == 2 * cfg.param_count() + 2 * 39 * 256 == 9_553_062_912
    assert nbytes(cache) - cache["v"]["moe"].size * 4 == 40 * 64 * 1024 * (512 + 64 + 4) == 1_520_435_200
    if which == "decode":
        assert "bf16[39,1536,6144]" not in "".join(
            line for line in text.splitlines() if " copy(" in line)  # `w_uq` read in place


def sdar_also(cell, which, operands, cfg, params, cache, text, mem):
    """The fused int8 cache of 48 layers and the counts beside it; a block
    round's passes read it through `block_attn_q8`, in order and by slot id."""
    if which == "block":
        # every pass's attention is the kernel's: the denoising loop's and the commit's
        assert text.count("custom_call_target=\"tpu_custom_call\"") >= 6
        cut = [ln.strip()[:160] for ln in text.splitlines()
               if " dynamic-slice(" in ln and re.search(r"= s8\[(1,)?\d+,[89],1024,128\]", ln)]
        assert cut == [], cut  # no slice of a layer's payload cut out of the stack (67 MB a layer and pass before PR 60)
    assert cache["k"]["q"].shape == (48, 64, 9, 1024, 128) and cache["v"]["v"] == {}
    assert cache["v"]["moe"].shape == (2, 48, 5)
    # nor a copy of the plain scales beside the payload: cut out of the stack a
    # layer's worth at a time, all 48 layers' were re-laid every layer of every
    # pass (120 ms of a 276 ms round on the chip; PERF.md section 6, PR 59)
    assert [ln for ln in text.splitlines() if "= bf16[48,64,8,1024]{" in ln and " copy(" in ln] == []
    assert params["layers"]["w1e"].shape == (48, 16, 2048, 768)
    assert nbytes(params) == 2 * cfg.param_count() == 10_329_944_064
    assert nbytes(cache) - cache["v"]["moe"].size * 4 == 48 * 64 * 1024 * (9 * 128 + 8 * 2) == 3_674_210_304


def _pool_and_kv(cache) -> int:
    return nbytes(cache["v"]["state"]) + nbytes(cache["k"])


_STATE_KERNELS = [f"%{k}_{form}" for k in ("kda", "gdn", "ssd") for form in ("decode_step", "chunk_scan")]
_QWEN3 = dict(kernels={"tpu_custom_call": everywhere}, also=qwen3_also)

# cell: `kernels` {name in the text: rule}; `grouped`: the rule for both of
# kernels/grouped.py's calls (and no product over all the pairs); `total` and
# `temps` [GiB] by program ("": the others); `alias` [bytes]: what at least is
# updated in place; `in_place`: the programs whose text copies no int8 array of
# the cache's size; `pool_above_temps`: where the state pool (0.78, 2.04 and 4.56
# GiB) is larger than all the temporaries the program may hold; `also`: what only this cell states. GiB in PERF.md section 4
# as "described-chip compile".
HELD = {
    "qwen3": _QWEN3, "qwen3_bf16": _QWEN3,
    "qwen3_xla_attention": dict(kernels={"tpu_custom_call": nowhere}, also=qwen3_also),
    # the state kernel and the attention kernels in decode, the two grouped expert
    # kernels in every program since PR 45 (the decode round's 64 rows through a
    # window of one row tile); no second copy of the state pool (0.75 GiB) nor of
    # an expert bank (a slice of a stacked bank that feeds a grouped product was
    # copied out, 0.39 GiB a bank and layer, until the banks went in whole:
    # models/moe.py): the decode round's temporaries are 0.09 GiB. The mixed
    # round's prompts' recurrence, a decay a key channel, stays the loop of
    # `jax.numpy` (models/kda.py); its 64 + rung rows go through the expert kernels
    "solar": dict(
        kernels={"kda_decode_step": only("decode", "mixed"), "%kda_decode_step": within("mixed"),
                 "decode_attn_q8": within("mixed"), "append_kv_q8": within("mixed"),
                 "chunk_scan": absent_in("mixed")},
        grouped=everywhere, total={"": 15.75}, temps={"decode": 0.21, "mixed": 0.25, "": 0.7},
        alias=lambda which, cache: 0.99 * _pool_and_kv(cache) if which == "mixed" else 0.9 * GiB,
        in_place=("mixed",), pool_above_temps=("mixed",)),
    # `gdn_decode_step` and the decode attention and append kernels at 30 KV heads,
    # group 1; the flash prefill kernel in the admit program; the chunked recurrence
    # of a prompt is the chunk kernel, one Mosaic call a layer (the bucketed chunk's
    # attention over [past | self] is `jax.numpy` for every configuration,
    # llama._chunk_attention: PERF.md section 7). Each under the 15.0 GiB at which
    # ISSUE 35 would have taken 48 slots, the 2.38 GiB KV cache and the 2.04 GiB
    # state pool in place; the mixed round copies neither pool nor cache (2.42 GiB)
    "olmo": dict(
        kernels={"%gdn_chunk_scan": only("admit", "chunk", "mixed"), "%ssd_chunk_scan": nowhere,
                 "%gdn_decode_step": only("decode", "mixed"), "%kda_decode_step": nowhere,
                 "decode_attn_q8": within("decode", "mixed"), "append_kv_q8": within("decode", "mixed"),
                 "flash_prefill_attn": within("admit")},
        grouped=absent_in("mixed"), total={"": 15.0}, temps={"mixed": 0.35},
        alias=lambda which, cache: 0.99 * _pool_and_kv(cache) if which == "mixed" else 4.3 * GiB,
        in_place=("mixed",), pool_above_temps=("mixed",), also=olmo_also),
    # `ssd_decode_step`, the decode attention (both arms under the dispatcher's
    # `cond`: heads of 64 two abreast, which the blocked arm's copies cut) and the
    # append kernel on those rows; no program copies the cache to another layout
    # (at [4, 64, 17, 1024, 64] every one did, 0.27 GiB there and 0.27 back: the
    # decode round's temporaries were 0.56 GiB, 0.05 now). The 4.5 GiB state pool in
    # place; carried with the pool's own last two axes, the mixed round's prompt
    # states made the compiler re-lay the whole pool out (their states ride the scan)
    "granite": dict(
        kernels={"%ssd_chunk_scan": only("admit", "chunk", "mixed"), "%gdn_chunk_scan": nowhere,
                 "%ssd_decode_step": only("decode", "mixed"), "%kda_decode_step": nowhere,
                 "%gdn_decode_step": nowhere, "decode_attn_q8_whole": within("decode"),
                 "decode_attn_q8_blocked": within("decode"), "decode_attn_q8": within("mixed"),
                 "append_kv_q8": within("decode", "mixed"), "flash_prefill_attn": within("admit")},
        grouped=absent_in("mixed"), total={"": 15.0}, temps={"decode": 0.15, "mixed": 0.75},
        alias=lambda which, cache: 0.99 * _pool_and_kv(cache) if which == "mixed" else 4.5 * GiB,
        in_place=("decode", "admit", "chunk", "mixed"), pool_above_temps=("mixed",), also=granite_also),
    # the decode attention in BOTH arms (blocked or whole-S over the global layer's
    # cache, the window arm over the rings), the append kernel twice; every program's
    # expert layers are the grouped kernels over a window of the pairs held here
    # (one row tile for the decode round's 64 rows since PR 45; 1,280 rows of the
    # 8,192 a 1,024-row prompt has: `moe.window_rows`); the admit programs'
    # temporaries under 0.55 GiB (the sorted copies of all 8,192 pairs' rows stood
    # at 0.72 and 0.64, PR 43's tree; 0.41 and 0.31 now); the chunk's 0.99 GiB are
    # its attention over [past | self]. Both kinds of cache in place: no copy of a
    # whole cache member among the temporaries
    "kexaone": dict(
        kernels={"decode_attn_win_q8": within("decode"), "decode_attn_q8_blocked": within("decode"),
                 "flash_prefill_attn": within("admit"), "decode_attn": absent_in("admit")},
        grouped=everywhere, total={"": 12.0}, temps={"decode": 0.7, "admit": 0.55, "chunk": 1.1},
        alias=lambda which, cache: 0.99 * nbytes(cache), also=kexaone_also),
    # the two grouped expert kernels at banks of [2048, 1792] (one column block of
    # two banks, 14.7 MB) and [1792, 2048]; no state kernel: no matrix state. The
    # temporaries hold no copy of a layer's banks (0.66 GiB a layer; the stack goes
    # in whole) nor of a leading layer's feed-forward (84 MB, unstacked: a slice of
    # a stack at a fixed index was copied out every step): a decode or mixed
    # round's are 0.02 and 0.05 GiB (0.42 and 0.45 with the cache re-laid and back)
    "lfm2": dict(
        kernels={**{k: nowhere for k in _STATE_KERNELS},
                 "decode_attn_q8_whole": within("decode", "mixed"),
                 "decode_attn_q8_blocked": within("decode", "mixed"),
                 "append_kv_q8": within("decode", "mixed"), "flash_prefill_attn": within("admit")},
        grouped=everywhere, total={"": 12.0}, temps={"decode": 0.15, "mixed": 0.15, "": 0.6},
        alias=lambda which, cache: 0.99 * _pool_and_kv(cache),
        in_place=("decode", "admit", "chunk", "mixed"), also=lfm2_also),
    # the MLA step programs' first compile for the chip (ROADMAP B2, debt (d)): the
    # latent decode attention as the whole-S arm (1024 positions fit its VMEM
    # budget), `ragged_prefill_attn_mla` in the packed chunk; beside 9.55 GB of
    # weights and the 1.52 GB latent cache. No copy of a layer's banks (0.14 GiB a
    # layer and step would be 5.5 GiB a round) nor of `w_uq` (0.69 GiB until its
    # columns were `[H dn | H dr]`), nor of the leading dense layer's feed-forward (a
    # stack of ONE layer scanned once: sliced in place). What the decode round still
    # holds, every ROUND: `w_ukv` transposed whole for the absorbed products (0.30)
    # and `w_dkv` (0.09): ROADMAP B2. Neither member of the pair copied or re-laid
    # (until PR 58 the rope keys' rows of 64 lanes were re-laid four times a round)
    "joyai": dict(
        kernels={"decode_attn_mla_q8_whole": only("decode"), "decode_attn_mla_q8_blocked": nowhere,
                 "decode_attn_mla_q8_paged": nowhere, "ragged_prefill_attn_mla": only("ragged")},
        grouped=everywhere, total={"": 14.5}, temps={"decode": 0.5, "": 1.0},
        alias=lambda which, cache: 0.99 * (nbytes(cache) - cache["v"]["moe"].size * 4),
        in_place=("decode", "admit", "chunk", "ragged"), also=joyai_also),
    # beside 10.33 GB of weights and the 3.67 GB fused int8 cache, which a denoising
    # pass does not even carry; the commit's writes after the kernel's read leave its
    # layout alone (written as updates of `[9, 4, 128]` they made the compiler re-lay
    # all 48 layers heads-minor, 6 GB, and back, every layer). No copy of a layer's
    # banks: 0.14 GiB a layer would be 6.75 GiB a pass. (The block round's 1.24 GiB:
    # the compiler transposes the wq / wk / wv STACKS once a round, 0.95 GiB outside
    # the loops, where it transposed a layer's slice inside them every layer of
    # every pass before)
    "sdar": dict(
        kernels={"flash_prefill_attn": only("admit"), "block_attn_q8": only("block")},
        grouped=everywhere, total={"": 15.0}, temps={"block": 1.5, "": 1.0},
        in_place=("block", "admit", "chunk"), also=sdar_also),
}


def test_every_cell_of_the_table_is_held_to_something():
    assert set(HELD) == set(cell_programs.CELLS)
    assert len({cell_programs.row_id(*row) for row in cell_programs.ROWS}) == len(cell_programs.ROWS)


@pytest.mark.parametrize("cell,which,operands", cell_programs.ROWS,
                         ids=[cell_programs.row_id(*row) for row in cell_programs.ROWS])
def test_a_cells_step_program_compiles_for_the_chip_inside_what_the_cell_has(
    one_chip, chip_kernels, cell, which, operands
):
    """A row of tests/cell_programs.py (the decode or block round, an admit, a
    chunk or a mixed program of a benchmark configuration at its published
    widths and its cell's slots and length) compiles for the described v5e from
    shapes alone, with its kernels as Mosaic calls and no fall to their
    reference, fits the chip inside the cell's limit, updates its cache (and
    state pool, rings or tails) in place and copies none of them to another
    layout, and holds no weight-sized temporary: HELD's row for the cell."""
    held = HELD[cell]
    cfg, params, cache = cell_programs.shapes(cell, one_chip)
    falls = dict(A.reference_falls)
    compiled = cell_programs.traced(cell, which, operands, one_chip).lower().compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text, mem = compiled.as_text(), compiled.memory_analysis()
    for name, rule in held["kernels"].items():
        assert rule(which, name in text), (name, which)
    if "grouped" in held:
        assert held["grouped"](which, grouped_kernels_in(text))
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{cell_programs.row_id(cell, which, operands)}: {total / GiB:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB, arguments {mem.argument_size_in_bytes / GiB:.2f} GiB "
          f"(weights {nbytes(params) / GiB:.2f}, caches {nbytes(cache) / GiB:.2f})")
    if "total" in held:
        assert total < held["total"].get(which, held["total"][""]) * GiB
    temps = held.get("temps", {})
    if which in temps or "" in temps:
        assert mem.temp_size_in_bytes < temps.get(which, temps.get("")) * GiB
    if which in held.get("pool_above_temps", ()):  # the pool is larger than all the temporaries together
        assert temps[which] * GiB < nbytes(cache["v"]["state"])
    if "alias" in held:
        assert mem.alias_size_in_bytes > held["alias"](which, cache)
    if which in held.get("in_place", ()):
        assert cache_relayouts(text, cache["k"]["q"].shape) == []
    if "also" in held:
        held["also"](cell, which, operands, cfg, params, cache, text, mem)
