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


@pytest.mark.parametrize(
    "weights, attn, n_layers, slots, relaid",
    [("int8", "pallas", 36, 32, ()), ("bf16", "pallas", 16, 8, ("wq", "wk")),
     ("int8", "xla", 36, 32, ())],
    ids=["int8", "bf16", "int8_xla_attention"],
)
def test_decode_step_reads_stacked_weights_in_place(
    one_chip, chip_kernels, weights, attn, n_layers, slots, relaid
):
    """The decode round at Qwen3-8B widths, from shapes alone: 4 steps in a
    scan as the engine's `decode_body` has them, int8 weights + int8 KV
    (`_decode_step_q8`, the whole model), bf16 weights + bf16 KV
    (`_decode_step_bf16`; 16 layers and 8 slots, so that bf16 fits the chip),
    and the XLA-attention scan of `llama_decode_step` that windows, softcaps
    and meshes take.
    No loop body may make a weight-sized array in HBM: with `unroll=4` on the
    layer scans each round copied every group of four layers' weights out of
    the stacked tree (six instructions here, 0.74 GiB of temporaries, more
    than half of a round's device time on the chip).

    What the bf16 program still does, ONCE a round and outside both loops: the
    compiler re-lays out the whole `wq` and `wk` stacks for the slices it
    prefetches into on-chip memory (PERF.md §7). The case holds it to that."""
    import dataclasses
    import importlib.util
    from functools import partial

    from llm_mcp_tpu.models import llama, quant
    from llm_mcp_tpu.models.configs import get_config

    spec = importlib.util.spec_from_file_location(
        "rehearse_tpu_compile",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "rehearse_tpu_compile.py"))
    rehearse = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rehearse)

    cfg = dataclasses.replace(get_config("qwen3-8b"), n_layers=n_layers)

    def init():
        if weights == "bf16":
            return llama.init_llama_params(cfg, jax.random.PRNGKey(0), dtype=BF)
        p = quant.init_llama_params_quantized(cfg, jax.random.PRNGKey(0), scale_dtype=BF)
        return quant.fuse_layer_weights(quant.quantize_params(p))

    def decode_round(params, ck, cv, tokens, lengths):
        def step(carry, _):
            ck, cv, toks, lens = carry
            logits, ck, cv = llama.llama_decode_step(
                cfg, params, ck, cv, toks, lens, attn_impl=attn)
            new = jnp.argmax(logits, axis=-1).astype(I32)
            return (ck, cv, new, lens + 1), new

        (ck, cv, _, _), out = jax.lax.scan(step, (ck, cv, tokens, lengths), None, length=4)
        return out, ck, cv

    params = jax.eval_shape(init)
    cache = jax.eval_shape(partial(
        llama.init_kv_cache, cfg, slots, S, dtype=BF, quantized=weights == "int8"))
    params, cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), (params, cache))
    rows = jax.ShapeDtypeStruct((slots,), I32, sharding=one_chip)

    compiled = jax.jit(decode_round, donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], rows, rows).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (attn == "pallas")

    made = rehearse.stacked_weight_producers(
        text, rehearse.stacked_weight_dims(params["layers"]))
    assert [m for m in made if m[0] != "ENTRY"] == [], "a loop body copies weights"
    whole = [params["layers"][k] for k in relaid]
    assert sorted(made) == sorted(
        ("ENTRY", "copy", f"bf16[{','.join(map(str, w.shape))}]") for w in whole)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (256 << 20) + sum(w.size * w.dtype.itemsize for w in whole)


@pytest.mark.parametrize("rung", [128, 256])
def test_mixed_round_fits_beside_decode_closed(one_chip, chip_kernels, rung):
    """The mixed round at `decode_closed`'s shapes, from shapes alone as the
    engine's `mixed_round_fn` has it: Qwen3-8B int8, 32 rows, the int8 cache at
    2,048, the packed prompt buffer of one rung, 4 prompt rows, then 3 plain
    steps in a scan. The cache is updated in place (the prompts' rows go in
    through `write_prompt_rows` after the decode rows' append), no loop body
    copies weights, and the program stays inside what the cell has left: its
    peak is 13.77 GB of the chip's 15.75 GiB (PERF.md section 4), the plain round
    compiles to 12.74 GiB and the ragged chunk programs to 13.34."""
    import importlib.util
    from functools import partial

    from llm_mcp_tpu.models import llama, quant
    from llm_mcp_tpu.models.configs import get_config

    spec = importlib.util.spec_from_file_location(
        "rehearse_tpu_compile",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "rehearse_tpu_compile.py"))
    rehearse = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rehearse)
    cfg = get_config("qwen3-8b")
    slots, R = 32, 4

    def init():
        p = quant.init_llama_params_quantized(cfg, jax.random.PRNGKey(0), scale_dtype=BF)
        return quant.fuse_layer_weights(quant.quantize_params(p))

    def mixed_round(params, ck, cv, tokens, lengths, p_tokens, p_rowids, p_positions,
                    p_slots, p_last):
        logits, ck, cv = llama.mixed_step_q8(
            cfg, params, ck, cv, tokens, lengths, p_tokens, p_rowids, p_positions,
            p_slots, p_last)
        new = jnp.argmax(logits, axis=-1).astype(I32)

        def step(carry, _):
            ck, cv, toks, lens = carry
            logits, ck, cv = llama.llama_decode_step(
                cfg, params, ck, cv, toks, lens, attn_impl="pallas")
            new = jnp.argmax(logits, axis=-1).astype(I32)
            return (ck, cv, new, lens + 1), new

        (ck, cv, _, _), out = jax.lax.scan(
            step, (ck, cv, new[:slots], lengths + 1), None, length=3)
        return jnp.concatenate([new[None, :slots], out]), new[slots:], ck, cv

    params = jax.eval_shape(init)
    cache = jax.eval_shape(partial(llama.init_kv_cache, cfg, slots, S, dtype=BF, quantized=True))
    params, cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), (params, cache))
    vec = lambda n: jax.ShapeDtypeStruct((n,), I32, sharding=one_chip)  # noqa: E731

    compiled = jax.jit(mixed_round, donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], vec(slots), vec(slots), vec(rung), vec(rung),
        vec(rung), vec(R), vec(R)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    made = rehearse.stacked_weight_producers(
        text, rehearse.stacked_weight_dims(params["layers"]))
    assert made == [], "the mixed round copies weights"
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    # the cache and nothing weight-sized beside it in temporaries; the whole
    # under the 13.34 GiB of the ragged programs the cell already holds
    assert mem.temp_size_in_bytes < (512 << 20), mem.temp_size_in_bytes
    assert total < int(13.34 * (1 << 30)), total


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


def hybrid_shapes(name: str, one_chip, slots: int, seq: int):
    """(cfg, params, cache) of a hybrid preset as shapes on the described chip:
    bf16 weights, int8 KV for the GQA layers, the float32 state pool beside it."""
    from functools import partial

    from llm_mcp_tpu.models import llama
    from llm_mcp_tpu.models.configs import get_config

    cfg = get_config(name)
    params = jax.eval_shape(partial(llama.init_llama_params, cfg, jax.random.PRNGKey(0), dtype=BF))
    cache = jax.eval_shape(partial(
        llama.init_kv_cache, cfg, slots, seq, dtype=BF, quantized=True))
    return (cfg, *jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), (params, cache)))


@pytest.fixture(scope="module")
def solar(one_chip):
    """`solar-open2-250b-ep8`, 64 slots x 1024."""
    return hybrid_shapes("solar-open2-250b-ep8", one_chip, SOLAR_SLOTS, SOLAR_S)


@pytest.fixture(scope="module")
def olmo(one_chip):
    """`olmo-hybrid-7b-d20`, 64 slots x 1024, as its cell boots it."""
    return hybrid_shapes("olmo-hybrid-7b-d20", one_chip, SOLAR_SLOTS, SOLAR_S)


def solar_program(which: str, cfg):
    """The three step programs the cell dispatches, as the engine builds them
    (`decode_body`'s scan of 4 steps; `admit_fn`'s prefill and row inserts; the
    bucketed chunk), without the sampler."""
    from llm_mcp_tpu.models import hybrid, llama

    def decode(params, ck, cv, tokens, lengths, ids, steps=4):
        def step(carry, _):
            ck, cv, toks, lens = carry
            logits, ck, cv = llama.llama_decode_step(
                cfg, params, ck, cv, toks, lens, attn_impl="pallas", slot_ids=ids)
            return (ck, cv, jnp.argmax(logits, axis=-1).astype(I32), lens + 1), None

        (ck, cv, toks, _), _ = jax.lax.scan(step, (ck, cv, tokens, lengths), None, length=steps)
        return toks, ck, cv

    def admit(params, ck, cv, tokens, lengths, slots):
        logits, ks, vs = llama.llama_prefill(
            cfg, params, tokens, lengths, attn_impl="pallas", quant_kv=True)

        def body(i, cc):
            ck, cv = cc
            ck = {"q": jax.lax.dynamic_update_slice(
                      ck["q"], jax.lax.dynamic_slice_in_dim(ks["q"], i, 1, 1), (0, slots[i], 0, 0, 0)),
                  "s": jax.lax.dynamic_update_slice(
                      ck["s"], jax.lax.dynamic_slice_in_dim(ks["s"], i, 1, 1), (0, slots[i], 0, 0))}
            return ck, dict(cv, **hybrid.insert_state_row(cv, vs, i, slots[i]))

        ck, cv = jax.lax.fori_loop(0, tokens.shape[0], body, (ck, cv))
        return logits, ck, hybrid.add_counts(cv, vs)

    def chunk(params, ck, cv, tokens, slots, starts, nvalid):
        return llama.llama_prefill_chunk_batch(
            cfg, params, ck, cv, tokens, slots, starts, nvalid, skey=min(512, tokens.shape[1]))

    def mixed(params, ck, cv, tokens, lengths, p_tokens, p_rowids, p_positions, p_slots, p_last):
        # `mixed_round_fn`: the first step carries the packed prompts, three plain ones follow
        logits, ck, cv = hybrid.hybrid_mixed_step(
            cfg, params, ck, cv, tokens, lengths, p_tokens, p_rowids, p_positions, p_slots, p_last)
        new = jnp.argmax(logits, axis=-1).astype(I32)
        n = tokens.shape[0]
        toks, ck, cv = decode(params, ck, cv, new[:n], lengths + 1, None, steps=3)
        return toks, new[n:], ck, cv

    return {"decode": decode, "admit": admit, "chunk": chunk, "mixed": mixed}[which]


@pytest.mark.parametrize("which,operands", [
    ("decode", [(64,), (64,), (64,)]),  # every slot a row
    ("admit", [(4, 256), (4,), (4,)]),  # four prompts in the 256 bucket
    ("chunk", [(2, 512), (2,), (2,), (2,)]),  # two prompts' second chunks of 512
    ("admit", [(1, 64), (1,), (1,)]),  # the smallest prompts: few rows, as a decode round
    ("admit", [(2, 256), (2,), (2,)]),  # the cell's largest admit program, 512 padded tokens
])
def test_solar_step_programs_fit_and_keep_state_and_banks_in_place(
    sd, solar, chip_kernels, which, operands
):
    """Each compiles for the described v5e with its kernels (the state kernel
    and the attention kernels in decode, the two grouped expert kernels in every
    program since PR 45: the decode round's 64 rows through a window of one row
    tile, with no fall to their reference), fits the chip, and makes no second
    copy of the state pool (0.75 GiB) nor of an expert bank (a slice of a
    stacked bank that feeds a grouped product was copied out, 0.39 GiB a bank
    and layer, until the banks went in whole: models/moe.py): the decode
    round's temporaries are 0.09 GiB. Bytes in PERF.md section 4 as
    "described-chip compile"."""
    cfg, params, cache = solar
    falls = dict(A.reference_falls)
    compiled = jax.jit(solar_program(which, cfg), donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], *(sd(shape, I32) for shape in operands)).compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text = compiled.as_text()
    assert ("kda_decode_step" in text) == (which == "decode")
    assert grouped_kernels_in(text)
    mem = compiled.memory_analysis()
    if which == "decode":
        assert mem.temp_size_in_bytes < 0.21 * 2**30  # no bank copied out of the stack
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"solar {which}: {total / 2**30:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB")
    assert total < 15.75 * 2**30
    assert mem.temp_size_in_bytes < 0.7 * 2**30
    assert mem.alias_size_in_bytes > 0.9 * 2**30  # KV cache and state pool updated in place


@pytest.mark.parametrize("which,operands", [
    ("decode", [(64,), (64,), (64,)]),  # every slot a row
    ("admit", [(4, 128), (4,), (4,)]),  # the cell's largest admit program, 512 padded tokens
    ("chunk", [(2, 512), (2,), (2,), (2,)]),  # two prompts' second chunks of 512
])
def test_olmo_hybrid_step_programs_fit_beside_64_slots(sd, olmo, chip_kernels, which, operands):
    """The decode round, an admit and a chunk program of `olmo-hybrid-7b-d20`
    at its cell's 64 slots x 1024 compile for the described v5e with their
    kernels: `gdn_decode_step` and the decode attention and append kernels at
    30 KV heads, group 1, as Mosaic calls with no fall to their reference; the
    flash prefill kernel in the admit program. Each fits under the 15.0 GiB at which
    ISSUE 35 would have taken 48 slots, and updates the 2.38 GiB KV cache and
    the 2.04 GiB state pool in place. The argument bytes hold the pool at its
    logical size. GiB in PERF.md section 4 as "described-chip compile"."""
    from llm_mcp_tpu.models import kda

    cfg, params, cache = olmo
    falls = dict(A.reference_falls)
    compiled = jax.jit(solar_program(which, cfg), donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], *(sd(shape, I32) for shape in operands)).compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text = compiled.as_text()
    # the bucketed chunk's attention over [past | self] is `jax.numpy` for every
    # configuration (llama._chunk_attention: PERF.md section 7); the chunked
    # recurrence of a prompt is the chunk kernel, one Mosaic call a layer
    assert ("%gdn_chunk_scan" in text) == (which != "decode") and "%ssd_chunk_scan" not in text
    assert ("%gdn_decode_step" in text) == (which == "decode") and "%kda_decode_step" not in text
    if which == "decode":
        assert "decode_attn_q8" in text and "append_kv_q8" in text
    if which == "admit":
        assert "flash_prefill_attn" in text
    S = cache["v"]["state"]["S"]
    assert S.shape == (15, 64, 15, 96, 384) and kda.state_abreast(cfg) == 2
    mem = compiled.memory_analysis()
    leaves = jax.tree.leaves((params, cache)) + [sd(shape, I32) for shape in operands]
    logical = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert mem.argument_size_in_bytes < logical * 1.002  # nothing pads: the pool least of all
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"olmo {which}: {total / 2**30:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB")
    assert total < 15.0 * 2**30
    assert mem.alias_size_in_bytes > 4.3 * 2**30  # KV cache and state pool updated in place


@pytest.fixture(scope="module")
def granite(one_chip):
    """`granite-4.0-h-micro`, whole, 64 slots x 1024, as its cell boots it."""
    return hybrid_shapes("granite-4.0-h-micro", one_chip, SOLAR_SLOTS, SOLAR_S)


@pytest.mark.parametrize("which,operands", [
    ("decode", [(64,), (64,), (64,)]),  # every slot a row
    ("admit", [(4, 128), (4,), (4,)]),  # the cell's largest admit program, 512 padded tokens
    ("admit", [(1, 64), (1,), (1,)]),  # and its smallest
    ("chunk", [(2, 512), (2,), (2,), (2,)]),  # two prompts' second chunks of 512
])
def test_granite_step_programs_fit_beside_64_slots(sd, granite, chip_kernels, which, operands):
    """The decode round, two admit programs and a chunk program of
    `granite-4.0-h-micro` at its cell's 64 slots x 1024 compile for the
    described v5e with their kernels: `ssd_decode_step` on the pool's
    [36, 64, 32, 128, 128] (two heads of 64 values abreast), the decode attention
    (both arms under the dispatcher's `cond`: the cache's heads of 64 lie two
    abreast in rows of 128 lanes, which the blocked arm's copies cut) and the
    append kernel on those rows, as Mosaic calls with no fall to their
    reference; the flash prefill kernel in the admit programs. No program copies
    the cache to another layout (at [4, 64, 17, 1024, 64] every one did, 0.27
    GiB there and 0.27 back: the decode round's temporaries were 0.56 GiB). Each
    fits under 15.0 GiB and updates the KV cache and the 4.5 GiB state pool in
    place; the pool's bytes as the compiler lays it out are its logical bytes.
    GiB in PERF.md section 4 as "described-chip compile"."""
    from llm_mcp_tpu.models import ssm

    cfg, params, cache = granite
    falls = dict(A.reference_falls)
    compiled = jax.jit(solar_program(which, cfg), donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], *(sd(shape, I32) for shape in operands)).compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text = compiled.as_text()
    assert ("%ssd_chunk_scan" in text) == (which != "decode") and "%gdn_chunk_scan" not in text
    assert ("%ssd_decode_step" in text) == (which == "decode")
    assert "%kda_decode_step" not in text and "%gdn_decode_step" not in text
    if which == "decode":
        assert "decode_attn_q8_whole" in text and "append_kv_q8" in text
        assert "decode_attn_q8_blocked" in text
    if which == "admit":
        assert "flash_prefill_attn" in text
    S = cache["v"]["state"]["S"]
    assert S.shape == (36, 64, 32, 128, 128) and ssm.state_abreast(cfg) == 2
    assert cache["k"]["q"].shape == (4, 64, 9, 1024, 128)
    assert cache_relayouts(text, cache["k"]["q"].shape) == []
    pool = jax.jit(lambda s: s + 1.0).lower(S).compile().memory_analysis()
    assert pool.argument_size_in_bytes == 36 * 64 * 64 * 128 * 64 * 4
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"granite {which} {operands[0]}: {total / 2**30:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB")
    assert total < 15.0 * 2**30
    assert mem.alias_size_in_bytes > 4.5 * 2**30  # KV cache and state pool updated in place
    if which == "decode":  # 0.05 GiB; 0.56 with the cache re-laid and back
        assert mem.temp_size_in_bytes < 0.15 * 2**30


@pytest.fixture(scope="module")
def kexaone(one_chip):
    """`k-exaone-236b-ep8`, 64 slots x 4096, as its cell boots it."""
    return hybrid_shapes("k-exaone-236b-ep8", one_chip, SOLAR_SLOTS, 4096)


@pytest.mark.parametrize("which,operands", [
    ("decode", [(64,), (64,), (64,)]),  # every slot a row
    ("admit", [(1, 1024), (1,), (1,)]),  # the cell's admit shapes: a prompt of 769-1024 tokens,
    ("admit", [(1, 768), (1,), (1,)]),  # and one of 640-768
    ("admit", [(2, 512), (2,), (2,)]),  # two shorter prompts: 1024 rows through the expert layer too
    ("chunk", [(1, 1024), (1,), (1,), (1,)]),  # a prompt over 1024 tokens: its second chunk
])
def test_kexaone_step_programs_fit_and_keep_both_kinds_of_cache_in_place(
        sd, kexaone, chip_kernels, which, operands):
    """The decode round, the admit programs and a chunk program of
    `k-exaone-236b-ep8` at its cell's 64 slots x 4096 compile for the described
    v5e with their kernels: the decode attention in BOTH arms (the blocked or
    whole-S arm over the global layer's cache, the window arm over the rings),
    the append kernel twice (the cache, the rings), the flash prefill kernel in
    the admit programs, as Mosaic calls with no fall to their reference. Window
    layers hold a ring of 128 positions a slot and not 4096: the arguments are
    the weights, 0.58 GB of the global layer's cache and 0.07 GB of rings. Each
    program fits under 12 GiB and updates both kinds in place: no copy of a
    whole cache member among the temporaries. Every program's expert layers are
    the grouped kernels over a window of the pairs held here (one row tile for
    the decode round's 64 rows since PR 45; 1,280 rows of the 8,192 a 1,024-row
    prompt has: `moe.window_rows`), and the admit programs'
    temporaries stay under 0.55 GiB: the sorted copies of all 8,192 pairs' rows
    stood at 0.72 and 0.64 (PR 43's tree, 1 x 1024 and 2 x 512; 0.41 and 0.31
    now). The chunk's 0.99 GiB are its attention over [past | self], the same
    on both trees. GiB in PERF.md section 4 as "described-chip compile"."""
    cfg, params, cache = kexaone
    ring, full = cache["v"]["win"]["k"], cache["k"]
    assert full["q"].shape == (1, 64, 17, 4096, 128) and ring["q"].shape == (4, 64, 17, 128, 128)
    assert ring["s"].shape == (4, 64, 16, 128) and cache["v"]["win"]["v"] == {}
    assert cache["v"]["moe"].shape == (2, 4, 5) and "state" not in cache["v"]
    falls = dict(A.reference_falls)
    compiled = jax.jit(solar_program(which, cfg), donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], *(sd(shape, I32) for shape in operands)).compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text = compiled.as_text()
    if which == "decode":
        assert "decode_attn_win_q8" in text and "decode_attn_q8_blocked" in text
        assert text.count("append_kv_q8") >= 2
    if which == "admit":
        assert "flash_prefill_attn" in text and "decode_attn" not in text
    assert grouped_kernels_in(text)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"kexaone {which} {operands[0]}: {total / 2**30:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB")
    caches = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert round(weights / 1e9, 2) == 7.42 and 0.64e9 < caches < 0.66e9
    assert mem.argument_size_in_bytes < weights + caches + 2**20  # rings, not 4 x 4096 positions
    limit = {"decode": 0.7, "admit": 0.55, "chunk": 1.1}[which]
    assert total < 12.0 * 2**30 and mem.temp_size_in_bytes < limit * 2**30
    assert mem.alias_size_in_bytes > 0.99 * caches  # the cache and the rings updated in place


@pytest.fixture(scope="module")
def lfm2(one_chip):
    """`lfm2-8b-a1b-d14`, 64 slots x 1024, as its cell boots it."""
    return hybrid_shapes("lfm2-8b-a1b-d14", one_chip, SOLAR_SLOTS, SOLAR_S)


@pytest.mark.parametrize("which,operands", [
    ("decode", [(64,), (64,), (64,)]),  # every slot a row
    ("admit", [(4, 128), (4,), (4,)]),  # the cell's largest admit program, 512 padded tokens
    ("admit", [(1, 64), (1,), (1,)]),  # and its smallest
    ("chunk", [(2, 512), (2,), (2,), (2,)]),  # two prompts' second chunks of 512
    ("mixed", 128), ("mixed", 256),  # the round that carries prompts, at both rungs
])
def test_lfm2_step_programs_fit_with_the_banks_whole_and_the_tails_in_place(
    sd, lfm2, chip_kernels, which, operands
):
    """The decode round of 64 rows, the admit programs the traffic meets, a
    chunk program and the mixed round at both rungs of `lfm2-8b-a1b-d14` (the
    published widths, 14 layers, all 32 experts of 2048 x 1792 a layer) at its
    cell's 64 slots x 1024 compile for the described v5e: the two grouped expert
    kernels at banks of [2048, 1792] (one column block of two banks, 14.7 MB) and
    [1792, 2048], the decode attention (both arms: heads of 64 WITH rotation,
    two abreast in rows of 128 lanes) and the append kernel on those rows, the
    flash prefill kernel in the admit programs, every one a Mosaic call with no
    fall to its reference. No program copies the cache to another layout. Each
    fits the chip;
    the temporaries hold no copy of a layer's banks (0.66 GiB a layer; the
    stack goes in whole) nor of a leading layer's feed-forward (84 MB, unstacked:
    a slice of a stack at a fixed index was copied out every step), and the KV
    cache and the tails (5.5 MiB: a pool with no matrix state) are updated in
    place. GiB in PERF.md section 4 as "described-chip compile"."""
    cfg, params, cache = lfm2
    falls = dict(A.reference_falls)
    vec = lambda n: sd((n,), I32)  # noqa: E731
    args = ((vec(64), vec(64), vec(operands), vec(operands), vec(operands), vec(4), vec(4))
            if which == "mixed" else tuple(sd(shape, I32) for shape in operands))
    compiled = jax.jit(solar_program(which, cfg), donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], *args).compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text = compiled.as_text()
    assert grouped_kernels_in(text)
    # no state kernel: no matrix state
    assert not any(f"%{k}_{form}" in text for k in ("kda", "gdn", "ssd")
                   for form in ("decode_step", "chunk_scan"))
    if which in ("decode", "mixed"):
        assert "decode_attn_q8_whole" in text and "append_kv_q8" in text
        assert "decode_attn_q8_blocked" in text
    if which == "admit":
        assert "flash_prefill_attn" in text
    state = cache["v"]["state"]
    assert set(state) == {"conv"} and state["conv"].shape == (11, 64, 2 * 2048)
    assert cache["k"]["q"].shape == (3, 64, 9, 1024, 128)
    assert cache_relayouts(text, cache["k"]["q"].shape) == []
    assert params["layers"]["w1e"].shape == (12, 32, 2048, 1792) and len(params["first"]) == 2
    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))
    weights, pool, kv = nbytes(params), nbytes(state), nbytes(cache["k"])
    # bfloat16 but for the twelve selection biases [32], float32
    assert weights == 2 * cfg.param_count() + 2 * 12 * 32 == 9_334_155_520 and pool == 64 * 90_112
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"lfm2 {which} {operands}: {total / 2**30:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB (weights {weights / 2**30:.2f}, "
          f"KV cache {kv / 2**30:.2f}, tails {pool / 2**20:.1f} MiB)")
    assert total < 12.0 * 2**30
    # under one layer's banks; a decode or mixed round's are 0.02 and 0.05 GiB
    # (0.42 and 0.45 with the cache of 0.20 GiB re-laid and back)
    assert mem.temp_size_in_bytes < (0.15 if which in ("decode", "mixed") else 0.6) * 2**30
    assert mem.alias_size_in_bytes > 0.99 * (pool + kv)  # KV cache and tails updated in place


@pytest.mark.parametrize("rung", [128, 256])
@pytest.mark.parametrize("name,kernel,limit,temps", [
    ("solar", "%kda_decode_step", 15.75, 0.25), ("olmo", "%gdn_decode_step", 15.0, 0.35),
    # the prompts' states ride the scan (its cache of 0.27 GiB was re-laid for the
    # kernels and back besides, 1.06 GiB in all, while its heads of 64 lay a row each)
    ("granite", "%ssd_decode_step", 15.0, 0.75)])
def test_hybrid_mixed_round_fits_beside_its_decode_round(
    sd, request, chip_kernels, name, kernel, limit, temps, rung
):
    """The mixed round of the three benchmark configurations with recurrent
    layers (`hybrid_mixed_step`, then three plain steps) at their cells' 64
    slots x 1024 and both rungs of the packed prompt buffer, four prompt rows,
    compiles for the described v5e: the decode rows' state kernel, decode
    attention and append kernel as Mosaic calls with no fall to their reference,
    inside the limit its decode round is held to, the KV cache and the state
    pool updated in place, and among the temporaries no copy of the pool (0.78,
    2.04 and 4.56 GiB: each is larger than all of them together; carried with
    the pool's own last two axes, Granite's prompt states made the compiler
    re-lay the whole pool out) nor of Olmo-Hybrid's cache (2.42 GiB). GiB in
    PERF.md section 4 as "described-chip compile"."""
    cfg, params, cache = request.getfixturevalue(name)
    falls = dict(A.reference_falls)
    vec = lambda n: sd((n,), I32)  # noqa: E731
    compiled = jax.jit(solar_program("mixed", cfg), donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], vec(64), vec(64), vec(rung), vec(rung), vec(rung),
        vec(4), vec(4)).compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text = compiled.as_text()
    assert kernel in text and "decode_attn_q8" in text and "append_kv_q8" in text
    # the prompts' recurrence with one decay a head is the chunk kernel; Solar's, a
    # decay a key channel, stays the loop of `jax.numpy` (models/kda.py)
    scan = kernel.replace("decode_step", "chunk_scan")
    assert (scan in text) == (name != "solar") and ("chunk_scan" in text) == (name != "solar")
    assert grouped_kernels_in(text) == (name == "solar")  # its 64 + rung rows through the expert kernels
    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))
    pool, kv = nbytes(cache["v"]["state"]), nbytes(cache["k"])
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{name} mixed {rung}: {total / 2**30:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB (pool {pool / 2**30:.2f}, "
          f"KV cache {kv / 2**30:.2f})")
    assert total < limit * 2**30
    assert mem.temp_size_in_bytes < temps * 2**30 < pool
    assert mem.alias_size_in_bytes > 0.99 * (pool + kv)
    assert cache_relayouts(text, cache["k"]["q"].shape) == []


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


@pytest.fixture(scope="module")
def joyai(one_chip):
    """`joyai-llm-flash-ep16`, whole depth, 64 slots x 1024, as its cell boots it."""
    return hybrid_shapes("joyai-llm-flash-ep16", one_chip, SOLAR_SLOTS, SOLAR_S)


def joyai_program(which: str, cfg):
    """The latent family's step programs as the engine builds them: the decode
    round and the bucketed chunk are `solar_program`'s (the same dispatch through
    models/llama.py); the admit program inserts rows of BOTH members of the
    latent pair, as `engine._insert_row` does for a counted pair; the packed
    chunk is the ragged program."""
    from llm_mcp_tpu.executor.engine import _put_rows
    from llm_mcp_tpu.models import hybrid, llama

    def admit(params, ck, cv, tokens, lengths, slots):
        logits, ks, vs = llama.llama_prefill(
            cfg, params, tokens, lengths, attn_impl="pallas", quant_kv=True)

        def put(c, rows, i, slot):  # `engine._insert_kv`'s: a prompt's rope keys lie apart
            return _put_rows(c, jax.lax.dynamic_slice_in_dim(rows, i, 1, 1), slot, 0)

        def body(i, cc):
            ck, cv = cc
            ck = jax.tree.map(lambda c, r: put(c, r, i, slots[i]), ck, ks)
            return ck, dict(cv, v=jax.tree.map(lambda c, r: put(c, r, i, slots[i]), cv["v"], vs["v"]))

        ck, cv = jax.lax.fori_loop(0, tokens.shape[0], body, (ck, cv))
        return logits, ck, hybrid.add_counts(cv, vs)

    def ragged(params, ck, cv, tokens, rowids, positions, slots, starts, last_idx):
        return llama.llama_prefill_chunk_ragged(
            cfg, params, ck, cv, tokens, rowids, positions, slots, starts, last_idx, impl="kernel")

    return {"admit": admit, "ragged": ragged}.get(which) or solar_program(which, cfg)


@pytest.mark.parametrize("which,operands", [
    ("decode", [(64,), (64,), (64,)]),  # every slot a row
    ("admit", [(4, 128), (4,), (4,)]),  # the cell's largest admit program, 512 padded tokens
    ("admit", [(1, 64), (1,), (1,)]),  # and its smallest
    ("chunk", [(2, 512), (2,), (2,), (2,)]),  # two prompts' second chunks of 512
    ("ragged", [(512,), (512,), (512,), (4,), (4,), (4,)]),  # a packed buffer of 512 tokens, four rows
])
def test_joyai_step_programs_fit_with_the_banks_whole_beside_the_latent_cache(
    sd, joyai, chip_kernels, which, operands
):
    """The decode round of 64 rows, the admit programs the traffic meets, a
    bucketed chunk and a packed chunk of `joyai-llm-flash-ep16` (the published
    widths, all 40 layers, 16 of 256 experts of 2048 x 768 a layer) at its cell's
    64 slots x 1024 compile for the described v5e: the MLA step programs' first
    compile for the chip (ROADMAP B2, debt (d)). The two grouped expert kernels in
    every program, the latent decode attention as the whole-S arm
    (`decode_attn_mla_q8_whole`: 1024 positions fit its VMEM budget) in the decode
    round, `ragged_prefill_attn_mla` in the packed chunk, every one a Mosaic call
    with no fall to its reference. Each fits the chip beside 9.55 GB of weights
    and the 1.52 GB latent cache; the temporaries hold no copy of a layer's banks
    (151 MB a layer: the stack goes in whole) nor of the leading dense layer's
    feed-forward (a stack of ONE layer scanned once: sliced in place), and the
    latent pair is updated in place, neither member copied or re-laid (the int8
    rope keys lie two positions abreast in rows of whole lanes: until PR 58 their
    rows of 64 lanes were re-laid four times a decode round). GiB in PERF.md section 4 as "described-chip
    compile"."""
    cfg, params, cache = joyai
    falls = dict(A.reference_falls)
    compiled = jax.jit(joyai_program(which, cfg), donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], *(sd(shape, I32) for shape in operands)).compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text = compiled.as_text()
    assert grouped_kernels_in(text)
    assert ("decode_attn_mla_q8_whole" in text) == (which == "decode")
    assert "decode_attn_mla_q8_blocked" not in text and "decode_attn_mla_q8_paged" not in text
    assert ("ragged_prefill_attn_mla" in text) == (which == "ragged")
    # the rope keys two positions abreast in rows of the 128 lanes (`positions_abreast`)
    assert cache["k"]["q"].shape == (40, 64, 1, 1024, 512) and cache["v"]["v"]["q"].shape == (40, 64, 1, 512, 128)
    assert cache["v"]["moe"].shape == (2, 39, 5)
    # no copy of either member of the latent pair, to another layout or otherwise
    assert cache_relayouts(text, cache["k"]["q"].shape) == []
    assert cache_relayouts(text, cache["v"]["v"]["q"].shape) == []
    assert params["layers"]["w1e"].shape == (39, 16, 2048, 768) and params["dense_layers"]["w1"].shape == (1, 2048, 7168)
    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))
    weights, latent = nbytes(params), nbytes(cache) - cache["v"]["moe"].size * 4
    # bfloat16 but for the 39 selection biases [256], float32
    assert weights == 2 * cfg.param_count() + 2 * 39 * 256 == 9_553_062_912
    assert latent == 40 * 64 * 1024 * (512 + 64 + 4) == 1_520_435_200  # 580 bytes a position and layer
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"joyai {which} {operands}: {total / 2**30:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB (weights {weights / 2**30:.2f}, "
          f"latent cache {latent / 2**30:.2f} logical)")
    assert total < 14.5 * 2**30
    # No copy of a layer's banks (0.14 GiB a layer and step would be 5.5 GiB a
    # round) nor of `w_uq` (0.69 GiB until its columns were `[H dn | H dr]`). What
    # the decode round still holds, every ROUND: `w_ukv` transposed whole for the
    # absorbed products (0.30) and `w_dkv` (0.09): ROADMAP B2.
    assert mem.temp_size_in_bytes < (0.5 if which == "decode" else 1.0) * 2**30
    if which == "decode":
        assert "bf16[39,1536,6144]" not in "".join(
            line for line in text.splitlines() if " copy(" in line)  # `w_uq` read in place
    assert mem.alias_size_in_bytes > 0.99 * latent  # the latent pair updated in place


# -- generation by diffusion over blocks: the block round and the admit programs ----


@pytest.fixture(scope="module")
def sdar(one_chip):
    """`sdar-30b-a3b-ep8`, whole depth, 64 slots x 1024, as its cell boots it."""
    return hybrid_shapes("sdar-30b-a3b-ep8", one_chip, SOLAR_SLOTS, SOLAR_S)


def sdar_program(which: str, cfg):
    """The block configuration's step programs as the engine builds them: the
    block round (`engine.block_round_fn`: a while loop of denoising passes over
    the whole batch in order, the unmask rule with the sampler, the commit
    pass), the admit program with the counted dense pair's row inserts, and the
    bucketed chunk (`solar_program`'s)."""
    from llm_mcp_tpu.executor.engine import _put_rows
    from llm_mcp_tpu.models import hybrid, llama

    def block(params, ck, cv, first, starts, counter, slots=None):
        live = starts < ck["q"].shape[3]
        temp = jnp.full(starts.shape, 0.7, jnp.float32)
        topk, topp = jnp.zeros(starts.shape, I32), jnp.ones(starts.shape, jnp.float32)

        def denoise(carry):
            tokens, passes, rng, moe, n = carry
            rng, sub = jax.random.split(rng)
            new, cv_p, _ = llama.block_denoise(
                cfg, params, ck, dict(cv, moe=moe), tokens, slots, starts, live, sub, temp, topk, topp,
                attn_impl="pallas")
            return new, passes + jnp.any(tokens == cfg.mask_token_id, axis=1), rng, cv_p["moe"], n + 1

        tokens, passes, _, moe, _ = jax.lax.while_loop(
            lambda c: jnp.any((c[0] == cfg.mask_token_id) & live[:, None]) & (c[4] < cfg.denoise_steps),
            denoise,
            (first, jnp.zeros(starts.shape, I32), jax.random.fold_in(jax.random.PRNGKey(1), counter[0]),
             cv["moe"], jnp.int32(0)))
        _, ck, cv = llama.block_pass(
            cfg, params, ck, dict(cv, moe=moe), tokens, slots, starts, live, commit=True,
            attn_impl="pallas")
        return jnp.concatenate([tokens.T, passes[None]]), ck, cv

    def admit(params, ck, cv, tokens, lengths, slots):
        logits, ks, vs = llama.llama_prefill(
            cfg, params, tokens, lengths, attn_impl="pallas", quant_kv=True)

        def body(i, cc):
            ck, cv = cc
            ck = jax.tree.map(
                lambda c, r: _put_rows(c, jax.lax.dynamic_slice_in_dim(r, i, 1, 1), slots[i], 0), ck, ks)
            return ck, cv

        ck, cv = jax.lax.fori_loop(0, tokens.shape[0], body, (ck, cv))
        return logits, ck, hybrid.add_counts(cv, vs)

    return {"block": block, "block_compact": block, "admit": admit}.get(which) or solar_program(which, cfg)


@pytest.mark.parametrize("which,operands", [
    ("block", [(64, 4), (64,), (1,)]),  # every slot a row: 256 rows a pass
    ("block_compact", [(32, 4), (32,), (1,), (32,)]),  # half the slots seated: rows by slot id
    ("admit", [(4, 128), (4,), (4,)]),  # the cell's largest admit program, 512 padded tokens
    ("admit", [(1, 64), (1,), (1,)]),  # and its smallest
    ("chunk", [(2, 512), (2,), (2,), (2,)]),  # two prompts' second chunks of 512
])
def test_sdar_step_programs_fit_with_the_banks_whole_beside_the_fused_cache(
    sd, sdar, chip_kernels, which, operands
):
    """The block round of 64 rows (256 rows a pass), the admit programs the
    traffic meets and a bucketed chunk of `sdar-30b-a3b-ep8` (the published
    widths, all 48 layers, 16 of 128 experts of 2048 x 768 a layer, the whole
    vocabulary) at its cell's 64 slots x 1024 compile for the described v5e. The
    two grouped expert kernels in every program, the prompt kernel in the admit
    programs, every one a Mosaic call with no fall to its reference. Each fits
    the chip beside 10.33 GB of weights and the 3.67 GB fused int8 cache; the
    temporaries hold no copy of a layer's banks (151 MB a layer: the stack goes
    in whole), and the cache is updated in place, neither copied nor re-laid:
    a denoising pass does not even carry it. The block round's passes read it
    through `block_attn_q8` (kernels/attention.py:block_attend_q8), in order and
    by slot id: no slice of a layer's payload is cut out of the stack (67 MB a
    layer and pass before PR 60), and the commit's writes after the kernel's
    read leave its layout alone (written as updates of `[9, 4, 128]` they made
    the compiler re-lay all 48 layers heads-minor, 6 GB, and back, every layer).
    GiB in PERF.md section 4 as "described-chip compile"."""
    cfg, params, cache = sdar
    falls = dict(A.reference_falls)
    compiled = jax.jit(sdar_program(which, cfg), donate_argnums=(1, 2)).lower(
        params, cache["k"], cache["v"], *(sd(shape, I32) for shape in operands)).compile()
    assert A.reference_falls == falls, "a kernel fell to its reference in this compile"
    text = compiled.as_text()
    assert grouped_kernels_in(text)
    assert ("flash_prefill_attn" in text) == (which == "admit")
    assert ("block_attn_q8" in text) == which.startswith("block")
    if which.startswith("block"):
        # every pass's attention is the kernel's: the denoising loop's and the commit's
        assert text.count("custom_call_target=\"tpu_custom_call\"") >= 6
        cut = [ln.strip()[:160] for ln in text.splitlines()
               if " dynamic-slice(" in ln and re.search(r"= s8\[(1,)?\d+,[89],1024,128\]", ln)]
        assert cut == [], cut
    assert cache["k"]["q"].shape == (48, 64, 9, 1024, 128) and cache["v"]["v"] == {}
    assert cache["v"]["moe"].shape == (2, 48, 5)
    assert cache_relayouts(text, cache["k"]["q"].shape) == []
    # nor of the plain scales beside it: cut out of the stack a layer's worth at
    # a time, all 48 layers' were re-laid every layer of every pass (120 ms of a
    # 276 ms round on the chip; PERF.md section 6, PR 59)
    assert [ln for ln in text.splitlines() if "= bf16[48,64,8,1024]{" in ln and " copy(" in ln] == []
    assert params["layers"]["w1e"].shape == (48, 16, 2048, 768)
    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))
    weights, kv = nbytes(params), nbytes(cache) - cache["v"]["moe"].size * 4
    assert weights == 2 * cfg.param_count() == 10_329_944_064
    assert kv == 48 * 64 * 1024 * (9 * 128 + 8 * 2) == 3_674_210_304
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"sdar {which} {operands}: {total / 2**30:.2f} GiB, of it temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB (weights {weights / 2**30:.2f}, "
          f"KV cache {kv / 2**30:.2f} logical)")
    assert total < 15.0 * 2**30
    # no copy of a layer's banks: 0.14 GiB a layer would be 6.75 GiB a pass. (The
    # block round's 1.24 GiB: with no slice of a layer's payload left in it the
    # compiler transposes the wq / wk / wv STACKS once a round, 0.95 GiB outside
    # the loops, where it transposed a layer's slice inside them every layer of
    # every pass before)
    assert mem.temp_size_in_bytes < (1.5 if which.startswith("block") else 1.0) * 2**30
