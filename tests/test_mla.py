"""MLA (DeepSeek-style multi-head latent attention, models/mla.py).

The decisive test is decode-vs-prefill agreement: prefill runs the
EXPANDED form (per-head K/V re-materialized) while decode runs the
ABSORBED form (attention in latent space) — matching logits over the same
positions proves the absorption algebra, the latent cache layout, and the
rope split all line up."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.executor import GenerationEngine
from llm_mcp_tpu.models import (
    get_config,
    init_kv_cache,
    init_llama_params,
    llama_decode_step,
    llama_prefill,
)

from family import stepwise  # noqa: E402

# every model call of this file is ONE trace and ONE compile a (configuration, shape):
# called bare, a step dispatches its primitives one by one and lowers its kernels again
llama_decode_step, llama_prefill = map(stepwise, (llama_decode_step, llama_prefill))


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tiny-mla")
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def test_param_tree_is_mla(setup):
    cfg, params = setup
    layers = params["layers"]
    for k in ("wq_mla", "w_dkv", "kv_norm", "w_ukv", "wo_mla"):
        assert k in layers, k
    for k in ("wq", "wk", "wv", "wo"):
        assert k not in layers, k


def test_latent_cache_is_small(setup):
    cfg, _ = setup
    cache = init_kv_cache(cfg, 4, 128, dtype=jnp.float32)
    lat_vals = sum(int(np.prod(x.shape)) for x in cache.values())
    gqa_cfg = get_config("tiny-llm")  # same dim/layers class
    gqa = init_kv_cache(gqa_cfg, 4, 128, dtype=jnp.float32)
    gqa_vals = sum(int(np.prod(x.shape)) for x in gqa.values())
    # per token: R + dr = 48 vs 2 * Hkv * hd = 128 at the tiny shapes
    assert lat_vals * 2 < gqa_vals


def test_decode_matches_prefill(setup):
    """Greedy continuation decoded step-by-step (absorbed attention over
    the latent cache) must match a fresh whole-sequence prefill (expanded
    attention) at every step."""
    cfg, params = setup
    B, S = 2, 32
    prompt = np.array([[7, 8, 9, 10, 11, 0, 0, 0],
                       [21, 22, 23, 0, 0, 0, 0, 0]], np.int32)
    lens = np.array([5, 3], np.int32)
    logits, cs, rs = llama_prefill(cfg, params, jnp.asarray(prompt), jnp.asarray(lens))
    cache = init_kv_cache(cfg, B, S, dtype=jnp.float32)
    ck = cache["k"].at[:, :, :, : prompt.shape[1]].set(cs)
    cv = cache["v"].at[:, :, :, : prompt.shape[1]].set(rs)

    seqs = [list(prompt[b, : lens[b]]) for b in range(B)]
    cur = jnp.asarray(np.argmax(np.asarray(logits), -1), jnp.int32)
    cur_lens = jnp.asarray(lens, jnp.int32)
    for step in range(4):
        dl, ck, cv = llama_decode_step(cfg, params, ck, cv, cur, cur_lens)
        for b in range(B):
            seqs[b].append(int(cur[b]))
        # reference: full expanded prefill over the grown sequences
        maxlen = max(len(s) for s in seqs)
        ref_toks = np.zeros((B, maxlen), np.int32)
        ref_lens = np.array([len(s) for s in seqs], np.int32)
        for b in range(B):
            ref_toks[b, : len(seqs[b])] = seqs[b]
        rl, _, _ = llama_prefill(
            cfg, params, jnp.asarray(ref_toks), jnp.asarray(ref_lens)
        )
        da, ra = np.asarray(dl), np.asarray(rl)
        assert (np.argmax(da, -1) == np.argmax(ra, -1)).all(), step
        corr = np.corrcoef(da.ravel(), ra.ravel())[0, 1]
        assert corr > 0.999, (step, corr)
        cur = jnp.asarray(np.argmax(da, -1), jnp.int32)
        cur_lens = cur_lens + 1


def test_decode_compaction_indirection(setup):
    """slot_ids routes compact rows to the right cache rows (parity with
    the 1:1 dispatch)."""
    cfg, params = setup
    B, S = 4, 32
    cache = init_kv_cache(cfg, B, S, dtype=jnp.float32)
    ck = jnp.asarray(np.random.default_rng(0).standard_normal(cache["k"].shape),
                     jnp.float32)
    cv = jnp.asarray(np.random.default_rng(1).standard_normal(cache["v"].shape),
                     jnp.float32)
    toks = jnp.asarray([3, 4], jnp.int32)
    lens = jnp.asarray([5, 9], jnp.int32)
    ids = jnp.asarray([2, 0], jnp.int32)
    l_c, ck_c, cv_c = llama_decode_step(
        cfg, params, ck, cv, toks, lens, slot_ids=ids
    )
    # reference: full-batch dispatch with rows 2 and 0 carrying the work
    full_toks = jnp.asarray([4, 0, 3, 0], jnp.int32)
    full_lens = jnp.asarray([9, S, 5, S], jnp.int32)  # rows 1,3 parked
    l_f, ck_f, cv_f = llama_decode_step(cfg, params, ck, cv, full_toks, full_lens)
    np.testing.assert_allclose(np.asarray(l_c[0]), np.asarray(l_f[2]), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(l_c[1]), np.asarray(l_f[0]), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ck_c), np.asarray(ck_f), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cv_c), np.asarray(cv_f), rtol=2e-4, atol=2e-5)


def test_engine_serves_mla_end_to_end():
    """tiny-mla through the full continuous-batching engine: greedy
    determinism, concurrent isolation, int8 weights."""
    import concurrent.futures as cf

    eng = GenerationEngine(
        "tiny-mla", max_slots=4, max_seq_len=128, dtype=jnp.float32,
        decode_chunk=4,
    ).start()
    try:
        assert eng.prefill_chunk > 0  # MLA chunks prompts like GQA families
        a = eng.generate("latent attention", max_tokens=8, temperature=0.0)
        b = eng.generate("latent attention", max_tokens=8, temperature=0.0)
        assert a["text"] == b["text"]
        assert a["usage"]["completion_tokens"] >= 1
        seq = [eng.generate(f"iso {i}", max_tokens=6, temperature=0.0)["text"]
               for i in range(3)]
        with cf.ThreadPoolExecutor(max_workers=3) as ex:
            conc = list(ex.map(
                lambda i: eng.generate(f"iso {i}", max_tokens=6, temperature=0.0)["text"],
                range(3),
            ))
        assert seq == conc
    finally:
        eng.shutdown()


def test_engine_serves_mla_int8_weights():
    eng = GenerationEngine(
        "tiny-mla", max_slots=2, max_seq_len=64, dtype=jnp.float32,
        decode_chunk=2, quant="int8",
    ).start()
    try:
        out = eng.generate("int8 mla", max_tokens=6, temperature=0.0)
        assert out["usage"]["completion_tokens"] >= 1
    finally:
        eng.shutdown()


def test_mla_under_virtual_mesh():
    """MLA prefill + decode compile and execute under a dp x tp mesh: tp
    shards head-packed projections, the latent cache replicates over tp."""
    from llm_mcp_tpu.parallel.mesh import make_mesh
    from llm_mcp_tpu.parallel.sharding import (
        kv_cache_specs,
        llama_param_specs,
        shard_pytree,
    )

    cfg = get_config("tiny-mla")
    mesh = make_mesh("dp=2,tp=4", devices=jax.devices()[:8])
    params = shard_pytree(
        init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
        llama_param_specs(cfg), mesh,
    )
    cache = shard_pytree(
        init_kv_cache(cfg, 4, 64, dtype=jnp.float32),
        kv_cache_specs(latent=True), mesh,
    )
    with mesh:
        logits, _, _ = jax.jit(lambda p, t, l: llama_prefill(cfg, p, t, l))(
            params, jnp.ones((2, 16), jnp.int32), jnp.asarray([10, 7], jnp.int32)
        )
        dl, _, _ = jax.jit(
            lambda p, ck, cv, t, l: llama_decode_step(cfg, p, ck, cv, t, l)
        )(
            params, cache["k"], cache["v"], jnp.zeros((4,), jnp.int32),
            jnp.asarray([3, 5, 64, 64], jnp.int32),
        )
    assert np.asarray(logits).shape == (2, cfg.vocab_size)
    assert np.asarray(dl).shape == (4, cfg.vocab_size)
    assert bool(np.isfinite(np.asarray(dl)[:2]).all())


def test_int8_latent_cache_matches_bf16(setup):
    """int8 latents (per-token scales, post-dot folding) track the f32
    latent cache: identical greedy tokens, tightly correlated logits."""
    cfg, params = setup
    B, S = 2, 32
    cache = init_kv_cache(cfg, B, S, dtype=jnp.float32)
    qcache = init_kv_cache(cfg, B, S, dtype=jnp.float32, quantized=True)
    ck, cv = cache["k"], cache["v"]
    qck, qcv = qcache["k"], qcache["v"]
    assert qck["q"].dtype == jnp.int8
    t = jnp.array([3, 5], jnp.int32)
    lens = jnp.zeros((B,), jnp.int32)
    for _ in range(5):
        la, ck, cv = llama_decode_step(cfg, params, ck, cv, t, lens)
        lb, qck, qcv = llama_decode_step(cfg, params, qck, qcv, t, lens)
        ta = np.argmax(np.asarray(la), -1)
        tb = np.argmax(np.asarray(lb), -1)
        assert (ta == tb).all()
        corr = np.corrcoef(np.asarray(la).ravel(), np.asarray(lb).ravel())[0, 1]
        assert corr > 0.999, corr
        t = jnp.asarray(ta)
        lens = lens + 1


def test_mla_s8_kernel_matches_xla_path(setup):
    """decode_attend_q8_mla (absorbed s8-MXU attention, interpret mode on
    CPU) against the XLA dequant-then-dot path: identical greedy tokens,
    tightly correlated logits, and byte-identical cache appends — including
    compaction indirection and a parked row."""
    cfg, params = setup
    B, S = 4, 32
    qcache = init_kv_cache(cfg, B, S, dtype=jnp.float32, quantized=True)
    rng = np.random.default_rng(3)
    qck = {
        "k": {"q": jnp.asarray(rng.integers(-127, 128, qcache["k"]["q"].shape), jnp.int8),
              "s": jnp.asarray(rng.random(qcache["k"]["s"].shape, np.float32) * 0.01)},
        "v": {"q": jnp.asarray(rng.integers(-127, 128, qcache["v"]["q"].shape), jnp.int8),
              "s": jnp.asarray(rng.random(qcache["v"]["s"].shape, np.float32) * 0.01)},
    }
    # compact dispatch: rows 2 and 0 active, row 1 parked in the full form
    toks_c = jnp.asarray([3, 4], jnp.int32)
    lens_c = jnp.asarray([5, 9], jnp.int32)
    ids = jnp.asarray([2, 0], jnp.int32)
    l_x, ckx, cvx = llama_decode_step(
        cfg, params, qck["k"], qck["v"], toks_c, lens_c,
        slot_ids=ids, attn_impl="xla",
    )
    l_p, ckp, cvp = llama_decode_step(
        cfg, params, qck["k"], qck["v"], toks_c, lens_c,
        slot_ids=ids, attn_impl="pallas",
    )
    assert (np.argmax(np.asarray(l_x), -1) == np.argmax(np.asarray(l_p), -1)).all()
    corr = np.corrcoef(np.asarray(l_x).ravel(), np.asarray(l_p).ravel())[0, 1]
    assert corr > 0.999, corr
    # appended rows agree after dequant (±1 LSB payload differences are
    # expected: the two attention impls round differently, so downstream
    # layers' latents differ at f32 epsilon before quantization)
    from llm_mcp_tpu.kernels.attention import rope_apart

    def dequant(x):  # the rope keys lie P positions abreast: pulled apart, a scale a row
        q = rope_apart(x["q"], x["s"].shape[3] // x["q"].shape[3])
        return np.asarray(q, np.float32) * np.asarray(x["s"])[..., None]

    for a, b in ((ckx, ckp), (cvx, cvp)):
        da, db = dequant(a), dequant(b)
        denom = max(np.abs(da).max(), 1e-9)
        assert np.abs(da - db).max() / denom < 0.02
    # parked row (w >= S) writes nothing on either path
    toks_f = jnp.asarray([1, 0, 2, 0], jnp.int32)
    lens_f = jnp.asarray([4, S, 7, S], jnp.int32)  # rows 1,3 parked
    _, ckx2, _ = llama_decode_step(
        cfg, params, qck["k"], qck["v"], toks_f, lens_f, attn_impl="xla"
    )
    _, ckp2, _ = llama_decode_step(
        cfg, params, qck["k"], qck["v"], toks_f, lens_f, attn_impl="pallas"
    )
    np.testing.assert_array_equal(
        np.asarray(ckx2["q"])[:, 1], np.asarray(qck["k"]["q"])[:, 1]
    )
    np.testing.assert_array_equal(
        np.asarray(ckp2["q"])[:, 1], np.asarray(qck["k"]["q"])[:, 1]
    )
    np.testing.assert_array_equal(
        np.asarray(ckp2["q"])[:, 3], np.asarray(qck["k"]["q"])[:, 3]
    )


def test_mla_s8_kernel_v2_structure():
    """The kernel path composes with the DeepSeek-V2 structure: dense
    prologue + shared-expert MoE layers through the same scan."""
    cfg = get_config("tiny-v2")
    params = init_llama_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    B, S = 2, 32
    qc = init_kv_cache(cfg, B, S, dtype=jnp.float32, quantized=True)
    t = jnp.asarray([3, 5], jnp.int32)
    lens = jnp.zeros((B,), jnp.int32)
    lx, lp_, = None, None
    ck_x, cv_x = qc["k"], qc["v"]
    ck_p, cv_p = qc["k"], qc["v"]
    for _ in range(3):
        lx, ck_x, cv_x = llama_decode_step(
            cfg, params, ck_x, cv_x, t, lens, attn_impl="xla"
        )
        lp_, ck_p, cv_p = llama_decode_step(
            cfg, params, ck_p, cv_p, t, lens, attn_impl="pallas"
        )
        ta = np.argmax(np.asarray(lx), -1)
        assert (ta == np.argmax(np.asarray(lp_), -1)).all()
        t = jnp.asarray(ta)
        lens = lens + 1
    da = np.asarray(ck_x["q"], np.float32) * np.asarray(ck_x["s"])[..., None]
    db = np.asarray(ck_p["q"], np.float32) * np.asarray(ck_p["s"])[..., None]
    assert np.abs(da - db).max() / max(np.abs(da).max(), 1e-9) < 0.02


def test_int8_latent_prefill_roundtrip(setup):
    """quant_kv prefill returns int8 latent dicts whose dequantized rows
    track the f32 prefill latents."""
    cfg, params = setup
    toks = jnp.asarray([[7, 8, 9, 10, 0, 0]], jnp.int32)
    lens = jnp.asarray([4], jnp.int32)
    _, cs, rs = llama_prefill(cfg, params, toks, lens)
    _, qcs, qrs = llama_prefill(cfg, params, toks, lens, quant_kv=True)
    assert qcs["q"].dtype == jnp.int8 and qcs["q"].shape == cs.shape
    deq = np.asarray(qcs["q"], np.float32) * np.asarray(qcs["s"])[..., None]
    ref = np.asarray(cs)
    # compare only the valid prompt rows
    err = np.abs(deq[:, :, :, :4] - ref[:, :, :, :4]).max()
    assert err < np.abs(ref[:, :, :, :4]).max() * 0.02


def test_engine_serves_mla_int8_latents():
    """Full engine with quant=int8 weights AND kv_quant=int8 latents:
    greedy determinism and compaction both engage."""
    eng = GenerationEngine(
        "tiny-mla", max_slots=16, max_seq_len=128, dtype=jnp.float32,
        decode_chunk=2, quant="int8", kv_quant="int8",
    ).start()
    try:
        assert eng.kv_quant == "int8"
        assert eng.decode_compact  # auto: int8 cache, single chip
        a = eng.generate("int8 latents", max_tokens=8, temperature=0.0)
        b = eng.generate("int8 latents", max_tokens=8, temperature=0.0)
        assert a["text"] == b["text"]
        assert a["usage"]["completion_tokens"] >= 1
    finally:
        eng.shutdown()


def test_mla_soak_churn_parity():
    """MLA variant of the churn soak: concurrent mixed prompts through
    whole-prompt prefill + compaction + int8 latents must match a one-slot
    sequential MLA engine token-for-token."""
    import concurrent.futures as cf

    full = GenerationEngine(
        "tiny-mla", max_slots=16, max_seq_len=192, dtype=jnp.float32,
        decode_chunk=4, kv_quant="int8", decode_compact="on",
        admit_batch=4, seed=11,
    ).start()
    plain = GenerationEngine(
        "tiny-mla", max_slots=1, max_seq_len=192, dtype=jnp.float32,
        decode_chunk=4, kv_quant="int8", decode_compact="off", seed=11,
    ).start()
    try:
        cases = [(f"mla churn {i} " * (1 + i % 5), 2 + i % 5) for i in range(24)]

        def run_one(i):
            p, n = cases[i]
            return full.generate(p, max_tokens=n, temperature=0.0)["text"]

        with cf.ThreadPoolExecutor(max_workers=len(cases)) as ex:
            got = list(ex.map(run_one, range(len(cases))))
        for i, (p, n) in enumerate(cases):
            want = plain.generate(p, max_tokens=n, temperature=0.0)["text"]
            assert got[i] == want, (i, p[:30])
        assert full.total_errors == 0
    finally:
        full.shutdown()
        plain.shutdown()


def test_mla_prefill_chunk_matches_full(setup):
    """Chunked MLA prefill (absorbed past-vs-cache + exact self segment)
    must reproduce whole-prompt mla_prefill: same latent/rope-key cache
    rows, same final logits — including a ragged last chunk and a nonzero
    slot."""
    from llm_mcp_tpu.models.llama import llama_prefill_chunk_batch

    cfg, params = setup
    P = 11  # 4 + 4 + ragged 3
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 3, cfg.vocab_size)
    lengths = jnp.array([P], dtype=jnp.int32)
    full_logits, cs, rs = llama_prefill(cfg, params, prompt, lengths)

    cache = init_kv_cache(cfg, 2, 32, dtype=jnp.float32)
    ck, cv = cache["k"], cache["v"]
    logits = None
    for start, n in ((0, 4), (4, 4), (8, 3)):
        chunk = jnp.zeros((1, 4), jnp.int32).at[0, :n].set(
            prompt[0, start : start + n]
        )
        logits, ck, cv = llama_prefill_chunk_batch(
            cfg, params, ck, cv, chunk,
            jnp.asarray([1], jnp.int32), jnp.asarray([start], jnp.int32),
            jnp.asarray([n], jnp.int32), skey=16,
        )
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(full_logits[0]), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(ck[:, 1, :, :P]), np.asarray(cs[:, 0, :, :P]),
        rtol=2e-4, atol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(cv[:, 1, :, :P]), np.asarray(rs[:, 0, :, :P]),
        rtol=2e-4, atol=2e-4,
    )
    assert not np.asarray(ck[:, 0]).any()  # untouched slot stays zero


def test_mla_prefill_chunk_int8_cache(setup):
    """Chunked MLA prefill into int8 latents: bounded quantization error,
    greedy token preserved (past segment dequants post-dot)."""
    from llm_mcp_tpu.models.llama import llama_prefill_chunk_batch

    cfg, params = setup
    P = 8
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 8), 3, cfg.vocab_size)
    full_logits, _, _ = llama_prefill(
        cfg, params, prompt, jnp.array([P], dtype=jnp.int32)
    )
    qc = init_kv_cache(cfg, 1, 16, dtype=jnp.float32, quantized=True)
    ck, cv = qc["k"], qc["v"]
    logits = None
    for start in (0, 4):
        logits, ck, cv = llama_prefill_chunk_batch(
            cfg, params, ck, cv, prompt[:, start : start + 4],
            jnp.asarray([0], jnp.int32), jnp.asarray([start], jnp.int32),
            jnp.asarray([4], jnp.int32), skey=8,
        )
    a, b = np.asarray(logits[0]), np.asarray(full_logits[0])
    assert np.argmax(a) == np.argmax(b)
    np.testing.assert_allclose(a, b, rtol=0.1, atol=0.35)


def test_mla_chunk_batched_two_slots(setup):
    """A=2 batched chunk dispatch writes each slot's rows independently and
    returns per-row logits matching the A=1 path."""
    from llm_mcp_tpu.models.llama import llama_prefill_chunk_batch

    cfg, params = setup
    prompts = jax.random.randint(jax.random.PRNGKey(5), (2, 4), 3, cfg.vocab_size)
    full_logits, cs, rs = llama_prefill(
        cfg, params, prompts, jnp.array([4, 4], dtype=jnp.int32)
    )
    cache = init_kv_cache(cfg, 4, 16, dtype=jnp.float32)
    logits, ck, cv = llama_prefill_chunk_batch(
        cfg, params, cache["k"], cache["v"], prompts,
        jnp.asarray([2, 0], jnp.int32), jnp.asarray([0, 0], jnp.int32),
        jnp.asarray([4, 4], jnp.int32), skey=8,
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(ck[:, 2, :, :4]), np.asarray(cs[:, 0, :, :4]),
        rtol=2e-4, atol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(ck[:, 0, :, :4]), np.asarray(cs[:, 1, :, :4]),
        rtol=2e-4, atol=2e-4,
    )


def test_engine_serves_mla_chunked_with_prefix_cache():
    """MLA through the engine with chunked prefill enabled: long prompts
    ride _prefill_round, a repeated long prefix hits the prompt-prefix KV
    cache, and greedy output matches a chunking-disabled engine."""
    kw = dict(
        max_slots=4, max_seq_len=192, dtype=jnp.float32, decode_chunk=4,
        admit_batch=2,
    )
    a = GenerationEngine("tiny-mla", prefill_chunk=8, **kw).start()
    b = GenerationEngine("tiny-mla", prefill_chunk=0, **kw).start()
    try:
        assert a._prefix_budget > 0  # chunked prefill unlocks the cache
        prefix = "shared system preamble " * 12  # > PREFIX_MIN tokens
        outs_a = [
            a.generate(prefix + f"q{i}", max_tokens=6, temperature=0.0)["text"]
            for i in range(3)
        ]
        outs_b = [
            b.generate(prefix + f"q{i}", max_tokens=6, temperature=0.0)["text"]
            for i in range(3)
        ]
        assert outs_a == outs_b
        assert a.prefix_cache_hits >= 1
        assert a.total_errors == 0
    finally:
        a.shutdown()
        b.shutdown()


def test_v2_chunk_matches_full_without_drops():
    """tiny-v2 (dense prologue + shared-expert MoE + yarn) chunked prefill
    is EXACTLY the whole-prompt program when expert capacity never drops
    (capacity_factor high enough for every token). At serving capacity
    factors chunking legitimately changes which tokens compete per dispatch
    (GShard drop sets differ), so exact parity is asserted drop-free."""
    import dataclasses

    from llm_mcp_tpu.models.llama import llama_prefill_chunk_batch

    cfg = dataclasses.replace(get_config("tiny-v2"), capacity_factor=100.0)
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    P = 11
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 3, cfg.vocab_size)
    full_logits, cs, rs = llama_prefill(
        cfg, params, prompt, jnp.array([P], jnp.int32)
    )
    cache = init_kv_cache(cfg, 2, 32, dtype=jnp.float32)
    ck, cv = cache["k"], cache["v"]
    logits = None
    for start, n in ((0, 4), (4, 4), (8, 3)):
        chunk = jnp.zeros((1, 4), jnp.int32).at[0, :n].set(
            prompt[0, start : start + n]
        )
        logits, ck, cv = llama_prefill_chunk_batch(
            cfg, params, ck, cv, chunk,
            jnp.asarray([1], jnp.int32), jnp.asarray([start], jnp.int32),
            jnp.asarray([n], jnp.int32), skey=16,
        )
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(full_logits[0]), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(ck[:, 1, :, :P]), np.asarray(cs[:, 0, :, :P]),
        rtol=2e-4, atol=2e-4,
    )


def test_engine_serves_v2_chunked():
    """tiny-v2 through the engine with chunked prefill: long prompts ride
    _prefill_round and serve cleanly (exact-output parity vs whole-prompt
    is not expected at serving capacity factors — see the drop-free test)."""
    eng = GenerationEngine(
        "tiny-v2", max_slots=2, max_seq_len=128, dtype=jnp.float32,
        decode_chunk=4, prefill_chunk=8,
    ).start()
    try:
        prompt = "deepseek v2 chunked prefill serving check " * 3
        out = eng.generate(prompt, max_tokens=6, temperature=0.0)
        out2 = eng.generate(prompt, max_tokens=6, temperature=0.0)
        assert out["text"] == out2["text"]  # deterministic under greedy
        assert out["usage"]["completion_tokens"] >= 1
        assert eng.total_errors == 0
    finally:
        eng.shutdown()


def test_mla_blocked_kernel_matches_fallback(monkeypatch):
    """The BLOCKED long-context MLA kernel (manual-DMA double buffering,
    dynamic trip count) matches the exact-f32 fallback — forced via the
    VMEM-fit seam so shapes stay CPU-small while interpret mode emulates
    the real DMA loop. S=384 forces BS=128 (384 is not divisible by 512
    or 256), so rows at lens 128/380 stream MULTIPLE blocks — the
    double-buffered prefetch and cross-block online-softmax accumulation
    actually execute. Lengths cover block boundaries, the compaction
    indirection, and a parked row."""
    import llm_mcp_tpu.kernels.attention as A

    monkeypatch.setattr(A, "mla_whole_s_fits", lambda *a: False)
    rng = np.random.default_rng(7)
    L, B, S, R, dr, H = 2, 4, 384, 32, 16, 4

    def q8(shape):
        return {
            "q": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "s": jnp.asarray(rng.random(shape[:-1], np.float32) * 0.01),
        }

    cache_c = q8((L, B, 1, S, R))
    cache_r = q8((L, B, 1, S, dr))
    qt = jnp.asarray(rng.standard_normal((B, H, R)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((B, H, dr)), jnp.float32)
    nc = jnp.asarray(rng.standard_normal((B, R)), jnp.float32)
    nr = jnp.asarray(rng.standard_normal((B, dr)), jnp.float32)
    # boundaries: first block, boundary-1, boundary (2 blocks), deep in
    # the third block (3-block dynamic trip count)
    lens = jnp.asarray([0, 127, 128, 380], jnp.int32)
    for ids in (None, jnp.asarray([3, 1, 0, 2], jnp.int32)):
        out = A.decode_attend_q8_mla(
            qt, qr, nc, nr, cache_c, cache_r, jnp.int32(1), lens,
            slot_ids=ids, scale=0.17, interpret=True,
        )
        ref = A._decode_attend_q8_mla_fallback(
            qt, qr, nc, nr, cache_c, cache_r, jnp.int32(1), lens, 0.17, ids
        )
        assert float(jnp.max(jnp.abs(out - ref))) < 0.05
        assert not bool(jnp.isnan(out).any())
    # parked row (w >= S): finite discarded output, one streamed block
    lens_p = jnp.asarray([S, 10, 5, 60], jnp.int32)
    out = A.decode_attend_q8_mla(
        qt, qr, nc, nr, cache_c, cache_r, jnp.int32(0), lens_p,
        scale=0.17, interpret=True,
    )
    assert not bool(jnp.isnan(out).any())
    ref = A._decode_attend_q8_mla_fallback(
        qt, qr, nc, nr, cache_c, cache_r, jnp.int32(0), lens_p, 0.17, None
    )
    assert float(jnp.max(jnp.abs(out[1:] - ref[1:]))) < 0.05
