"""Engine tests: continuous batching, streaming, stop conditions, embeddings.

These exercise the decode hot loop end-to-end on the CPU backend with the
tiny model config — same code paths as TPU serving (SURVEY.md §4 notes the
reference has no such in-process tests; we exceed it).
"""

import concurrent.futures as cf
import contextlib
import functools
import os
import sys
import tempfile
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.executor import GenerationEngine, EmbeddingEngine
from llm_mcp_tpu.executor.engine import _DONE, GenRequest
from llm_mcp_tpu.executor.tokenizer import ByteTokenizer
from llm_mcp_tpu.telemetry import recorder as flight
from llm_mcp_tpu.telemetry.recorder import FlightRecorder
from llm_mcp_tpu.utils import faults


@pytest.fixture(scope="module")
def engine():
    eng = GenerationEngine(
        "tiny-llm", max_slots=4, max_seq_len=128, dtype=jnp.float32, decode_chunk=4
    ).start()
    yield eng
    eng.shutdown()


def test_generate_basic(engine):
    out = engine.generate("hello", max_tokens=8, temperature=0.0)
    assert out["usage"]["completion_tokens"] <= 8
    assert out["usage"]["prompt_tokens"] == len(engine.tokenizer.encode("hello"))
    assert out["finish_reason"] in ("stop", "length")


def test_phase_budget_accumulates(engine):
    """The `phase_s` block of /v1/dashboard relies on this contract: phase
    keys are stable, values accumulate monotonically, and generation moves
    at least the dispatch/fetch/emit phases."""
    before = engine.phase_budget()
    assert set(before) == {"dispatch", "fetch", "admit", "prefill", "emit", "idle"}
    engine.generate("phase budget probe", max_tokens=6, temperature=0.0)
    after = engine.phase_budget()
    assert all(after[k] >= before[k] for k in before)
    assert after["dispatch"] > before["dispatch"]
    assert after["fetch"] > before["fetch"]
    assert after["emit"] > before["emit"]


def test_engine_counts_finished_and_errors():
    """The counters `/v1/dashboard` and the shed's Retry-After estimate read
    move with real engine lifecycles."""
    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=128, dtype=jnp.float32,
        decode_chunk=2,
    ).start()
    try:
        out = eng.generate("count me", max_tokens=5, temperature=0.0)
        assert eng.finished_requests == 1
        assert eng.finished_tokens == out["usage"]["completion_tokens"]
        assert eng.total_errors == 0
    finally:
        eng.shutdown()


def test_generate_deterministic_greedy(engine):
    a = engine.generate("same prompt", max_tokens=12, temperature=0.0)
    b = engine.generate("same prompt", max_tokens=12, temperature=0.0)
    assert a["text"] == b["text"]


def test_streaming_events(engine):
    events = list(engine.generate_stream("stream me", max_tokens=6, temperature=0.0))
    assert events[-1]["type"] == "done"
    tokens = [e for e in events if e["type"] == "token"]
    assert len(tokens) >= 1
    assert "usage" in events[-1]
    assert events[-1]["ttft_ms"] >= 0


def test_max_tokens_respected(engine):
    out = engine.generate("count", max_tokens=3, temperature=0.0)
    assert out["usage"]["completion_tokens"] <= 3


def test_concurrent_requests_continuous_batching(engine):
    def gen(i):
        return engine.generate(f"prompt number {i}", max_tokens=10, temperature=0.0)

    with cf.ThreadPoolExecutor(max_workers=6) as ex:
        results = list(ex.map(gen, range(6)))
    assert len(results) == 6
    for r in results:
        assert r["usage"]["completion_tokens"] >= 1
    # batching stats recorded
    assert engine.total_requests >= 6
    assert engine.total_tokens > 0


def test_concurrent_matches_sequential(engine):
    """Continuous batching must not change greedy outputs (slot isolation)."""
    seq = [engine.generate(f"isolation {i}", max_tokens=8, temperature=0.0)["text"] for i in range(3)]
    with cf.ThreadPoolExecutor(max_workers=3) as ex:
        conc = list(ex.map(lambda i: engine.generate(f"isolation {i}", max_tokens=8, temperature=0.0)["text"], range(3)))
    assert seq == conc


def test_long_prompt_truncation(engine):
    long_prompt = "x" * 5000  # way beyond max_seq_len=128
    out = engine.generate(long_prompt, max_tokens=4, temperature=0.0)
    assert out["usage"]["prompt_tokens"] <= 126


def test_stop_sequences():
    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=64, dtype=jnp.float32, decode_chunk=2
    ).start()
    try:
        out = eng.generate("q", max_tokens=50, temperature=1.0, stop=["zzz-never"])
        assert out["finish_reason"] in ("stop", "length")
    finally:
        eng.shutdown()


def test_stop_sequence_trimmed_from_output(engine):
    """The stop string must never be delivered (OpenAI/Ollama semantics):
    generate without stop, pick a substring of the output as the stop, rerun
    greedy and check the output ends right before it."""
    full = engine.generate("trim test", max_tokens=24, temperature=0.0)["text"]
    if len(full) < 4:
        pytest.skip("model emitted too little text to derive a stop string")
    stop = full[len(full) // 2 : len(full) // 2 + 2]
    out = engine.generate("trim test", max_tokens=24, temperature=0.0, stop=[stop])
    assert stop not in out["text"]
    assert full.startswith(out["text"])


def test_max_tokens_zero(engine):
    out = engine.generate("zero", max_tokens=0, temperature=0.0)
    assert out["usage"]["completion_tokens"] == 0
    assert out["text"] == ""


def test_shutdown_unblocks_waiters():
    eng = GenerationEngine(
        "tiny-llm", max_slots=1, max_seq_len=64, dtype=jnp.float32, decode_chunk=2
    ).start()
    import threading

    results = []

    def gen():
        try:
            results.append(eng.generate("x" * 40, max_tokens=1000, temperature=0.5))
        except RuntimeError as e:
            results.append(e)

    threads = [threading.Thread(target=gen) for _ in range(3)]
    for t in threads:
        t.start()
    eng.shutdown()
    for t in threads:
        t.join(timeout=15)
    assert all(not t.is_alive() for t in threads), "waiters must not deadlock on shutdown"
    assert len(results) == 3


def test_byte_tokenizer_stream_utf8():
    tok = ByteTokenizer()
    ids = tok.encode("héllo ⚡", add_bos=False)
    # feed one id at a time; concatenation must reproduce the string
    pending, text = b"", ""
    for i in ids:
        t, pending = tok.decode_stream(pending, [i])
        text += t
    assert text == "héllo ⚡"
    assert pending == b""


def test_fine_prefill_buckets_parity():
    """The fine (pow2 + 1.5x midpoint) admission-bucket ladder: rung values,
    sp-divisibility fallback, and greedy parity with the pow2 ladder on a
    prompt that lands in a midpoint rung."""
    from llm_mcp_tpu.executor.common import fine_bucket

    assert [fine_bucket(n, 2048) for n in (1, 33, 49, 65, 100, 200, 300, 600)] \
        == [32, 48, 64, 96, 128, 256, 384, 768]
    assert fine_bucket(5000, 2048) == 2048

    ef = GenerationEngine("tiny-llm", max_slots=2, max_seq_len=512,
                          dtype=jnp.float32, decode_chunk=4).start()
    ep = GenerationEngine("tiny-llm", max_slots=2, max_seq_len=256,
                          dtype=jnp.float32, decode_chunk=4,
                          prefill_buckets="pow2").start()
    try:
        assert ef.prefill_fine and not ep.prefill_fine
        assert ef._bucket(33) == 48 and ep._bucket(33) == 64
        # pallas prefill gate: rungs that aren't legal flash block shapes
        # (192; sub-128 non-pow2) fall back to the pow2 rung, while
        # 128-multiple midpoints (384) stay fine
        orig_impl = ef.attn_impl
        ef.attn_impl = "pallas"
        try:
            assert ef._bucket(33) == 64  # 48 not pow2 below one block
            assert ef._bucket(130) == 256  # 192 % 128 != 0
            assert ef._bucket(260) == 384  # legal 128-multiple midpoint
        finally:
            ef.attn_impl = orig_impl
        # sp-divisibility gate: a rung the sp axis can't divide falls back
        orig_sp = ef.sp
        ef.sp = 32
        try:
            assert ef._bucket(33) == 64  # 48 % 32 != 0 → pow2 rung
        finally:
            ef.sp = orig_sp
        prompt = "x " * 40  # straddles the 48/96 midpoint rungs
        a = ef.generate(prompt, max_tokens=6, temperature=0.0)
        b = ep.generate(prompt, max_tokens=6, temperature=0.0)
        assert a["text"] == b["text"]
    finally:
        ef.shutdown()
        ep.shutdown()


def test_embedding_engine_basic():
    eng = EmbeddingEngine("tiny-embed", max_batch=4, max_seq_len=64, dtype=jnp.float32)
    vecs, tokens = eng.embed(["hello world", "second text", "third"])
    assert len(vecs) == 3
    assert len(vecs[0]) == eng.cfg.dim
    assert tokens > 0
    np.testing.assert_allclose(np.linalg.norm(vecs, axis=1), 1.0, rtol=1e-4)


def test_embedding_engine_int8_matches_float():
    """quant="int8" quantizes a supplied tree; vectors must stay directionally
    faithful to the float engine (the 8B-class embedder only fits a 16 GB
    chip quantized — BASELINE config #4)."""
    from llm_mcp_tpu.models.embedder import init_embedder_params

    import jax

    from llm_mcp_tpu.models import get_config

    cfg = get_config("tiny-embed")
    params = init_embedder_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    f_eng = EmbeddingEngine(cfg, params=params, max_batch=4, max_seq_len=64,
                            dtype=jnp.float32)
    q_eng = EmbeddingEngine(cfg, params=params, max_batch=4, max_seq_len=64,
                            dtype=jnp.float32, quant="int8")
    texts = ["int8 embedder parity", "second probe text"]
    fv, _ = f_eng.embed(texts)
    qv, _ = q_eng.embed(texts)
    for a, b in zip(fv, qv):
        cos = float(np.dot(a, b))
        assert cos > 0.99, cos


def test_embedding_engine_direct_int8_init():
    """quant="int8" with no params: the direct-quantized init path (no bf16
    tree ever materializes) produces unit-norm finite vectors."""
    eng = EmbeddingEngine("tiny-embed", max_batch=4, max_seq_len=64,
                          dtype=jnp.float32, quant="int8")
    vecs, tokens = eng.embed(["direct int8 init", "another"])
    assert len(vecs) == 2 and tokens > 0
    arr = np.asarray(vecs)
    assert np.isfinite(arr).all()
    np.testing.assert_allclose(np.linalg.norm(arr, axis=1), 1.0, rtol=1e-4)


def test_embedding_matryoshka_dimensions():
    eng = EmbeddingEngine("tiny-embed", max_batch=4, max_seq_len=64, dtype=jnp.float32)
    full, _ = eng.embed(["same input"])
    trunc, _ = eng.embed(["same input"], dimensions=16)
    assert len(trunc[0]) == 16
    np.testing.assert_allclose(np.linalg.norm(trunc, axis=1), 1.0, rtol=1e-4)
    # direction preserved: truncated+renormalized equals manual computation
    manual = np.array(full[0][:16])
    manual /= np.linalg.norm(manual)
    np.testing.assert_allclose(trunc[0], manual, rtol=1e-4)


def test_embedding_batch_buckets():
    """Batch sizes pad to pow2 buckets: 5/6/7/8 inputs share ONE executable
    shape (VERDICT r2 weak #7 — each ragged final chunk used to compile
    fresh), and pad-row vectors are dropped from the output."""
    eng = EmbeddingEngine("tiny-embed", max_seq_len=128, dtype=jnp.float32)
    shapes = []
    orig = eng._fwd
    eng._fwd = lambda p, t, l: (shapes.append(t.shape), orig(p, t, l))[1]
    for n in (5, 6, 7, 8):
        vecs, _ = eng.embed([f"bucket test input {i}" for i in range(n)])
        assert len(vecs) == n
    assert {s[0] for s in shapes} == {8}


def test_embedding_batch_exceeds_max_batch():
    eng = EmbeddingEngine("tiny-embed", max_batch=2, max_seq_len=64, dtype=jnp.float32)
    vecs, _ = eng.embed([f"text {i}" for i in range(5)])
    assert len(vecs) == 5
    # same text embeds identically regardless of batch position
    a, _ = eng.embed(["anchor", "other1", "other2"])
    b, _ = eng.embed(["anchor"])
    np.testing.assert_allclose(a[0], b[0], rtol=1e-4, atol=1e-5)


# -- sequence packing (PR 31): the planner and the engine around it --------------


def _cell_lengths(seed=7_777_777, request=0):
    """Token counts of one embed_batch request, as the benchmark's generator
    deals them (32 of a deck of 64 log-normal quantiles, 64-512)."""
    import json
    import os

    from benchmark import trafficgen

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "traffic", "embed_batch.json")) as f:
        traffic = json.load(f)
    plan = trafficgen.make_plan(traffic, seed, 40.0, model="m")
    return [size for size, _rank in plan["requests"][request]["prompt"]]


def _shape_spy(eng):
    """Replace the forward by one that records (tokens.shape, lengths) and
    computes nothing: the planner's tests need shapes, not vectors."""
    calls = []

    def fwd(params, tokens, lengths):
        calls.append((tokens.shape, np.array(lengths)))
        return jnp.zeros(lengths.shape + (eng.cfg.dim,), jnp.float32)  # a jax.Array, as `_fwd` gives

    eng._fwd = fwd
    return calls


@pytest.fixture(scope="module")
def packing_engine():
    return EmbeddingEngine("tiny-qwen3", max_seq_len=512, dtype=jnp.float32)


def test_pack_rows_first_fit_longest_first():
    from llm_mcp_tpu.executor.embedding import pack_rows

    lens = [100, 500, 30, 300, 212, 12, 12, 400]
    rows = pack_rows(lens, 512, 16)
    assert rows == [[1, 5], [7, 0, 6], [3, 4], [2]]
    assert all(sum(lens[i] for i in row) <= 512 for row in rows)
    # at most `per_row` texts a row, and with one a row: the texts, longest first
    assert [len(r) for r in pack_rows([8] * 10, 512, 4)] == [4, 4, 2]
    assert pack_rows(lens, 512, 1) == [[1], [7], [3], [4], [0], [2], [5], [6]]


def test_embedding_packed_answers_in_callers_order(packing_engine):
    """Shuffled lengths through the REAL forward: every text's vector equals
    the vector of the same text sent alone, at its place in the answer."""
    eng = packing_engine
    rng = np.random.default_rng(3)
    texts = ["".join(chr(97 + int(c)) for c in rng.integers(0, 26, int(n)))
             for n in (200, 9, 130, 77, 300, 40, 41, 5, 250, 64)]
    before = eng.stats(recent=False)
    vecs, total = eng.embed(texts)
    st = eng.stats()
    assert total == sum(len(eng.prepare_ids(t)) for t in texts)
    assert st["rows"] - before["rows"] == 10
    assert st["rows_packed"] - before["rows_packed"] == 3  # 1,116 tokens in rows of 512
    assert st["rows_padded"] - before["rows_padded"] == 4
    assert st["true_tokens"] - before["true_tokens"] == total
    assert st["padded_tokens"] - before["padded_tokens"] == 4 * 512
    assert all(len(r) == 3 for r in st["recent"])  # (t, forward_s, host_locked_s)
    for i in (0, 1, 4, 7, 9):
        alone, _ = eng.embed([texts[i]])
        np.testing.assert_allclose(vecs[i], alone[0], rtol=1e-4, atol=1e-5)
    # Matryoshka on a packed call: truncate, renormalise, same order
    cut, _ = eng.embed(texts, dimensions=16)
    want = np.asarray(vecs)[:, :16]
    want /= np.linalg.norm(want, axis=1, keepdims=True)
    np.testing.assert_allclose(cut, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize(
    "lens,shapes,rows_packed",
    [
        # a request of the benchmark's cell: 32 texts in 15 rows, equal forwards of one shape
        (None, [(2, 512)] * 8, 15),
        # over the cap: EQUAL forwards of one shape, not one of the next bucket
        ([300] * 3, [(2, 512)] * 2, 3),
        ([300] * 17, [(2, 512)] * 9, 17),
        # a single short text reaches the shape it reached before packing
        ([5], [(1, 32)], 1),
        ([200], [(1, 256)], 1),
        ([512], [(1, 512)], 1),
        # texts that fit one row of their own bucket share it
        ([10, 10, 10], [(1, 32)], 1),
        # two texts just over a bucket: the row grows to their sum's bucket, not to 512
        ([33, 33], [(1, 128)], 1),
        # many short texts: 16 a row, never more positions than one text a row took
        ([20] * 64, [(2, 512)] * 2, 4),
    ],
)
def test_embedding_plan_shapes(lens, shapes, rows_packed):
    from llm_mcp_tpu.executor.embedding import FORWARD_TOKENS

    eng = EmbeddingEngine("tiny-qwen3", max_seq_len=512, dtype=jnp.float32)
    calls = _shape_spy(eng)
    lens = lens or _cell_lengths()
    # the byte tokenizer: one token a byte and a BOS in front
    texts = ["x" * (n - 1) for n in lens]
    assert [len(eng.prepare_ids(t)) for t in texts] == lens
    vecs, total = eng.embed(texts)
    assert len(vecs) == len(lens) and total == sum(lens)
    assert [c[0] for c in calls] == shapes
    assert all(c[0][0] * c[0][1] <= FORWARD_TOKENS for c in calls)
    st = eng.stats()
    assert (st["forwards"], st["rows"], st["rows_packed"]) == (len(shapes), len(lens), rows_packed)
    assert st["rows_padded"] == sum(s[0] for s in shapes)
    assert st["true_tokens"] == sum(lens) and len(st["recent"]) == len(shapes)
    # what the benchmark's wrapper reads: lengths.sum() is the true tokens and
    # one place of 1 for each padding row
    assert sum(int(c[1].sum()) for c in calls) == sum(lens) + st["rows_padded"] - rows_packed
    # every text sits whole in one row, in its place's span
    for _shape, lengths in calls:
        assert (lengths.sum(axis=1) <= shapes[0][1]).all()


def test_embedding_cell_requests_reach_one_shape():
    """Requests of the cell under the warm-up's seed and another: whatever
    the row count (12-18), every forward is (2, 512)."""
    eng = EmbeddingEngine("tiny-qwen3", max_seq_len=512, dtype=jnp.float32)
    seen = set()
    for seed in (7_777_777, 4_100_002_913):
        for k in range(40):
            row_len, rows_fwd, forwards = eng.plan(_cell_lengths(seed, k))
            rows = sum(len(f) for f in forwards)
            assert 12 <= rows <= 18 and len(forwards) == -(-rows // 2)
            seen.add((rows_fwd, row_len))
    assert seen == {(2, 512)}


def test_embedding_long_context_engine_packs_short_texts_into_short_rows():
    """max_seq_len 8,192: short texts get rows of 512, not rows whose
    attention is quadratic in 8,192; a long text gets the row it needs and
    a forward carries fewer of them."""
    eng = EmbeddingEngine("tiny-qwen3", max_seq_len=8192, dtype=jnp.float32)
    row_len, rows_fwd, forwards = eng.plan([100] * 40)
    assert (row_len, rows_fwd, [len(f) for f in forwards]) == (512, 2, [2, 2, 2, 2])
    row_len, rows_fwd, forwards = eng.plan([3000, 100, 100, 2000, 2000])
    assert (row_len, rows_fwd) == (4096, 1) and [len(f) for f in forwards] == [1, 1]
    row_len, rows_fwd, forwards = eng.plan([8192] * 3)
    assert (row_len, rows_fwd) == (8192, 1) and [len(f) for f in forwards] == [1, 1, 1]


def test_embedding_encoder_arch_keeps_one_text_a_row():
    """The bidirectional encoders know no segments: one text a row, the
    length bucket of the longest, `max_batch` the cap on a forward's rows."""
    eng = EmbeddingEngine("tiny-embed", max_batch=4, max_seq_len=128, dtype=jnp.float32)
    assert eng.texts_per_row == 1
    calls = _shape_spy(eng)
    vecs, _ = eng.embed(["a" * n for n in (3, 60, 10, 20, 5)])
    assert len(vecs) == 5
    assert [c[0] for c in calls] == [(4, 64), (4, 64)]  # 3 + 2 rows, one shape
    assert all(c[1].shape == (4, 1) for c in calls)
    st = eng.stats(recent=False)
    assert st["rows"] == st["rows_packed"] == 5 and st["rows_padded"] == 8


class _GatedOut:
    """What a forward returns, ready when the test opens its gate: the three
    calls `embed` makes on a `jax.Array` (is it ready, start the copy, read)."""

    def __init__(self, value, by, ready, fail):
        self.value, self.by, self.fail = value, by, fail
        self.gate, self.fetching = threading.Event(), threading.Event()
        if ready:
            self.gate.set()

    def is_ready(self):
        return self.gate.is_set()

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        self.fetching.set()
        assert self.gate.wait(60.0), "the test never opened this forward's gate"
        if self.fail:
            raise RuntimeError("forward lost")
        return self.value.astype(dtype)


class _GatedForwards:
    """Stands in for `eng._fwd`: computes at once (the real program, read back
    here) and hands out a `_GatedOut`, kept in `outs` in dispatch order.
    `born_ready`: the gates of new forwards start open; `fail_next`: the next
    forward's fetch raises."""

    def __init__(self, eng):
        self.real, self.outs, self.born_ready, self.fail_next = eng._fwd, [], False, False
        eng._fwd = self

    def __call__(self, params, tokens, lengths):
        value = np.asarray(self.real(params, tokens, lengths))
        out = _GatedOut(value, threading.current_thread().name, self.born_ready, self.fail_next)
        self.fail_next = False
        self.outs.append(out)
        return out

    def wait(self, n, fetching=None, timeout=60.0):
        """Until `n` forwards are dispatched (and `outs[fetching]` is being read)."""
        t_end = time.monotonic() + timeout
        while len(self.outs) < n or (fetching is not None and not self.outs[fetching].fetching.is_set()):
            assert time.monotonic() < t_end, f"{len(self.outs)} forwards dispatched, waiting for {n}"
            time.sleep(0.005)

    def open(self, order=iter):
        for out in order(self.outs):
            out.gate.set()


def _gated_engine():
    """tiny-embed with two rows a forward (three texts are two forwards), two
    callers' texts and the vectors each gets alone; then the gates go in."""
    eng = EmbeddingEngine("tiny-embed", max_batch=2, max_seq_len=64, dtype=jnp.float32)
    texts = {"A": ["alpha", "a much longer second text", "a3"], "B": ["bravo one", "b2", "the third of B"]}
    alone = {k: eng.embed(v)[0] for k, v in texts.items()}
    return eng, texts, alone, _GatedForwards(eng)


def test_embedding_lock_is_free_during_a_fetch():
    """PR 53: a call dispatches all its forwards under the lock and fetches
    them with the lock free, so a second caller's first forward is dispatched
    BEFORE the first caller's first fetch returns; each gets its own vectors
    in its own order."""
    eng, texts, alone, fwd = _gated_engine()
    with cf.ThreadPoolExecutor(2) as pool:
        fa = pool.submit(eng.embed, texts["A"])
        fwd.wait(2, fetching=0)
        assert not eng._lock.locked()
        fb = pool.submit(eng.embed, texts["B"])
        fwd.wait(4)  # B's forwards dispatched while A stands in its first fetch
        assert not fa.done() and not fb.done() and not fwd.outs[1].fetching.is_set()
        by = [out.by for out in fwd.outs]
        assert by[0] == by[1] != by[2] == by[3]  # lock order, a call whole
        fwd.open(reversed)  # ready in any order: a call reads its own in its own order
        got = {"A": fa.result(60.0)[0], "B": fb.result(60.0)[0]}
    assert got == alone
    assert eng._inflight == 0 and not eng._lock.locked()


def test_embedding_ahead_and_inflight_against_a_hand_count():
    """`ahead` counts a forward dispatched while the one dispatched before it
    was not ready; `inflight_max` is the most dispatched and not fetched."""
    eng, texts, alone, fwd = _gated_engine()
    base = eng.stats(recent=False)
    assert (base["forwards"], base["inflight_max"]) == (4, 2)  # `alone`: two calls of two

    def since():
        st = eng.stats(recent=False)
        return st["forwards"] - base["forwards"], st["ahead"] - base["ahead"], st["inflight_max"]

    # each forward ready before the next is dispatched: none is ahead
    fwd.born_ready = True
    assert eng.embed(texts["A"])[0] == alone["A"]
    assert since() == (2, 0, 2)
    # two callers behind closed gates: B's first follows a READY forward, the other three are ahead
    fwd.born_ready = False
    with cf.ThreadPoolExecutor(2) as pool:
        fb = pool.submit(eng.embed, texts["B"])
        fwd.wait(4, fetching=2)
        fa = pool.submit(eng.embed, texts["A"])
        fwd.wait(6)
        assert since() == (6, 3, 4) and eng._inflight == 4
        assert len(eng.stats()["recent"]) == 4 + 2  # a forward is `recent` once fetched
        fwd.open()
        assert fb.result(60.0)[0] == alone["B"] and fa.result(60.0)[0] == alone["A"]
    st = eng.stats()
    assert eng._inflight == 0 and st["inflight_max"] == 4 and len(st["recent"]) == st["forwards"] == 10
    assert st["forward_s"] == pytest.approx(sum(r[1] for r in st["recent"]))
    assert st["host_locked_s"] == pytest.approx(sum(r[2] for r in st["recent"]))


def test_embedding_a_lost_fetch_reaches_its_caller_alone():
    """An exception raised by a forward's fetch reaches its caller, leaves the
    lock free, nothing counted in flight, another caller's forwards untouched
    and a later call sound."""
    eng, texts, alone, fwd = _gated_engine()
    fwd.fail_next = True
    with cf.ThreadPoolExecutor(2) as pool:
        fa = pool.submit(eng.embed, texts["A"])
        fwd.wait(2, fetching=0)
        fb = pool.submit(eng.embed, texts["B"])
        fwd.wait(4)
        fwd.open()
        with pytest.raises(RuntimeError, match="forward lost"):
            fa.result(60.0)
        assert fb.result(60.0)[0] == alone["B"]
    assert not eng._lock.locked() and eng._inflight == 0
    st = eng.stats()
    assert st["forwards"] == 4 + 4 and len(st["recent"]) == 4 + 2  # A's two were dispatched, never read
    fwd.born_ready = True
    assert eng.embed(texts["A"])[0] == alone["A"]
    assert not eng._lock.locked() and eng._inflight == 0


def test_embedding_a_lost_dispatch_leaves_the_lock_free():
    """A dispatch that raises under the lock releases it; the forward the call
    had already dispatched is read by nobody and was never counted in flight."""
    eng, texts, alone, fwd = _gated_engine()
    fwd.born_ready = True

    def second_lost(params, tokens, lengths):
        if len(fwd.outs) % 2:
            raise RuntimeError("dispatch lost")
        return fwd(params, tokens, lengths)

    eng._fwd = second_lost
    with pytest.raises(RuntimeError, match="dispatch lost"):
        eng.embed(texts["A"])
    assert len(fwd.outs) == 1 and not eng._lock.locked() and eng._inflight == 0
    eng._fwd = fwd
    assert eng.embed(texts["B"])[0] == alone["B"] and eng._inflight == 0


@pytest.mark.parametrize("model,dimensions", [("tiny-embed", None), ("tiny-embed", 8),
                                              ("tiny-qwen3", None), ("tiny-qwen3", 16)])
def test_embedding_callers_side_by_side_get_what_they_get_alone(model, dimensions):
    """Four threads, each with texts of its own, several forwards a call: every
    caller gets exactly the vectors its texts give alone (same program, same
    operands, same order of a call's forwards), both encoders, `dimensions` cut."""
    eng = EmbeddingEngine(model, max_batch=2, max_seq_len=128, dtype=jnp.float32)
    texts = [[f"{who}{n} " * (1 + (3 * n + k) % 9) for n in range(5)] for k, who in enumerate("wxyz")]
    alone = [eng.embed(t, dimensions=dimensions)[0] for t in texts]
    assert all(len(v[0]) == (dimensions or eng.cfg.dim) for v in alone)
    a_round = eng.stats(recent=False)["forwards"]  # of all four calls
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # more hand-overs between the threads than the default gives
    try:
        with cf.ThreadPoolExecutor(4) as pool:
            futs = [pool.submit(lambda t=t: [eng.embed(t, dimensions=dimensions)[0] for _ in range(6)])
                    for t in texts]
            got = [f.result(120.0) for f in futs]
    finally:
        sys.setswitchinterval(was)
    for mine, rounds in zip(alone, got):
        assert all(r == mine for r in rounds)
    st = eng.stats()
    assert st["forwards"] == 7 * a_round == len(st["recent"])
    assert eng._inflight == 0 and not eng._lock.locked() and 1 <= st["inflight_max"] <= a_round


def test_chunked_prefill_matches_single_shot():
    """A prompt prefilled chunk-by-chunk must produce the same greedy output
    as one-shot prefill (VERDICT r1 #4: no head-of-line blocking, no drift)."""
    kw = dict(max_slots=2, max_seq_len=256, dtype=jnp.float32, decode_chunk=2, seed=3)
    a = GenerationEngine("tiny-llm", prefill_chunk=8, **kw).start()
    b = GenerationEngine("tiny-llm", prefill_chunk=0, **kw).start()
    prompt = "chunked prefill equivalence " * 6  # ~170 byte-tokens, many chunks
    try:
        ta = a.generate(prompt, max_tokens=12, temperature=0.0)
        tb = b.generate(prompt, max_tokens=12, temperature=0.0)
        assert ta["text"] == tb["text"]
        assert ta["usage"] == tb["usage"]
    finally:
        a.shutdown()
        b.shutdown()


def test_chunked_prefill_interleaves_with_decode():
    """While a long prompt is being admitted, an in-flight stream must keep
    receiving tokens: under the token-budget scheduler, prefill chunks ride
    FUSED inside decode rounds (fused_step_fn) instead of stalling them."""
    import threading

    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=512, dtype=jnp.float32,
        decode_chunk=2, prefill_chunk=8,
    )
    trace: list[str] = []
    orig_d = eng._dispatch_decode

    def spy_dispatch(active, group=None):
        trace.append("f" if group is not None else "d")
        return orig_d(active, group)

    eng._dispatch_decode = spy_dispatch
    eng.start()
    try:
        results = {}

        def gen(name, prompt, n):
            results[name] = eng.generate(prompt, max_tokens=n, temperature=0.0)

        t1 = threading.Thread(target=gen, args=("short", "hi", 200))
        t1.start()
        # wait until the short request is decoding, then admit a long prompt
        import time as _t

        for _ in range(200):
            if eng.total_requests >= 1 and "d" in trace:
                break
            _t.sleep(0.01)
        t2 = threading.Thread(target=gen, args=("long", "y" * 300, 4))
        t2.start()
        t1.join(timeout=60)
        t2.join(timeout=60)
        assert results["long"]["usage"]["prompt_tokens"] >= 295
        joined = "".join(trace)
        # the long prompt's chunks must have ridden inside decode rounds
        # (fused dispatches) while the short stream kept decoding
        if results["short"]["usage"]["completion_tokens"] >= 20:
            assert "f" in joined, joined
        # decode rounds running concurrently with the chunked prefill must
        # not corrupt the prefilling slot's prompt KV: the long request's
        # greedy output must match a quiet single-shot engine's
        ref = GenerationEngine(
            "tiny-llm", max_slots=2, max_seq_len=512, dtype=jnp.float32,
            decode_chunk=2, prefill_chunk=0,
        ).start()
        try:
            expect = ref.generate("y" * 300, max_tokens=4, temperature=0.0)
            assert results["long"]["text"] == expect["text"]
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kv_quant", ["int8", ""])
def test_decode_compact_matches_full_batch(kv_quant):
    """Slot compaction must not change a single greedy token.

    Two engines, identical seed/config except decode_compact; max_slots=16
    with ≤3 concurrent requests keeps the compact bucket (8) strictly below
    the full batch, so the compacted engine really exercises the slot_ids
    indirection (kernels/attention.py) every round. Covers both the int8
    cache (q8 kernel/fallback path) and bf16 (xla gather path, forced on).
    """
    mk = lambda mode: GenerationEngine(
        "tiny-llm", max_slots=16, max_seq_len=128, dtype=jnp.float32,
        decode_chunk=2, kv_quant=kv_quant, prefill_chunk=8,
        decode_compact=mode,
    ).start()
    on = mk("on")
    off = mk("off")
    try:
        assert on.decode_compact and not off.decode_compact
        prompts = [f"compaction check {i} " * (i + 1) for i in range(3)]
        # staggered lifetimes: different max_tokens make slots free at
        # different rounds, so the active set (and bucket) shifts mid-stream
        toks = [6, 11, 16]
        with cf.ThreadPoolExecutor(max_workers=3) as ex:
            got = list(ex.map(
                lambda i: on.generate(prompts[i], max_tokens=toks[i], temperature=0.0),
                range(3),
            ))
        want = [
            off.generate(prompts[i], max_tokens=toks[i], temperature=0.0)
            for i in range(3)
        ]
        for g, w in zip(got, want):
            assert g["text"] == w["text"]
            assert g["usage"] == w["usage"]
    finally:
        on.shutdown()
        off.shutdown()


@pytest.mark.parametrize("attn,model,abreast", [
    ("pallas", "tiny-llm", 1), ("xla", "tiny-llm", 1), ("pallas", "tiny-qwen3", 2),
    ("pallas", "tiny-mla", 8), ("pallas", "tiny-joyai", 8)])
def test_perf_stats_counts_what_the_blocked_attention_arm_streams(monkeypatch, attn, model, abreast):
    """`perf_stats()["decode_attn"]` exists where decode rounds read the int8
    cache through the Pallas arm, counts every step of every round dispatched
    from the positions the host packed, and is absent where XLA attends;
    `heads_abreast` says how many KV heads a row of the cache holds (two of
    `tiny-qwen3`'s 64 wide, one of `tiny-llm`'s 32 wide: two KV heads cannot
    fill 128 lanes four abreast), `positions_abreast` how many POSITIONS a row
    of a latent pair's int8 rope keys holds (eight of the tiny twins' 16 lanes;
    1 for every other cache)."""
    from llm_mcp_tpu.kernels.attention import q8_block_tokens

    monkeypatch.setenv("LLM_MCP_TPU_ATTN", attn)
    eng = GenerationEngine(
        model, max_slots=4, max_seq_len=128, dtype=jnp.float32,
        decode_chunk=2, kv_quant="int8", prefill_chunk=8,
    ).start()
    try:
        out = eng.generate("count what the arm streams", max_tokens=9, temperature=0.0)
        got = eng.perf_stats().get("decode_attn")
        if attn == "xla":
            assert got is None
            return
        if eng._layout.latent:  # the whole-S arm: every row's 128 positions a step
            rope = eng._layout.kv_rows(eng._ck, eng._cv)["v"]
            assert got["positions_abreast"] == abreast and got["heads_abreast"] == 1
            assert rope["q"].shape[3:] == (128 // abreast, abreast * eng.cfg.qk_rope_head_dim) == (16, 128)
            assert rope["s"].shape[3] == eng._ck["q"].shape[3] == 128  # a scale a position, the latents untouched
            assert got["block_tokens"] == 0 and got["tokens_streamed"] == got["steps"] * 128 * 4
            assert out["usage"]["completion_tokens"] == 9 and 0 < got["tokens_live"] < got["tokens_streamed"]
            return
        assert got["positions_abreast"] == 1
        heads, seq, hd = eng._ck["q"].shape[2:]
        assert got["block_tokens"] == q8_block_tokens(heads, seq, hd) == 128
        assert got["heads_abreast"] == abreast and hd == abreast * eng.cfg.resolved_head_dim
        assert heads == 2 * eng.cfg.n_kv_heads // abreast + 1
        # every decode round's steps are counted (a verify round of the
        # speculation path is another program and reads no blocked arm)
        assert out["usage"]["completion_tokens"] == 9
        assert got["steps"] >= eng.decode_chunk and got["steps"] % eng.decode_chunk == 0
        # every row streams one block a step (4 slots, contexts under 128)
        assert got["tokens_streamed"] == got["steps"] * 128 * 4
        assert 0 < got["tokens_live"] < got["tokens_streamed"]
        assert got["live_over_streamed"] == round(got["tokens_live"] / got["tokens_streamed"], 4)
    finally:
        eng.shutdown()


_RAGGED_PROMPTS = [
    "ragged prefill equivalence " * 6,
    "short",
    "another mixed-length prompt for the packer " * 3,
]
_RAGGED_SHARED = "you are a helpful assistant. answer briefly. " * 3


# tier-1 runs one GQA and one MLA layout; the other two ride the same code
# paths (layout dispatch happens inside the model fn) and run under -m slow
@pytest.mark.parametrize(
    "model,kv_quant",
    [
        ("tiny-llm", ""),
        pytest.param("tiny-llm", "int8", marks=pytest.mark.slow),
        # two KV heads of 64 ABREAST in a row of 128 lanes: the chunk program
        # multiplies the rows as they lie, the ragged one pulls the heads apart
        ("tiny-qwen3", "int8"),
        pytest.param("tiny-mla", "", marks=pytest.mark.slow),
        pytest.param("tiny-mla", "int8", marks=pytest.mark.slow),
    ],
)
def test_ragged_prefill_toggle_token_identical(monkeypatch, model, kv_quant):
    """The escape hatch is bit-exact: TPU_RAGGED_PREFILL=0 (bucketed chunk
    groups) and =1 (packed ragged staging) produce identical greedy tokens
    per cache layout, across concurrent mixed-length admissions AND a
    prefix-cache-hit admission whose suffix chunks read pinned blocks."""
    outs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("TPU_RAGGED_PREFILL", flag)
        eng = GenerationEngine(
            model, max_slots=4, max_seq_len=256, dtype=jnp.float32,
            decode_chunk=2, prefill_chunk=8, kv_quant=kv_quant, seed=3,
            prompt_cache_mb=8,
        )
        staged: list[int] = []
        if flag == "1":
            assert eng.ragged_prefill, "ragged gate should be on"
            orig = eng._stage_ragged_group

            def spy(budget, _o=orig):
                g = _o(budget)
                if g is not None:
                    staged.append(g.n_tokens)
                return g

            eng._stage_ragged_group = spy
        else:
            assert not eng.ragged_prefill
        eng.start()
        try:
            with cf.ThreadPoolExecutor(max_workers=3) as ex:
                res = list(ex.map(
                    lambda p: eng.generate(p, max_tokens=10, temperature=0.0),
                    _RAGGED_PROMPTS,
                ))
            # 1st records the shared prompt, 2nd stores the entry, 3rd hits
            # it — the hit's suffix chunks ride the staging path under test
            hs = [
                eng.generate(_RAGGED_SHARED + f"question {i}", max_tokens=8,
                             temperature=0.0)
                for i in range(3)
            ]
            assert eng.prefix_cache_hits >= 1, "prefix cache never hit"
            if flag == "1":
                assert staged, "ragged staging never ran"
            outs[flag] = (
                [r["text"] for r in res + hs],
                [r["usage"] for r in res + hs],
            )
        finally:
            eng.shutdown()
    assert outs["0"][0] == outs["1"][0]
    assert outs["0"][1] == outs["1"][1]


def test_ragged_prefill_preempt_restore_token_identical(monkeypatch):
    """A slot preempted while its prompt is still chunking under ragged
    staging must restore to a token-identical stream: the packed-buffer
    descriptors are rebuilt from the committed length, not from any state
    the offload could have lost."""
    import threading
    import time

    monkeypatch.setenv("TPU_KV_HOST_OFFLOAD", "1")
    monkeypatch.setenv("TPU_RAGGED_PREFILL", "1")
    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=256, dtype=jnp.float32,
        decode_chunk=4, prefill_chunk=8, seed=3,
    )
    assert eng.ragged_prefill
    eng.start()
    # long prompts × chunk 8 keep both slots mid-prefill for many rounds,
    # so the high-priority admission preempts a still-chunking victim
    victim = "preempt during chunked admission " * 6
    other = "second low priority stream holding its slot " * 4
    results: dict[str, dict] = {}
    lock = threading.Lock()

    def low(p):
        r = eng.generate(p, max_tokens=24, temperature=0.0, priority=0)
        with lock:
            results[p] = r

    try:
        threads = [
            threading.Thread(target=low, args=(p,), daemon=True)
            for p in (victim, other)
        ]
        for t in threads:
            t.start()
        deadline = time.time() + 60
        while eng.slots_in_use() < 2 and time.time() < deadline:
            time.sleep(0.002)
        assert eng.slots_in_use() == 2, "low-priority streams never admitted"
        hi = eng.generate("urgent request", max_tokens=6, temperature=0.0,
                          priority=5)
        assert hi["usage"]["completion_tokens"] >= 1
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "preempted stream hung"
        st = eng.memory_stats()
        assert st["preempted_total"] >= 1, "no preemption happened"
        assert st["restored_total"] >= 1, "offloaded slot never restored"
        # uncontended references on the same engine, same executables
        for p in (victim, other):
            ref = eng.generate(p, max_tokens=24, temperature=0.0)
            assert results[p]["text"] == ref["text"]
        assert eng.total_errors == 0
    finally:
        eng.shutdown()


def test_engine_int8_kv_cache():
    """int8 KV cache serves coherently through both prefill paths."""
    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=256, dtype=jnp.float32,
        decode_chunk=2, kv_quant="int8", prefill_chunk=8,
    ).start()
    try:
        short = eng.generate("int8 kv", max_tokens=8, temperature=0.0)
        assert short["usage"]["completion_tokens"] >= 1
        long = eng.generate("int8 chunked " * 8, max_tokens=8, temperature=0.0)
        assert long["usage"]["completion_tokens"] >= 1
        # greedy determinism holds with the quantized cache too
        again = eng.generate("int8 kv", max_tokens=8, temperature=0.0)
        assert short["text"] == again["text"]
    finally:
        eng.shutdown()


def _mk_prefix_engine(**kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("decode_chunk", 2)
    kw.setdefault("prefill_chunk", 64)
    return GenerationEngine("tiny-llm", **kw).start()


@pytest.mark.parametrize("kv_quant", ["", "int8"])
def test_prefix_cache_greedy_parity(kv_quant):
    """Prefix-cache hits must not change a single greedy token: the cached
    rows are the same prefill output a cold run would compute."""
    shared = "you are a helpful assistant. answer briefly and precisely. " * 2
    prompts = [shared + f"question number {i}?" for i in range(4)]
    cached = _mk_prefix_engine(kv_quant=kv_quant, prompt_cache_mb=64)
    plain = _mk_prefix_engine(kv_quant=kv_quant, prompt_cache_mb=0)
    try:
        assert cached._prefix_budget > 0 and plain._prefix_budget == 0
        got = [cached.generate(p, max_tokens=8, temperature=0.0) for p in prompts]
        want = [plain.generate(p, max_tokens=8, temperature=0.0) for p in prompts]
        for g, w in zip(got, want):
            assert g["text"] == w["text"]
            assert g["usage"] == w["usage"]
        # the shared prefix was stored after its second sighting and later
        # prompts hit it
        assert len(cached._prefix_cache) >= 1
        assert cached.prefix_cache_hits >= 1
    finally:
        cached.shutdown()
        plain.shutdown()


def test_prefix_cache_identical_prompts_hit():
    """Identical repeated prompts hit a len-1 prefix (>=1 suffix token must
    remain to produce the first-sample logits)."""
    eng = _mk_prefix_engine(prompt_cache_mb=64)
    try:
        p = "the same exact prompt repeated for every single request here."
        first = eng.generate(p, max_tokens=6, temperature=0.0)
        second = eng.generate(p, max_tokens=6, temperature=0.0)
        third = eng.generate(p, max_tokens=6, temperature=0.0)
        assert first["text"] == second["text"] == third["text"]
        assert eng.prefix_cache_hits >= 1
    finally:
        eng.shutdown()


def test_prefix_cache_eviction_by_budget():
    eng = _mk_prefix_engine(prompt_cache_mb=64)
    try:
        # force a tiny byte budget so the second stored prefix evicts the first
        eng._prefix_budget = 1
        a = "alpha " * 20
        b = "bravo " * 20
        for p in (a, a + "one", b, b + "two"):
            eng.generate(p, max_tokens=2, temperature=0.0)
        assert len(eng._prefix_cache) <= 1
        assert eng._prefix_cache_bytes <= max(
            (e["bytes"] for e in eng._prefix_cache.values()), default=0
        )
    finally:
        eng.shutdown()


def test_prefix_cache_concurrent_hit_group():
    """Several queued hits of one entry admit as a single fused group."""
    eng = _mk_prefix_engine(prompt_cache_mb=64, max_slots=8)
    try:
        shared = "shared system preamble for every request in this test. " * 2
        # suffixes diverge at the FIRST character so the learned prefix is
        # exactly `shared` (a common suffix head would overshoot the key)
        eng.generate(shared + "alpha", max_tokens=2, temperature=0.0)
        eng.generate(shared + "bravo", max_tokens=2, temperature=0.0)  # stores
        with cf.ThreadPoolExecutor(max_workers=4) as ex:
            outs = list(ex.map(
                lambda i: eng.generate(shared + f"{i} query", max_tokens=4, temperature=0.0),
                range(4),
            ))
        assert all(o["usage"]["completion_tokens"] >= 1 for o in outs)
        assert eng.prefix_cache_hits >= 2
    finally:
        eng.shutdown()


@pytest.mark.parametrize("mesh_shape,model", [
    ("dp=1,tp=2", "tiny-llm"),
    ("sp=2,tp=2", "tiny-llm"),   # ring sequence-parallel prefill in-engine
    ("dp=1,tp=2", "tiny-mla"),   # latent attention under tp
])
def test_engine_serves_under_virtual_mesh(mesh_shape, model):
    """The ENGINE (not just the model fns) serves over a device mesh: slot
    machinery, donation, admission, and emission all run with sharded
    params/cache on the virtual CPU mesh. The multichip dryrun covers the
    model functions; this covers the serving stack around them."""
    import jax

    from llm_mcp_tpu.parallel.mesh import make_mesh

    n = 1
    for part in mesh_shape.split(","):
        n *= int(part.split("=")[1])
    mesh = make_mesh(mesh_shape, devices=jax.devices()[:n])
    eng = GenerationEngine(
        model, mesh=mesh, max_slots=2, max_seq_len=128, dtype=jnp.float32,
        decode_chunk=2,
    ).start()
    try:
        if mesh_shape.startswith("sp="):
            assert eng.sp == 2  # the ring-prefill path actually engaged
        a = eng.generate("mesh serving", max_tokens=6, temperature=0.0)
        assert a["usage"]["completion_tokens"] >= 1
        b = eng.generate("mesh serving", max_tokens=6, temperature=0.0)
        assert a["text"] == b["text"]  # deterministic under sharding
    finally:
        eng.shutdown()


# Teacher-forced regret a greedy token may carry and still count as the
# reference's choice. With the int8 cache a chunk reads its PAST quantized and
# its own rows exact, so where the token-budget scheduler cuts the chunks
# moves the logits by the quantization noise: measured 0.007 and 0.015 on the
# two knife-edge cases of this soak (26, 46: top-2 margins 0.003 and 0.002),
# 0.0 on every other token. Another request's logits miss by the spread of the
# logits themselves (> 0.5 here, asserted below).
_SOAK_TIE = 0.05


@pytest.mark.parametrize("kv_quant", ["", "int8"])
def test_soak_churn_parity(kv_quant):
    """Soak: 60 requests with mixed prompt families (shared prefixes, long
    chunked prompts, unique shorts), staggered lengths, high concurrency —
    through an engine running ALL round-3 SCHEDULING machinery at once
    (pipelined loop, fast finish-scan, slot compaction, prefix cache,
    batched chunked prefill), against a one-slot sequential engine with that
    machinery off and the same cache dtype: any cross-request cache
    corruption, slot-reuse race, or stale-emission bug under churn shows up
    as a text diff.

    With the float cache the numerics do not depend on where the scheduler
    cuts a prompt into chunks, so every text must match EXACTLY. With the
    int8 cache they do (see _SOAK_TIE), and the cuts follow the scheduler's
    measured costs — under compile stalls on an empty cache they move — so a
    text that differs must still be the reference model's greedy choice at
    every token to within _SOAK_TIE, teacher-forced through the plain float
    forward. int8-vs-float accuracy itself is test_quant's job."""
    import jax

    from llm_mcp_tpu.models.llama import llama_prefill

    S = 192
    # max_slots=16 with the pow2 floor of 8 keeps the compact bucket
    # strictly below B at partial occupancy, so compaction really engages
    full = GenerationEngine(
        "tiny-llm", max_slots=16, max_seq_len=S, dtype=jnp.float32,
        decode_chunk=4, kv_quant=kv_quant, prefill_chunk=32,
        prompt_cache_mb=64, decode_compact="on", admit_batch=4, seed=11,
    )
    plain = GenerationEngine(
        "tiny-llm", max_slots=1, max_seq_len=S, dtype=jnp.float32,
        decode_chunk=4, kv_quant=kv_quant, prefill_chunk=0,
        prompt_cache_mb=0, decode_compact="off", seed=11,
    ).start()
    # the token ids behind each text (the byte tokenizer's text is lossy)
    emitted: dict[tuple, list[int]] = {}
    process_token = full._process_token

    def tap(s, tok, pos):
        emitted.setdefault(tuple(s.req.prompt_ids), []).append(tok)
        return process_token(s, tok, pos)

    full._process_token = tap
    full.start()

    allowed = np.asarray(full._allowed_mask)
    forward = jax.jit(
        lambda params, tokens, lengths: llama_prefill(
            full.cfg, params, tokens, lengths
        )[0]
    )

    def regrets(ids, toks):
        """How far each of `toks` sits under the reference's best allowed
        logit when `ids + toks` is teacher-forced through the plain forward."""
        seq = list(ids) + list(toks)
        tokens = np.zeros((16, S), np.int32)  # max_tokens <= 9 here
        lengths = np.ones((16,), np.int32)
        for k in range(len(toks)):
            tokens[k, : len(ids) + k] = seq[: len(ids) + k]
            lengths[k] = len(ids) + k
        logits = np.asarray(forward(full.params, tokens, lengths))
        logits = np.where(allowed[None], logits, -np.inf)
        return [float(logits[k].max() - logits[k, t]) for k, t in enumerate(toks)]

    try:
        shared_a = "system preamble alpha for the soak test run. " * 2
        shared_b = "different preamble bravo with its own words here. "
        cases = []
        for i in range(60):
            fam = i % 4
            if fam == 0:
                prompt = shared_a + f"{i} ask"
            elif fam == 1:
                prompt = shared_b + f"{i} query"
            elif fam == 2:
                prompt = f"long prompt {i} " * 9  # > prefill_chunk: chunked
            else:
                prompt = f"unique short {i}"
            cases.append((prompt, 3 + (i % 7)))

        def run_one(idx):
            p, n = cases[idx]
            return full.generate(p, max_tokens=n, temperature=0.0)["text"]

        with cf.ThreadPoolExecutor(max_workers=len(cases)) as ex:
            results = list(ex.map(run_one, range(len(cases))))
        ids = [tuple(full.tokenizer.encode(p)) for p, _ in cases]
        for i, (p, n) in enumerate(cases):
            want = plain.generate(p, max_tokens=n, temperature=0.0)["text"]
            if results[i] == want:
                continue
            assert kv_quant, (i, p[:40], results[i], want)
            worst = max(regrets(ids[i], emitted[ids[i]]))
            assert worst <= _SOAK_TIE, (i, p[:40], results[i], want, worst)
        # the adjudication has teeth: another family's tokens are far from
        # this prompt's greedy path
        assert max(regrets(ids[2], emitted[ids[3]])) > 10 * _SOAK_TIE
        assert max(regrets(ids[3], emitted[ids[0]])) > 10 * _SOAK_TIE
        assert full.prefix_cache_hits >= 10  # the cache really engaged
        assert full.total_errors == 0
    finally:
        full.shutdown()
        plain.shutdown()


def test_pipelined_decode_depth_parity(monkeypatch):
    """Depth-2/3 pipelined decode (device token ring, optimistic lengths,
    slot-reuse cooling) is token-for-token the depth-1 engine under greedy:
    sequential AND concurrent mixed-length requests, slot churn included."""
    import concurrent.futures as cf

    kw = dict(
        max_slots=4, max_seq_len=96, dtype=jnp.float32, decode_chunk=4,
        admit_batch=2, seed=5,
    )
    monkeypatch.setenv("TPU_PIPELINE_DEPTH", "1")
    ref = GenerationEngine("tiny-llm", **kw).start()
    try:
        cases = [(f"pipe {i} " * (1 + i % 4), 2 + i % 6) for i in range(12)]
        want = [ref.generate(p, max_tokens=n, temperature=0.0)["text"]
                for p, n in cases]
    finally:
        ref.shutdown()
    for depth in ("2", "3"):
        monkeypatch.setenv("TPU_PIPELINE_DEPTH", depth)
        eng = GenerationEngine("tiny-llm", **kw).start()
        try:
            assert eng.pipeline_depth == int(depth)
            got = [eng.generate(p, max_tokens=n, temperature=0.0)["text"]
                   for p, n in cases]
            assert got == want, f"sequential parity at depth {depth}"
            with cf.ThreadPoolExecutor(max_workers=len(cases)) as ex:
                conc = list(ex.map(
                    lambda i: eng.generate(
                        cases[i][0], max_tokens=cases[i][1], temperature=0.0
                    )["text"],
                    range(len(cases)),
                ))
            assert conc == want, f"concurrent parity at depth {depth}"
            assert eng.total_errors == 0
        finally:
            eng.shutdown()


def test_pipelined_seq_cap_finishes(monkeypatch):
    """At depth 2, rows that reach the context cap mid-pipeline still
    finish with reason 'length' (the dispatch filter + fast-scan cap rule
    leave no dangling active row)."""
    monkeypatch.setenv("TPU_PIPELINE_DEPTH", "2")
    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=32, dtype=jnp.float32,
        decode_chunk=4,
    ).start()
    try:
        out = eng.generate("fill the window " * 4, max_tokens=512,
                           temperature=0.0)
        assert out["finish_reason"] == "length"
        assert out["usage"]["completion_tokens"] >= 1
        # engine stays serviceable after cap finishes (slots uncooled)
        again = eng.generate("after cap", max_tokens=4, temperature=0.0)
        assert again["usage"]["completion_tokens"] >= 1
    finally:
        eng.shutdown()


def test_pipelined_compact_cap_churn(monkeypatch):
    """Compact dispatch under the pipelined loop when every slot is
    occupied and some rows sit at the context cap awaiting their fetch:
    the pad-row search must find a safe non-dispatched target (review
    regression: it used to StopIteration and error every live stream)."""
    import concurrent.futures as cf

    monkeypatch.setenv("TPU_PIPELINE_DEPTH", "2")
    eng = GenerationEngine(
        "tiny-llm", max_slots=16, max_seq_len=32, dtype=jnp.float32,
        decode_chunk=4, kv_quant="int8", decode_compact="on",
        admit_batch=8,
    ).start()
    try:
        # staggered prompt lengths -> rows reach the cap on different
        # rounds, so occupied-at-cap and still-active rows coexist
        cases = ["w " * (3 + i) for i in range(16)]
        with cf.ThreadPoolExecutor(max_workers=16) as ex:
            outs = list(ex.map(
                lambda p: eng.generate(p, max_tokens=512, temperature=0.0),
                cases,
            ))
        assert all(o["finish_reason"] == "length" for o in outs), [
            o["finish_reason"] for o in outs
        ]
        assert eng.total_errors == 0
        # engine remains serviceable afterwards
        again = eng.generate("post churn", max_tokens=3, temperature=0.0)
        assert again["usage"]["completion_tokens"] >= 1
    finally:
        eng.shutdown()


# -- admissions in the in-flight queue (PR 29) -------------------------------
# An admission joins the same queue as the decode rounds, in device order,
# and its first tokens are read when it is the oldest item there. On the CPU:
# the ORDER of the loop's reads and emissions and what each stream received,
# never a time.

QKW = dict(max_slots=16, max_seq_len=96, dtype=jnp.float32, decode_chunk=4, seed=5)
QUEUE_CASES = [(1, 1, "off"), (1, 4, "off"), (2, 1, "off"), (2, 4, "off"), (2, 4, "on"), (1, 1, "on")]
# (round at whose dispatch the request arrives, prompt, max_tokens): the first
# starts the engine, the others arrive while rounds are in flight, two at once
SCRIPT = [(0, "the long stream that keeps rounds in flight", 40), (2, "second " * 3, 9),
          (3, "a pair, one", 6), (3, "a pair, two " * 2, 14), (6, "late", 1), (8, "last one in", 11)]


@contextlib.contextmanager
def _queue_engine(depth, env=(), **kw):
    """An engine with a ring of its own and the pipeline depth asked for."""
    env = {"TPU_PIPELINE_DEPTH": str(depth), "TPU_SPEC": "0", **dict(env)}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    rec = FlightRecorder(capacity=16384, dump_dir=tempfile.mkdtemp(prefix="flight"))
    prev = flight.set_recorder(rec)
    eng = None
    try:
        eng = GenerationEngine("tiny-llm", **{**QKW, **kw})
        yield eng, rec
    finally:
        if eng is not None:
            eng.shutdown()
        flight.set_recorder(prev)
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def _request(eng, prompt, max_tokens, **kw):
    return GenRequest(prompt_ids=eng.tokenizer.encode(prompt), max_tokens=max_tokens,
                      temperature=0.0, **kw)


def _events(req, timeout=120.0):
    """A stream's events up to its end, without the clocks."""
    out = []
    while True:
        evt = req.out.get(timeout=timeout)
        if evt is _DONE:
            return out
        out.append({k: v for k, v in evt.items() if k not in ("t", "ttft_ms")})
        if evt["type"] == "done":
            return out


def _spy(eng, script=()):
    """Hand `script`'s requests to the engine from its own thread at the
    dispatch of the round they name (so the admission that follows is
    dispatched behind that round), and note what the loop does: each
    admission's dispatch (rounds dispatched and fetched by then), each read,
    each round's rows by request, every token a request was given, and every
    text event put with what brought it (`puts`: a round's emission by its
    rid, an admission's read by its place in `admits`)."""
    seen = {"admits": [], "reads": [], "rounds": [], "tokens": {}, "log": [], "puts": [],
            "due": sorted(script, key=lambda x: x[0])}  # a test may add to it while the engine is idle
    due = seen["due"]
    dispatch, start, read, process = (eng._dispatch_decode, eng._start_batch, eng._read_admit,
                                      eng._process_token)
    emit, put = eng._emit_round, eng._put_text
    bringing = [None]

    def spy_dispatch(active, group=None):
        rid = eng._rid_dispatched + 1
        while due and due[0][0] <= rid:
            _, req = due.pop(0)
            req() if callable(req) else eng.submit(req)
        seen["rounds"].append((rid, [eng._slots[b].req.request_id for b in active]))
        seen["log"].append(("round", rid))
        return dispatch(active, group)

    def spy_start(batch, held_by=""):
        adm = start(batch, held_by)
        seen["admits"].append({"adm": adm, "dispatched": eng._rid_dispatched, "fetched": eng._rid_fetched,
                               "requests": [r.request_id for _, r, _ in batch],
                               "slots": [slot for slot, _, _ in batch]})
        seen["log"].append(("admit", id(adm)))
        return adm

    def spy_read(adm, at_once=False):
        seen["reads"].append({"adm": adm, "fetched": eng._rid_fetched, "at_once": at_once})
        seen["log"].append(("read", id(adm)))
        bringing[0] = ("adm", next(i for i, a in enumerate(seen["admits"]) if a["adm"] is adm))
        return read(adm, at_once)

    def spy_emit(p):
        bringing[0] = ("round", p.rid)
        return emit(p)

    def spy_put(s, text, mark=None):
        seen["puts"].append((s.req.request_id, bringing[0]))
        return put(s, text, mark)

    def spy_process(s, tok, pos):
        seen["tokens"].setdefault(s.req.request_id, []).append((pos, tok))
        return process(s, tok, pos)

    eng._dispatch_decode, eng._start_batch, eng._read_admit, eng._process_token = (
        spy_dispatch, spy_start, spy_read, spy_process)
    eng._emit_round, eng._put_text = spy_emit, spy_put
    return seen


@functools.lru_cache(maxsize=None)
def _one_at_a_time():
    """SCRIPT's requests on a depth-1 engine, each alone: what every stream
    must receive, and the tokens behind it."""
    with _queue_engine(1) as (eng, _rec):
        seen = _spy(eng)
        eng.start()
        want = []
        for _, prompt, n in SCRIPT:
            req = eng.submit(_request(eng, prompt, n))
            want.append((_events(req), seen["tokens"][req.request_id]))
        return want


@functools.lru_cache(maxsize=None)
def _scripted(depth, admit_batch, compact):
    with _queue_engine(depth, admit_batch=admit_batch, decode_compact=compact) as (eng, rec):
        reqs = [_request(eng, p, n) for _, p, n in SCRIPT]
        seen = _spy(eng, [(at, r) for (at, _, _), r in zip(SCRIPT, reqs) if at])
        eng.start()
        eng.submit(reqs[0])
        got = [_events(r) for r in reqs]
        deadline = time.monotonic() + 30.0
        while (eng._inflight or any(s is not None for s in eng._slots)) and time.monotonic() < deadline:
            time.sleep(0.01)  # rounds dispatched before the last finish was known are still fetched
        return {"got": got, "tokens": [seen["tokens"].get(r.request_id) for r in reqs], "seen": seen,
                "ring": [(e["etype"], e["fields"]) for e in rec.snapshot()], "stats": eng.perf_stats(),
                "gaps": eng._perf.samples("event_gap", whole=True),
                "errors": eng.total_errors, "ids": [r.request_id for r in reqs],
                "left": len(eng._inflight)}


@pytest.mark.parametrize("depth,admit_batch,compact", QUEUE_CASES)
def test_queued_admissions_serve_what_one_at_a_time_serves(depth, admit_batch, compact):
    """(a) every stream's events (text split as the rounds split it, usage,
    finish reason) and tokens are those of the same request served alone, and
    a stream's first token comes before its first round's."""
    run, want = _scripted(depth, admit_batch, compact), _one_at_a_time()
    assert run["errors"] == 0 and run["left"] == 0
    for (_, prompt, n), got, toks, (want_events, want_toks) in zip(SCRIPT, run["got"], run["tokens"], want):
        assert got == want_events, prompt
        assert toks == want_toks, prompt
        assert got[-1]["type"] == "done" and got[-1]["usage"]["completion_tokens"] <= n
        # positions rise from the prompt's last: the first token is processed
        # (and its text put) before any token of a round
        assert [p for p, _ in toks] == list(range(toks[0][0], toks[0][0] + len(toks)))
    assert sum(1 for r in run["seen"]["admits"] if r["dispatched"] > r["fetched"]) >= 2


@pytest.mark.parametrize("depth,admit_batch,compact", QUEUE_CASES)
def test_an_admission_is_read_when_it_is_the_oldest_item_in_flight(depth, admit_batch, compact):
    """(b) from the flight ring: an `admit_read` comes after the fetch AND the
    emit of every round up to its `after_rid` and before the fetch of the
    next; no read of an admission waits while a round dispatched before it is
    unfetched."""
    run = _scripted(depth, admit_batch, compact)
    ring, seen = run["ring"], run["seen"]
    at = {kind: {} for kind in ("fetch", "emit")}
    for i, (etype, f) in enumerate(ring):
        if etype in at:
            at[etype][f["rid"]] = i
    dispatched = [f["rid"] for etype, f in ring if etype in ("decode", "fused", "fused_rag")]
    reads = [(i, f) for i, (etype, f) in enumerate(ring) if etype == "admit_read"]
    assert len(reads) == len(seen["admits"]) == len(seen["reads"]) == run["stats"]["admit"]["reads"]
    assert run["stats"]["admit"]["reads_at_once"] == 0
    assert sum(f["rows"] for _, f in reads) == len(SCRIPT)
    for (i, f), admit, read in zip(reads, seen["admits"], seen["reads"]):
        assert read["adm"] is admit["adm"]  # read in the order dispatched
        r = f["after_rid"]
        before = [rid for rid in dispatched if rid <= r]
        assert all(at["fetch"][rid] < i and at["emit"][rid] < i for rid in before)
        assert all(at["fetch"][rid] > i for rid in dispatched if rid > r and rid in at["fetch"])
        # every round dispatched before the admission was fetched by its read
        assert r == read["fetched"] >= admit["dispatched"]
        assert f["wait_ms"] >= 0 and f["blocked"] in (True, False) and f["t"] > 0
    if admit_batch == 4:
        assert max(f["rows"] for _, f in reads) == 2  # the pair went in one program
    # an admission dispatched behind a full pipeline: those rounds were
    # emitted before its first tokens were read
    full = [a for a in seen["admits"] if a["dispatched"] - a["fetched"] == depth]
    assert full, [(a["dispatched"], a["fetched"]) for a in seen["admits"]]


@pytest.mark.parametrize("depth,admit_batch,compact", QUEUE_CASES)
def test_every_admission_dispatched_has_one_record_and_the_block_sums_them(depth, admit_batch, compact):
    """PR 37: the ring's `admit_prog`, one a dispatched admission in the order
    dispatched, states the program's shape and what it carried; the sums of
    `perf_stats()["admit"]` are the ring's."""
    run = _scripted(depth, admit_batch, compact)
    progs = [f for etype, f in run["ring"] if etype == "admit_prog"]
    admits, block = run["seen"]["admits"], run["stats"]["admit"]
    lens = {rid: len(ByteTokenizer().encode(p)) for rid, (_, p, _) in zip(run["ids"], SCRIPT)}
    assert [f["aid"] for f in progs] == list(range(1, len(admits) + 1))
    for f, a in zip(progs, admits):
        assert f["kind"] == "batch" and f["rows"] == len(a["requests"]) <= f["rows_padded"] <= admit_batch
        assert f["rows_padded"] & (f["rows_padded"] - 1) == 0
        assert f["true_tokens"] == sum(lens[r] for r in a["requests"])
        assert f["padded_tokens"] == f["rows_padded"] * f["bucket"] >= f["true_tokens"]
        assert f["bucket"] >= max(lens[r] for r in a["requests"])
        assert f["after_rid"] == a["dispatched"] and f["queued"] >= 0 and f["wait_ms_max"] >= 0
        assert f["held_by"] in ("queue_empty", "admit_batch", "no_slot", "budget")
    for key, field in (("programs", None), ("prompts", "rows"), ("rows_padded", "rows_padded"),
                       ("true_tokens", "true_tokens"), ("padded_tokens", "padded_tokens"),
                       ("queued_sum", "queued")):
        assert block[key] == sum(1 if field is None else f[field] for f in progs), key
    assert block["prompts"] == len(SCRIPT) and block["reads"] == block["programs"]
    assert sum(block["by_shape"].values()) == sum(block["held_by"].values()) == block["programs"]
    assert block["by_shape"] == {k: sum(f"{f['rows_padded']}:{f['bucket']}" == k for f in progs)
                                 for k in block["by_shape"]}
    reads = [f for etype, f in run["ring"] if etype == "admit_read"]
    assert [f["aid"] for f in reads] == [f["aid"] for f in progs]  # read in the order dispatched
    if admit_batch == 1:
        assert block["held_by"].get("admit_batch", 0) == block["programs"]  # every batch closed full
    else:
        assert block["held_by"].get("queue_empty", 0) >= 1


@pytest.mark.parametrize("depth,admit_batch,compact", QUEUE_CASES)
def test_a_gap_counts_the_admissions_dispatched_between_its_two_events(depth, admit_batch, compact):
    """PR 37: an `event_gap` sample says how many admit programs, of how many
    padded tokens, the device ran between the stream's two events. The oracle
    is the loop as the spy saw it: an admission dispatched when `d` rounds had
    been stands between round d and round d + 1; a gap from round r1's event
    to round r2's holds those with r1 <= d < r2, and a gap from a first token
    those dispatched after its own admission with d < r2."""
    run = _scripted(depth, admit_batch, compact)
    admits = run["seen"]["admits"]
    padded = [f["padded_tokens"] for etype, f in run["ring"] if etype == "admit_prog"]
    gaps = iter(run["gaps"])
    last: dict[str, tuple] = {}
    n = with_one = without = 0
    for rid, what in run["seen"]["puts"]:
        if rid in last:
            kind, at = last[rid]
            first = at + 1 if kind == "adm" else next(
                (i for i, a in enumerate(admits) if a["dispatched"] >= at), len(admits))
            assert what[0] == "round"  # a stream's later events all come from rounds
            between = [i for i in range(first, len(admits)) if admits[i]["dispatched"] < what[1]]
            _t, seconds, n_admits, n_tokens = next(gaps)
            assert (n_admits, n_tokens) == (len(between), sum(padded[i] for i in between)), (rid, last[rid], what)
            assert seconds >= 0
            n += 1
            with_one += n_admits >= 1
            without += n_admits == 0
        last[rid] = what
    assert next(gaps, None) is None and n == len(run["gaps"])
    # the long stream rode rounds with an admission between them and rounds without
    assert with_one >= 2 and without >= 2


@pytest.mark.parametrize("depth", [1, 2])
def test_a_slots_empty_time_is_split_by_owner_on_one_clock(depth):
    """PR 37: `_free_now` stamps a slot, the fetch that ends its fence stamps
    it cool, `_seat` closes the vacancy: cooling + no_request + queued = seat
    less free for every vacancy; a request queued before the slot was freed
    reads no_request 0, one that arrives into an empty engine reads what it
    was late by."""
    with _queue_engine(depth, max_slots=1) as (eng, _rec):
        booked = []
        vacancy = eng._adm.vacancy

        def spy(t_free, t_cool, t_arrived, t_seat):
            parts = vacancy(t_free, t_cool, t_arrived, t_seat)
            booked.append(((t_free, t_cool, t_arrived, t_seat), parts))
            return parts

        eng._adm.vacancy = spy
        eng.start()
        first, queued = (eng.submit(_request(eng, p, 9)) for p in ("holds the only slot", "waits in the queue"))
        _events(first), _events(queued)
        assert [a[2] < a[0] for a, _ in booked] == [True]  # the first seat closed no vacancy
        time.sleep(0.2)
        t_late = time.monotonic()
        _events(eng.submit(_request(eng, "arrives into an empty engine", 5)))
        assert len(booked) == 2
        for (t_free, t_cool, _arr, t_seat), parts in booked:
            assert t_free <= t_cool <= t_seat and min(parts) >= 0
            assert sum(parts) == pytest.approx(t_seat - t_free, abs=1e-9)
        (_, (cool_q, none_q, queued_q)), ((t_free, t_cool, t_arr, t_seat), (cool_l, none_l, queued_l)) = booked
        assert none_q == 0 and queued_q > 0
        assert t_arr >= t_late and none_l == pytest.approx(t_arr - t_cool) and none_l > 0.1  # of the 0.2 s slept
        assert queued_l == pytest.approx(t_seat - t_arr)
        v = eng.perf_stats()["admit"]["vacancy"]
        assert v["count"] == 2
        assert (v["cooling_s"], v["no_request_s"], v["queued_s"]) == pytest.approx(
            (cool_q + cool_l, none_q + none_l, queued_q + queued_l))
        if depth == 1:
            assert cool_q < 0.05  # at depth 1 the fetch that frees the slot ends its fence


@pytest.mark.parametrize("depth,admit_batch,compact", [(1, 1, "off"), (2, 1, "off"), (2, 4, "on")])
def test_a_first_token_that_ends_the_reply_frees_its_slot(depth, admit_batch, compact):
    """(c) a first token that is EOS, and `max_tokens` 1, learned after the
    row has ridden a round: the reply ends with the usage it has alone on an
    idle engine (where the admission is read before any round), no token of
    the round leaks, the slot is admitted again."""
    long_prompt, n_long = SCRIPT[0][1], SCRIPT[0][2]
    with _queue_engine(depth, admit_batch=admit_batch, decode_compact=compact) as (eng, _rec):
        seen = _spy(eng)
        eng.start()
        alone = lambda prompt, n: _events(eng.submit(_request(eng, prompt, n)))  # noqa: E731
        probe = eng.submit(_request(eng, "ends at once", 4))
        _events(probe)
        tok0 = seen["tokens"][probe.request_id][0][1]
        assert tok0 not in {t for _, t in _one_at_a_time()[0][1]}  # the long stream never samples it
        real_eos = eng.tokenizer.eos_id
        eng.tokenizer.eos_id = tok0
        try:
            want_eos, want_one = alone("ends at once", 4), alone("late", 1)
            assert want_eos == [{"type": "done", "finish_reason": "stop", "usage": {
                "prompt_tokens": len(eng.tokenizer.encode("ends at once")), "completion_tokens": 0,
                "total_tokens": len(eng.tokenizer.encode("ends at once"))}}]
            assert want_one[-1]["finish_reason"] == "length" and want_one[-1]["usage"]["completion_tokens"] == 1
            n_admits = len(seen["admits"])
            long_req, eos_req, one_req = (_request(eng, long_prompt, n_long), _request(eng, "ends at once", 4),
                                          _request(eng, "late", 1))
            later = [_request(eng, f"after the slot is free {i}", 5) for i in range(3)]
            r0 = eng._rid_dispatched
            seen["due"] += [(r0 + 2, eos_req), (r0 + 4, one_req)] + [
                (r0 + 9, r) for r in later]  # past the cooling fences
            eng.submit(long_req)
            got_long, got_eos, got_one = _events(long_req), _events(eos_req), _events(one_req)
            got_later = [_events(r) for r in later]
        finally:
            eng.tokenizer.eos_id = real_eos
        assert got_eos == want_eos and got_one == want_one
        assert got_long == _one_at_a_time()[0][0]  # its neighbours' ends changed nothing for it
        assert all(g[-1]["type"] == "done" for g in got_later) and eng.total_errors == 0
        for req, n_tok in ((eos_req, 1), (one_req, 1)):
            # the row rode at least one round before its first token was read ...
            assert any(req.request_id in rows for _, rows in seen["rounds"])
            # ... and nothing of that round was processed for it
            assert len(seen["tokens"][req.request_id]) == n_tok
        new = seen["admits"][n_admits:]
        slot_of = {rid: slot for a in new for rid, slot in zip(a["requests"], a["slots"])}
        reused = [slot_of[r.request_id] for r in later]
        assert slot_of[eos_req.request_id] in reused and slot_of[one_req.request_id] in reused
        assert eng.perf_stats()["admit"]["reads"] == len(seen["admits"])


@pytest.mark.parametrize("depth,admit_batch", [(1, 1), (2, 1), (2, 4)])
def test_an_admission_that_raises_at_its_read_fails_its_own_and_what_followed(depth, admit_batch):
    """(d) a poisoned admission surfaces at its read (the `engine.admit`
    chaos site): its requests get the error event and the end of the stream,
    whatever was dispatched after it fails as after a poisoned round, and the
    loop serves the next request."""
    with _queue_engine(depth, admit_batch=admit_batch) as (eng, rec):
        seen = _spy(eng)
        eng.start()
        long_req = _request(eng, SCRIPT[0][1], 40)
        victims = [_request(eng, "poisoned one", 8), _request(eng, "poisoned, the second", 8)]

        def arm():  # on the engine's thread, with the victims: every read from here on raises
            faults.configure("engine.admit:1.0", seed=0)
            for v in victims:
                eng.submit(v)

        seen["due"].append((2, arm))
        try:
            eng.submit(long_req)
            got = [_events(r) for r in [long_req, *victims]]
            assert faults.trip_counts() == {"engine.admit": 1}  # the first poisoned read ended them all
        finally:
            faults.configure("")
        for events in got[1:]:
            assert events == [{"type": "error", "error": "injected fault at engine.admit"}]
        # the stream that rode the rounds behind the admission: its tokens
        # up to the last round emitted, then the same error
        assert got[0][-1] == {"type": "error", "error": "injected fault at engine.admit"}
        assert [e["type"] for e in got[0][:-1]] == ["token"] * (len(got[0]) - 1) and len(got[0]) >= 2
        assert "".join(e["text"] for e in got[0][:-1]) and _one_at_a_time()[0][0][0] == got[0][0]
        assert eng.total_errors == 3 and not eng._inflight and all(s is None for s in eng._slots)
        assert eng._rid_fetched == eng._rid_dispatched
        # the victims' admissions were dispatched (one program or two) and none was read
        assert sum(len(a["requests"]) for a in seen["admits"]) == 3
        assert eng.perf_stats()["admit"]["reads"] == 1 == len(rec.snapshot(etype="admit_read"))
        after = eng.generate("served after the failure", max_tokens=6, temperature=0.0)
        assert after["usage"]["completion_tokens"] == 6 and eng.total_errors == 3
        assert eng.perf_stats()["admit"]["reads"] == 2


def test_a_constrained_admission_is_read_where_it_is_dispatched():
    """(e) the automaton's cursor must stand on the first token before the
    slot's masked round: a batch that holds a constrained request is read at
    once, behind whatever is in flight, and counted."""
    with _queue_engine(2, admit_batch=4) as (eng, _rec):
        choice = _request(eng, "heads or tails?", 8, constraint={"type": "choice", "choices": ["heads", "tails"]})
        plain = _request(eng, "an unconstrained neighbour", 6)
        seen = _spy(eng, [(2, choice), (4, plain)])
        eng.start()
        long_req = eng.submit(_request(eng, SCRIPT[0][1], 40))
        got = {k: _events(r) for k, r in (("long", long_req), ("choice", choice), ("plain", plain))}
        assert "".join(e["text"] for e in got["choice"] if e["type"] == "token") in ("heads", "tails")
        assert got["long"] == _one_at_a_time()[0][0] and got["plain"][-1]["type"] == "done"
        st = eng.perf_stats()["admit"]
        assert (st["reads"], st["reads_at_once"]) == (3, 1) and eng.total_errors == 0
        by_req = {a["requests"][0]: (a, r) for a, r in zip(seen["admits"], seen["reads"])}
        a, r = by_req[choice.request_id]
        assert r["adm"] is a["adm"] and r["at_once"] and r["fetched"] < a["dispatched"]  # rounds were unfetched
        i = seen["log"].index(("admit", id(a["adm"])))
        assert seen["log"][i + 1] == ("read", id(a["adm"]))
        a, r = by_req[plain.request_id]
        assert not r["at_once"] and r["fetched"] >= a["dispatched"]


def test_shutdown_leaves_no_admission_unread():
    """(e) an admission in flight when the loop stops is read by the last
    drain: its first token is on the stream before the shutdown's error."""
    with _queue_engine(2) as (eng, _rec):
        late = _request(eng, "admitted as the loop stops", 30)

        def stop():
            eng.submit(late)
            eng._stop_evt.set()

        seen = _spy(eng, [(3, stop)])
        eng.start()
        long_req = eng.submit(_request(eng, SCRIPT[0][1], 40))
        eng._thread.join(timeout=60)
        assert not eng._thread.is_alive() and not eng._inflight
        assert len(seen["admits"]) == len(seen["reads"]) == eng.perf_stats()["admit"]["reads"] == 2
        assert len(seen["tokens"][late.request_id]) >= 1  # read and processed, by the drain
        assert seen["log"][-1] == ("read", id(seen["admits"][-1]["adm"]))
        eng.shutdown()
        for req in (long_req, late):
            events = _events(req)
            assert events[-1] == {"type": "error", "error": "engine shutdown"}
            assert [e["type"] for e in events[:-1]] == ["token"] * (len(events) - 1)


def test_a_preemption_leaves_no_admission_unread():
    """(e) preemption snapshots committed rows: the drain before it reads
    the admission that was dispatched an iteration earlier."""
    with _queue_engine(2, env={"TPU_KV_HOST_OFFLOAD": "1"}, max_slots=2, max_seq_len=128) as (eng, _rec):
        assert eng._pool is not None
        second = _request(eng, "second low priority stream", 40)
        urgent = _request(eng, "urgent request", 8, priority=5)
        seen = _spy(eng, [(2, lambda: (eng.submit(second), eng.submit(urgent)))])
        preempt_one = eng._preempt_one

        def spy_preempt():
            seen["log"].append(("preempt", len(eng._inflight), [s.first_token_at > 0 for s in eng._slots if s]))
            return preempt_one()

        eng._preempt_one = spy_preempt
        eng.start()
        first = eng.submit(_request(eng, "preempt me please", 40))
        got = [_events(r) for r in (first, second, urgent)]
        assert all(g[-1]["type"] == "done" for g in got) and eng.total_errors == 0
        assert eng.memory_stats()["preempted_total"] >= 1
        adm = next(a["adm"] for a in seen["admits"] if a["requests"] == [second.request_id])
        log = seen["log"]
        i, j = log.index(("admit", id(adm))), log.index(("read", id(adm)))
        k = next(n for n, e in enumerate(log) if e[0] == "preempt")
        # dispatched, then read by the drain (no round between), then the victim was picked
        assert j == i + 1 and k == j + 1 and log[k] == ("preempt", 0, [True, True])


def test_a_speculative_verify_round_leaves_no_admission_unread():
    """(e) drafts continue the committed history: whenever a verify round
    runs, nothing is in flight and every seated slot has its first token."""
    prompt = "repeat this exact list again and again: alpha beta gamma delta alpha beta gamma delta"
    with _queue_engine(2, env={"TPU_SPEC": "1"}, max_seq_len=256) as (eng, _rec):
        assert eng._verify_fn is not None
        reqs = [_request(eng, prompt + tail, 32) for tail in ("", " alpha", " alpha beta")]
        seen = _spy(eng, [(1, reqs[1]), (2, reqs[2])])
        spec_round, calls = eng._spec_round, []

        def spy_spec(entries):
            calls.append((len(eng._inflight), [s.first_token_at > 0 for s in eng._slots if s]))
            return spec_round(entries)

        eng._spec_round = spy_spec
        eng.start()
        eng.submit(reqs[0])
        got = [_events(r) for r in reqs]
        assert all(g[-1]["type"] == "done" for g in got) and eng.total_errors == 0
        assert calls and eng.speculation_stats()["verify_calls"] == len(calls)
        assert all(n == 0 and all(stamped) for n, stamped in calls)
        assert len(seen["reads"]) == len(seen["admits"]) == eng.perf_stats()["admit"]["reads"]
        assert any(a["dispatched"] > a["fetched"] for a in seen["admits"])  # one did wait in the queue
