"""Admitted prompts riding a decode round's first step (`mixed_round_fn`,
`models/llama.py:mixed_step_q8`): held to `admit_fn` followed by a plain
round on the same prompts, slots and counters, and the engine's loop held to
when an admission rides and when it takes a program of its own."""

import queue
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family import device_state as _state, engine_for, engine_of_its_own, restore as _restore
from llm_mcp_tpu.executor.engine import GenRequest
from llm_mcp_tpu.kernels.attention import fused_q8_heads

S, B, K = 128, 8, 2


def _engine(monkeypatch, model="tiny-llm", attn="pallas", own=False, **kw):
    """The module's engine of these options as it was built (tests/family.py): a
    case that only calls its step programs shares it; one that starts it, queues
    requests or patches it takes one of its `own`."""
    kw = {"max_slots": B, "max_seq_len": S, "dtype": jnp.float32, "decode_chunk": K,
          "quant": "int8", "kv_quant": "int8", "prefill_chunk": 64, **kw}
    return (engine_of_its_own if own else engine_for)(monkeypatch, model, attn, **kw)


def _prompt(rng, n):
    return rng.integers(3, 250, size=n).astype(np.int32)


def _seed_rows(eng, rng, rows):
    """Give `rows` {slot: length} a context through admit_fn, one program a
    row; returns the host's lengths for a full-batch round (parked = S)."""
    lengths = np.full(B, S, np.int32)
    for slot, n in rows.items():
        ipack = np.asarray([slot, n, 0, 1, 1000 + slot], np.int32)
        tokens = np.zeros((1, eng._bucket(n)), np.int32)
        tokens[0, :n] = _prompt(rng, n)
        eng._ops["admit"](tokens, ipack, np.asarray([0.0, 1.0], np.float32))
        lengths[slot] = n
    return lengths


def _ride_arrays(eng, prompts, slots, counter, rung):
    """The packed buffer as `_stage_ride` lays it out: a prompt from a multiple
    of the engine's `_ride_align` on (1, or the recurrence's chunk)."""
    R = eng._ride_rows
    tokens = np.zeros(rung, np.int32)
    rowids = np.full(rung, R, np.int32)
    positions = np.full(rung, S, np.int32)
    ipack = np.zeros(3 * R + 2, np.int32)
    fpack = np.zeros(2 * R, np.float32)
    fpack[R:] = 1.0
    at = 0
    for i, (p, slot) in enumerate(zip(prompts, slots)):
        tokens[at:at + len(p)] = p
        rowids[at:at + len(p)] = i
        positions[at:at + len(p)] = np.arange(len(p))
        ipack[i], ipack[R + i] = slot, at + len(p) - 1
        at += eng._ride_len(p)
    assert at <= rung
    ipack[len(prompts):R] = slots[0]
    ipack[3 * R], ipack[3 * R + 1] = len(prompts), counter
    return tokens, rowids, positions, ipack, fpack


def _admit_arrays(eng, prompts, slots, counter):
    A = len(prompts)
    Ab = 1 << (A - 1).bit_length()
    bucket = eng._bucket(max(len(p) for p in prompts))
    tokens = np.zeros((Ab, bucket), np.int32)
    ipack = np.zeros(3 * Ab + 2, np.int32)
    ipack[Ab:2 * Ab] = 1
    fpack = np.zeros(2 * Ab, np.float32)
    fpack[Ab:] = 1.0
    for i, (p, slot) in enumerate(zip(prompts, slots)):
        tokens[i, :len(p)] = p
        ipack[i], ipack[Ab + i] = slot, len(p)
    ipack[3 * Ab], ipack[3 * Ab + 1] = A, counter
    return tokens, ipack, fpack


def _rows_match(eng, ck, ck_ref, slot, n):
    """Rows [0, n) of a slot in two caches. Layer 0 is exact: its K/V come
    from the embeddings through `qdot`, whose activation scales are a row's
    own, so a row's products do not depend on what shares the matmul. Deeper
    layers pass through the prompt's attention, which admit_fn runs as the
    flash kernel and the mixed step as one masked product: float32 rounding
    apart, so scales to 1e-5 and a payload step of 1 on a few entries."""
    heads, _, abreast = fused_q8_heads(ck)  # the K and V rows; the packed scales' row follows them
    hk = 2 * heads // abreast
    assert heads == eng.cfg.n_kv_heads
    q, q_ref = ck["q"][:, slot, :hk, :n].astype(int), ck_ref["q"][:, slot, :hk, :n].astype(int)
    s, s_ref = ck["s"][:, slot, :, :n], ck_ref["s"][:, slot, :, :n]
    assert np.array_equal(ck["q"][0, slot, :, :n], ck_ref["q"][0, slot, :, :n])
    assert np.array_equal(s[0], s_ref[0])
    assert np.abs(q - q_ref).max() <= 1 and (q != q_ref).mean() < 2e-3
    np.testing.assert_allclose(s, s_ref, rtol=1e-5)


CASES = {
    # name: (decoding rows {slot: length}, prompt lengths, their slots, rung)
    "one_prompt": ({0: 20, 1: 33, 3: 9}, [37], [2], 128),
    "three_packed": ({0: 20, 1: 33, 3: 9}, [41, 17, 30], [2, 5, 4], 128),
    "rung_edge": ({0: 20, 5: 12}, [64, 64], [1, 2], 128),
    "pads_write_nothing": ({0: 20, 1: 33}, [5], [6], 128),
    "parked_row": ({1: 33}, [23, 11], [0, 7], 128),
    "slot_reused": ({0: 20, 1: 33, 2: 50}, [29], [2], 128),
    # weights left unquantised: `qdot` is a plain matmul, row-independent too
    "plain_weights": ({0: 20, 1: 33, 3: 9}, [41, 17, 30], [2, 5, 4], 128, {"quant": ""}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_mixed_round_is_admit_fn_and_a_plain_round(monkeypatch, case):
    """Cache rows and scales of the new slots, the decode rows' K tokens under
    greedy, the prompts' first tokens, the token ring and the sampling rows:
    what admit_fn then decode_chunk_fn leave, mixed_round_fn leaves."""
    rows, plens, slots, rung, *kw = CASES[case]
    eng = _engine(monkeypatch, **(kw[0] if kw else {}))
    assert eng._ride_off() == "" and eng.decode_impl == "pallas"
    rng = np.random.default_rng(7)
    rows = dict(rows)
    lengths = _seed_rows(eng, rng, rows)
    if case == "slot_reused":
        lengths[2] = S  # the slot was another request's a round ago: now parked
    prompts = [_prompt(rng, n) for n in plens]
    start = _state(eng)
    packed = np.concatenate([lengths, [77]]).astype(np.int32)

    # the reference: a program of its own, then a plain round
    toks0_ref = np.asarray(eng._ops["admit"](*_admit_arrays(eng, prompts, slots, 55)))
    after_admit = _state(eng)
    out_ref = np.asarray(eng._ops["decode"]("plain", 0, packed, (), False, 0, None))
    ref = _state(eng)

    _restore(eng, start)
    out, toks0 = eng._ops["decode"](
        "mixed", 0, packed, _ride_arrays(eng, prompts, slots, 55, rung), False, 0, None)
    got = _state(eng)

    A = len(prompts)
    assert np.array_equal(np.asarray(toks0)[:A], toks0_ref[:A])
    live = [b for b in range(B) if lengths[b] < S]
    assert np.array_equal(np.asarray(out)[:, live], out_ref[:, live])
    ck_ref, ck = ref[0], got[0]
    for p, slot in zip(prompts, slots):
        _rows_match(eng, ck, ck_ref, slot, len(p))
    for b in live:  # the decode rows' appended positions
        n = lengths[b] + K
        assert np.array_equal(ck["q"][:, b, :, :n], ck_ref["q"][:, b, :, :n])
        assert np.array_equal(ck["s"][:, b, :, :n], ck_ref["s"][:, b, :, :n])
    # nothing else was written: a slot neither decoding nor admitted is as it
    # was before the round (pad tokens and unused descriptor rows write no row)
    idle = [b for b in range(B) if b not in live and b not in slots]
    for b in idle:
        assert np.array_equal(ck["q"][:, b], start[0]["q"][:, b])
        assert np.array_equal(ck["s"][:, b], start[0]["s"][:, b])
    # a new slot past its prompt is untouched too (admit_fn writes its bucket)
    for p, slot in zip(prompts, slots):
        assert np.array_equal(ck["q"][:, slot, :, len(p):], start[0]["q"][:, slot, :, len(p):])
    # sampling rows and the token ring of the new slots and of the live rows
    for i in (2, 3, 4):
        assert np.array_equal(got[i], ref[i])
    # (the reference's plain round ran with the new slots parked, so ITS ring
    # holds a parked row's token there; admit_fn left the first tokens)
    assert np.array_equal(got[5][live], ref[5][live])
    assert np.array_equal(got[5][list(slots)], toks0_ref[:A])
    assert np.array_equal(after_admit[5][list(slots)], toks0_ref[:A])


def test_first_token_logits_match_admit_fn(monkeypatch):
    """Each prompt's last-token logits from the mixed step against
    `llama_prefill`'s, at the tolerance the chunked prefill's tests use."""
    from llm_mcp_tpu.models.llama import llama_prefill, mixed_step_q8

    eng = _engine(monkeypatch)
    rng = np.random.default_rng(3)
    lengths = _seed_rows(eng, rng, {0: 20, 1: 33})
    prompts = [_prompt(rng, n) for n in (41, 17, 30)]
    slots = [2, 5, 4]
    tokens, rowids, positions, ipack, _ = _ride_arrays(eng, prompts, slots, 1, 128)
    R = eng._ride_rows
    logits, _, _ = jax.jit(lambda *a: mixed_step_q8(eng.cfg, *a))(
        eng.params, eng._ck, eng._cv, eng._d_last_tok, jnp.asarray(lengths),
        tokens, rowids, positions, ipack[:R], ipack[R:2 * R])
    a_tokens, a_ipack, _ = _admit_arrays(eng, prompts, slots, 1)
    Ab = a_tokens.shape[0]
    ref, _, _ = jax.jit(lambda p, t, n: llama_prefill(
        eng.cfg, p, t, n, attn_impl="pallas", quant_kv=True))(
        eng.params, a_tokens, a_ipack[Ab:2 * Ab])
    np.testing.assert_allclose(
        np.asarray(logits)[B:B + 3], np.asarray(ref)[:3], rtol=2e-4, atol=2e-4)


# -- the engine's loop ----------------------------------------------------------


def _drain(req, timeout=60.0):
    events = []
    end = time.time() + timeout
    while time.time() < end:
        try:
            ev = req.out.get(timeout=0.5)
        except queue.Empty:
            continue
        if not isinstance(ev, dict):
            return events
        events.append(ev)
    raise AssertionError("stream did not end")


def _submit(eng, text, **kw):
    req = GenRequest(prompt_ids=eng.tokenizer.encode(text), max_tokens=kw.pop("max_tokens", 12),
                     temperature=0.0, **kw)
    return eng.submit(req)


def _wait_active(eng, n, timeout=120.0):  # an admit program's first dispatch compiles: 30 s and more a hybrid's, cold, beside five workers
    end = time.time() + timeout
    while time.time() < end:
        if sum(s is not None for s in eng._slots) >= n:
            return
        time.sleep(0.01)
    raise AssertionError("rows never became active")


def test_a_queued_request_rides_a_round_beside_active_rows(monkeypatch):
    """With a full-batch round decoding, a queued request rides it: the ride
    counter moves and `admit_prog` does not, its first token is emitted before
    its first decode token, its text equals an idle engine's (admit_fn), and
    the occupancy bookkeeping sees the mixed round as a `fused` sample. (With
    recurrent layers of each kind: tests/test_mixed_round_hybrid.py.)"""
    rides_beside_active_rows(monkeypatch, "tiny-llm")


def rides_beside_active_rows(monkeypatch, model):
    monkeypatch.setenv("TPU_PERF_SAMPLE", "1")
    kw = {} if model == "tiny-llm" else {"model": model, "quant": ""}
    eng = _engine(monkeypatch, own=True, max_slots=4, decode_chunk=2, **kw).start()
    try:
        alone = eng.generate("the quick brown fox rides along", max_tokens=10, temperature=0.0)
        # three of four rows decoding: a full-batch round (pow2 of 3 = 4)
        long = [_submit(eng, f"row {i} keeps decoding for a while", max_tokens=100)
                for i in range(3)]
        _wait_active(eng, 3)
        before = eng.perf_stats()["admit"]
        progs_before = len(eng._flight.snapshot(etype="admit_prog"))
        rider = _submit(eng, "the quick brown fox rides along", max_tokens=10)
        events = _drain(rider)
        after = eng.perf_stats()["admit"]
        assert after["rides"]["rounds"] == before["rides"]["rounds"] + 1
        assert after["rides"]["prompts"] == before["rides"]["prompts"] + 1
        assert after["rides"]["padded_tokens"] - before["rides"]["padded_tokens"] in (128, 256)
        assert after["programs"] == before["programs"]
        assert len(eng._flight.snapshot(etype="admit_prog")) == progs_before
        mixed = eng._flight.snapshot(etype="mixed")
        assert mixed and mixed[-1]["fields"]["prompts"] == 1
        reads = [e["fields"] for e in eng._flight.snapshot(etype="admit_read")]
        assert reads[-1].get("rid") == mixed[-1]["fields"]["rid"] and "aid" not in reads[-1]
        text = "".join(e["text"] for e in events if e["type"] == "token")
        assert text == alone["text"]
        done = [e for e in events if e["type"] == "done"][0]
        assert done["usage"]["completion_tokens"] == 10
        # the occupancy bookkeeping: a mixed round is a `fused` sample whose
        # tokens are its decode rows', and the seat closes the slot's vacancy
        # where a batch's seat does (the rider's slot, freed, rides again)
        fused = eng.perf_stats()["phases"]["fused"]
        assert fused["samples"] >= 1 and fused["tokens"] == fused["samples"] * 3 * 2
        _drain(_submit(eng, "the slot the rider left is taken again", max_tokens=4))
        again = eng.perf_stats()["admit"]
        assert again["rides"]["rounds"] == after["rides"]["rounds"] + 1
        assert again["vacancy"]["count"] == after["vacancy"]["count"] + 1
        for r in long:
            r.cancelled = True
    finally:
        eng.shutdown()


@pytest.mark.parametrize("why", ["no active rows", "compact", "reads at once", "recurrent",
                                 "over the cap", "other"])
def test_what_may_not_ride_takes_admit_fn_and_says_why(monkeypatch, why):
    """`recurrent` is no reason any more (a configuration with a state pool
    rides: above): its case is such a configuration's request that meets an idle
    engine, which takes a program of its own for the reason any configuration's
    does, and the pool's `mixed_round` counter counts that program."""
    kw, reason = {}, why
    if why == "recurrent":
        kw, reason = dict(model="tiny-olmo-hybrid", quant="", max_slots=4), "no active rows"
    elif why == "other":
        kw = dict(attn="xla", max_slots=4)
    elif why == "compact":
        kw = dict(max_slots=16)
    else:
        kw = dict(max_slots=4)
    if why == "over the cap":
        kw.update(max_seq_len=512, prefill_chunk=512)
    eng = _engine(monkeypatch, own=True, **kw).start()
    try:
        busy = []
        if reason != "no active rows":
            n = 2 if why == "compact" else 3  # 2 of 16: a compact round
            busy = [_submit(eng, f"row {i} keeps decoding for a while", max_tokens=100)
                    for i in range(n)]
            _wait_active(eng, n)
        before = eng.perf_stats()["admit"]
        extra = {}
        if why == "reads at once":
            extra = dict(logit_bias=[(65, 2.0)])
        text = "w " * 150 if why == "over the cap" else "takes a program of its own"
        events = _drain(_submit(eng, text, max_tokens=6, **extra))
        assert [e for e in events if e["type"] == "done"]
        after = eng.perf_stats()["admit"]
        assert after["rides"] == before["rides"]
        assert after["programs"] == before["programs"] + 1
        assert after["own"].get(reason, 0) == before["own"].get(reason, 0) + 1
        assert after["own_prompts"].get(reason, 0) == before["own_prompts"].get(reason, 0) + 1
        if why == "recurrent":
            assert eng._ride_off() == "" and eng._runs("mixed_round") and "recurrent" not in after["own"]
            assert eng.perf_stats()["state_pool"]["off"]["mixed_round"] == after["programs"]
        for r in busy:
            r.cancelled = True
    finally:
        eng.shutdown()


# -- set-up: every mixed shape is warmed, and only this configuration's ------------


def test_every_mixed_shape_the_engine_dispatches_is_in_the_zoo(monkeypatch):
    """A shape first met inside the window stops the server for seconds: the
    zoo lists a `mixed` step a rung (the full batch only), `_stage_ride` picks
    no other size, the plan can lower each, and a key of another
    configuration's (a rung it lacks, the other paging flag, an engine that
    keeps admit_fn) is refused. (With recurrent layers:
    tests/test_mixed_round_hybrid.py.)"""
    every_mixed_shape_is_in_the_zoo(monkeypatch, "tiny-llm")


def every_mixed_shape_is_in_the_zoo(monkeypatch, model):
    base = {} if model == "tiny-llm" else {"model": model, "quant": ""}
    eng = _engine(monkeypatch, own=True, max_seq_len=256, **base)
    phys = eng._phys is not None
    zoo = eng.warmup_shape_zoo()
    # a configuration with recurrent layers rides at the largest rung alone
    rungs = (256,) if eng.cfg.recurrent else (128, 256)
    assert [k for ph, k in zoo if ph == "mixed"] == [(r, phys) for r in rungs]
    assert eng._ride_rungs == rungs and eng.RIDE_RUNGS == (128, 256)
    for rung in eng._ride_rungs:
        assert eng._warmup_key_fits("mixed", (rung, phys))
        fn, args, kw = eng.warmup_operands("mixed", (rung, phys))
        assert fn is eng._mixed_fn and args[8].shape == (rung,)
        assert args[11].shape == (3 * eng._ride_rows + 2,) and set(kw) == {"paged"}
        assert eng.warmup_operands("mixed", (rung, not phys)) is None
    assert not eng._warmup_key_fits("mixed", (512, phys))
    assert not eng._warmup_key_fits("mixed", (64, phys))
    assert eng._warmup_key_fits("mixed", (128, phys)) == (not eng.cfg.recurrent)
    assert eng.warmup_lower("mixed", (rungs[0], phys)) is not None
    # a cache too short for a rung lists none of it; an engine that keeps
    # admit_fn lists no mixed step and refuses a prior that carries one
    short = _engine(monkeypatch, max_seq_len=128, **base)
    assert [k[0] for ph, k in short.warmup_shape_zoo() if ph == "mixed"] == [128]
    xla = _engine(monkeypatch, attn="xla", own=True, **base)
    assert xla._ride_off() == "other"
    assert not [ph for ph, _ in xla.warmup_shape_zoo() if ph == "mixed"]
    assert not xla._warmup_key_fits("mixed", (128, False))
    assert xla.warmup_operands("mixed", (128, False)) is None


@pytest.mark.parametrize("phase", ["mixed", "decode", "admit"])
def test_the_plans_module_is_the_one_the_live_call_lowers(monkeypatch, phase, model="tiny-llm"):
    """The warm-up plan's compile serves a shape's first real dispatch only if
    both lower to the SAME module (the persistent cache's key is made of it).
    Off a mesh the live call's module carries no argument shardings, so the
    plan's operands carry none: with a single-device sharding on each the
    plan compiled every shape under a key no dispatch ever asked for."""
    eng = _engine(monkeypatch, **({} if model == "tiny-llm" else {"model": model, "quant": ""}))
    phys = eng._phys is not None
    R = eng._ride_rows
    state = (eng._d_temp, eng._d_topk, eng._d_topp, eng._d_last_tok)
    packed = np.concatenate([np.full(B, S, np.int32), [77]]).astype(np.int32)
    paged = eng._paged_from(eng._paged_payload())
    if phase == "mixed":
        key = (128, phys)
        live = (eng.params, eng._ck, eng._cv, packed, *state,
                *_ride_arrays(eng, [np.arange(3, 40, dtype=np.int32)], [2], 55, 128))
        kw = {"paged": paged}
    elif phase == "decode":
        key = (B, False, phys)
        live, kw = (eng.params, eng._ck, eng._cv, packed, *state), {"compact": False, "paged": paged}
    else:
        key = (1, 64)
        live = (eng.params, eng._ck, eng._cv, *state,
                *_admit_arrays(eng, [np.arange(3, 40, dtype=np.int32)], [2], 55))
        kw = {}
    fn, args, plan_kw = eng.warmup_operands(phase, key)
    assert fn.lower(*args, **plan_kw).as_text() == fn.lower(*live, **kw).as_text()


def test_a_round_carries_prompts_only_at_a_full_batch_with_nothing_ahead_of_the_queue(monkeypatch):
    """`_round_carries`: no rows, a compact round, a chunk group in the round
    or a preempted snapshot waiting (it yields to the queue's head, so a head
    held back for a ride would hold both) keep the iteration on admit_fn."""
    from types import SimpleNamespace

    eng = _engine(monkeypatch, own=True, max_slots=16)
    assert not eng._round_carries(0, None) and eng._ride_state == "no active rows"
    assert not eng._round_carries(2, None) and eng._ride_state == "compact"
    assert eng._round_carries(9, None) and eng._ride_state == "other"
    assert not eng._round_carries(9, object()) and eng._ride_state == "other"
    eng._pool = SimpleNamespace(has_preempted=lambda: True)
    assert not eng._round_carries(9, None)
    eng._pool = SimpleNamespace(has_preempted=lambda: False)
    assert eng._round_carries(9, None)


def test_a_staged_batch_is_cut_at_the_cap_and_keeps_the_queues_order(monkeypatch):
    """`_stage_ride` takes the prompts that fit the largest rung; the one that
    does not leads the queue again, and a prompt that may not ride (here: a
    logit bias, whose first token is read at once) stops the staging and stays
    queued for `_admit_pending`."""
    eng = _engine(monkeypatch, own=True, max_seq_len=512, prefill_chunk=512, max_slots=8)
    mk = lambda n, **kw: GenRequest(prompt_ids=list(range(3, 3 + n)), max_tokens=4, **kw)  # noqa: E731
    reqs = [mk(100), mk(100), mk(100), mk(20)]
    for r in reqs:
        eng._admit.put(r)
    ride = eng._stage_ride()
    assert [r for _, r, _ in ride.batch] == reqs[:2] and ride.held_by == "budget"
    assert ride.rung == 256 and list(eng._admit.queue) == reqs[2:]
    assert list(ride.rowids[:200]) == [0] * 100 + [1] * 100 and set(ride.rowids[200:]) == {eng._ride_rows}
    assert list(ride.positions[98:102]) == [98, 99, 0, 1] and set(ride.positions[200:]) == {512}
    R = eng._ride_rows
    assert list(ride.ipack[R:R + 2]) == [99, 199] and ride.ipack[3 * R] == 2
    eng._admit.queue.clear()
    biased = mk(10, logit_bias=[(65, 1.0)])
    for r in (mk(10), biased, mk(10)):
        eng._admit.put(r)
    ride = eng._stage_ride()
    assert len(ride.batch) == 1 and ride.rung == 256 and ride.held_by == "own"
    assert eng._admit.queue[0] is biased and eng._admit.qsize() == 2
    # a single prompt over the cap never rides: it takes admit_fn ("over the cap")
    eng._admit.queue.clear()
    eng._admit.put(mk(300))
    assert eng._stage_ride() is None and eng._admit.qsize() == 1
    # the rung: one never dispatched yet takes the first batch it holds, the
    # largest first (the first two rides first-dispatch both executables);
    # after that the smallest that holds the batch
    phys = eng._phys is not None
    for seen, n, want in (((), 1, 256), ((256,), 1, 128), ((256,), 129, 256), ((128,), 60, 256),
                          ((128, 256), 128, 128), ((128, 256), 129, 256)):
        eng._admit.queue.clear()
        eng._seen_exec_shapes = {("mixed", r, phys) for r in seen}
        eng._admit.put(mk(n))
        assert eng._stage_ride().rung == want, (seen, n)
