"""Admitted prompts riding a decode round in a configuration with recurrent
layers (`models/hybrid.py:hybrid_mixed_step`, PR 42): held to `admit_fn`
followed by a plain round for the four kinds of recurrent layer (the fourth, a
gated short convolution, has a tail and no matrix state, rope on its attention
layers and two leading dense layers with pool rows of their own: PR 52), and
the engine's loop, zoo and warm-up plan with such a preset. The helpers and the
dense family's cases are tests/test_mixed_round.py's. A kind's cases run
together (`scope="module"` groups them) and share the kind's engine, which ends
before the next kind's is built (tests/family.py: one preset's engines at a
time; while every case built its own and none ended, the fifth preset's had to
run in a process of their own)."""

import numpy as np
import pytest

from llm_mcp_tpu.kernels.attention import fused_q8_heads

from test_mixed_round import (
    B, K, S, _admit_arrays, _engine, _prompt, _restore, _ride_arrays, _seed_rows, _state,
    every_mixed_shape_is_in_the_zoo, rides_beside_active_rows,
    test_the_plans_module_is_the_one_the_live_call_lowers as plans_module_is_the_live_calls)

HYBRIDS = {"kda": "tiny-solar", "gdn": "tiny-olmo-hybrid", "ssm": "tiny-granite-hybrid",
           "conv": "tiny-lfm2"}
every_kind = pytest.mark.parametrize("kind", list(HYBRIDS), scope="module")
HYBRID_CASES = {
    # name: (decoding rows {slot: length}, prompt lengths, their slots)
    "one_prompt": ({0: 20, 1: 33, 3: 9}, [37], [2]),
    "two_packed": ({0: 20, 1: 33, 3: 9}, [41, 17], [2, 5]),
    # one of exactly a chunk multiple, one a token over it, one under the taps
    "chunk_edges": ({0: 20, 5: 12}, [32, 33, 2], [1, 2, 7]),
    "parked_row_and_reused_slot": ({1: 33, 2: 50}, [23, 64], [0, 2]),
}


def _hybrid_engine(monkeypatch, kind, **kw):
    eng = _engine(monkeypatch, model=HYBRIDS[kind], quant="", **kw)
    assert eng._ride_off() == "" and eng.decode_impl == "pallas" and eng._ride_align == 32
    return eng


def _kv_close(eng, ck, ck_ref, slot, n):
    """Rows [0, n) of a slot in two int8 caches: the prompt's attention is the
    flash kernel in admit_fn and one masked product in the mixed step, and the
    recurrence before it the same chunks in a scan of another length, so
    float32 rounding apart: scales to 1e-4, a payload step of 1 on a few entries."""
    heads, _, abreast = fused_q8_heads(ck)  # the K and V rows; the packed scales' row follows them
    hk = 2 * heads // abreast
    assert heads == eng.cfg.n_kv_heads
    q, q_ref = ck["q"][:, slot, :hk, :n].astype(int), ck_ref["q"][:, slot, :hk, :n].astype(int)
    assert np.abs(q - q_ref).max() <= 1 and (q != q_ref).mean() < 2e-3
    np.testing.assert_allclose(ck["s"][:, slot, :, :n], ck_ref["s"][:, slot, :, :n], rtol=1e-4)


@pytest.mark.parametrize("case", list(HYBRID_CASES))
@every_kind
def test_a_hybrid_mixed_round_is_admit_fn_and_a_plain_round(monkeypatch, kind, case):
    """What admit_fn then decode_chunk_fn leave, mixed_round_fn leaves in a
    configuration with recurrent layers of each kind: the prompts' first
    tokens, their int8 KV rows and scales, each prompt's row of the state pool
    and its convolution tail (float32 rounding), the decode rows' tokens, KV and
    state bit for bit, no other row of cache or pool touched, and the expert
    counts under the phase they belong to."""
    rows, plens, slots = HYBRID_CASES[case]
    eng = _hybrid_engine(monkeypatch, kind)
    rng = np.random.default_rng(11)
    lengths = _seed_rows(eng, rng, dict(rows))
    if "reused" in case:
        lengths[2] = S  # another request's a round ago (its state still in the pool): parked
    prompts = [_prompt(rng, n) for n in plens]
    start = _state(eng)
    packed = np.concatenate([lengths, [77]]).astype(np.int32)

    toks0_ref = np.asarray(eng._ops["admit"](*_admit_arrays(eng, prompts, slots, 55)))
    out_ref = np.asarray(eng._ops["decode"]("plain", 0, packed, (), False, 0, None))
    ref = _state(eng)
    _restore(eng, start)
    out, toks0 = eng._ops["decode"](
        "mixed", 0, packed, _ride_arrays(eng, prompts, slots, 55, 128), False, 0, None)
    got = _state(eng)

    A = len(prompts)
    live = [b for b in range(B) if lengths[b] < S]
    idle = [b for b in range(B) if b not in live and b not in slots]
    assert np.array_equal(np.asarray(toks0)[:A], toks0_ref[:A])
    assert np.array_equal(np.asarray(out)[:K, live], out_ref[:K, live])
    ck, ck_ref = got[0], ref[0]
    pool, pool_ref, pool0 = got[1]["state"], ref[1]["state"], start[1]["state"]
    members = tuple(pool_ref)  # ("S", "conv"), or the tail alone for a kind without a matrix state
    assert members == (("conv",) if kind == "conv" else ("S", "conv"))
    for p, slot in zip(prompts, slots):
        _kv_close(eng, ck, ck_ref, slot, len(p))
        assert np.array_equal(ck["q"][:, slot, :, len(p):], start[0]["q"][:, slot, :, len(p):])
        for member in members:  # a state was written
            assert np.abs(pool_ref[member][:, slot]).max() > 1e-3
            np.testing.assert_allclose(pool[member][:, slot], pool_ref[member][:, slot],
                                       rtol=1e-3, atol=1e-4)
    # the decode rows: their tokens (above) as the plain round leaves them; their
    # appended positions and state to float32 rounding against it (these
    # presets' weights are float32, and the host's float32 product blocks its sum
    # by the row count, so 8 rows alone and 8 of 136 round apart through the
    # layers), and bit for bit whatever the prompts beside them hold
    _restore(eng, start)
    others = [_prompt(rng, n) for n in plens]
    out_others, _ = eng._ops["decode"](
        "mixed", 0, packed, _ride_arrays(eng, others, slots, 55, 128), False, 0, None)
    beside = _state(eng)
    assert np.array_equal(np.asarray(out_others)[:K, live], np.asarray(out)[:K, live])
    for b in live:
        _kv_close(eng, ck, ck_ref, b, lengths[b] + K)
        for member in members:
            np.testing.assert_allclose(pool[member][:, b], pool_ref[member][:, b],
                                       rtol=1e-3, atol=1e-4)
            assert np.array_equal(pool[member][:, b], beside[1]["state"][member][:, b])
        for plane in ("q", "s"):
            assert np.array_equal(ck[plane][:, b], beside[0][plane][:, b])
    for b in idle:  # pads and unused descriptor rows write no row of cache or pool
        assert np.array_equal(ck["q"][:, b], start[0]["q"][:, b])
        for member in members:
            assert np.array_equal(pool[member][:, b], pool0[member][:, b])
    for i in (2, 3, 4):
        assert np.array_equal(got[i], ref[i])
    assert np.array_equal(got[5][live], ref[5][live])
    assert np.array_equal(got[5][list(slots)], toks0_ref[:A])
    if eng.cfg.n_experts:
        # [2, L, 5]: decode steps under 0, prefills under 1; a mixed step's
        # decode rows and prompt tokens each where a program of their own counts
        moved, moved_ref = got[1]["moe"] - start[1]["moe"], ref[1]["moe"] - start[1]["moe"]
        assert (moved[0, :, 4] == K).all() and (moved[1, :, 4] == 1).all()
        assert (moved[0, :, 0] == K * len(live)).all() and (moved[1, :, 0] == sum(plens)).all()
        assert np.array_equal(moved[0], moved_ref[0])
        if A & (A - 1) == 0:  # (admit_fn's padding row routes a token of its own)
            assert np.array_equal(moved[1], moved_ref[1])
    else:
        assert "moe" not in got[1]


@every_kind
def test_two_prompts_packed_in_one_rung_do_not_see_each_other(monkeypatch, kind):
    """A prompt packed behind another leaves the KV rows, the state and the
    convolution tail it leaves riding alone, and takes the same first token:
    the recurrence starts from zero at its first chunk, the convolution reads
    nothing before its position 0, attention is masked to its own tokens."""
    eng = _hybrid_engine(monkeypatch, kind)
    rng = np.random.default_rng(5)
    lengths = _seed_rows(eng, rng, {0: 20, 1: 33})
    first, second = _prompt(rng, 45), _prompt(rng, 39)
    start = _state(eng)
    packed = np.concatenate([lengths, [77]]).astype(np.int32)
    _, toks_alone = eng._ops["decode"](
        "mixed", 0, packed, _ride_arrays(eng, [second], [5], 55, 128), False, 0, None)
    alone = _state(eng)
    _restore(eng, start)
    _, toks_both = eng._ops["decode"](
        "mixed", 0, packed, _ride_arrays(eng, [first, second], [4, 5], 55, 128), False, 0, None)
    both = _state(eng)
    assert np.asarray(toks_both)[1] == np.asarray(toks_alone)[0]
    n = len(second)
    for plane in ("q", "s"):
        assert np.array_equal(both[0][plane][:, 5, :, :n], alone[0][plane][:, 5, :, :n])
    for member in alone[1]["state"]:
        got, want = both[1]["state"][member][:, 5], alone[1]["state"][member][:, 5]
        assert np.abs(want).max() > 1e-3
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)
        assert np.abs(both[1]["state"][member][:, 4]).max() > 1e-3  # the first one's own


# -- the engine's loop, its zoo and its plan with recurrent layers -------------------------


@every_kind
def test_a_queued_request_rides_a_round_with_recurrent_layers(monkeypatch, kind):
    rides_beside_active_rows(monkeypatch, HYBRIDS[kind])


@pytest.mark.parametrize("kind", ["ssm", "conv"])
def test_every_mixed_shape_of_a_recurrent_configuration_is_in_the_zoo(monkeypatch, kind):
    every_mixed_shape_is_in_the_zoo(monkeypatch, HYBRIDS[kind])


@every_kind
def test_the_plans_hybrid_mixed_round_is_the_one_the_live_call_lowers(monkeypatch, kind):
    plans_module_is_the_live_calls(monkeypatch, "mixed", HYBRIDS[kind])
