"""Grammar-constrained decoding subsystem (constrain/ + engine hooks +
the API surface): byte-automaton legality for regex/choice/json_schema
grammars, the token-lift (trie → packed bitmask) and its per-state memo,
the mask-then-sample fusion in ops/sampling.py (bias cannot resurrect a
forbidden token; a masked chi-square proving rejection resampling stays
exact under an adversarial drafter), engine-level guarantees (greedy
constrained spec ≡ non-spec, TPU_CONSTRAIN=0 as a structural no-op with
ZERO new executables, logit_bias riding the same mask-add path), the
automaton surviving preempt→restore and the migration wire (raw spec +
consumed ids, never automaton internals), and the OpenAI-style
response_format / tools / tool_choice / logit_bias parsing with its 400
paths.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from llm_mcp_tpu.constrain import ByteAutomaton, GrammarError
from llm_mcp_tpu.constrain.grammar import choices_to_grammar, regex_to_grammar
from llm_mcp_tpu.constrain.masks import ConstraintCompiler, mask_words
from llm_mcp_tpu.constrain.schema import build_automaton

# --------------------------------------------------------------- grammar --


def _accepts(auto: ByteAutomaton, data: bytes) -> bool:
    sid = auto.step_bytes(auto.start_state, data)
    return sid >= 0 and auto.accepting(sid)


def test_regex_grammar_legality():
    auto = ByteAutomaton(*regex_to_grammar("a(b|c){2}d?"))
    for ok in (b"abb", b"acc", b"abc", b"abbd"):
        assert _accepts(auto, ok), ok
    for bad in (b"a", b"abbb", b"ad", b"abbx", b"babb"):
        assert not _accepts(auto, bad), bad
    # stepping an illegal byte is a dead end, not an exception
    assert auto.step(auto.start_state, ord("z")) == -1


def test_regex_char_class_and_quantifiers():
    auto = ByteAutomaton(*regex_to_grammar("[a-c]+[0-9]*!"))
    assert _accepts(auto, b"abc123!")
    assert _accepts(auto, b"a!")
    assert not _accepts(auto, b"1!")  # digits cannot lead
    assert not _accepts(auto, b"abc")  # missing terminator
    # negated class
    neg = ByteAutomaton(*regex_to_grammar("[^x]x"))
    assert _accepts(neg, b"yx")
    assert not _accepts(neg, b"xx")


def test_bad_regex_raises_grammar_error():
    for pat in ("a(b", "a{3,1}", "[z-a]", "a**"):
        with pytest.raises(GrammarError):
            ByteAutomaton(*regex_to_grammar(pat))


def test_choice_grammar_accepts_exactly_the_choices():
    auto = ByteAutomaton(*choices_to_grammar(["yes", "no", "maybe"]))
    for c in ("yes", "no", "maybe"):
        assert _accepts(auto, c.encode())
    for bad in (b"ye", b"yess", b"nope", b""):
        assert not _accepts(auto, bad)


CLOSED_SCHEMA = {
    "type": "object",
    "properties": {
        "tool": {"enum": ["search", "fetch"]},
        "urgent": {"type": "boolean"},
    },
    "required": ["tool", "urgent"],
}


def test_closed_schema_accepts_exactly_enumerated_json():
    """A closed schema (every field enum/boolean) admits a FINITE
    language: the four enumerations and nothing else — the property the
    agent-trace schemas lean on so the accepting state is EOS-only."""
    auto = build_automaton({"type": "json_schema", "schema": CLOSED_SCHEMA})
    # canonical output is compact: keys in schema order, no whitespace
    for tool in ("search", "fetch"):
        for urg in ("true", "false"):
            s = '{"tool":"%s","urgent":%s}' % (tool, urg)
            assert _accepts(auto, s.encode()), s
    for bad in (
        b'{"tool":"search"}',  # missing property
        b'{"tool":"grep","urgent":true}',  # off-enum value
        b'{"urgent":true,"tool":"search"}',  # property order is fixed
        b'{"tool":"search","urgent":1}',  # wrong type
        b'{"tool": "search", "urgent": true}',  # non-canonical whitespace
    ):
        assert not _accepts(auto, bad), bad
    # closed ⇒ the accepting state has no outgoing bytes: generation
    # cannot continue past a finished object
    sid = auto.step_bytes(
        auto.start_state, b'{"tool":"fetch","urgent":false}'
    )
    assert auto.accepting(sid)
    assert not auto.live_bytes(sid)


def test_json_object_spec_accepts_generic_json():
    """json_object admits any object in the CANONICAL compact form — a
    generation language, not a parser: whitespace variants are simply
    never emitted, so the grammar does not carry them."""
    auto = build_automaton({"type": "json_object"})
    for ok in (
        b"{}",
        b'{"a":1}',
        b'{"k":[1,-2.5e3,"s",true,null],"n":{"x":false}}',
    ):
        assert _accepts(auto, ok), ok
    for bad in (b"[]", b"17", b'{"a":}', b'{"a" 1}'):
        assert not _accepts(auto, bad), bad


def test_schema_ref_const_and_anyof():
    schema = {
        "$defs": {"lvl": {"enum": ["low", "high"]}},
        "anyOf": [
            {
                "type": "object",
                "properties": {
                    "op": {"const": "set"},
                    "level": {"$ref": "#/$defs/lvl"},
                },
            },
            {"const": "noop"},
        ],
    }
    auto = build_automaton({"type": "json_schema", "schema": schema})
    assert _accepts(auto, b'{"op":"set","level":"low"}')
    assert _accepts(auto, b'"noop"')
    assert not _accepts(auto, b'{"op":"get","level":"low"}')
    assert not _accepts(auto, b'"nope"')


# ------------------------------------------------------------ token lift --


class _FakeTok:
    """Byte-tokenizer stand-in: ids 3..258 are bytes 0..255 (OFFSET fast
    path), 0/1/2 are pad/bos/eos — the tiny-llm ByteTokenizer contract."""

    vocab_size = 259
    pad_id, bos_id, eos_id = 0, 1, 2
    OFFSET = 3

    def decode(self, ids):
        return "".join(chr(i - 3) for i in ids if 3 <= i < 259)


def _tid(ch: str) -> int:
    return 3 + ord(ch)


def _legal(row, n_vocab: int) -> set[int]:
    return {
        t for t in range(n_vocab) if (row[t >> 5] >> (t & 31)) & 1
    }


def test_mask_rows_track_automaton_and_advance():
    comp = ConstraintCompiler(_FakeTok(), 259)
    sa = comp.make({"type": "choice", "choices": ["ab", "ad", "xy"]})
    assert sa.constrained and not sa.accepting
    assert _legal(sa.mask_row(), 259) == {_tid("a"), _tid("x")}
    assert sa.advance(_tid("a"))
    # mid-choice: both continuations legal, EOS not (not accepting yet)
    assert _legal(sa.mask_row(), 259) == {_tid("b"), _tid("d")}
    assert not sa.allows(_FakeTok.eos_id)
    assert sa.advance(_tid("b"))
    # accepting + closed choice ⇒ EOS-only mask
    assert sa.accepting
    assert _legal(sa.mask_row(), 259) == {_FakeTok.eos_id}
    assert sa.allows(_FakeTok.eos_id)
    assert sa.illegal == 0 and sa.consumed == [_tid("a"), _tid("b")]
    # an illegal advance is counted and lands in the dead state
    sa2 = comp.make({"type": "choice", "choices": ["ab"]})
    assert not sa2.advance(_tid("q"))
    assert sa2.illegal == 1
    assert _legal(sa2.mask_row(), 259) == {_FakeTok.eos_id}


def test_filter_draft_and_masks_for_draft():
    comp = ConstraintCompiler(_FakeTok(), 259)
    sa = comp.make({"type": "regex", "pattern": "abc+"})
    draft = [_tid("a"), _tid("b"), _tid("c"), _tid("z"), _tid("c")]
    # longest legal prefix — the composition guarantee that staged drafts
    # are constraint-legal by construction
    assert sa.filter_draft(draft) == draft[:3]
    assert sa.filter_draft([_tid("z")]) == []
    good = draft[:3]
    rows = sa.masks_for_draft(good)
    assert rows.shape == (4, mask_words(259))
    assert _legal(rows[0], 259) == {_tid("a")}
    assert _legal(rows[1], 259) == {_tid("b")}
    assert _legal(rows[2], 259) == {_tid("c")}
    # after "abc" the automaton accepts: c or EOS
    assert _legal(rows[3], 259) == {_tid("c"), _FakeTok.eos_id}
    # filtering must not move the live cursor
    assert sa.consumed == [] and not sa.accepting


def test_compiler_lru_cache_hits_and_eviction():
    comp = ConstraintCompiler(_FakeTok(), 259, cache_size=2)
    s1 = {"type": "choice", "choices": ["a"]}
    s2 = {"type": "choice", "choices": ["b"]}
    s3 = {"type": "choice", "choices": ["c"]}
    comp.make(s1), comp.make(s1)
    st = comp.stats()
    assert st["misses"] == 1 and st["hits"] == 1
    comp.make(s2), comp.make(s3)  # evicts s1 (LRU)
    st = comp.stats()
    assert st["entries"] == 2 and st["evictions"] == 1
    comp.make(s1)  # recompiles
    assert comp.stats()["misses"] == 4  # s1, s2, s3, s1-again
    # bias-only request: pass-through automaton, nothing compiled
    sa = comp.make(None, logit_bias=[[5, 2.0]])
    assert not sa.constrained and sa.accepting
    assert sa.bias_ids == [5] and sa.bias_vals == [2.0]
    assert _legal(sa.mask_row(), 259) == set(range(259))


def test_constrain_modules_stay_pure():
    """Import-direction lint: grammar.py must stay pure stdlib (it runs
    in purity probes and host threads); masks.py may use numpy but never
    jax or the executor. Probes single-sourced from the purity manifest
    (llm_mcp_tpu/analysis/imports_lint.py)."""
    from llm_mcp_tpu.analysis.imports_lint import run_probe

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for key in ("cn-grammar", "cn-masks"):
        proc = run_probe(key, repo)
        assert proc.returncode == 0, proc.stderr or proc.stdout


# --------------------------------------------------- mask-then-sample op --


def _pack(legal, V: int):
    import numpy as np

    row = np.zeros(mask_words(V), dtype=np.uint32)
    for t in legal:
        row[t >> 5] |= np.uint32(1 << (t & 31))
    return row


def test_apply_token_mask_bias_cannot_resurrect():
    import numpy as np

    from llm_mcp_tpu.ops.sampling import apply_token_mask

    V = 8
    logits = np.zeros((1, V), np.float32)
    packed = np.asarray([_pack({1, 2}, V)])
    bias_ids = np.asarray([[5, 2, -1]], np.int32)
    bias_vals = np.asarray([[100.0, 3.0, 9.9]], np.float32)
    out = np.asarray(apply_token_mask(logits, packed, bias_ids, bias_vals))
    # bias lands first (reweights within the legal set) ...
    assert out[0, 2] == pytest.approx(3.0)
    # ... then the mask wins: +100 on a forbidden token stays -inf, and
    # the -1 pad entry is inert
    assert np.isinf(out[0, 5]) and out[0, 5] < 0
    assert np.isinf(out[0, 0]) and out[0, 0] < 0
    assert out[0, 1] == pytest.approx(0.0)


def _verify(logits, drafts, n_draft, *, temp, seed=0, exact=True):
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.ops.sampling import spec_verify

    A = logits.shape[0]
    return spec_verify(
        jnp.asarray(logits, dtype=jnp.float32),
        jnp.asarray(drafts, dtype=jnp.int32),
        jnp.asarray(n_draft, dtype=jnp.int32),
        jax.random.PRNGKey(seed),
        jnp.full((A,), temp, dtype=jnp.float32),
        jnp.full((A,), 0, dtype=jnp.int32),
        jnp.full((A,), 1.0, dtype=jnp.float32),
        exact=exact,
    )


def test_masked_verify_greedy_never_emits_illegal():
    """Greedy constrained spec: the global argmax is ILLEGAL at every
    position; masked-before-verify logits must emit the best legal token
    and judge drafts against the MASKED argmax."""
    import numpy as np

    from llm_mcp_tpu.ops.sampling import apply_token_mask

    V, legal = 8, {1, 4, 6}
    logits = np.zeros((2, 3, V), np.float32)
    logits[:, :, 0] = 10.0  # global argmax: forbidden
    logits[:, :, 4] = 5.0  # best legal
    logits[:, :, 1] = 3.0
    packed = np.broadcast_to(_pack(legal, V), (2, 3, mask_words(V))).copy()
    masked = np.asarray(apply_token_mask(logits, packed))
    # row 0 drafts the masked argmax (legal), row 1 drafts the unmasked
    # argmax (illegal — the automaton filter would never stage it, but
    # the verify must reject it on its own)
    drafts = np.array([[4, 4], [0, 0]], np.int32)
    n_acc, final = _verify(masked, drafts, [2, 2], temp=0.0)
    assert [int(x) for x in n_acc] == [2, 0]
    assert [int(x) for x in final] == [4, 4]


def test_masked_chi_square_rejection_resampling_stays_exact():
    """The distribution-exactness acceptance bar under constraint: with
    per-position masks applied BEFORE accept/reject and an ADVERSARIAL
    drafter proposing the least-likely LEGAL token, the emitted-token
    marginal must match the mask-renormalized target softmax. Chi-square
    over the 5 legal outcomes, df=4: critical value 18.47 at p=0.999."""
    import numpy as np

    from llm_mcp_tpu.ops.sampling import apply_token_mask

    A, V = 3000, 8
    legal = sorted({0, 1, 2, 4, 6})
    row = np.array([2.0, 1.5, 1.0, 0.5, 0.0, -0.5, -1.0, -2.0], np.float32)
    p = np.exp(row[legal] - row[legal].max())
    p /= p.sum()  # the mask-renormalized target over the legal set
    logits = np.tile(row, (A, 2, 1)).astype(np.float32)
    packed = np.broadcast_to(_pack(set(legal), V), (A, 2, mask_words(V)))
    masked = np.asarray(apply_token_mask(logits, packed.copy()))
    worst = legal[int(np.argmin(row[legal]))]
    drafts = np.full((A, 1), worst, np.int32)
    n_acc, final = _verify(masked, drafts, np.ones(A, np.int32), temp=1.0,
                           seed=11)
    n_acc, final = np.asarray(n_acc), np.asarray(final)
    first = np.where(n_acc >= 1, drafts[:, 0], final)
    counts = np.bincount(first, minlength=V).astype(np.float64)
    # not one masked token leaked through accept, reject, or resample
    assert counts[3] == 0 and counts[5] == 0 and counts[7] == 0
    expected = p * A
    chi2 = float(((counts[legal] - expected) ** 2 / expected).sum())
    assert chi2 < 18.47, (chi2, counts.tolist(), expected.tolist())
    # the adversarial draft was accepted at its masked target probability
    acc = float((n_acc >= 1).mean())
    assert abs(acc - p[legal.index(worst)]) < 0.05


# ------------------------------------------------------------ engine e2e --


def _mk_engine(**kw):
    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("decode_chunk", 4)
    return GenerationEngine("tiny-llm", **kw).start()


def test_engine_choice_constraint_emits_a_choice(monkeypatch):
    monkeypatch.delenv("TPU_CONSTRAIN", raising=False)
    eng = _mk_engine()
    try:
        out = eng.generate(
            "pick a side", max_tokens=16, temperature=0.0,
            constraint={"type": "choice", "choices": ["heads", "tails"]},
        )
        assert out["text"] in ("heads", "tails")
        assert out["finish_reason"] == "stop"
        cs = eng.constrain_stats()
        assert cs["enabled"] == 1.0 and cs["requests"] == 1.0
        assert cs["illegal_tokens"] == 0.0
        assert cs["finished"] == 1.0 and cs["finished_accepting"] == 1.0
        assert cs["schema_valid_rate"] == 1.0
        assert cs["cache"]["misses"] >= 1
        assert eng.cn_bias_max == 64  # LLM_MCP_TPU_CN_BIAS_MAX default
    finally:
        eng.shutdown()


# a fully-forced regex: at every automaton state exactly one byte (or,
# at the end, only EOS) is legal, so greedy output is the literal below
# on ANY model — and the repetition gives the n-gram drafter something
# to speculate on
FORCED_RE = "(alpha beta gamma delta ){4}done"
FORCED_TEXT = "alpha beta gamma delta " * 4 + "done"


def test_engine_greedy_constrained_spec_identity(monkeypatch):
    """The tentpole acceptance bar: greedy constrained speculative decode
    emits token-for-token what constrained non-speculative decode emits,
    while the composition actually engages (constraint-filtered drafts
    accepted through the masked verify)."""
    monkeypatch.delenv("TPU_SPEC", raising=False)
    monkeypatch.delenv("TPU_CONSTRAIN", raising=False)
    cn = {"type": "regex", "pattern": FORCED_RE}
    spec = _mk_engine()
    try:
        got = spec.generate("say the phrase", max_tokens=128,
                            temperature=0.0, constraint=cn)
        assert spec.cn_spec_drafted > 0, "spec composition never engaged"
        assert spec.cn_spec_accepted > 0
        assert spec.constrain_stats()["illegal_tokens"] == 0.0
    finally:
        spec.shutdown()
    monkeypatch.setenv("TPU_SPEC", "0")
    plain = _mk_engine()
    try:
        want = plain.generate("say the phrase", max_tokens=128,
                              temperature=0.0, constraint=cn)
        assert plain.constrain_stats()["illegal_tokens"] == 0.0
    finally:
        plain.shutdown()
    assert got["text"] == want["text"] == FORCED_TEXT
    assert got["usage"] == want["usage"]


def test_engine_sampled_constrained_stays_legal(monkeypatch):
    """Sampled constrained requests (temperature, top-k — the exact-window
    path) must emit only automaton-legal tokens and finish accepting."""
    monkeypatch.delenv("TPU_CONSTRAIN", raising=False)
    eng = _mk_engine(max_slots=4)
    try:
        cn = {"type": "regex", "pattern": "(ha|ho){1,8}!"}
        import concurrent.futures as cf

        cases = [
            dict(temperature=0.9),
            dict(temperature=0.8, top_k=8),
            dict(temperature=0.7, top_p=0.9),
            dict(temperature=0.0),
        ]
        with cf.ThreadPoolExecutor(max_workers=4) as ex:
            outs = list(ex.map(
                lambda kw: eng.generate("laugh", max_tokens=24,
                                        constraint=cn, **kw),
                cases,
            ))
        import re

        for o in outs:
            assert re.fullmatch("(ha|ho){1,8}!", o["text"]), o["text"]
        cs = eng.constrain_stats()
        assert cs["illegal_tokens"] == 0.0
        assert cs["schema_valid_rate"] == 1.0
        assert eng.total_errors == 0
    finally:
        eng.shutdown()


def test_engine_logit_bias_rides_the_mask_path(monkeypatch):
    monkeypatch.delenv("TPU_CONSTRAIN", raising=False)
    eng = _mk_engine()
    try:
        zid = 3 + ord("z")  # ByteTokenizer: OFFSET 3
        out = eng.generate("anything", max_tokens=4, temperature=0.0,
                           logit_bias=[[zid, 100.0]])
        assert out["text"] == "zzzz"
        # bias-only traffic counts as constrained requests but compiles
        # no grammar
        cs = eng.constrain_stats()
        assert cs["requests"] == 1.0 and cs["cache"]["misses"] == 0
    finally:
        eng.shutdown()


def test_engine_rejects_bad_constraint_spec(monkeypatch):
    monkeypatch.delenv("TPU_CONSTRAIN", raising=False)
    eng = _mk_engine()
    try:
        with pytest.raises(RuntimeError, match="constraint"):
            eng.generate("x", max_tokens=4, temperature=0.0,
                         constraint={"type": "regex", "pattern": "a(b"})
        # the engine stays healthy for the next request
        ok = eng.generate("x", max_tokens=4, temperature=0.0)
        assert ok["usage"]["completion_tokens"] >= 1
    finally:
        eng.shutdown()


def test_constrain_kill_switch_noop_and_zero_executables(monkeypatch):
    """TPU_CONSTRAIN=0 is a structural no-op: the compiler never exists,
    a constraint kwarg is ignored, greedy output is token-identical to an
    unconstrained TPU_CONSTRAIN=1 run — and the compile ledger traces the
    IDENTICAL executable set (zero new executables for plain traffic)."""
    from llm_mcp_tpu.telemetry import recorder as _rec

    prompt = "tell me something interesting"

    def run(constrain_env, **gen_kw):
        monkeypatch.setenv("TPU_CONSTRAIN", constrain_env)
        prev = _rec.get_compile_ledger()
        _rec.set_compile_ledger(_rec.CompileLedger())
        try:
            eng = _mk_engine()
            try:
                out = eng.generate(prompt, max_tokens=24, temperature=0.0,
                                   **gen_kw)
                keys = {
                    (r["phase"], r["key"])
                    for r in _rec.get_compile_ledger().table()
                }
                return out, keys, eng.constrain_stats(), eng
            finally:
                eng.shutdown()
        finally:
            _rec.set_compile_ledger(prev)

    off, keys_off, cs_off, eng_off = run(
        "0", constraint={"type": "choice", "choices": ["ignored"]}
    )
    assert eng_off._constrain is None and eng_off._cn_step_fn is None
    assert cs_off == {
        "enabled": 0.0, "requests": 0.0, "tokens": 0.0,
        "illegal_tokens": 0.0, "finished": 0.0, "finished_accepting": 0.0,
        "schema_valid_rate": 1.0, "mask_us_per_tok": 0.0,
        "spec_drafted": 0.0, "spec_accepted": 0.0, "spec_accept_rate": 0.0,
    }
    on, keys_on, cs_on, _ = run("1")
    assert off["text"] == on["text"] and off["usage"] == on["usage"]
    assert keys_off == keys_on, (
        "constrain machinery traced executables for plain traffic"
    )
    assert not any("cnstep" in p for p, _ in keys_on)
    assert cs_on["enabled"] == 1.0 and cs_on["requests"] == 0.0


# -------------------------------------------- preempt / restore / migrate --


def test_constrained_preempt_restore_token_identical(monkeypatch):
    """The automaton cursor must survive a preempt → host offload →
    restore cycle: the constrained victim's greedy output stays
    token-identical to an uncontended constrained run (a reset cursor
    would re-force the pattern from the start and diverge)."""
    monkeypatch.setenv("TPU_KV_HOST_OFFLOAD", "1")
    monkeypatch.delenv("TPU_CONSTRAIN", raising=False)
    eng = _mk_engine(max_seq_len=128)
    cn = {"type": "regex", "pattern": "(alpha beta gamma delta ){6}done"}
    prompt = "constrained preempt probe"
    try:
        results: dict[str, dict] = {}
        lock = threading.Lock()

        def low(p):
            r = eng.generate(p, max_tokens=64, temperature=0.0, priority=0,
                             constraint=cn)
            with lock:
                results[p] = r

        threads = [
            threading.Thread(target=low, args=(p,), daemon=True)
            for p in (prompt, "second constrained stream")
        ]
        for t in threads:
            t.start()
        deadline = time.time() + 60
        while eng.slots_in_use() < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert eng.slots_in_use() == 2
        hi = eng.generate("urgent", max_tokens=8, temperature=0.0,
                          priority=5)
        assert hi["usage"]["completion_tokens"] >= 1
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        st = eng.memory_stats()
        assert st["preempted_total"] >= 1, "no preemption happened"
        assert st["restored_total"] >= 1
        ref = eng.generate(prompt, max_tokens=64, temperature=0.0,
                           constraint=cn)
        assert results[prompt]["text"] == ref["text"]
        assert eng.constrain_stats()["illegal_tokens"] == 0.0
        assert eng.total_errors == 0
    finally:
        eng.shutdown()


def test_snapshot_header_round_trips_constraint_state():
    """Wire contract: the raw spec + consumed ids cross, automaton
    internals never do — and a fresh host rebuilds the SAME cursor by
    recompiling and replaying."""
    import numpy as np

    from llm_mcp_tpu.executor import migration
    from llm_mcp_tpu.executor.memory import KVSnapshot

    comp = ConstraintCompiler(_FakeTok(), 259)
    spec = {"type": "regex", "pattern": "ab*c"}
    sa = comp.make(spec, logit_bias=[[7, 1.5]])
    sa.advance(_tid("a")), sa.advance(_tid("b"))

    class _Req:
        max_tokens, stop, prompt_ids = 8, [], [3, 4]
        created_at, trace_ctx, request_id = 1.0, None, "r-cn"
        constraint, logit_bias = spec, [[7, 1.5]]

    class _Slot:
        generated, text, pending, prompt_len = 2, "ab", b"", 2
        cn = sa

    k = np.zeros((1, 1, 1, 4, 2), np.float32)
    snap = KVSnapshot(
        req_id="r-cn", priority=0, length=4, bucket=4, last_tok=_tid("b"),
        temperature=0.0, top_k=0, top_p=1.0, k_rows=k, v_rows=k,
        nbytes=k.nbytes * 2, preempted_at=0.0,
    )
    header = migration.snapshot_header(snap, _Req(), _Slot())
    data = migration.encode_payload(header, {"k": k, "v": k})
    h2, _ = migration.wire_to_snapshot(data)
    assert h2["constraint"] == spec
    assert h2["logit_bias"] == [[7, 1.5]]
    assert h2["cn_tokens"] == [_tid("a"), _tid("b")]
    # destination-side rebuild: recompile from the raw spec, replay ids
    rebuilt = ConstraintCompiler(_FakeTok(), 259).make(
        h2["constraint"], h2["logit_bias"]
    )
    rebuilt.replay(h2["cn_tokens"])
    assert rebuilt.state == sa.state or (
        _legal(rebuilt.mask_row(), 259) == _legal(sa.mask_row(), 259)
    )
    assert rebuilt.illegal == 0


def test_constrained_disaggregated_migration_identity(monkeypatch):
    """A constrained request prefilled on engine A and decoded on engine B
    (coordinator handoff) emits exactly the single-engine constrained
    output — the destination recompiled the spec and resumed the
    automaton mid-constraint."""
    monkeypatch.setenv("TPU_MIGRATE", "1")
    monkeypatch.delenv("TPU_CONSTRAIN", raising=False)
    from llm_mcp_tpu.executor import migration

    cn = {"type": "regex", "pattern": "(alpha beta gamma delta ){2}done"}
    prompt = "migrate this constrained request"
    ref_eng = _mk_engine(max_seq_len=128)
    try:
        ref = ref_eng.generate(prompt, max_tokens=64, temperature=0.0,
                               constraint=cn)
    finally:
        ref_eng.shutdown()
    assert ref["text"] == "alpha beta gamma delta " * 2 + "done"

    a = _mk_engine(max_seq_len=128)
    b = _mk_engine(max_seq_len=128)
    coord = migration.MigrationCoordinator(
        {"a": a, "b": b}, roles={"a": "prefill", "b": "decode"},
        interval_s=0.05,
    ).start()
    try:
        out = a.generate(prompt, max_tokens=64, temperature=0.0,
                         constraint=cn)
        assert out["text"] == ref["text"]
        assert out["usage"] == ref["usage"]
        assert a.migration_stats()["migrated_out_total"] == 1.0
        assert b.migration_stats()["migrated_in_total"] == 1.0
        # the destination compiled its own automaton and it stayed legal
        assert b.cn_requests >= 1
        assert b.constrain_stats()["illegal_tokens"] == 0.0
        assert a.total_errors == 0 and b.total_errors == 0
    finally:
        coord.stop()
        a.shutdown()
        b.shutdown()


# ------------------------------------------------------------ API surface --


def test_parse_constraints_response_format_shapes():
    from llm_mcp_tpu.api.inference import parse_constraints

    # OpenAI nesting and the flat extension both reach the same spec
    for body in (
        {"response_format": {"type": "json_schema",
                             "json_schema": {"schema": CLOSED_SCHEMA}}},
        {"response_format": {"type": "json_schema",
                             "schema": CLOSED_SCHEMA}},
    ):
        cn, lb, err = parse_constraints(body, 259, 64)
        assert err is None and lb is None
        assert cn == {"type": "json_schema", "schema": CLOSED_SCHEMA}
    cn, _, err = parse_constraints(
        {"response_format": {"type": "json_object"}}, 259, 64)
    assert err is None and cn == {"type": "json_object"}
    cn, _, err = parse_constraints(
        {"response_format": {"type": "choice", "choices": ["a", "b"]}},
        259, 64)
    assert err is None and cn == {"type": "choice", "choices": ["a", "b"]}
    cn, _, err = parse_constraints(
        {"response_format": {"type": "text"}}, 259, 64)
    assert err is None and cn is None
    for bad in (
        {"response_format": {"type": "yaml"}},
        {"response_format": {"type": "regex"}},
        {"response_format": {"type": "choice", "choices": []}},
        {"response_format": {"type": "json_schema"}},
        {"response_format": "json"},
    ):
        _, _, err = parse_constraints(bad, 259, 64)
        assert err, bad


def test_parse_constraints_tool_choice():
    from llm_mcp_tpu.api.inference import parse_constraints

    tools = [
        {"type": "function",
         "function": {"name": "search", "parameters": CLOSED_SCHEMA}},
        {"type": "function", "function": {"name": "noop"}},
    ]
    # auto / none / absent: unconstrained
    for tc in (None, "auto", "none"):
        cn, _, err = parse_constraints(
            {"tools": tools, "tool_choice": tc}, 259, 64)
        assert err is None and cn is None
    # forced named tool: single call-object schema with a const name
    cn, _, err = parse_constraints(
        {"tools": tools,
         "tool_choice": {"type": "function", "function": {"name": "search"}}},
        259, 64)
    assert err is None
    assert cn["type"] == "json_schema"
    assert cn["schema"]["properties"]["name"] == {"const": "search"}
    assert cn["schema"]["properties"]["arguments"] == CLOSED_SCHEMA
    # "required" with several tools: anyOf over the call objects
    cn, _, err = parse_constraints(
        {"tools": tools, "tool_choice": "required"}, 259, 64)
    assert err is None and "anyOf" in cn["schema"]
    assert len(cn["schema"]["anyOf"]) == 2
    # unknown tool name is a request error, not a silent fallback
    _, _, err = parse_constraints(
        {"tools": tools,
         "tool_choice": {"function": {"name": "ghost"}}}, 259, 64)
    assert err and "ghost" in err


def test_parse_constraints_logit_bias_paths():
    from llm_mcp_tpu.api.inference import parse_constraints

    _, lb, err = parse_constraints(
        {"logit_bias": {"5": 150, "7": -3.5}}, 259, 64)
    assert err is None
    assert sorted(lb) == [[5, 100.0], [7, -3.5]]  # clamped to ±100
    # out-of-range id, oversize map, junk entries: 400s, never truncation
    _, _, err = parse_constraints({"logit_bias": {"999": 1}}, 259, 64)
    assert err and "out of range" in err
    _, _, err = parse_constraints(
        {"logit_bias": {str(i): 1 for i in range(3)}}, 259, 2)
    assert err and "at most 2" in err
    _, _, err = parse_constraints({"logit_bias": {"x": 1}}, 259, 64)
    assert err
    _, _, err = parse_constraints({"logit_bias": [5, 1]}, 259, 64)
    assert err
    # n_vocab 0 (engine without a known vocab) skips the range check
    _, lb, err = parse_constraints({"logit_bias": {"999": 1}}, 0, 64)
    assert err is None and lb == [[999, 1.0]]


@pytest.fixture(scope="module")
def cn_server():
    import jax.numpy as jnp

    from llm_mcp_tpu.api.server import CoreServer
    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.state.db import Database
    from llm_mcp_tpu.utils.config import Config

    cfg = Config()
    cfg.db_path = ":memory:"
    gen = GenerationEngine(
        "tiny-llm", max_slots=4, max_seq_len=128, dtype=jnp.float32
    ).start()
    srv = CoreServer(
        cfg, db=Database(":memory:"), gen_engines={"tiny-llm": gen},
    ).start("127.0.0.1", 0)
    yield srv
    srv.shutdown()


@pytest.fixture(scope="module")
def cn_base(cn_server):
    return f"http://127.0.0.1:{cn_server.api.port}"


def test_http_constrained_chat_completion(cn_base):
    import httpx

    r = httpx.post(
        f"{cn_base}/v1/chat/completions",
        json={
            "model": "tiny-llm",
            "messages": [{"role": "user", "content": "yes or no?"}],
            "max_tokens": 8,
            "temperature": 0,
            "response_format": {"type": "choice", "choices": ["yes", "no"]},
        },
        timeout=120.0,
    )
    assert r.status_code == 200
    assert r.json()["choices"][0]["message"]["content"] in ("yes", "no")


def test_http_logit_bias_400(cn_base):
    import httpx

    r = httpx.post(
        f"{cn_base}/v1/chat/completions",
        json={
            "model": "tiny-llm",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4,
            "logit_bias": {"99999": 2},
        },
        timeout=120.0,
    )
    assert r.status_code == 400
    assert "out of range" in r.text


def test_http_debug_constrain_endpoint(cn_base):
    import httpx

    # depends on test_http_constrained_chat_completion having served one
    # constrained request on the module engine
    r = httpx.get(f"{cn_base}/v1/debug/constrain", timeout=30.0)
    assert r.status_code == 200
    stats = r.json()["tiny-llm"]
    assert stats["enabled"] == 1.0
    assert stats["requests"] >= 1.0
    assert stats["illegal_tokens"] == 0.0
    assert stats["schema_valid_rate"] == 1.0
    assert "cache" in stats


def test_workload_agent_schemas_are_closed():
    """A constrained replay of the agent trace ends with valid JSON on any
    model only if every agent-trace schema is CLOSED — the automaton
    accepting state must have no outgoing bytes so the mask forces EOS."""
    import json

    from llm_mcp_tpu.telemetry.workload import AGENT_TOOL_SCHEMAS, synth_trace

    assert len(AGENT_TOOL_SCHEMAS) >= 2
    for sch in AGENT_TOOL_SCHEMAS:
        auto = build_automaton({"type": "json_schema", "schema": sch})
        # probe one concrete accepted string: first enum/boolean value of
        # every property, in schema order
        parts = []
        for name, sub in sch["properties"].items():
            if "enum" in sub:
                parts.append(f'"{name}":"{sub["enum"][0]}"')
            else:
                parts.append(f'"{name}":true')
        probe = "{" + ",".join(parts) + "}"
        sid = auto.step_bytes(auto.start_state, probe.encode())
        assert sid >= 0 and auto.accepting(sid), probe
        assert not auto.live_bytes(sid), (
            f"schema is open — generation can continue past {probe!r}"
        )
    recs = synth_trace("agent", 40, seed=3)
    stamped = [r for r in recs if r.get("schema")]
    assert stamped, "agent synth never stamps schemas"
    assert all(
        json.dumps(r["schema"], sort_keys=True)
        in {json.dumps(s, sort_keys=True) for s in AGENT_TOOL_SCHEMAS}
        for r in stamped
    )
