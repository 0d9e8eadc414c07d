"""Cold-start warmup subsystem (executor/warmup.py + engine AOT hooks +
routing/serving integration): plan ordering against ledger aggregates,
pow2 dedup, the critical/background split, readiness transitions under
injected slow compiles, the TPU_WARMUP=0 true no-op with greedy token
identity, the real-engine AOT sweep with ledger provenance, the
hash-keyed prefix export for boot peer warm-fill, and the elastic
join-mid-window drain through MigrationCoordinator.add_engine.
"""

from __future__ import annotations

import queue
import threading
import time

import jax.numpy as jnp
import pytest

from llm_mcp_tpu.executor import migration, warmup
from llm_mcp_tpu.telemetry import recorder as _rec


@pytest.fixture(autouse=True)
def _fresh_ledger():
    """The compile ledger is process-shared; engines built here must not
    inherit priors from whatever other tests compiled earlier in the run
    (start_warmup merges ledger.table() into the plan), and must not leak
    warmup rows forward. Fresh ledger per test, restored after."""
    prev = _rec.get_compile_ledger()
    _rec.set_compile_ledger(_rec.CompileLedger())
    try:
        yield
    finally:
        _rec.set_compile_ledger(prev)


# ------------------------------------------------------------ pure planner --


def _table(rows):
    """Ledger-table-shaped rows: (phase, key str, count, total_s)."""
    return [
        {"phase": p, "key": k, "count": c, "total_s": t}
        for p, k, c, t in rows
    ]


def test_plannable_phases_match_perf_registry():
    # warmup.py duplicates the registry as a literal to stay importable
    # standalone; this is the pin that keeps the two in sync
    from llm_mcp_tpu.telemetry.perf import WARMUP_PHASES

    assert tuple(sorted(warmup.PLANNABLE_PHASES)) == tuple(sorted(WARMUP_PHASES))


def test_plan_orders_by_measured_cost_times_hits():
    zoo = [
        ("decode", (2, True, False)),
        ("admit", (1, 32)),
        ("admit", (4, 64)),
        ("chunk", (1, 32, 128, False)),
        ("chunk", (8, 64, 128, False)),
    ]
    # admit(4,64): 10 hits x 6s = 60; chunk(8,...): 2 x 9s = 18;
    # admit(1,32): 1 x 2s = 2 — background order must follow that score
    priors = warmup.priors_from_table(_table([
        ("admit", "4:64", 10, 60.0),
        ("chunk", "8:64:128:False", 2, 18.0),
        ("admit", "1:32", 1, 2.0),
    ]))
    steps = warmup.plan_steps(zoo, priors)
    crit = [s for s in steps if s.critical]
    rest = [s for s in steps if not s.critical]
    # critical first, in slot order, and drawn from the measured shapes
    assert steps[: len(crit)] == crit
    assert [s.phase for s in crit] == ["admit", "chunk", "decode"]
    assert crit[0].key == (4, 64)  # most-valuable measured admit
    assert crit[1].key == (8, 64, 128, False)
    bg_scores = [s.priority for s in rest]
    assert bg_scores == sorted(bg_scores, reverse=True)
    # measured always outranks unmeasured
    measured = {("admit", "1:32")}
    first_unmeasured = next(
        i for i, s in enumerate(rest)
        if (s.phase, warmup.key_str(s.key)) not in measured
    )
    assert all(
        (s.phase, warmup.key_str(s.key)) in measured
        for s in rest[:first_unmeasured]
    )


def test_plan_dedups_overlapping_pow2_keys():
    # config enumeration and ledger-observed keys overlap on pow2 ladders;
    # the plan must collapse them (an AOT compile per duplicate would
    # double boot cost for nothing)
    zoo = [("admit", (1, 32)), ("admit", (1, 32)), ("decode", (2, True, False)),
           ("decode", (2, True, False))]
    steps = warmup.plan_steps(zoo, {})
    assert len(steps) == 2
    assert {(s.phase, s.key) for s in steps} == {
        ("admit", (1, 32)), ("decode", (2, True, False))}


def test_critical_split_cold_picks_smallest_shapes():
    zoo = [
        ("admit", (8, 512)), ("admit", (1, 32)),
        ("pf_rag", (256, 0, True)), ("pf_rag", (32, 0, True)),
        ("decode", (16, False, True)), ("decode", (8, True, True)),
    ]
    crit = warmup.select_critical(zoo, {})
    assert crit == [
        ("admit", (1, 32)), ("pf_rag", (32, 0, True)), ("decode", (8, True, True))
    ]
    steps = warmup.plan_steps(zoo, {})
    assert sum(1 for s in steps if s.critical) == 3
    assert len(steps) == len(zoo)


def test_the_mixed_rungs_come_last_in_an_unmeasured_plan():
    # no first request dispatches a mixed round (it needs a full batch
    # decoding), and the serve path's first two rides first-dispatch both
    # rungs: the plan reaches them last and mostly records them as skip.
    # Measured, a rung ranks by its cost x hits like any other shape.
    zoo = [
        ("mixed", (128, True)), ("mixed", (256, True)),
        ("admit", (4, 512)), ("admit", (1, 32)),
        ("pf_rag", (2048, 0, True)), ("pf_rag", (32, 0, True)),
        ("decode", (32, False, True)), ("decode", (8, True, True)),
    ]
    steps = warmup.plan_steps(zoo, {})
    assert [(s.phase, s.key) for s in steps[-2:]] == [
        ("mixed", (128, True)), ("mixed", (256, True))]
    assert not any(s.critical for s in steps[-2:])
    priors = warmup.priors_from_table(_table([("mixed", "256:True", 3, 18.0)]))
    rest = [s for s in warmup.plan_steps(zoo, priors) if not s.critical]
    assert (rest[0].phase, rest[0].key) == ("mixed", (256, True))
    assert (rest[-1].phase, rest[-1].key) == ("mixed", (128, True))


def test_priors_from_table_drops_malformed_rows():
    priors = warmup.priors_from_table(
        _table([("admit", "1:32", 3, 6.0)])
        + [{"phase": "chunk"}, {"key": "1:2"}, {"phase": "x", "key": "y",
                                                "count": "NaNny", "total_s": {}}]
    )
    assert priors == {("admit", "1:32"): {"count": 3, "cost_s": 2.0}}


# ------------------------------------------------- readiness state machine --


class _SlowCompiles:
    """Injected compile hook: per-(phase,key) walls, optional block event,
    records call order."""

    def __init__(self, wall_s=0.0, gate: threading.Event | None = None):
        self.wall_s = wall_s
        self.gate = gate
        self.calls: list[tuple[str, tuple]] = []

    def __call__(self, phase, key):
        self.calls.append((phase, key))
        if self.gate is not None:
            self.gate.wait(5.0)
        if self.wall_s:
            time.sleep(self.wall_s)
        if phase not in warmup.PLANNABLE_PHASES:
            return None
        return self.wall_s or 0.001


def _steps():
    return warmup.plan_steps(
        [("admit", (1, 32)), ("chunk", (1, 32, 128, False)),
         ("decode", (2, True, False)), ("admit", (2, 64)),
         ("fused", (2, True, 1, 32, 128, False))],
        {},
    )


def test_readiness_transitions_under_slow_compiles():
    gate = threading.Event()
    fn = _SlowCompiles(gate=gate)
    events: list[tuple] = []
    pl = warmup.WarmupPlanner(
        fn, _steps(), event=lambda et, **kw: events.append((et, kw)))
    assert pl.state == "cold"
    t = threading.Thread(target=pl.run_critical)
    t.start()
    # compiles are gated: still cold while the critical prefix is in flight
    assert pl.state == "cold"
    gate.set()
    t.join(10)
    assert pl.state == "first_token_ready"
    assert pl.stats()["first_token_ready_s"] is not None
    pl.start_background()
    deadline = time.time() + 10
    while pl.state != "fully_warm" and time.time() < deadline:
        time.sleep(0.01)
    assert pl.state == "fully_warm"
    st = pl.stats()
    assert st["by_status"]["done"] == 4  # fused records skip, not done
    assert st["by_status"]["skip"] == 1
    assert st["bg_compiles_done"] == 1  # one non-critical plannable shape
    # flight events: one wu per step + both state transitions
    assert [kw["state"] for et, kw in events if et == "warmup"] == [
        "first_token_ready", "fully_warm"]
    assert sum(1 for et, _ in events if et == "wu") == 5
    pl.stop()


@pytest.mark.parametrize("when", ["before_the_plan", "while_the_plan_runs"])
def test_a_shape_the_serve_path_dispatched_is_skipped_and_the_plan_ends_warm(when):
    """The plan's thread does not lower again what a real dispatch has already
    traced, loaded and run: a ledger row from `src` "serve" makes the step a
    `skip`, the hook is never asked for it, and `fully_warm` still means every
    shape is compiled or already served."""
    ledger = _rec.CompileLedger()

    def served(phase, key):
        return any(r["phase"] == phase and r["key"] == warmup.key_str(key)
                   and r["by_src"].get("serve") for r in ledger.table())

    class Hook(_SlowCompiles):
        def __call__(self, phase, key):
            if when == "while_the_plan_runs" and (phase, key) == ("decode", (2, True, False)):
                # the engine's thread first-dispatches the next shape of the
                # plan while this one compiles
                ledger.observe("admit", "2:64", 3.0, src="serve")
            return super().__call__(phase, key)

    ledger.observe("admit", "2:64", 1.5, src="warmup")  # an AOT compile is not a dispatch
    if when == "before_the_plan":
        ledger.observe("admit", "2:64", 3.0, src="serve")
    fn = Hook()
    events: list[tuple] = []
    pl = warmup.WarmupPlanner(fn, _steps(), served=served,
                              event=lambda et, **kw: events.append((et, kw)))
    pl.run_critical()
    pl.start_background()
    deadline = time.time() + 10
    while pl.state != "fully_warm" and time.time() < deadline:
        time.sleep(0.01)
    assert pl.state == "fully_warm"
    assert ("admit", (2, 64)) not in fn.calls and len(fn.calls) == 4
    by_key = {(s.phase, s.key): s.status for s in pl.steps}
    assert by_key[("admit", (2, 64))] == "skip" and by_key[("admit", (1, 32))] == "done"
    assert pl.stats()["by_status"] == {"done": 3, "skip": 2}  # the served one and `fused`
    assert [kw["outcome"] for et, kw in events if et == "wu" and kw["key"] == "2:64"] == ["skip"]
    pl.stop()


def test_stop_mid_background_skips_remainder_monotone():
    gate = threading.Event()
    fn = _SlowCompiles(gate=gate)
    pl = warmup.WarmupPlanner(fn, _steps())
    gate.set()
    pl.run_critical()
    gate.clear()
    pl.start_background()  # first bg compile blocks on the gate
    time.sleep(0.05)
    gate.set()
    pl.stop()
    assert pl.state == "fully_warm"  # stop never leaves it mid-state
    assert not any(s.status == "pending" for s in pl.steps)
    # monotone: a late advance attempt cannot regress the state
    pl._advance("first_token_ready")
    assert pl.state == "fully_warm"


def test_compile_failure_records_fail_never_raises():
    def boom(phase, key):
        raise RuntimeError("XLA exploded")

    pl = warmup.WarmupPlanner(boom, _steps())
    pl.run_critical()  # must not raise: warmup is an accelerant, not a gate
    pl.start_background()
    deadline = time.time() + 10
    while pl.state != "fully_warm" and time.time() < deadline:
        time.sleep(0.01)
    assert pl.stats()["by_status"] == {"fail": 5}
    pl.stop()


def test_empty_plan_is_immediately_fully_warm():
    pl = warmup.WarmupPlanner(_SlowCompiles(), [])
    pl.run_critical()
    assert pl.state == "fully_warm"
    assert pl.stats()["first_token_ready_s"] is not None


# ------------------------------------------------------------- real engine --


def _engine(model="tiny-llm", **kw):
    from llm_mcp_tpu.executor import GenerationEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("decode_chunk", 4)
    return GenerationEngine(model, **kw).start()


def test_warmup_env_off_is_true_noop(monkeypatch):
    """TPU_WARMUP=0: start_warmup returns None, no planner, no AOT
    compiles, no warmup ledger entries — and greedy output is
    token-identical with a warmed twin."""
    from llm_mcp_tpu.telemetry import recorder as flight

    monkeypatch.setenv("TPU_WARMUP", "0")
    eng = _engine()
    try:
        assert eng.start_warmup() is None
        assert eng._warmup is None
        st = eng.warmup_stats()
        assert st == {"state": "fully_warm", "steps": 0, "enabled": False}
        ref = eng.generate("warmup no-op probe?", max_tokens=8, temperature=0.0)
    finally:
        eng.shutdown()

    monkeypatch.setenv("TPU_WARMUP", "1")
    led = flight.get_compile_ledger()
    warm_before = led.stats()["by_src"].get("warmup", 0)
    eng2 = _engine()
    try:
        pl = eng2.start_warmup()
        assert pl is not None and pl.state in ("first_token_ready", "fully_warm")
        assert eng2.start_warmup() is pl  # idempotent
        out = eng2.generate("warmup no-op probe?", max_tokens=8, temperature=0.0)
        assert out["text"] == ref["text"]
        assert out["usage"] == ref["usage"]
        # every critical compile carries warmup provenance in the ledger
        assert led.stats()["by_src"].get("warmup", 0) > warm_before
    finally:
        eng2.shutdown()


def test_engine_warmup_reaches_fully_warm_and_covers_zoo(monkeypatch):
    monkeypatch.setenv("TPU_WARMUP", "1")
    monkeypatch.setenv("TPU_WARMUP_THROTTLE_S", "0")
    eng = _engine()
    try:
        zoo = eng.warmup_shape_zoo()
        assert len(zoo) >= 3
        # every zoo key round-trips through the ledger string encoding
        for ph, key in zoo:
            assert eng.parse_ledger_key(warmup.key_str(key)) == key
        pl = eng.start_warmup()
        assert pl.state in ("first_token_ready", "fully_warm")
        deadline = time.time() + 120
        while eng.warmup_stats()["state"] != "fully_warm" and time.time() < deadline:
            time.sleep(0.05)
        st = eng.warmup_stats()
        assert st["state"] == "fully_warm"
        assert st["enabled"] is True
        assert st["by_status"].get("done", 0) == len(zoo)
        assert 1 <= st["critical"] <= 3
        assert st["fully_warm_s"] is not None
    finally:
        eng.shutdown()


def test_engine_plan_skips_the_shapes_its_own_traffic_dispatched(monkeypatch):
    """An engine that has served before its plan runs: the shapes of those
    first dispatches are `skip` with no warm-up entry in the ledger, a
    constrained admission's program is not taken for the plan's, and the rest
    of the zoo compiles."""
    monkeypatch.setenv("TPU_WARMUP", "1")
    monkeypatch.setenv("TPU_WARMUP_THROTTLE_S", "0")
    eng = _engine()
    try:
        eng.generate("served before the plan", max_tokens=6, temperature=0.0)
        served = set(eng._served_shapes)
        assert {ph for ph, _ in served} == {"admit", "decode"}
        eng._compile_obs("admit", (2, 64), 0.1, planned=False)  # as a constrained batch files it
        assert ("admit", "2:64") not in eng._served_shapes
        pl = eng.start_warmup()
        deadline = time.time() + 120
        while pl.state != "fully_warm" and time.time() < deadline:
            time.sleep(0.05)
        st = eng.warmup_stats()
        assert st["state"] == "fully_warm"
        status = {(s["phase"], s["key"]): s["status"] for s in st["plan"]}
        assert all(status[shape] == "skip" for shape in served)
        assert status[("admit", "2:64")] == "done"
        assert st["by_status"] == {"skip": len(served), "done": len(status) - len(served)}
        rows = {(r["phase"], r["key"]): r["by_src"] for r in eng._ledger.table()}
        assert all("warmup" not in rows[shape] for shape in served)
    finally:
        eng.shutdown()


def test_warmup_bg_off_skips_zoo_but_reaches_fully_warm(monkeypatch):
    monkeypatch.setenv("TPU_WARMUP", "1")
    monkeypatch.setenv("TPU_WARMUP_BG", "0")
    eng = _engine()
    try:
        pl = eng.start_warmup()
        assert pl.state == "fully_warm"  # as warm as it will get — not
        st = eng.warmup_stats()          # "warming" forever in the router
        assert st["by_status"].get("skip", 0) > 0
        assert st["by_status"].get("done", 0) >= 1  # critical still compiled
    finally:
        eng.shutdown()


def test_stale_prior_from_other_pool_config_records_skip(monkeypatch):
    """A warmup-pack row recorded on a paged-pool fleet must not poison a
    contiguous boot: the phys flag mismatch returns None → step skips."""
    monkeypatch.setenv("TPU_WARMUP", "1")
    eng = _engine()
    try:
        phys = eng._phys is not None
        stale = _table([("decode", f"2:True:{not phys}", 4, 8.0)])
        pl = eng.start_warmup(priors=stale)
        deadline = time.time() + 120
        while pl.state != "fully_warm" and time.time() < deadline:
            time.sleep(0.05)
        skipped = [s for s in pl.steps
                   if s.key == (2, True, not phys) and s.phase == "decode"]
        assert len(skipped) == 1 and skipped[0].status == "skip"
    finally:
        eng.shutdown()


# ------------------------------------------- elastic join + peer warm-fill --


SHARED = "you are a helpful assistant. answer briefly and precisely. " * 2


def test_prefix_export_by_hash_round_trip(monkeypatch):
    """Digest head hash → token ids recovered on the holder → export →
    import on a cold peer → the peer's first shared-prefix request rides
    the fetched blocks, token-identically."""
    monkeypatch.setenv("TPU_KV_BLOCK_TOKENS", "16")
    kw = dict(max_seq_len=256, prefill_chunk=64, prompt_cache_mb=64)
    a = _engine(**kw)
    b = _engine(**kw)
    try:
        # the store heuristic wants a repeated prefix before caching it
        a.generate(SHARED + "prime one", max_tokens=4, temperature=0.0)
        a.generate(SHARED + "prime two", max_tokens=4, temperature=0.0)
        dig = a.prefix_digest()
        assert dig and dig["heads"]
        h = max(dig["heads"], key=lambda k: dig["heads"][k])
        assert a.prefix_export_by_hash("no-such-hash") is None
        payload = a.prefix_export_by_hash(h)
        assert payload is not None
        ref = a.generate(SHARED + "join tail?", max_tokens=8, temperature=0.0)

        hits_before = b.prefix_cache_hits
        assert b.prefix_import(payload)
        out = b.generate(SHARED + "join tail?", max_tokens=8, temperature=0.0)
        assert out["text"] == ref["text"]
        assert b.prefix_cache_hits > hits_before  # served from fetched blocks
    finally:
        a.shutdown()
        b.shutdown()


class _FakeEngine:
    """Duck-typed engine for coordinator policy (mirrors
    test_migration.py): queues + counters, no jax anywhere."""

    def __init__(self, headroom=1.0, max_slots=4, in_use=0, queued=0):
        self._headroom = headroom
        self.max_slots = max_slots
        self.in_use = in_use
        self.queued = queued
        self._migrate_outbox = queue.Queue()
        self._migrate_in = queue.Queue()
        self.migrate_after_prefill = False
        self.exports: list[dict] = []
        self.imports: list[bytes] = []
        self.submitted: list = []
        self.stealable: list = []

    def memory_stats(self):
        return {"enabled": 1.0, "headroom": self._headroom}

    def slots_in_use(self):
        return self.in_use

    def queue_depth(self):
        return self.queued

    def migrate_export_one(self):
        return self.exports.pop(0) if self.exports else None

    def migrate_steal_queued(self):
        return self.stealable.pop(0) if self.stealable else None

    def migrate_import(self, payload, out=None):
        self.imports.append(payload)

    def submit(self, req):
        self.submitted.append(req)


class _FakeQueued:
    request_id = "queued-req-join"
    migrations = 0


def test_add_engine_mid_window_absorbs_shedding_backlog():
    """The elasticity loop: a lone saturated engine has nowhere to drain;
    a second engine joining mid-window via add_engine becomes the target
    on the very next tick and absorbs both the offloaded snapshot and the
    queued request."""
    src = _FakeEngine(headroom=0.0, max_slots=2, in_use=2, queued=4)
    out: queue.Queue = queue.Queue()
    src.exports = [{"payload": b"SNAP", "out": out, "req_id": "r1"}]
    src.stealable = [_FakeQueued()]
    c = migration.MigrationCoordinator({"src": src}, burst=3)
    c.tick()  # nowhere to go: nothing moves, nothing fails spuriously
    assert not src.submitted and src.stealable and src.exports

    with pytest.raises(ValueError):
        c.add_engine("bad", _FakeEngine(), role="bogus")
    joined = _FakeEngine(headroom=0.9)
    c.add_engine("joined", joined)
    c.tick()
    assert joined.imports == [b"SNAP"]
    assert len(joined.submitted) == 1
    st = c.stats()
    assert st["snapshots_moved_total"] == 1.0
    assert st["requeues_total"] == 1.0


def test_add_engine_prefill_role_flags_outbox_export():
    c = migration.MigrationCoordinator({"d": _FakeEngine()})
    pf = _FakeEngine()
    c.add_engine("pf", pf, role="prefill")
    assert pf.migrate_after_prefill is True


def test_join_mid_window_real_engines_serve_from_fetched_blocks(monkeypatch):
    """End-to-end elasticity: engine A saturated with a queued backlog of
    shared-prefix requests, engine B joins mid-window (add_engine), warm-
    filled over the hash-keyed prefix path — the drained requests complete
    token-identically and B's admissions hit the fetched prefix."""
    monkeypatch.setenv("TPU_KV_BLOCK_TOKENS", "16")
    monkeypatch.setenv("TPU_MIGRATE", "1")
    kw = dict(max_slots=2, max_seq_len=256, prefill_chunk=64, prompt_cache_mb=64)
    a = _engine(**kw)
    coord = migration.MigrationCoordinator({"a": a}, interval_s=0.05).start()
    b = None
    try:
        a.generate(SHARED + "prime one", max_tokens=4, temperature=0.0)
        a.generate(SHARED + "prime two", max_tokens=4, temperature=0.0)
        refs = [
            a.generate(SHARED + f"window req {i}?", max_tokens=8, temperature=0.0)
            for i in range(4)
        ]
        # build the mid-window backlog: 4 concurrent clients on 2 slots
        results: dict[int, dict] = {}

        def client(i):
            results[i] = a.generate(
                SHARED + f"window req {i}?", max_tokens=8, temperature=0.0)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        # B joins mid-window: warm-filled from A's digest, then registered
        b = _engine(**kw)
        h = max(a.prefix_digest()["heads"], key=lambda k: a.prefix_digest()["heads"][k])
        payload = a.prefix_export_by_hash(h)
        assert payload is not None and b.prefix_import(payload)
        hits_before = b.prefix_cache_hits
        coord.add_engine("b", b)
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        for i in range(4):
            assert results[i]["text"] == refs[i]["text"]
        if coord.stats()["requeues_total"] > 0:
            # a drained request admitted on B rode the fetched blocks
            assert b.prefix_cache_hits > hits_before
        assert a.total_errors == 0 and b.total_errors == 0
        assert a.paging_stats()["leaks"] == 0.0
        assert b.paging_stats()["leaks"] == 0.0
    finally:
        coord.stop()
        a.shutdown()
        if b is not None:
            b.shutdown()


def test_warmup_compiles_every_plannable_phase_under_a_mesh():
    """A sharded engine's AOT warmup compiles must lower: the small per-slot
    arrays live on the default device, uncommitted, and a ShapeDtypeStruct
    that pinned them to device 0 beside mesh-sharded weights made EVERY
    warmup compile of the tp=4 engine fail on the four-chip host
    ("incompatible devices"), so the serve path paid all of them cold. Also
    pins that weights and cache are born sharded (no whole tree on one
    device) and that the engine's `xla` ragged choice reaches the model."""
    import dataclasses

    from jax.sharding import NamedSharding

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.models.configs import resolve_config
    from llm_mcp_tpu.parallel.mesh import make_mesh

    import jax

    cfg = dataclasses.replace(
        resolve_config("tiny-llm", ""), name="tiny-tp4", n_heads=8, n_kv_heads=4)
    mesh = make_mesh("tp=4", devices=jax.devices()[:4])  # 4 of the 8 virtual devices
    eng = GenerationEngine(cfg, mesh=mesh, max_slots=4, max_seq_len=128,
                           dtype=jnp.float32, decode_chunk=4)
    try:
        for leaf in jax.tree.leaves((eng.params, eng._ck, eng._cv)):
            assert isinstance(leaf.sharding, NamedSharding)
            assert len(leaf.sharding.device_set) == 4
        assert eng._ragged_impl == "xla"
        phases = {ph for ph, _ in eng.warmup_shape_zoo()}
        assert {"admit", "decode", "pf_rag"} <= phases
        done = set()
        for ph, key in eng.warmup_shape_zoo():
            if ph in done:
                continue
            assert eng.warmup_compile(ph, key) is not None, (ph, key)
            done.add(ph)
    finally:
        eng.shutdown()
