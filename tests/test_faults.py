"""Fault injection (utils/faults.py) + chaos tests proving the recovery
machinery the reference relies on actually recovers: retry budgets, lease
expiry reclaim, engine poisoned-round guard, HTTP 500 containment."""

from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from llm_mcp_tpu.utils import faults
from llm_mcp_tpu.utils.faults import FaultInjected


@pytest.fixture(autouse=True)
def disarm():
    """Faults are process-global: always disarm after each test."""
    yield
    faults.configure("")


def test_spec_parsing_and_determinism():
    faults.configure("a.site:0.5,b.site:1.0:error=boom,bad_spec,c:notanumber", seed=7)
    assert faults.armed("a.site") and faults.armed("b.site")
    assert not faults.armed("bad_spec") and not faults.armed("c")
    with pytest.raises(FaultInjected, match="boom"):
        faults.maybe_fail("b.site")
    # seeded: same seed → same trip pattern
    faults.configure("a.site:0.5", seed=42)
    pattern1 = []
    for _ in range(20):
        try:
            faults.maybe_fail("a.site")
            pattern1.append(False)
        except FaultInjected:
            pattern1.append(True)
    faults.configure("a.site:0.5", seed=42)
    pattern2 = []
    for _ in range(20):
        try:
            faults.maybe_fail("a.site")
            pattern2.append(False)
        except FaultInjected:
            pattern2.append(True)
    assert pattern1 == pattern2 and any(pattern1) and not all(pattern1)


def test_delay_mode_sleeps_not_raises():
    faults.configure("slow.site:1.0:delay=0.05")
    t0 = time.monotonic()
    faults.maybe_fail("slow.site")  # must not raise
    assert time.monotonic() - t0 >= 0.05


def test_unarmed_site_is_noop():
    faults.configure("")
    faults.maybe_fail("anything.at.all")  # no raise, no delay


@pytest.fixture()
def stack():
    from llm_mcp_tpu.api.server import CoreServer
    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.state import Database
    from llm_mcp_tpu.utils.config import Config
    from llm_mcp_tpu.worker.client import CoreClient
    from llm_mcp_tpu.worker.executors import Executors
    from llm_mcp_tpu.worker.worker import Worker

    gen = GenerationEngine("tiny-llm", max_slots=2, max_seq_len=64, dtype=jnp.float32).start()
    srv = CoreServer(
        Config(db_path=":memory:", discovery_interval_s=10_000),
        db=Database(":memory:"),
        gen_engines={"tiny-llm": gen},
    ).start("127.0.0.1", 0)
    client = CoreClient(f"http://127.0.0.1:{srv.api.port}", backoff_s=0.01)
    worker = Worker(
        client,
        Executors(gen_engines={"tiny-llm": gen}),
        worker_id="chaos-w",
        lease_seconds=0.3,
    )
    worker.register_forever()
    yield srv, worker, gen
    srv.shutdown()
    gen.shutdown()


def test_worker_execute_faults_consume_retry_budget(stack):
    """Deterministic execute failures drive the job through its full retry
    budget to a terminal error with an attempts audit trail."""
    srv, worker, gen = stack
    faults.configure("worker.execute:1.0", seed=0)
    job = srv.queue.submit("generate", {"model": "tiny-llm", "prompt": "x",
                                        "max_tokens": 4}, max_attempts=3)
    for _ in range(10):
        worker.run_once()
        j = srv.queue.get(job.id)
        if j.status == "error":
            break
        time.sleep(0.35)  # let the lease lapse between attempts
    j = srv.queue.get(job.id)
    assert j.status == "error"
    assert j.attempts == 3
    assert "injected fault" in (j.error or "")
    # recovery: disarm → a new job sails through
    faults.configure("")
    ok = srv.queue.submit("generate", {"model": "tiny-llm", "prompt": "y",
                                       "max_tokens": 4})
    assert worker.run_once()
    assert srv.queue.get(ok.id).status == "done"


def test_worker_death_before_complete_requeues_via_lease(stack):
    """worker.complete fault = work done but never reported (simulated
    crash). The lease must expire and a healthy claim must finish the job."""
    srv, worker, gen = stack
    faults.configure("worker.complete:1.0", seed=0)
    job = srv.queue.submit("generate", {"model": "tiny-llm", "prompt": "x",
                                        "max_tokens": 4})
    assert worker.run_once()  # executes, report dropped
    j = srv.queue.get(job.id)
    assert j.status == "running"  # leased, unreported
    faults.configure("")  # the replacement worker is healthy
    time.sleep(0.4)  # lease (0.3 s) expires
    assert worker.run_once()
    j = srv.queue.get(job.id)
    assert j.status == "done"
    assert j.attempts == 2  # the lost attempt is on the audit trail


def test_engine_decode_fault_fails_slots_not_callers(stack):
    """A poisoned decode round must surface as an error event, and the
    engine must keep serving afterwards."""
    srv, worker, gen = stack
    faults.configure("engine.decode:1.0", seed=0)
    events = list(gen.generate_stream("hello", max_tokens=4))
    assert any(e.get("type") == "error" for e in events)
    faults.configure("")
    out = gen.generate("hello again", max_tokens=4)
    assert out["usage"]["completion_tokens"] > 0


def test_api_request_fault_returns_500_and_contains(stack):
    import urllib.error
    import urllib.request

    srv, worker, gen = stack
    base = f"http://127.0.0.1:{srv.api.port}"
    faults.configure("api.request:1.0", seed=0)
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{base}/health", timeout=10)
    assert ei.value.code == 500
    faults.configure("")
    with urllib.request.urlopen(f"{base}/health", timeout=10) as r:
        assert r.status == 200


def test_engine_stall_watchdog(monkeypatch):
    """A wedged device call (a hung runtime: uninterruptible, error-less
    silence) must not
    strand callers: the watchdog detects the stalled loop, errors queued
    requests, fails new submits fast, and clears on recovery."""
    import threading
    import time

    import jax.numpy as jnp
    import pytest

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.executor.engine import GenRequest

    monkeypatch.setenv("TPU_STALL_TIMEOUT_S", "3")
    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=64, dtype=jnp.float32, decode_chunk=2
    ).start()
    release = threading.Event()
    try:
        # warm BEFORE wedging: first-compile time must not trip the watchdog
        assert eng.generate("ok", max_tokens=2, temperature=0.0)["finish_reason"]
        state = {"wedged": False}
        orig_p = eng._stage_prefill_group

        def wedge(n_active):
            # _stage_prefill_group runs every loop iteration, after a
            # request activates: wedging here guarantees an in-flight slot
            # exists when the loop blocks (simulated uninterruptible device
            # call)
            if not state["wedged"]:
                state["wedged"] = True
                release.wait(40)
            return orig_p(n_active)

        eng._stage_prefill_group = wedge
        # an IN-FLIGHT stream when the wedge hits: its consumer must get a
        # terminal error too, not hang forever on req.out.get()
        results: list = []

        def consume():
            try:
                results.append(eng.generate("inflight", max_tokens=100_000))
            except RuntimeError as e:
                results.append(e)

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        deadline = time.time() + 15
        while not state["wedged"] and time.time() < deadline:
            time.sleep(0.02)
        assert state["wedged"], "loop never reached the wedge"
        # a request already queued behind the wedge: the watchdog must
        # error it (its consumer would otherwise hang forever)
        stuck = GenRequest(prompt_ids=[1, 2, 3], max_tokens=4)
        eng._admit.put(stuck)
        deadline = time.time() + 15
        while not eng.stalled and time.time() < deadline:
            time.sleep(0.05)
        assert eng.stalled, "watchdog never flagged the stall"
        evt = stuck.out.get(timeout=10)
        assert evt["type"] == "error" and "stalled" in evt["error"]
        t.join(timeout=10)
        assert results and isinstance(results[0], RuntimeError), results
        assert "stalled" in str(results[0])
        # new submissions fail fast instead of queueing behind the wedge
        with pytest.raises(RuntimeError, match="stalled"):
            eng.generate("fail fast", max_tokens=2)
        release.set()
        deadline = time.time() + 15
        while eng.stalled and time.time() < deadline:
            time.sleep(0.05)
        assert not eng.stalled, "watchdog never cleared after recovery"
        # and the engine serves again
        assert eng.generate("back", max_tokens=2, temperature=0.0)["finish_reason"]
    finally:
        release.set()
        eng.shutdown()


def test_server_flips_device_offline_on_stall():
    """Serving layer maps an engine stall to device state: offline + circuit
    failure while stalled (routing fails over), back online on recovery —
    the reference's offline propagation (offline_handler.go:12-38) driven
    by silence instead of connection errors."""
    import jax.numpy as jnp

    from llm_mcp_tpu.api.server import CoreServer
    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.state.db import Database
    from llm_mcp_tpu.utils.config import Config

    # UNSTARTED engine: the running idle loop clears a manually-set stall
    # flag within one iteration (correct behavior — but this test drives
    # the SERVER mapping, so the flag must hold still)
    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=64, dtype=jnp.float32, decode_chunk=2
    )
    srv = CoreServer(
        Config(), db=Database(":memory:"), gen_engines={"tiny-llm": eng}
    )
    try:
        srv.register_local_device()
        eng.stalled = True
        srv._check_engine_stalls()
        row = srv.catalog.get_device(srv.device_id)
        assert row is not None and not row["online"]
        eng.stalled = False
        # recovery does NOT flip the device back itself (another path may
        # have offlined it meanwhile); the periodic discovery re-register
        # brings a healthy self-device online on its own cadence
        srv._check_engine_stalls()
        assert not srv.catalog.get_device(srv.device_id)["online"]
        srv.register_local_device()  # the discovery tick's effect
        assert srv.catalog.get_device(srv.device_id)["online"]
    finally:
        eng.shutdown()
