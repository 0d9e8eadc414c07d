"""Token-budget scheduler tests: the decide() policy table, tenant quotas,
and engine-loop fairness under a prefill backlog (CPU backend, tiny model).

What these guard against: prefill monopolizing the engine loop while time
to first token still blows out. The scheduler bounds prefill per round by
the fairness cap.
"""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import pytest

from llm_mcp_tpu.executor import GenerationEngine
from llm_mcp_tpu.executor.scheduler import (
    TENANT_BURST_S,
    TokenBudgetScheduler,
    parse_tenant_quotas,
)


# --------------------------------------------------------- decide() policy --


def test_no_backlog_means_zero_budget():
    s = TokenBudgetScheduler(target_ttft_ms=2000.0, min_budget=8)
    assert s.decide(0, n_active=4, oldest_wait_s=0.0) == 0
    assert s.last_budget == 0
    assert s.stats()["prefill_token_budget"] == 0.0


def test_pure_prefill_window_runs_whole_backlog():
    """No active decode slots → nothing to protect: the budget is the whole
    backlog, so cold bursts drain back-to-back (the stale-budget bug fix)."""
    s = TokenBudgetScheduler(target_ttft_ms=2000.0, min_budget=8)
    assert s.decide(10_000, n_active=0, oldest_wait_s=0.0) == 10_000
    # and the very next mixed round is NOT stuck with the burst budget
    mixed = s.decide(10_000, n_active=4, oldest_wait_s=0.0)
    assert mixed <= s.fair_cap()


def test_fair_cap_clamps_and_counts_starvation():
    # decode round 10 ms, prefill 100 us/tok → fair cap = 100 tokens
    s = TokenBudgetScheduler(
        target_ttft_ms=1000.0, min_budget=4,
        decode_seed_s=0.010, prefill_tok_seed_s=100e-6,
    )
    assert s.fair_cap() == 100
    # deadline nearly spent: need >> cap, budget pinned at cap, starvation++
    b = s.decide(50_000, n_active=4, oldest_wait_s=0.99)
    assert b == 100
    assert s.starved_rounds == 1
    # relaxed deadline: need is small, budget well under the cap
    b2 = s.decide(200, n_active=4, oldest_wait_s=0.0)
    assert b2 < 100
    assert s.starved_rounds == 1  # unchanged


def test_min_budget_floor():
    s = TokenBudgetScheduler(
        target_ttft_ms=60_000.0, min_budget=32,
        decode_seed_s=0.010, prefill_tok_seed_s=100e-6,
    )
    # tiny backlog + huge deadline → need≈1, floored at min_budget
    assert s.decide(5, n_active=2, oldest_wait_s=0.0) == 32


def test_emas_move_toward_observations():
    s = TokenBudgetScheduler(decode_seed_s=0.05, prefill_tok_seed_s=1e-4)
    for _ in range(30):
        s.observe_decode(0.010)
        s.observe_prefill(1000, 0.010)  # 10 us/token
    assert s.decode_round_s == pytest.approx(0.010, rel=0.05)
    assert s.prefill_tok_s == pytest.approx(10e-6, rel=0.05)
    # fused rounds attribute the over-EMA residual to prefill
    before = s.prefill_tok_s
    s.observe_fused(0.030, prefill_tokens=100)  # 20 ms residual / 100 tok
    assert s.prefill_tok_s > before
    # rounds faster than the decode EMA teach nothing
    at = s.prefill_tok_s
    s.observe_fused(0.001, prefill_tokens=100)
    assert s.prefill_tok_s == at


def test_degenerate_observations_ignored():
    s = TokenBudgetScheduler()
    d0, p0 = s.decode_round_s, s.prefill_tok_s
    s.observe_decode(0.0)
    s.observe_decode(-1.0)
    s.observe_prefill(0, 1.0)
    s.observe_prefill(100, 0.0)
    assert (s.decode_round_s, s.prefill_tok_s) == (d0, p0)
    # absurd per-token cost is clamped, keeping fair_cap() > 0 forever
    s.observe_prefill(1, 3600.0)
    assert s.prefill_tok_s <= 1.0
    assert s.fair_cap() >= 1


def test_a_first_dispatch_counts_its_tokens_and_teaches_no_cost():
    """seconds=0 is how the engine reports a first dispatch (its wall is a
    compile's): tokens, pads and the waste EMA move, the cost EMA does not,
    for a prefill chunk as for a verify."""
    s = TokenBudgetScheduler()
    p0 = s.prefill_tok_s
    s.observe_prefill(96, 0.0, padded_tokens=128)
    s.observe_verify(32, 0.0)
    assert s.prefill_tok_s == p0
    assert (s.prefill_true_tokens, s.prefill_padded_tokens) == (128, 160)
    assert (s.verify_rounds, s.verify_tokens) == (1, 32)
    assert s.pad_waste > 0.0


def test_stats_contract():
    s = TokenBudgetScheduler()
    s.decide(100, n_active=1, oldest_wait_s=0.0)
    st = s.stats()
    assert set(st) == {
        "prefill_token_budget", "starved_rounds", "decode_round_ema_ms",
        "prefill_tok_cost_us", "fair_cap_tokens",
        "verify_rounds", "verify_tokens",
        "prefill_true_tokens", "prefill_padded_tokens",
        "prefill_pad_waste_pct",
        "tenant_quota_tenants", "tenant_throttled_total",
        "tenant_charged_tokens",
    }
    assert all(isinstance(v, float) for v in st.values())


def test_reserved_tokens_come_off_the_budget():
    """A staged speculative verify dispatch owes chunk positions to the
    round; the prefill budget shrinks by that reservation AFTER the
    min/cap clamp (so it can reach 0 — never negative)."""
    s = TokenBudgetScheduler(
        target_ttft_ms=1000.0, min_budget=4,
        decode_seed_s=0.010, prefill_tok_seed_s=100e-6,
    )
    full = s.decide(50_000, n_active=4, oldest_wait_s=0.99)
    assert full == s.fair_cap()
    reserved = s.decide(50_000, n_active=4, oldest_wait_s=0.99,
                        reserved_tokens=30)
    assert reserved == full - 30
    assert s.last_budget == reserved
    # a reservation larger than the whole budget floors at 0, not negative
    assert s.decide(50_000, n_active=4, oldest_wait_s=0.99,
                    reserved_tokens=10_000) == 0
    # no backlog: reservation is irrelevant, budget stays 0
    assert s.decide(0, n_active=4, oldest_wait_s=0.0, reserved_tokens=30) == 0


def test_observe_verify_counts_and_feeds_prefill_ema():
    s = TokenBudgetScheduler()
    p0 = s.prefill_tok_s
    s.observe_verify(32, 0.004)
    s.observe_verify(16, 0.002)
    assert s.verify_rounds == 2
    assert s.verify_tokens == 48
    assert s.prefill_tok_s != p0  # verify cost feeds the same EMA
    st = s.stats()
    assert st["verify_rounds"] == 2.0
    assert st["verify_tokens"] == 48.0


# ------------------------------------------------------- per-tenant quotas --


def test_parse_tenant_quotas():
    assert parse_tenant_quotas("") == {}
    assert parse_tenant_quotas(None) == {}
    q = parse_tenant_quotas("alice=600, bob=300,*=1000")
    assert q == {"alice": 600.0, "bob": 300.0, "*": 1000.0}
    # malformed / non-positive / nameless entries drop; the rest survive —
    # a typo'd quota must not take the serve path down
    assert parse_tenant_quotas("alice=x,=5,bob=-3,carol=10,stray") == {
        "carol": 10.0
    }


def test_tenant_bucket_admits_burst_then_throttles():
    s = TokenBudgetScheduler(tenant_quotas={"alice": 100.0})
    t0 = 1000.0
    # new buckets start full (one burst of rate) — a tenant's first
    # request never 429s
    ok, retry = s.tenant_admit("alice", now=t0)
    assert ok and retry == 0.0
    # burn past the burst: the level goes negative (floored at -burst)
    s.tenant_charge("alice", 500, now=t0)
    ok, retry = s.tenant_admit("alice", now=t0)
    assert not ok and retry > 0.0
    # retry_after is deficit/rate: floored debt = burst ⇒ exactly BURST_S
    assert retry == pytest.approx(TENANT_BURST_S)
    # refill: after enough seconds the bucket crosses zero again
    ok, _ = s.tenant_admit("alice", now=t0 + TENANT_BURST_S + 0.01)
    assert ok
    st = s.tenant_stats()["alice"]
    assert st["quota_tok_per_s"] == 100.0
    assert st["throttled_total"] == 1.0
    assert st["charged_tokens"] == 500.0
    flat = s.stats()
    assert flat["tenant_quota_tenants"] == 1.0
    assert flat["tenant_throttled_total"] == 1.0
    assert flat["tenant_charged_tokens"] == 500.0


def test_unmetered_tenants_never_throttle():
    """No quota config ⇒ tenant_admit is a constant-true no-op: the
    single-tenant serve path cannot change behavior."""
    s = TokenBudgetScheduler()
    s.tenant_charge("whoever", 10**9)
    ok, retry = s.tenant_admit("whoever")
    assert ok and retry == 0.0
    assert s.stats()["tenant_quota_tenants"] == 0.0
    assert s.stats()["tenant_throttled_total"] == 0.0
    # quota'd scheduler, but the EMPTY tenant id (no header) is unmetered
    s2 = TokenBudgetScheduler(tenant_quotas={"alice": 10.0})
    s2.tenant_charge("", 10**9)
    assert s2.tenant_admit("") == (True, 0.0)


def test_default_star_quota_applies_to_unknown_tenants():
    s = TokenBudgetScheduler(tenant_quotas={"*": 50.0, "vip": 5000.0})
    t0 = 2000.0
    s.tenant_charge("mystery", 10_000, now=t0)
    ok, retry = s.tenant_admit("mystery", now=t0)
    assert not ok and retry > 0.0
    # the explicit row wins over the default
    s.tenant_charge("vip", 10_000, now=t0)
    assert s.tenant_admit("vip", now=t0 + 2.1)[0]


def test_tenant_quota_contention_bounds_admissions():
    """Threaded contention: N workers hammering one metered tenant admit at
    most burst + rate·wall tokens' worth of requests — the bucket is the
    bound, not the thread count."""
    rate, cost = 200.0, 100  # tokens/s quota; tokens billed per request
    s = TokenBudgetScheduler(tenant_quotas={"hammered": rate})
    admitted = []
    lock = threading.Lock()
    stop_at = time.monotonic() + 0.5

    def worker():
        while time.monotonic() < stop_at:
            ok, _ = s.tenant_admit("hammered")
            if ok:
                s.tenant_charge("hammered", cost)
                with lock:
                    admitted.append(1)
            time.sleep(0.001)

    ts = [threading.Thread(target=worker) for _ in range(6)]
    t0 = time.monotonic()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.monotonic() - t0
    # bucket arithmetic upper bound, generously padded for scheduling
    # jitter: one full burst + refill over the wall, in request units
    bound = (rate * TENANT_BURST_S + rate * wall) / cost + len(ts)
    assert len(admitted) <= bound
    assert s.stats()["tenant_throttled_total"] > 0  # the flood did throttle


def test_slo_debt_victim_selection():
    """slo_debt preemption: the slot whose tenant is furthest AHEAD of its
    SLO is evicted first; surplus ties fall back to the per-policy keys;
    and candidates WITHOUT the key order byte-identically to the historical
    policies (the single-tenant no-op guarantee)."""
    from llm_mcp_tpu.executor.memory import KVPool

    def pool(policy):
        return KVPool(
            max_slots=4, max_seq_len=128, bytes_per_slot=1024, policy=policy
        )

    cands = [
        # the worst-served tenant's slot: surplus 0 — never the victim
        {"slot": 0, "priority": 0, "last_activity": 10.0,
         "tokens_remaining": 5, "slo_surplus": 0.0},
        # two slots from well-served tenants, tied on surplus
        {"slot": 1, "priority": 5, "last_activity": 50.0,
         "tokens_remaining": 50, "slo_surplus": 0.4},
        {"slot": 2, "priority": 0, "last_activity": 1.0,
         "tokens_remaining": 100, "slo_surplus": 0.4},
    ]
    v = pool("slo_debt").pick_victim(cands)
    # surplus leads; the 0.4 tie breaks on the priority-policy base key
    assert v["slot"] == 2
    # absent key reads 0.0: ordering degrades exactly to each base policy
    plain = [
        {k: v for k, v in c.items() if k != "slo_surplus"} for c in cands
    ]
    for pol in ("priority", "idle", "tokens"):
        with_zero = [dict(c, slo_surplus=0.0) for c in plain]
        assert (
            pool(pol).pick_victim(plain)["slot"]
            == pool(pol).pick_victim(with_zero)["slot"]
        )
    assert pool("slo_debt").pick_victim([]) is None


def test_two_tenant_isolation_soak():
    """The zoo tenancy invariant, at the scheduler + observatory layer:
    tenant A flooding far past its quota (and violating its own SLO) must
    not move tenant B's goodput_ratio — B sheds nothing, B's ledger stays
    clean, and A's overflow turns into A's 429s."""
    from llm_mcp_tpu.telemetry.perf import PerfObservatory

    sched = TokenBudgetScheduler(tenant_quotas={"alice": 200.0})
    perf = PerfObservatory(target_ttft_ms=100.0, target_itl_ms=0.0)
    sheds = {"alice": 0, "bob": 0}
    lock = threading.Lock()
    stop_at = time.monotonic() + 0.8

    def run(tenant, ttft_ms, tokens, pace_s):
        while time.monotonic() < stop_at:
            ok, _ = sched.tenant_admit(tenant)
            if not ok:
                perf.note_tenant_shed(tenant)
                with lock:
                    sheds[tenant] += 1
                time.sleep(0.002)
                continue
            perf.finish_request(ttft_ms, 0.0, tokens, tenant=tenant)
            sched.tenant_charge(tenant, tokens)
            if pace_s:
                time.sleep(pace_s)

    ts = [
        threading.Thread(target=run, args=("alice", 500.0, 120, 0.0))
        for _ in range(3)
    ]
    ts.append(threading.Thread(target=run, args=("bob", 20.0, 30, 0.01)))
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert sheds["alice"] > 0  # the flood actually hit the quota
    assert sheds["bob"] == 0  # unmetered tenant never sheds
    ratios = perf.tenant_goodput_ratios()
    # bob's every token met the SLO: ratio pinned at 1.0 — A's overload
    # never reached B's ledger
    assert ratios["bob"] == 1.0
    # alice's admitted requests all violated TTFT: her debt is visible
    assert ratios["alice"] < 0.5
    tg = perf.tenant_goodput()
    assert tg["alice"]["shed"] == float(sheds["alice"])
    assert tg["bob"]["goodput_ratio"] == 1.0


# ------------------------------------------------- engine-loop integration --


def test_staged_groups_respect_budget_with_active_decode():
    """While other slots are decoding, no staged chunk group may exceed the
    budget the scheduler decided — the fairness contract that keeps
    in-flight inter-token latency bounded under a prefill backlog."""
    eng = GenerationEngine(
        "tiny-llm", max_slots=2, max_seq_len=512, dtype=jnp.float32,
        decode_chunk=2, prefill_chunk=8,
    )
    staged: list[tuple[int, int]] = []  # (budget decided, tokens staged)
    orig = eng._stage_prefill_group

    def spy(n_active, reserved_tokens=0):
        g = orig(n_active, reserved_tokens)
        if n_active > 0 and g is not None:
            staged.append((eng._sched.last_budget, g.n_tokens))
        return g

    eng._stage_prefill_group = spy
    eng.start()
    try:
        results = {}

        def gen(name, prompt, n):
            results[name] = eng.generate(prompt, max_tokens=n, temperature=0.0)

        t1 = threading.Thread(target=gen, args=("short", "hi there", 200))
        t1.start()
        for _ in range(200):
            if eng.total_requests >= 1:
                break
            time.sleep(0.01)
        t2 = threading.Thread(target=gen, args=("long", "z" * 400, 4))
        t2.start()
        t1.join(timeout=120)
        t2.join(timeout=120)
        assert results["long"]["usage"]["prompt_tokens"] >= 390
        assert results["short"]["usage"]["completion_tokens"] >= 1
        for budget, n_tokens in staged:
            assert n_tokens <= budget, (budget, n_tokens)
    finally:
        eng.shutdown()


def test_deep_backlog_measures_ttft_for_every_request():
    """A burst deeper than the slot count must activate every prompt and
    record a TTFT sample for each — the p95 the dashboard
    reads is real, not a survivor subset."""
    import concurrent.futures as cf

    eng = GenerationEngine(
        "tiny-llm", max_slots=4, max_seq_len=256, dtype=jnp.float32,
        decode_chunk=2, prefill_chunk=16,
    ).start()
    try:
        _, _, n0 = eng.ttft_percentiles()
        prompts = [f"backlog request {i} " * (3 + i % 4) for i in range(8)]
        with cf.ThreadPoolExecutor(max_workers=8) as ex:
            outs = list(ex.map(
                lambda p: eng.generate(p, max_tokens=12, temperature=0.0),
                prompts,
            ))
        assert all(o["usage"]["completion_tokens"] >= 1 for o in outs)
        p50, p95, n = eng.ttft_percentiles()
        assert n - n0 >= 8
        assert p95 >= p50 > 0
        # the loop spent wall-clock in every phase the budget tracks
        pb = eng.phase_budget()
        assert pb["prefill"] > 0 and pb["dispatch"] > 0
    finally:
        eng.shutdown()


def test_scheduler_stats_surface():
    eng = GenerationEngine(
        "tiny-llm", max_slots=4, max_seq_len=128, dtype=jnp.float32,
        decode_chunk=2, prefill_chunk=8,
    ).start()
    try:
        eng.generate("stats probe " * 4, max_tokens=6, temperature=0.0)
        st = eng.scheduler_stats()
        assert {"prefill_token_budget", "starved_rounds", "decode_round_ema_ms",
                "prefill_tok_cost_us", "fair_cap_tokens",
                "decode_batch_occupancy"} <= set(st)
        assert 0.0 <= st["decode_batch_occupancy"] <= 1.0
        assert st["decode_round_ema_ms"] > 0
    finally:
        eng.shutdown()


def test_config_target_ttft_knob(monkeypatch):
    from llm_mcp_tpu.utils.config import Config

    monkeypatch.delenv("TPU_TARGET_TTFT_MS", raising=False)
    cfg = Config()
    assert cfg.tpu_target_ttft_ms == 2000.0
    assert not hasattr(cfg, "tpu_prefill_boost")
    monkeypatch.setenv("TPU_TARGET_TTFT_MS", "750")
    assert Config().tpu_target_ttft_ms == 750.0
