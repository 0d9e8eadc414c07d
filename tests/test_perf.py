"""Perf observatory: ITL window + drain-exactly-once, goodput ledger SLO
classification, sampling cadence, phase attribution, the four-layout cost
models and roofline, the ITL-degradation detector, the stdlib-only import
lint, the dispatch-phase registry lint (every `_compile_obs` phase string
in the engine must be covered by the perf cost models AND the recorder
etype census), the scheduler prefill-economy stats contract, ragged-etype
ring round-trips rendered by flight_dump.py, and the e2e acceptance shape:
a real chat completion under TPU_PERF_SAMPLE=1 makes /v1/debug/perf report
per-phase {host, device, wait} walls and MFU/MBU for all four layouts."""

import io
import json
import os
import sys
import time

import httpx
import jax.numpy as jnp
import pytest

from llm_mcp_tpu.api.server import CoreServer
from llm_mcp_tpu.executor import GenerationEngine
from llm_mcp_tpu.executor.scheduler import TokenBudgetScheduler
from llm_mcp_tpu.state.db import Database
from llm_mcp_tpu.telemetry import perf
from llm_mcp_tpu.telemetry.perf import (
    CACHE_LAYOUTS,
    DISPATCH_PHASES,
    ModelShape,
    PerfObservatory,
    decode_flops_per_token,
    decode_hbm_bytes_per_token,
    kv_bytes_per_token,
    layout_name,
    phase_cost,
    prefill_flops_per_token,
)
from llm_mcp_tpu.telemetry.recorder import (
    AnomalyMonitor,
    FlightRecorder,
    ITLDegradationDetector,
)
from llm_mcp_tpu.utils.config import Config

SHAPE = ModelShape(
    dim=2048, n_layers=16, n_heads=16, n_kv_heads=4, head_dim=128,
    param_count=1_000_000_000, kv_lora_rank=512, qk_rope_head_dim=64,
)

# ---------------------------------------------------------------------------
# token timelines: ITL window, percentiles, drain-exactly-once
# ---------------------------------------------------------------------------


def test_observe_itl_splits_gap_over_tokens():
    obs = PerfObservatory()
    assert obs.observe_itl(0.4, 4) == pytest.approx(0.1)
    assert obs.observe_itl(0.0, 0) == 0.0  # no tokens, no sample
    assert obs.observe_itl(-1.0, 2) == 0.0  # clock skew clamps to 0
    pct = obs.itl_percentiles()
    assert pct["samples"] == 6.0  # 4 + 2 real tokens counted
    assert pct["p50_ms"] == pytest.approx(100.0)


def test_itl_percentiles_and_fanout_cap():
    obs = PerfObservatory()
    for i in range(1, 101):
        obs.observe_itl(i / 1000.0, 1)
    pct = obs.itl_percentiles()
    assert pct["p50_ms"] == pytest.approx(50.0)
    assert pct["p95_ms"] == pytest.approx(95.0)
    assert pct["p99_ms"] == pytest.approx(99.0)
    # one giant coalesced round adds at most 64 window entries but counts
    # every token toward the sample total
    obs2 = PerfObservatory()
    obs2.observe_itl(10.0, 10_000)
    assert len(obs2._itl) == 64
    assert obs2.itl_percentiles()["samples"] == 10_000.0


def test_drain_itl_exactly_once():
    obs = PerfObservatory()
    obs.observe_itl(0.2, 2)
    first = obs.drain_itl()
    assert first == pytest.approx([0.1, 0.1])
    assert obs.drain_itl() == []  # drained
    obs.observe_itl(0.3, 1)
    assert obs.drain_itl() == pytest.approx([0.3])
    # draining never empties the percentile window
    assert obs.itl_percentiles()["samples"] == 3.0


def test_itl_mean_in_stats():
    obs = PerfObservatory()
    obs.observe_itl(0.1, 1)
    obs.observe_itl(0.3, 1)
    assert obs.stats()["itl_mean_ms"] == pytest.approx(200.0)


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------


def test_goodput_joint_slo_classification():
    obs = PerfObservatory(target_ttft_ms=500.0, target_itl_ms=50.0)
    assert obs.finish_request(400.0, 40.0, 100) is True
    assert obs.finish_request(600.0, 40.0, 50) is False  # TTFT breach
    assert obs.finish_request(400.0, 60.0, 50) is False  # ITL breach
    g = obs.goodput()
    assert g["finished_requests"] == 3.0 and g["good_requests"] == 1.0
    assert g["finished_tokens"] == 200.0 and g["good_tokens"] == 100.0
    assert g["goodput_ratio"] == pytest.approx(0.5)
    assert g["target_ttft_ms"] == 500.0 and g["target_itl_ms"] == 50.0
    # the rolling window turns tokens into tok/s over the window
    assert g["raw_finished_tok_per_s"] == pytest.approx(200.0 / 60.0)
    assert g["goodput_tok_per_s"] == pytest.approx(100.0 / 60.0)


def test_goodput_zero_target_is_unconstrained():
    obs = PerfObservatory(target_ttft_ms=0.0, target_itl_ms=0.0)
    assert obs.finish_request(1e9, 1e9, 10) is True
    assert obs.goodput()["goodput_ratio"] == 1.0
    # one axis constrained, the other free
    obs2 = PerfObservatory(target_ttft_ms=0.0, target_itl_ms=50.0)
    assert obs2.finish_request(1e9, 10.0, 1) is True
    assert obs2.finish_request(1.0, 90.0, 1) is False


def test_goodput_targets_fall_back_to_env(monkeypatch):
    monkeypatch.setenv("TPU_TARGET_TTFT_MS", "750")
    monkeypatch.setenv("TPU_TARGET_ITL_MS", "25")
    obs = PerfObservatory()
    assert obs.target_ttft_ms == 750.0 and obs.target_itl_ms == 25.0
    # explicit args win over env
    obs2 = PerfObservatory(target_ttft_ms=100.0, target_itl_ms=0.0)
    assert obs2.target_ttft_ms == 100.0 and obs2.target_itl_ms == 0.0


# ---------------------------------------------------------------------------
# sampling cadence
# ---------------------------------------------------------------------------


def test_should_sample_every_nth(monkeypatch):
    monkeypatch.setenv("TPU_PERF_SAMPLE", "4")
    obs = PerfObservatory()
    hits = [obs.should_sample("decode") for _ in range(12)]
    assert hits == [False, False, False, True] * 3
    # phases count independently
    assert [obs.should_sample("verify") for _ in range(4)] == [
        False, False, False, True,
    ]
    # unknown phases never sample (and never crash)
    assert obs.should_sample("nonsense") is False


def test_sample_zero_disables_dynamically(monkeypatch):
    monkeypatch.setenv("TPU_PERF_SAMPLE", "0")
    obs = PerfObservatory()
    assert not any(obs.should_sample("decode") for _ in range(64))
    # the knob is dynamic: flipping it on a live observatory takes effect
    monkeypatch.setenv("TPU_PERF_SAMPLE", "1")
    assert obs.should_sample("decode") is True
    monkeypatch.setenv("TPU_PERF_SAMPLE", "garbage")
    assert obs.sample_every == perf.DEFAULT_PERF_SAMPLE


# ---------------------------------------------------------------------------
# phase attribution
# ---------------------------------------------------------------------------


def test_observe_phase_accumulates_and_preseeds_all_phases():
    obs = PerfObservatory()
    att = obs.phase_attribution()
    assert set(att) == set(DISPATCH_PHASES)  # all phases present from boot
    assert all(v["samples"] == 0.0 for v in att.values())
    obs.observe_phase("decode", 0.001, 0.009, 0.002, tokens=8, rows=4,
                      ctx_mean=100.0)
    obs.observe_phase("decode", 0.001, 0.011, 0.0, tokens=8, rows=4,
                      ctx_mean=100.0)
    obs.observe_phase("nonsense", 1.0, 1.0)  # unknown: dropped, no crash
    d = obs.phase_attribution()["decode"]
    assert d["samples"] == 2.0 and d["tokens"] == 16.0
    assert d["host_s"] == pytest.approx(0.002)
    assert d["device_s"] == pytest.approx(0.020)
    assert d["wait_s"] == pytest.approx(0.002)
    # negative walls (clock skew) clamp instead of corrupting the sums
    obs.observe_phase("verify", -1.0, -1.0, -1.0)
    v = obs.phase_attribution()["verify"]
    assert v["host_s"] == 0.0 and v["device_s"] == 0.0


# ---------------------------------------------------------------------------
# cost models
# ---------------------------------------------------------------------------


def test_layout_name_matrix():
    assert layout_name(False, False) == "gqa_bf16"
    assert layout_name(False, True) == "gqa_int8"
    assert layout_name(True, False) == "mla_bf16"
    assert layout_name(True, True) == "mla_int8"
    assert set(CACHE_LAYOUTS) == {
        layout_name(m, q) for m in (False, True) for q in (False, True)
    }


def test_fused_scale_row_is_as_wide_as_the_caches_rows():
    """The packed scales ride a pseudo-head as wide as the payload's rows: a
    head, or the 128 lanes where narrower heads lie abreast. The rule is
    `kernels/attention.py:kv_heads_abreast`, restated in this module (it
    imports nothing of jax): held to it here, and to what `init_kv_cache`
    allocates at Granite-4.0-H's and Qwen3-8B's heads."""
    from llm_mcp_tpu.kernels.attention import kv_heads_abreast
    from llm_mcp_tpu.telemetry.perf import _fused_scale_bytes

    for hkv in (1, 2, 3, 4, 8, 12, 30):
        for hd in (16, 32, 64, 96, 128, 256):
            width = hd * kv_heads_abreast(hkv, hd)
            assert _fused_scale_bytes(hkv, hd) == -(-2 * hkv * 4 // width) * width, (hkv, hd)
    # 8 KV heads of 64: 9 rows of 128 lanes a position (17 x 64 padded to as much in VMEM)
    granite = ModelShape(dim=2048, n_layers=4, n_heads=32, n_kv_heads=8, head_dim=64,
                         param_count=1)
    assert kv_bytes_per_token(granite, "gqa_int8") == 4 * 9 * 128
    qwen = ModelShape(dim=4096, n_layers=36, n_heads=32, n_kv_heads=8, head_dim=128,
                      param_count=1)
    assert kv_bytes_per_token(qwen, "gqa_int8") == 36 * 17 * 128


def test_kv_bytes_per_token_orderings():
    # bf16 GQA: L * 2 (k+v) * Hkv * hd * 2 bytes
    assert kv_bytes_per_token(SHAPE, "gqa_bf16") == 16 * 2 * 4 * 128 * 2
    # int8 halves the payload but pays padded scale pseudo-head rows:
    # 2*4 kv-heads * 4B = 32B -> one 128-lane row
    assert kv_bytes_per_token(SHAPE, "gqa_int8") == 16 * (2 * 4 * 128 + 128)
    # MLA latents: (rank + rope) per token, bf16 = 2B each
    assert kv_bytes_per_token(SHAPE, "mla_bf16") == 16 * (512 + 64) * 2
    assert kv_bytes_per_token(SHAPE, "mla_int8") == 16 * (512 + 64 + 4)
    # the orderings the what-if column exists to show: quantizing shrinks
    # within a family, and the MLA latent beats per-head KV at equal width
    kb = {l: kv_bytes_per_token(SHAPE, l) for l in CACHE_LAYOUTS}
    assert kb["gqa_int8"] < kb["gqa_bf16"]
    assert kb["mla_int8"] < kb["mla_bf16"]
    assert kb["mla_bf16"] < kb["gqa_bf16"]
    assert kb["mla_int8"] < kb["gqa_int8"]


def test_decode_flops_weights_dominate_and_ctx_grows_attn():
    f0 = decode_flops_per_token(SHAPE, "gqa_bf16", 0.0)
    assert f0 == 2.0 * SHAPE.param_count  # ctx=0: pure weight MACs
    f1k = decode_flops_per_token(SHAPE, "gqa_bf16", 1024.0)
    assert f1k == f0 + 4.0 * 16 * 16 * 128 * 1024
    # quantization changes bytes, not FLOPs
    assert decode_flops_per_token(SHAPE, "gqa_int8", 1024.0) == f1k
    # MLA absorbed attention scores against the latent, not per-head KV
    mla = decode_flops_per_token(SHAPE, "mla_bf16", 1024.0)
    assert mla == f0 + 2.0 * 16 * 16 * 1024 * (512 + 64 + 512)


def test_decode_hbm_bytes_amortizes_weights_and_charges_paged_tables():
    kw = dict(ctx=1000.0, rows=1.0, weight_bytes_per_param=2.0)
    b1 = decode_hbm_bytes_per_token(SHAPE, "gqa_bf16", **kw)
    b8 = decode_hbm_bytes_per_token(SHAPE, "gqa_bf16", **{**kw, "rows": 8.0})
    # 8 rows share one weight stream: exactly 7/8 of the weight bytes gone
    assert b1 - b8 == pytest.approx(2.0 * SHAPE.param_count * 7 / 8)
    # paged adds one i32 per block per layer of table gather
    bp = decode_hbm_bytes_per_token(
        SHAPE, "gqa_bf16", paged=True, block_tokens=16, **kw
    )
    assert bp - b1 == pytest.approx(16 * 4.0 * (1000.0 / 16))
    # KV read dominates at long context: bytes grow ~linearly with ctx
    b2k = decode_hbm_bytes_per_token(SHAPE, "gqa_bf16", **{**kw, "ctx": 2000.0})
    assert b2k - b1 == pytest.approx(1000.0 * kv_bytes_per_token(SHAPE, "gqa_bf16"))


def test_prefill_is_decode_at_half_context():
    assert prefill_flops_per_token(SHAPE, "gqa_bf16", 800.0) == (
        decode_flops_per_token(SHAPE, "gqa_bf16", 400.0)
    )


def test_phase_cost_registry_covers_every_dispatch_phase():
    assert set(perf.PHASE_COSTS) == set(DISPATCH_PHASES)
    for phase in DISPATCH_PHASES:
        flops, byts = phase_cost(
            phase, SHAPE, "gqa_bf16", ctx=256.0, rows=4.0, paged=True
        )
        assert flops > 0 and byts > 0, phase
    with pytest.raises(KeyError):
        phase_cost("cow", SHAPE, "gqa_bf16", ctx=1.0, rows=1.0)


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------


def test_roofline_without_shape_returns_no_layouts():
    r = PerfObservatory().roofline()
    assert r["layouts"] == {} and "decode_mbu" not in r


V5E = "TPU v5 lite"  # what jax.devices()[0].device_kind says on a v5e


def test_roofline_four_layouts_against_one_measured_rate():
    obs = PerfObservatory(
        SHAPE, active_layout="gqa_int8", paged=True, block_tokens=16,
        weight_bytes_per_param=1.0, device_kind=V5E,
    )
    # a sampled round of 100 tokens whose 10 ms of device wall were told at
    # its fetch -> 10k tok/s
    obs.observe_phase("decode", 0.001, 0.0, tokens=100, rows=4,
                      ctx_mean=512.0)
    obs.observe_device("decode", "plain", 0.010, 4, 100, sampled=True)
    r = obs.roofline()
    assert r["device_tok_per_s"] == pytest.approx(10_000.0)
    assert r["device_rounds"] == 1.0
    assert r["ctx_mean"] == 512.0 and r["rows_mean"] == 4.0
    assert set(r["layouts"]) == set(CACHE_LAYOUTS)
    assert [l for l, v in r["layouts"].items() if v["active"]] == ["gqa_int8"]
    for v in r["layouts"].values():
        assert v["flops_per_token"] > 0 and v["hbm_bytes_per_token"] > 0
        assert 0 < v["mfu"] and 0 < v["mbu"]
        assert v["arith_intensity"] == pytest.approx(
            v["flops_per_token"] / v["hbm_bytes_per_token"]
        )
    # all four share the measured rate, so mbu orders exactly like bytes:
    # the weight stream dominates, so int8 weights beat bf16 across
    # families, and the MLA latent wins within each precision
    mbus = {l: v["mbu"] for l, v in r["layouts"].items()}
    assert mbus["mla_int8"] < mbus["gqa_int8"] < mbus["mla_bf16"]
    assert mbus["mla_bf16"] < mbus["gqa_bf16"]
    assert r["decode_mfu"] == pytest.approx(
        r["layouts"]["gqa_int8"]["mfu"], abs=1e-4
    )
    assert r["decode_mbu"] == pytest.approx(
        r["layouts"]["gqa_int8"]["mbu"], abs=1e-4
    )
    assert (r["peak_tflops"], r["peak_hbm_gbps"]) == perf.chip_peaks(V5E)
    assert r["device_kind"] == V5E


def test_the_device_rate_pairs_seconds_and_tokens_of_the_same_rounds():
    """Samples whose device seconds never arrive (an admission between every
    two fetches) count for occupancy and leave the token rate alone; rounds
    that were not sampled and could be told feed it."""
    obs = PerfObservatory(SHAPE, device_kind=V5E)
    for _ in range(5):  # five due samples, none told
        obs.observe_phase("decode", 0.001, 0.0, tokens=128, rows=32, ctx_mean=200.0)
    assert obs.roofline()["device_tok_per_s"] == 0.0
    assert obs.roofline()["rows_mean"] == 32.0  # the sampled EMA, until a round can tell
    obs.observe_phase("admit", 0.001, 0.010, tokens=40, rows=1)  # not a decode shape
    obs.observe_device("fused", "mixed_128", 0.100, 30, 120, sampled=False)  # one told round, not a sample
    obs.observe_device("decode", "plain", 0.140, 32, 128, sampled=True)
    obs.observe_device("nonsense", "plain", 1.0, 1, 1, sampled=True)  # unknown phase: dropped
    r = obs.roofline()
    assert r["device_tok_per_s"] == pytest.approx(248 / 0.240, rel=1e-3)
    assert (r["device_rounds"], r["device_tokens"]) == (2.0, 248.0)
    assert r["device_s"] == pytest.approx(0.240)
    assert r["rows_mean"] == 31.0  # of the rounds the rate was measured on
    # the account of rounds is the one book: the totals over its rows by program
    by = obs.rounds.stats()["by_program"]
    assert {k: (v["told"], v["told_rows"], v["told_tokens"]) for k, v in by.items()} == {
        "mixed_128": (1, 30, 120), "plain": (1, 32, 128)}
    assert sum(v["device_s"] for v in by.values()) == pytest.approx(r["device_s"])
    assert not hasattr(obs, "_told")
    att = obs.phase_attribution()
    assert att["decode"]["samples"] == 5.0 and att["decode"]["tokens"] == 640.0
    assert att["decode"]["device_s"] == pytest.approx(0.140)  # the sampled round's only
    assert att["fused"]["device_s"] == 0.0


def test_roofline_peaks_are_keyed_by_device_kind():
    """No default chip: a kind without published peaks (the CPU, a bare
    observatory) gets the counts from shapes and NO utilization, and asking
    for its peaks outright is an error."""
    for kind in ("", "cpu", "TPU v9"):
        obs = PerfObservatory(SHAPE, device_kind=kind)
        obs.observe_phase("decode", 0.0, 0.010, tokens=100, rows=1, ctx_mean=64.0)
        r = obs.roofline()
        assert "decode_mbu" not in r and "peak_hbm_gbps" not in r
        for v in r["layouts"].values():
            assert v["hbm_bytes_per_token"] > 0 and "mbu" not in v
        with pytest.raises(KeyError, match="no published peaks"):
            perf.chip_peaks(kind)
    assert perf.chip_peaks(V5E) == (197.0, 819.0)


def test_stats_document_shape():
    st = PerfObservatory(SHAPE).stats()
    assert set(st) == {
        "sample_every", "itl", "itl_mean_ms", "goodput", "phases", "roofline",
        "tenants", "event_gap", "stream_lag", "samples_evicted", "rounds",
    }
    assert st["samples_evicted"] == {"event_gap": 0, "stream_lag": 0}
    assert st["event_gap"] == {"p50_ms": 0.0, "p95_ms": 0.0, "samples": 0.0}
    assert set(st["phases"]) == set(DISPATCH_PHASES)
    assert set(st["roofline"]["layouts"]) == set(CACHE_LAYOUTS)


# ---------------------------------------------------------------------------
# ITL-degradation detector
# ---------------------------------------------------------------------------


def test_itl_degradation_window_latch_and_rearm():
    d = ITLDegradationDetector(target_ms=50.0, mult=3.0, window=8,
                               min_samples=4)
    # under min_samples: no verdict no matter how bad
    for _ in range(3):
        assert d.observe(1000.0) is None
    reason = d.observe(1000.0)
    assert reason and "ITL degradation" in reason
    assert d.observe(1000.0) is None  # latched
    # healthy rounds pull the windowed mean back under 3x target and re-arm
    for _ in range(8):
        d.observe(1.0)
    assert d.observe(1000.0) is None  # window mean still healthy: one spike
    fired = [d.observe(1000.0) for _ in range(8)]
    assert sum(1 for f in fired if f) == 1, "re-armed episode fires once"
    # no SLO configured -> never fires
    assert ITLDegradationDetector(target_ms=0.0).observe(1e9) is None


def test_itl_degradation_wired_into_monitor(tmp_path, monkeypatch):
    monkeypatch.setenv("TPU_TARGET_ITL_MS", "10")
    rec = FlightRecorder(capacity=64, dump_dir=str(tmp_path),
                         dump_interval_s=0.0)
    mon = AnomalyMonitor(rec)
    assert "itl_degradation" in mon._detectors
    for i in range(32):
        rec.event("decode", i=i)
    out = None
    for _ in range(32):
        out = out or mon.signal("itl_degradation", itl_ms=500.0)
    assert out, "sustained 50x-target ITL must journal"
    assert mon.stats()["by_detector"]["itl_degradation"] == 1
    # unset target -> the default-built detector never fires
    monkeypatch.setenv("TPU_TARGET_ITL_MS", "0")
    mon2 = AnomalyMonitor(rec)
    assert not any(
        mon2.signal("itl_degradation", itl_ms=1e9) for _ in range(64)
    )


# ---------------------------------------------------------------------------
# import-direction lint: perf.py stays stdlib-only
# ---------------------------------------------------------------------------


def test_perf_never_imports_executor_or_jax():
    """perf.py is loaded by file path with stubbed parent packages; after
    exercising every layer (ITL, goodput, sampling, roofline) nothing from
    the serving stack — and no jax or numpy — may be in sys.modules. The
    probe is single-sourced from the purity manifest
    (llm_mcp_tpu/analysis/imports_lint.py)."""
    from llm_mcp_tpu.analysis.imports_lint import run_probe

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = run_probe("perf", repo)
    assert proc.returncode == 0, proc.stderr or proc.stdout


# ---------------------------------------------------------------------------
# dispatch-phase registry lint (the KERNEL_PARITY pattern for telemetry):
# every phase string the engine feeds the compile ledger must be registered
# in perf.py, every steady-state phase must have a cost model, and every
# flight etype the engine emits must be in the recorder's docstring census.
# ---------------------------------------------------------------------------


def test_engine_phase_and_etype_registries_reconcile():
    """The registry-census pass owns the reconciliation now: every
    `_compile_obs` phase the engine ledgers registered in perf.py, every
    DISPATCH_PHASES entry reaching the ledger + PHASE_COSTS +
    `_note_exec_shape`, and every engine `.event()` etype in the recorder
    docstring census (pf_rag/fused_rag/perf pinned). Assertions preserved
    verbatim as finding keys — run
    `python -m llm_mcp_tpu.analysis` for the same report with messages."""
    from llm_mcp_tpu.analysis.census import RegistryCensusPass
    from llm_mcp_tpu.analysis.core import RepoIndex

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = RegistryCensusPass().run(RepoIndex(repo))
    phase_etype = [
        f.key for f in found
        if not f.key.startswith(("kernel-", "parity-", "no-kernels"))
    ]
    assert not phase_etype, phase_etype


# ---------------------------------------------------------------------------
# scheduler prefill-economy stats contract (the dashboard's input)
# ---------------------------------------------------------------------------


def test_scheduler_prefill_economy_stats_contract():
    sched = TokenBudgetScheduler()
    st = sched.stats()
    assert st["prefill_true_tokens"] == 0.0
    assert st["prefill_padded_tokens"] == 0.0
    assert st["prefill_pad_waste_pct"] == 0.0  # no dispatches: 0, not NaN
    sched.observe_prefill(100, 0.01, padded_tokens=128)
    sched.observe_prefill(60, 0.01, padded_tokens=72)
    st = sched.stats()
    assert st["prefill_true_tokens"] == 160.0
    assert st["prefill_padded_tokens"] == 200.0
    assert st["prefill_pad_waste_pct"] == pytest.approx(20.0)
    # unpadded dispatches (padded_tokens=0) charge the true count
    sched.observe_prefill(50, 0.01)
    assert sched.stats()["prefill_padded_tokens"] == 250.0
    # padded can never be reported below true
    sched.observe_prefill(40, 0.01, padded_tokens=8)
    assert sched.stats()["prefill_true_tokens"] == 250.0
    assert sched.stats()["prefill_padded_tokens"] == 290.0


# ---------------------------------------------------------------------------
# ragged etypes: ring round-trip + flight_dump.py rendering
# ---------------------------------------------------------------------------


def test_ragged_etypes_roundtrip_and_flight_dump_render(tmp_path):
    rec = FlightRecorder(capacity=64, dump_dir=str(tmp_path),
                         dump_interval_s=0.0)
    rec.event("pf_rag", trace_id="d" * 32, groups=1, rows=3, tokens=190,
              packed=256, wall_ms=4.2)
    rec.event("fused_rag", rows=5, prefill_tokens=120, prefill_padded=128,
              bucket=128)
    rec.event("perf", phase="decode", host_ms=0.4, device_ms=9.6,
              wait_ms=0.1, rows=4)
    rows = rec.snapshot()
    assert [r["etype"] for r in rows] == ["pf_rag", "fused_rag", "perf"]
    assert rows[0]["fields"]["tokens"] == 190  # true tokens
    assert rows[0]["fields"]["packed"] == 256  # padded/dispatched shape
    assert rows[1]["fields"]["prefill_padded"] == 128

    path = rec.dump("ragged round trip", force=True)
    header, events = json.loads(open(path).readline()), None
    assert header["events"] == 3

    sys.path.insert(0, "scripts")
    try:
        import flight_dump
    finally:
        sys.path.pop(0)
    hdr, evs = flight_dump.load_from_file(path)
    assert hdr["kind"] == "flight_dump" and len(evs) == 3
    buf = io.StringIO()
    flight_dump.render(hdr, evs, None, "", 0, out=buf)
    text = buf.getvalue()
    assert "pf_rag" in text and "fused_rag" in text and "perf" in text
    assert "tokens=190" in text and "packed=256" in text
    assert f"[{'d' * 8}]" in text  # the trace lane renders
    # etype filtering renders only the ragged prefill lane
    buf2 = io.StringIO()
    flight_dump.render(hdr, evs, {"pf_rag"}, "", 0, out=buf2)
    assert "pf_rag" in buf2.getvalue() and "fused_rag" not in buf2.getvalue()


# ---------------------------------------------------------------------------
# e2e: real server + engine, TPU_PERF_SAMPLE=1
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    """Sample every non-first dispatch so a short CPU generation lands
    phase samples; the env flips back after the module (the knob is read
    per call, so the ordering with engine construction doesn't matter)."""
    import os

    prev = os.environ.get("TPU_PERF_SAMPLE")
    os.environ["TPU_PERF_SAMPLE"] = "1"
    cfg = Config()
    cfg.db_path = ":memory:"
    gen = GenerationEngine(
        "tiny-llm", max_slots=4, max_seq_len=128, dtype=jnp.float32
    ).start()
    srv = CoreServer(
        cfg, db=Database(":memory:"), gen_engines={"tiny-llm": gen}
    ).start("127.0.0.1", 0)
    yield srv
    srv.shutdown()
    if prev is None:
        os.environ.pop("TPU_PERF_SAMPLE", None)
    else:
        os.environ["TPU_PERF_SAMPLE"] = prev


@pytest.fixture(scope="module")
def base(server):
    return f"http://127.0.0.1:{server.api.port}"


def _chat(base, max_tokens=24):
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={
            "model": "tiny-llm",
            "messages": [{"role": "user", "content": "perf check"}],
            "max_tokens": max_tokens,
            "temperature": 0,
        },
        timeout=120.0,
    )
    assert r.status_code == 200
    return r


@pytest.mark.parametrize("kind", perf.SAMPLE_KINDS)
def test_a_full_sample_window_counts_what_it_pushes_out(kind, monkeypatch):
    """PR 37: a reader that cuts `samples(kind)` by its own window must know
    when the window's start is gone: every sample pushed out is counted."""
    monkeypatch.setattr(perf, "SAMPLE_WINDOW", 4)
    obs = PerfObservatory()
    for k in range(4):
        obs.observe_sample(kind, 0.001 * (k + 1))
    assert obs.samples_evicted[kind] == 0 and obs.stats()["samples_evicted"][kind] == 0
    for k in range(3):
        obs.observe_sample(kind, 1.0)
    other = next(o for o in perf.SAMPLE_KINDS if o != kind)
    assert obs.stats()["samples_evicted"] == {kind: 3, other: 0}
    assert [v for _t, v in obs.samples(kind)] == [0.004, 1.0, 1.0, 1.0]  # the oldest three went
    assert obs.stats()[kind]["samples"] == 4.0


def test_the_shipped_window_holds_the_largest_cells_run():
    # the largest cell's 40 s and drain put 56,000 gaps (PERF.md §6, PR 37): room for rounds twice as fast
    assert perf.SAMPLE_WINDOW >= 2 * 56_000
    assert PerfObservatory()._samples["event_gap"].maxlen == perf.SAMPLE_WINDOW


def test_a_sample_keeps_what_its_writer_knew_and_the_old_readers_get_pairs():
    obs = PerfObservatory()
    obs.observe_sample("event_gap", 0.05, 1, 64)
    obs.observe_sample("event_gap", 0.04, 0, 0)
    obs.observe_sample("stream_lag", 0.002)
    whole = obs.samples("event_gap", whole=True)
    assert [s[1:] for s in whole] == [(0.05, 1, 64), (0.04, 0, 0)]
    assert obs.samples("event_gap") == [s[:2] for s in whole]  # (t, seconds): what spans.window_samples unpacks
    assert [len(s) for s in obs.samples("stream_lag", whole=True)] == [2]
    assert obs.sample_percentiles("event_gap")["p95_ms"] == 50.0


@pytest.mark.parametrize("times,parts", [
    # (free, cool, arrived, seat) -> (cooling, no_request, queued)
    ((10.0, 10.05, 9.0, 10.2), (0.05, 0.0, 0.15)),    # queued before the slot was freed
    ((10.0, 10.05, 10.02, 10.2), (0.05, 0.0, 0.15)),  # arrived while the slot cooled
    ((10.0, 10.05, 10.15, 10.2), (0.05, 0.10, 0.05)),  # arrived into a cool, empty slot
    ((10.0, 10.0, 10.0, 10.0), (0.0, 0.0, 0.0)),
    ((10.0, 10.5, 10.1, 10.2), (0.2, 0.0, 0.0)),      # seated before the fence's fetch was seen
    ((10.0, 9.0, 10.1, 10.2), (0.0, 0.1, 0.1)),       # a cool stamp older than the free
    ((10.0, 10.05, 11.0, 10.2), (0.05, 0.15, 0.0)),   # an arrival stamped after the seat
])
def test_a_vacancys_parts_sum_to_seat_less_free(times, parts):
    adm = perf.AdmitAccount()
    got = adm.vacancy(*times)
    assert got == pytest.approx(parts) and sum(got) == pytest.approx(times[3] - times[0])
    adm.vacancy(*times)
    v = adm.stats()["vacancy"]
    assert v["count"] == 2 and isinstance(v["count"], int)
    assert (v["cooling_s"], v["no_request_s"], v["queued_s"]) == pytest.approx([2 * p for p in parts])


def test_admit_account_sums_programs_by_shape_kind_and_reason():
    adm = perf.AdmitAccount()
    assert adm.program("batch", 1, 1, 64, 48, queued=2, held_by="admit_batch") == 1
    assert adm.program("batch", 3, 4, 64, 150, queued=0, held_by="queue_empty") == 2
    assert adm.program("chunk", 2, 2, 512, 900, queued=1) == 3
    adm.read(blocked=True, at_once=False)
    adm.read(blocked=False, at_once=True)
    st = adm.stats()
    assert {k: st[k] for k in perf.AdmitAccount.SUMS} == {
        "programs": 3, "prompts": 6, "rows_padded": 7, "true_tokens": 1098,
        "padded_tokens": 64 + 256 + 1024, "queued_sum": 3,
        "reads": 2, "reads_blocked": 1, "reads_at_once": 1}
    assert st["by_shape"] == {"1:64": 1, "4:64": 1, "chunk 2:512": 1}
    assert st["held_by"] == {"admit_batch": 1, "queue_empty": 1}  # a batch alone has a reason
    assert st["vacancy"] == {"count": 0, "cooling_s": 0.0, "no_request_s": 0.0, "queued_s": 0.0}
    json.dumps(st)  # /v1/debug/perf and the dashboard serialise it as it is


def test_debug_perf_endpoint_full_document(base):
    _chat(base)
    deadline = time.monotonic() + 15.0
    doc = {}
    while time.monotonic() < deadline:
        doc = httpx.get(f"{base}/v1/debug/perf").json()["tiny-llm"]
        if doc["phases"]["decode"]["samples"] >= 1:
            break
        time.sleep(0.05)
    assert set(doc["phases"]) == set(DISPATCH_PHASES)
    for ph in DISPATCH_PHASES:
        assert {"host_s", "device_s", "wait_s", "samples", "tokens"} <= set(
            doc["phases"][ph]
        )
    d = doc["phases"]["decode"]
    assert d["samples"] >= 1, doc["phases"]
    assert d["device_s"] > 0 and d["tokens"] > 0
    rf = doc["roofline"]
    assert set(rf["layouts"]) == set(CACHE_LAYOUTS)
    assert rf["active_layout"] in CACHE_LAYOUTS
    assert rf["device_tok_per_s"] > 0
    # a CPU run counts; it has no utilization of a chip to report
    assert rf["device_kind"] == "cpu" and "decode_mfu" not in rf
    assert doc["sample_every"] == 1.0
    assert doc["itl"]["samples"] > 0 and doc["itl"]["p50_ms"] >= 0
    assert doc["goodput"]["finished_requests"] >= 1
    assert doc["goodput"]["finished_tokens"] > 0
    # admission's account, as perf_stats() gives it (PR 37)
    assert set(doc["admit"]) == {*perf.AdmitAccount.SUMS, "by_shape", "held_by", "vacancy",
                                 "rides", "own", "own_prompts"}
    assert doc["admit"]["programs"] >= 1 and doc["admit"]["reads"] == doc["admit"]["programs"]
    assert set(doc["admit"]["vacancy"]) == set(perf.AdmitAccount.VACANCY)
    assert doc["samples_evicted"] == {"event_gap": 0, "stream_lag": 0}
    assert not any(k.startswith("admit_reads") for k in doc)  # in one place only


def test_perf_events_land_in_flight_ring(base):
    _chat(base)
    deadline = time.monotonic() + 15.0
    events = []
    while time.monotonic() < deadline:
        events = httpx.get(
            f"{base}/v1/debug/flight?limit=500&etype=perf"
        ).json()["events"]
        if events:
            break
        time.sleep(0.05)
    assert events, "sampled rounds must journal perf etypes"
    f = events[-1]["fields"]
    assert {"phase", "host_ms", "device_ms", "wait_ms"} <= set(f)
    assert f["phase"] in DISPATCH_PHASES


def test_metrics_and_dashboard_carry_perf_blocks(base):
    _chat(base)
    text = httpx.get(f"{base}/metrics").text
    assert "llmtpu_itl_seconds" in text
    assert "llmtpu_goodput_tok_per_s" in text
    assert "llmtpu_goodput_ratio" in text
    assert "llmtpu_decode_mbu" in text
    assert "llmtpu_perf_phase_seconds_total" in text
    doc = httpx.get(f"{base}/v1/dashboard").json()
    assert "perf" in doc and "prefill" in doc
    p = doc["perf"]["tiny-llm"]
    assert {"itl_p50_ms", "itl_p95_ms", "goodput_tok_per_s", "goodput_ratio",
            "decode_mfu", "decode_mbu", "active_layout"} <= set(p)
    pe = doc["prefill"]["tiny-llm"]
    assert {"true_tokens", "padded_tokens", "pad_waste_pct"} <= set(pe)
    # tiny prompts admit whole (no chunk dispatches), so the counters may
    # legitimately be zero here — the accounting itself is unit-tested;
    # the contract is that the block exists and carries finite numbers
    assert pe["true_tokens"] >= 0 and 0.0 <= pe["pad_waste_pct"] <= 100.0


def test_finished_requests_carry_itl_and_goodput(server, base):
    eng = server.gen_engines["tiny-llm"]
    before = eng.perf_stats()["goodput"]["finished_requests"]
    _chat(base, max_tokens=12)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        g = eng.perf_stats()["goodput"]
        if g["finished_requests"] > before:
            break
        time.sleep(0.05)
    assert g["finished_requests"] > before
    assert g["finished_tokens"] > 0
    # drain-exactly-once through the engine facade
    eng.drain_itl_samples()
    assert eng.drain_itl_samples() == []
