#!/usr/bin/env python3
"""Performance gate: compare a bench record against a baseline and exit
nonzero on regression.

    python scripts/perf_gate.py tests/fixtures/gate_record_r05.json BASELINE.json

The r05 regression (serve 2428 → 464.7 tok/s, p95 TTFT 3.4 s → 15.7 s)
shipped silently because the numbers lived in a JSON blob nobody diffed.
This gate makes that class of regression impossible to ship silently: run
it in CI (or by hand before committing a BENCH_*.json) and a regressed
serve line fails the build with a per-metric report.

Inputs (either argument may be any of these shapes):
  - a BENCH_rXX.json harness capture: {"n", "cmd", "rc", "tail", ...} —
    the line of record is the LAST JSON object line inside "tail" that
    carries a "value" field;
  - a flat bench line of record (the JSON object bench.py prints);
  - BASELINE.json (no numeric serve metrics) — comparisons fall back to
    the ABSOLUTE floors below.

Checks, in order of authority:
  1. Relative, when the baseline has the metric: higher-is-better metrics
     (value, engine_direct_tok_per_s, serve_efficiency, vs_baseline,
     mean_completion_tokens) may drop at most TOLERANCE; lower-is-better
     metrics (p50/p95 TTFT) may rise at most TTFT_TOLERANCE;
     window_errors may not increase.
  2. Absolute floors, always: vs_baseline and serve_efficiency >= 0.5
     (serve_efficiency is derived from value / engine_direct_tok_per_s
     when the line predates the field), p95_ttft_ms <= 5000,
     window_errors == 0. The floors alone catch r05 against the
     metric-less BASELINE.json.
  3. Self-speculative decoding floors, when the record carries them:
     spec_accept_rate >= 0.05 and spec_tok_per_call >= 1.0 — below
     either, drafting is pure verify-pass overhead and TPU_SPEC=0
     beats shipping it.
  4. Paged-KV floors, when the record carries them: the shared-prompt
     oversubscription sweep must show paged_admit_ratio >= 3.0 (the
     ISSUE 6 acceptance bar: >= 3x the slots at equal HBM budget when 90%
     of prompts share a prefix) and cow_copies_per_req <= 2.0 (more means
     boundary blocks are churning — check TPU_KV_BLOCK_TOKENS against the
     stored prefix lengths). With the physical block pool (ISSUE 10),
     paged_hbm_bytes_ratio >= 2.5: peak contiguous-equivalent HBM bytes
     (logical blocks + resident prefix-cache rows, what the slot-contiguous
     arena would have spent) over peak physical pool bytes actually
     allocated — under 2.5 on the 90%-shared sweep means admission is
     copying rows instead of pinning them. paged_block_leaks is an exact
     check like window_errors: any nonzero end-of-run leak/double-free
     count from the ledger audit fails the gate outright.
  5. KV-migration floors, when the record carries them: the 2-engine
     oversubscribed sweep must have moved at least one snapshot or
     queued request (migration_count >= 1) and its admitted p95 TTFT
     must beat (or tie) the shedding-only leg (migrate_ttft_gain >=
     1.0). Records from hosts that cannot give each engine its own
     silicon (one device, or a single-core CPU) carry neither key
     and [SKIP].
  5b. Prefix-locality routing floor, when the record carries it: the
     2-engine 90%-shared-prefix sweep must show prefix_route_hit_rate
     >= 0.5 — the share of routed requests landing on an engine that
     already holds the prefix (or pulls it over the fetch path). Same
     single-device escape hatch as the migration sweep: a marker key
     instead, and the metric [SKIP]s with a warning.
  5c. Unified-dispatch floors, when the record carries them: the pp×tp
     sweep must report dispatch_parity == 1.0 (GSPMD leader/follower
     step-program replay is token- and state-identical to the
     local-arrays engine; any fraction under 1.0 is a divergence, not a
     slowdown) and pp_tp_serve_tok_per_s >= 1.0 as a liveness floor for
     the pipeline×tensor boot. Hosts without enough devices for the
     mesh emit the dispatch_single_device marker and both [SKIP].
  6. Raw-decode kernel floors, when the record carries them: the B=112
     headline-shape sweep >= 5600 tok/s (the pre-fusion starting line —
     the fused-layout work climbs FROM here), the MLA S=32k int8-latent
     sweep >= 150 tok/s, and layers_gbps >= 500 (achieved weight-stream
     bandwidth of the w8a8 layer pass; r05 measured ~570 of 819 GB/s).
     attn_us_per_cell gates relatively (latency-class) when a baseline
     carries it.
  7. Prefill-economy checks, when the record carries them (ISSUE 11
     ragged packed prefill): prefill_tok_per_s >= 500 (collapse floor),
     prefill_pad_waste_pct <= 50 (the bucketed pow2 staging wastes
     30-60% on mixed fills; the ragged packed buffer must not regress
     back to it), and prefill_executables gates relatively against the
     baseline (the executable-zoo count must never grow back).
  8. Perf-observatory checks, when the record carries them (ISSUE 12):
     goodput_ratio >= 0.5 (under half the finished tokens meeting the
     TTFT+ITL SLO means the headline is mostly violation traffic),
     decode_mbu >= 0.3 (sampled decode HBM bandwidth collapse floor),
     itl_p95_ms <= 500 absolute plus relative latency-class gating, and
     goodput_tok_per_s gates relatively like other throughput metrics.

  9. Capture→replay + latency-waterfall checks, when the record carries
     them (ISSUE 16): replay_determinism is an exact check (must be 1.0 —
     two seeded builds of the replay stream hashed differently, i.e. the
     replay harness itself went nondeterministic); waterfall_coverage must
     sit within 5% of 1.0 (the stage partition is exact by construction —
     drift means a stage went missing from the ledger); and the per-stage
     p95 ceilings (waterfall_stall_p95_ms, waterfall_total_p95_ms) are
     generous collapse bars, with relative latency-class gating when a
     baseline carries them.

  10. Model-zoo + tenancy checks, when the record carries them (ISSUE
     19): tenant_isolation >= 0.5 — tenant B's goodput_ratio while
     tenant A is driven far past its quota on the same engine — and
     zoo_swap_in_s <= 60, the wall for paging a parked model back into
     HBM through the warmup path. Hosts that skip the zoo sweep omit
     both keys and [SKIP].

  11. Constrained-decoding checks, when the record carries them (ISSUE
     20, the BENCH_CONSTRAIN=1 agent-trace replay): schema_valid_rate
     is an exact check — it must be EXACTLY 1.0, no baseline leniency
     and no tolerance band. The bench agent schemas are closed (every
     field enum/boolean), so the automaton's accepting state has no
     outgoing transitions and the mask forces EOS: a finished request
     that is not valid JSON matching its schema, or any single
     automaton-illegal token, is a masking bug, not model weakness.
     constrain_mask_us_per_tok <= 500 ceilings the host-side mask
     fuse/lift cost per constrained token (past it the automaton walk
     is recompiling masks instead of hitting the per-state memo);
     constrain_spec_accept_rate >= 0.05 mirrors the spec_accept_rate
     floor — constraint-filtered drafts accepted below that rate mean
     the masked verify is rejecting legal drafts and TPU_SPEC=0 beats
     composing them. Unconstrained runs omit all three keys and [SKIP].

Missing metrics are reported as [SKIP] with a stderr warning but never
fail the gate (older records predate newer fields — a KeyError here
would make every old BENCH_*.json ungateable); a metric PRESENT and
regressed always fails.
"""

from __future__ import annotations

import glob
import json
import os
import sys

# relative tolerances (fraction of baseline)
TOLERANCE = 0.10  # throughput-class metrics may drop <= 10%
TTFT_TOLERANCE = 0.25  # latency-class metrics may rise <= 25%

HIGHER_BETTER = (
    "value",
    "vs_baseline",
    "serve_efficiency",
    "engine_direct_tok_per_s",
    "mean_completion_tokens",
    "spec_accept_rate",
    "spec_tok_per_call",
    "embed_per_s_nomic-embed-text_b1_tpu",
    "embed_per_s_qwen3-embedding-8b-int8_b64_d1024_tpu",
    "paged_admit_ratio",
    "paged_hbm_bytes_ratio",
    "migration_count",
    "migrate_ttft_gain",
    "prefix_route_hit_rate",
    "dispatch_parity",
    "pp_tp_serve_tok_per_s",
    "raw_decode_tok_per_s_llama-3.1-8b-int8_kv8_b112_tpu",
    "raw_decode_tok_per_s_mla-8b-int8_kv8_b4_s32768_tpu",
    "layers_gbps",
    "prefill_tok_per_s",
    "goodput_tok_per_s",
    "goodput_ratio",
    "decode_mbu",
    "tenant_isolation",
    "constrain_spec_accept_rate",
)
LOWER_BETTER = ("p50_ttft_ms", "p95_ttft_ms", "cow_copies_per_req",
                "attn_us_per_cell", "attn_us_per_cell_paged",
                "prefill_pad_waste_pct", "prefill_executables",
                "itl_p95_ms", "waterfall_stall_p95_ms",
                "waterfall_total_p95_ms",
                "coldstart_first_token_s", "coldstart_first_token_cold_s",
                "coldstart_fully_warm_s", "zoo_swap_in_s",
                "constrain_mask_us_per_tok")

# absolute floors/ceilings applied regardless of baseline coverage (only
# ever read with .get(): a floor for a metric the record lacks must skip,
# never KeyError — old records predate new fields)
ABS_MIN = {
    "vs_baseline": 0.5,
    "serve_efficiency": 0.5,
    # self-speculative decoding: accepting under 5% of drafts, or emitting
    # barely one token per fused verify call, means the draft-and-verify
    # pass is pure overhead over plain decode
    "spec_accept_rate": 0.05,
    "spec_tok_per_call": 1.0,
    # constrained spec composition (ISSUE 20): drafts are automaton-
    # filtered before staging, so they are constraint-legal by
    # construction — the masked verify rejecting nearly all of them means
    # the per-position masks disagree with the filter that built the
    # drafts, and the composition is overhead, not speedup
    "constrain_spec_accept_rate": 0.05,
    # embedding throughput drifted down unnoticed across rounds (nomic b1
    # 9.3 → 7.9 /s, qwen3-8b-int8 b64 98 → 90.5 /s between r4 and r5);
    # these floors are well under the worst observed value — they catch a
    # collapse (broken kernel path, silent CPU fallback), while the
    # cross-round best-prior warning in main() catches gradual drift
    "embed_per_s_nomic-embed-text_b1_tpu": 6.5,
    "embed_per_s_qwen3-embedding-8b-int8_b64_d1024_tpu": 80.0,
    # paged KV: the oversubscribed 90%-shared sweep must multiply admitted
    # slots at least 3x at equal HBM budget (peak logical/physical blocks)
    "paged_admit_ratio": 3.0,
    # physical block pool: peak contiguous-equivalent HBM bytes over peak
    # physical bytes. 2.5 (not 3.0) because the numerator charges the real
    # prefix-cache rows the contiguous arena keeps resident, while the
    # denominator includes the pool's one shared copy — honest accounting
    # sits a little under the slot-count admit ratio
    "paged_hbm_bytes_ratio": 2.5,
    # KV migration: the 2-engine oversubscribed sweep must actually move
    # work (at least one snapshot or queued-steal) and the drained leg's
    # admitted p95 TTFT must be no worse than shedding-only — a gain under
    # 1.0 means the coordinator ships bytes without relieving the queue
    # and TPU_MIGRATE=0 beats shipping it
    "migration_count": 1.0,
    "migrate_ttft_gain": 1.0,
    # prefix-locality routing: the 2-engine 90%-shared-prefix sweep must
    # land at least half its routed requests where the prefix is already
    # resident (or arrives via fetch) — under 0.5 the digest channel is
    # stale/ignored and TPU_PREFIX_ROUTE=0 beats shipping it. Hosts that
    # cannot give each engine its own silicon emit a marker instead and
    # the key [SKIP]s with a warning.
    "prefix_route_hit_rate": 0.5,
    # unified dispatch plane (pp×tp sweep): parity is pass/fail, not a
    # throughput — anything under 1.0 means the GSPMD leader/follower
    # step-program diverged from the local-arrays engine (wrong tokens or
    # non-replicated device state) and the dispatch refactor regressed.
    # The serve key is a liveness floor only (the sweep runs the tiny
    # model); round-to-round drift is the relative check's job. Hosts
    # without enough devices for the mesh emit the dispatch_single_device
    # marker and both keys [SKIP] with a warning.
    "dispatch_parity": 1.0,
    "pp_tp_serve_tok_per_s": 1.0,
    # raw-decode kernel floors (promoted top-level by bench.py). The b112
    # headline-shape sweep measured 5609 tok/s pre-fusion (r5): the fused
    # cache layout + wqkv/w13 layer pass must never regress BELOW that
    # starting line — the whole point of the restructure is to climb from
    # it toward 6000. The MLA S=32k int8-latent sweep (199 tok/s in r5) is
    # the blocked s8 kernel's only on-hardware evidence; 150 catches a
    # collapse (silent fallback) without flaking on round-to-round noise.
    "raw_decode_tok_per_s_llama-3.1-8b-int8_kv8_b112_tpu": 5600.0,
    "raw_decode_tok_per_s_mla-8b-int8_kv8_b4_s32768_tpu": 150.0,
    # achieved weight-stream bandwidth of the w8a8 layer pass: r05 measured
    # ~570 GB/s of the v5e's 819; 500 is the collapse floor (a drop below
    # means the fused pass re-materializes weights or lost the s8 MXU path)
    "layers_gbps": 500.0,
    # prefill economy (ISSUE 11 ragged packed prefill): true prompt tok/s
    # over the headline window. 500 is the collapse floor for the 8B
    # headline — prefill riding a broken path (per-prompt serial admission,
    # silent CPU fallback) lands far below it, while any healthy chunked
    # window clears it with margin
    "prefill_tok_per_s": 500.0,
    # perf observatory (telemetry/perf.py). goodput_ratio: under half the
    # finished tokens meeting the TTFT+ITL SLO means the headline tok/s is
    # mostly SLO-violating traffic — DistServe's "raw throughput lied"
    # case. decode_mbu: sampled decode rounds moving under 30% of
    # the chip's published HBM peak on the 8B int8 headline is a bandwidth collapse
    # (lost fused layout / silent fallback); healthy rounds measured well
    # above it (layers_gbps ~570/819 ≈ 0.70 on the weight stream alone)
    "goodput_ratio": 0.5,
    "decode_mbu": 0.3,
    # model zoo + tenancy (ISSUE 19, bench.py zoo_sweep): with tenant A
    # driven far past its token-bucket quota, tenant B's goodput_ratio on
    # the same engine must stay at least half-healthy — under 0.5 the
    # per-tenant admission gate and SLO-debt preemption are not isolating
    # and TPU_TENANT_QUOTAS is a decoration, not a quota
    "tenant_isolation": 0.5,
}
ABS_MAX = {
    "p95_ttft_ms": 5000.0,
    "window_errors": 0.0,
    # staging pad waste: 1 - true/dispatched prefill tokens. The bucketed
    # pow2 path measures 30-60% on mixed fills; the ragged packed path's
    # bound is one partial pow2-T buffer per window. 50% catches a ragged
    # regression to worst-case bucketing without flaking the bucketed
    # escape hatch (TPU_RAGGED_PREFILL=0 runs gate relatively instead)
    "prefill_pad_waste_pct": 50.0,
    # more than ~2 copy-on-write blocks per completed request means the
    # block size fights the stored prefix lengths instead of sharing them
    "cow_copies_per_req": 2.0,
    "paged_block_leaks": 0.0,
    # per-token ITL p95 (perf observatory): the streaming-smoothness
    # collapse ceiling. A healthy decode round spreads its wall over K
    # tokens per slot (tens of ms each at the 8B headline); half a second
    # per token means rounds are stalling or emission is starved
    "itl_p95_ms": 500.0,
    # latency waterfall (telemetry/workload.py): per-request p95 collapse
    # ceilings. stall is decode wall beyond the TPU_WATERFALL_STALL_MS
    # inter-token threshold — a healthy window keeps it near zero, but the
    # ceiling stays generous enough to absorb first-compile pauses that
    # land in early requests' decode gaps. total is the end-to-end request
    # wall; past 30 s the serve loop is wedged, not slow.
    "waterfall_stall_p95_ms": 2500.0,
    "waterfall_total_p95_ms": 30000.0,
    # cold start (ISSUE 18 acceptance): boot-to-first-token in a fresh
    # process. With a warm shipped compile cache the
    # critical-prefix warmup deserializes executables instead of compiling
    # them — over 10 s means the cache keyed wrong (recompiling) or the
    # critical prefix grew past "one admit bucket + one prefill + one
    # decode". The cold (empty-cache) leg pays real XLA compiles; 60 s
    # ceilings a compile-queue pileup without flaking on one slow compile.
    # Hosts that skip the coldstart sweep omit both keys → [SKIP]+warning.
    "coldstart_first_token_s": 10.0,
    "coldstart_first_token_cold_s": 60.0,
    # model zoo (ISSUE 19): a parked model's swap-in — evict LRU, rebuild
    # the engine around the host tree, warm from the model's own compile
    # priors — rides the same warmup path as cold start, so it inherits
    # the same pileup ceiling: over 60 s means the swap re-paid compiles
    # the persistent cache + priors should have amortized. Hosts that skip
    # the zoo sweep omit the key → [SKIP]+warning.
    "zoo_swap_in_s": 60.0,
    # constrained decoding (ISSUE 20): amortized host-side cost of
    # building/fusing the per-slot token mask, per constrained token.
    # The per-state mask memo makes steady state a dict hit plus a
    # [W] uint32 row copy; past 500 µs/tok the automaton walk is
    # rebuilding masks (memo misses — state explosion or a cache bug)
    # and the constrain path is throttling decode
    "constrain_mask_us_per_tok": 500.0,
}


def extract_record(doc: dict) -> dict:
    """The bench line of record from any supported JSON shape."""
    if "value" in doc:
        return doc
    tail = doc.get("tail", "")
    rec = None
    for line in str(tail).splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and "value" in obj:
            rec = obj  # last wins: the line of record is printed last
    return rec if rec is not None else doc


def metric(rec: dict, name: str) -> float | None:
    v = rec.get(name)
    if isinstance(v, (int, float)):
        return float(v)
    if name == "serve_efficiency":
        # derive for records that predate the field (bench.py emits it now)
        val, direct = rec.get("value"), rec.get("engine_direct_tok_per_s")
        if isinstance(val, (int, float)) and isinstance(direct, (int, float)) and direct > 0:
            return float(val) / float(direct)
    return None


def check(cand: dict, base: dict) -> list[tuple[str, str, str]]:
    """[(metric, message, status)] for every check that could be evaluated;
    status is "pass" | "fail" | "skip". A metric absent from the candidate
    is a skip (warned by main(), never a failure and never a KeyError)."""
    results: list[tuple[str, str, str]] = []
    for name in HIGHER_BETTER:
        c, b = metric(cand, name), metric(base, name)
        if c is None:
            results.append((name, "absent from candidate", "skip"))
            continue
        if b is not None:
            floor = b * (1.0 - TOLERANCE)
            ok = c >= floor
            results.append(
                (name, f"{c:.3f} vs baseline {b:.3f} (floor {floor:.3f})",
                 "pass" if ok else "fail")
            )
        abs_floor = ABS_MIN.get(name)
        if abs_floor is not None:
            ok = c >= abs_floor
            results.append(
                (name, f"{c:.3f} >= {abs_floor} (abs floor)",
                 "pass" if ok else "fail")
            )
    for name in LOWER_BETTER:
        c, b = metric(cand, name), metric(base, name)
        if c is None or c < 0:  # bench emits -1.0 for "not measured"
            results.append((name, "absent from candidate", "skip"))
            continue
        if b is not None and b >= 0:
            ceil = b * (1.0 + TTFT_TOLERANCE)
            ok = c <= ceil
            results.append(
                (name, f"{c:.1f} vs baseline {b:.1f} (ceiling {ceil:.1f})",
                 "pass" if ok else "fail")
            )
        abs_ceil = ABS_MAX.get(name)
        if abs_ceil is not None:
            ok = c <= abs_ceil
            results.append(
                (name, f"{c:.1f} <= {abs_ceil} (abs ceiling)",
                 "pass" if ok else "fail")
            )
    c = metric(cand, "window_errors")
    if c is not None:
        b = metric(base, "window_errors") or 0.0
        ok = c <= max(b, ABS_MAX.get("window_errors", 0.0))
        results.append(
            ("window_errors", f"{c:.0f} (baseline {b:.0f})",
             "pass" if ok else "fail")
        )
    else:
        results.append(("window_errors", "absent from candidate", "skip"))
    # exact check, no baseline leniency: a leaked or double-freed block is
    # a refcount bug whatever the previous round leaked
    c = metric(cand, "paged_block_leaks")
    if c is not None:
        ok = c <= ABS_MAX.get("paged_block_leaks", 0.0)
        results.append(
            ("paged_block_leaks", f"{c:.0f} (must be 0)",
             "pass" if ok else "fail")
        )
    else:
        results.append(("paged_block_leaks", "absent from candidate", "skip"))
    # exact check, no baseline leniency: a dropped flight-recorder event
    # means a dump froze the ring long enough to lose serve-path history —
    # the post-mortem tool lying about the incident it exists to capture
    c = metric(cand, "recorder_dropped_events")
    if c is not None:
        results.append(
            ("recorder_dropped_events", f"{c:.0f} (must be 0)",
             "pass" if c <= 0.0 else "fail")
        )
    else:
        results.append(
            ("recorder_dropped_events", "absent from candidate", "skip")
        )
    # exact checks, no baseline leniency: two seeded builds of the replay
    # stream hashing differently (determinism) or a replayed capture not
    # reproducing the captured outputs (match) is a harness bug whatever
    # the previous round did
    for name in ("replay_determinism", "replay_match"):
        c = metric(cand, name)
        if c is not None:
            results.append(
                (name, f"{c:.3f} (must be 1.0)",
                 "pass" if c >= 1.0 else "fail")
            )
        else:
            results.append((name, "absent from candidate", "skip"))
    # exact check, no baseline leniency and no tolerance band: the closed
    # agent schemas force EOS at the accepting state, so every finished
    # constrained request IS schema-valid by construction — any fraction
    # under 1.0 means an automaton-illegal token got sampled (a masking
    # bug), never that the model was too weak to follow the schema
    c = metric(cand, "schema_valid_rate")
    if c is not None:
        results.append(
            ("schema_valid_rate", f"{c:.4f} (must be exactly 1.0)",
             "pass" if c >= 1.0 else "fail")
        )
    else:
        results.append(("schema_valid_rate", "absent from candidate", "skip"))
    # the waterfall stage partition is exact by construction: coverage
    # (sum of stage seconds / measured wall) drifting past 5% of 1.0 means
    # a stage fell out of the ledger, not that requests got slower
    c = metric(cand, "waterfall_coverage")
    if c is not None:
        ok = 0.95 <= c <= 1.05
        results.append(
            ("waterfall_coverage", f"{c:.4f} (must be within 5% of 1.0)",
             "pass" if ok else "fail")
        )
    else:
        results.append(("waterfall_coverage", "absent from candidate", "skip"))
    return results


def best_prior_headline(candidate_path: str) -> tuple[float, str] | None:
    """Best headline `value` among sibling BENCH_r*.json captures (excluding
    the candidate itself). The pairwise baseline check only sees ONE prior
    round — a slow leak (each round 10% under the last) passes every gate
    while compounding; comparing against the best-ever round surfaces it."""
    best: tuple[float, str] | None = None
    pattern = os.path.join(os.path.dirname(os.path.abspath(candidate_path)), "BENCH_r*.json")
    for path in sorted(glob.glob(pattern)):
        if os.path.abspath(path) == os.path.abspath(candidate_path):
            continue
        try:
            with open(path) as f:
                rec = extract_record(json.load(f))
        except (OSError, json.JSONDecodeError):
            continue
        v = metric(rec, "value")
        if v is not None and (best is None or v > best[0]):
            best = (v, os.path.basename(path))
    return best


def main(argv: list[str]) -> int:
    if len(argv) != 2:
        print(__doc__)
        print("usage: perf_gate.py CANDIDATE.json BASELINE.json", file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        cand = extract_record(json.load(f))
    with open(argv[1]) as f:
        base = extract_record(json.load(f))
    if "value" not in cand:
        print(f"perf_gate: no bench line of record in {argv[0]}", file=sys.stderr)
        return 2
    print(f"candidate: {cand.get('metric', argv[0])}")
    print(f"baseline:  {base.get('metric', argv[1])}")
    failed = 0
    skipped: list[str] = []
    for name, msg, status in check(cand, base):
        print(f"  [{status.upper()}] {name}: {msg}")
        if status == "fail":
            failed += 1
        elif status == "skip":
            skipped.append(name)
    if skipped:
        print(
            "perf_gate: WARNING metrics absent from candidate, not gated: "
            + ", ".join(skipped),
            file=sys.stderr,
        )
    # cross-round drift check: warn (never fail — the best round may have
    # run on beefier hardware) when the headline is >20% under the best
    # prior BENCH_r*.json next to the candidate
    prior = best_prior_headline(argv[0])
    cand_value = metric(cand, "value")
    if prior is not None and cand_value is not None and cand_value < 0.8 * prior[0]:
        print(
            f"perf_gate: WARNING headline value {cand_value:.1f} is "
            f"{100 * (1 - cand_value / prior[0]):.0f}% below best prior round "
            f"({prior[0]:.1f} in {prior[1]}) — cross-round drift",
            file=sys.stderr,
        )
    if failed:
        print(f"perf_gate: {failed} metric(s) regressed", file=sys.stderr)
        return 1
    print("perf_gate: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
