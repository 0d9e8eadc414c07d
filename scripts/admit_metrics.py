#!/usr/bin/env python3
"""One traced run of a generation cell with the seven admission readers beside
the cell's own per-layer metrics, through the harness itself.

    chiprun -- python3 scripts/admit_metrics.py --workload olmo_hybrid_decode_closed --seed <n>

The readers (`benchmark/layer_metrics/admit_*.py`, `event_gap_admit_*.py`,
`slot_vacant*.py`) take any generation cell; until `BENCHMARK.json` lists them
(PERF.md §7: a `benchmark` PR's), this is how a builder reads them. The result
line is `benchmark/run.py`'s with the seven added, and with the traced run's own
`itl_p95_ms` and `out_tokens_per_s` (beside an untraced run's they are what the
tracing costs); the line `admission:` before it holds what no metric reads:
the window's difference of `perf_stats()["admit"]` whole (`by_shape`, `held_by`,
the vacancy's three parts), `samples_evicted` and how many `event_gap` samples
the window and the drain put, and the ring's admit programs inside the
profiler's slice beside the runs of `jit_admit_fn` in it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

READERS = ("admit_program_share", "admit_rows_mean", "admit_pad_waste_pct",
           "event_gap_admit_share", "event_gap_admit_ms", "slot_vacant_ms",
           "slot_vacant_queued_ms")
END_TO_END = ("itl_p95_ms", "out_tokens_per_s")  # of the TRACED run: the tracing's cost
EXTRAS = "_admission"


def diff(a, b):
    """End minus start of a tree of sums; a key the start lacks starts at 0."""
    if isinstance(b, dict):
        return {k: diff((a or {}).get(k), v) for k, v in b.items()}
    return b - (a or 0)


def extras(run: dict):
    """Print what the block holds beyond the metrics; a reader that gives no
    metric (None)."""
    from benchmark import admit_spans, spans

    gen = run["sut"]["gen"]
    start, end = (run[e]["perf"] for e in ("start", "end"))
    w0, w1 = run["window_abs"]
    held = gen._perf.samples("event_gap", whole=True)
    out = {
        "window": diff(start.get("admit"), end["admit"]),
        "samples_evicted": {"start": start.get("samples_evicted"), "end": end.get("samples_evicted"),
                            "at_read": dict(gen._perf.samples_evicted)},
        "event_gap_samples": {"held": len(held), "in_window": sum(w0 <= s[0] < w1 for s in held),
                              "after_window": sum(s[0] >= w1 for s in held),
                              "oldest_before_window_s": round(w0 - held[0][0], 3) if held else None},
    }
    tr = run.get("trace") or {}
    if "start" in tr:
        progs = admit_spans.ring(run, "admit_prog").values()
        got = spans.planes(run)
        out["slice"] = {
            "ring_programs": sum(tr["start"] <= f["t"] < tr["stop"] and f["kind"] == "batch" for f in progs),
            "runs": len(spans.program_runs(got[0], admit_spans.ADMIT_PROGRAM)) if got else None,
            "dispatches_annotated": len(admit_spans.dispatches(run) or ()),
        }
    print("admission: " + json.dumps(out), flush=True)
    return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args()

    load_cell, load_reader = bench_run.load_cell, bench_run.load_reader

    def cell_with_admission(root: str, workload: str) -> dict:
        spec = load_cell(root, workload)
        have = {m["name"] for m in spec["per_layer"]}
        for name in (EXTRAS, *READERS):
            if name not in have:
                unit = EXTRAS if name == EXTRAS else load_reader("layer_metrics", name).UNIT
                spec["per_layer"].append({"name": name, "unit": unit})
        spec["per_layer"] += [m for m in spec["end_to_end"] if m["name"] in END_TO_END]
        return spec

    def reader(kind: str, name: str):
        if name == EXTRAS:
            return types.SimpleNamespace(read=extras)
        return load_reader("end_to_end" if name in END_TO_END else kind, name)

    bench_run.load_cell, bench_run.load_reader = cell_with_admission, reader
    return bench_run.main(["--workload", args.workload, "--seed", str(args.seed),
                           "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
