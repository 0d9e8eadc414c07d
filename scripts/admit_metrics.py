#!/usr/bin/env python3
"""One traced run of a generation cell with the seven admission readers beside
the cell's own per-layer metrics, through the harness itself.

    chiprun -- python3 scripts/admit_metrics.py --workload olmo_hybrid_decode_closed --seed <n> [--trace 0]

The readers (`benchmark/layer_metrics/admit_*.py`, `event_gap_admit_*.py`,
`slot_vacant*.py`) take any generation cell; until `BENCHMARK.json` lists them
(PERF.md §7: a `benchmark` PR's), this is how a builder reads them. The result
line is `benchmark/run.py`'s with the seven added, and with the traced run's own
`itl_p95_ms` and `out_tokens_per_s` (beside an untraced run's they are what the
tracing costs); the line `admission:` before it holds what no metric reads:
the window's difference of `perf_stats()["admit"]` whole (`by_shape`, `held_by`,
the vacancy's three parts; since PR 38 `rides`, the batches that rode a decode
round, and `own` / `own_prompts`, those that took a program of their own by
reason, with `engaged_share` = riding prompts over all), `samples_evicted` and
how many `event_gap` samples the window and the drain put, the ring's admit
programs inside the profiler's slice beside the runs of `jit_admit_fn` in it,
the window's difference of the engine's account of rounds (`rounds`, since
PR 54: `perf_stats()["rounds"]`, every round of the WINDOW by step program with
its rounds, told rounds and device ms, the mixed round by its rung, and the
stalls of the in-flight queue by the loop's phase; nothing from a program
without the account), the rows of EVERY
round of the window over the slots (`occupancy_ring`, from the ring's `emit`
events: `decode_occupancy` reads one dispatch in 32 of a phase, about two
dozen a window), the warm-up plan's seconds by phase (`plan`) and what the
int8 decode-attention arm streamed (`decode_attn`, since PR 55: the window's
difference of `perf_stats()["decode_attn"]` with `block_tokens`,
`heads_abreast` and, since PR 58, `positions_abreast` beside it: how many
positions share a row of the latent pair's int8 rope keys, 1 for any other cache).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
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


def wall_shift() -> float:
    """What a wall-clock stamp (the ring's `ts`, `_ttft_window`) is ahead of
    the monotonic clock the window's edges are on."""
    return time.time() - time.monotonic()


def first_token_ms(gen, w0: float, w1: float) -> dict | None:
    """The engine's own first-token samples of the window (arrival to the
    first token's emission, `_ttft_window`: wall-clock stamps, so the window's
    edges are moved onto that clock)."""
    shift = wall_shift()
    got = sorted(ms for t, ms in list(gen._ttft_window) if w0 <= t - shift < w1)
    if not got:
        return None
    return {"n": len(got), "p50": round(got[len(got) // 2], 2),
            "p95": round(got[min(len(got) - 1, int(len(got) * 0.95))], 2),
            "mean": round(sum(got) / len(got), 2)}


def rounds(run: dict, apart_s: float = 0.015) -> dict | None:
    """The window's difference of `perf_stats()["rounds"]`: rounds, told rounds
    and device ms by program, the stalls by phase, and beside them the rounds
    of the window by the CLIENTS' records (`client_rounds`: the bursts of
    content deltas over all streams; a round's emission reaches its streams
    within milliseconds and rounds are 36 ms and more apart). None from a
    program without the account."""
    from benchmark import round_account

    rows, st = round_account.by_program(run), round_account.stalls(run)
    if rows is None or st is None:
        return None
    out = {"by_program": {prog: {**r, "ms": round_account.ms(r)} for prog, r in sorted(rows.items())}, "stalls": st}
    if "records" in run:
        w0, w1 = run["window"]
        ts = sorted(t for r in run["records"] for t in (r.get("events") or []) if w0 <= t < w1)
        out["client_rounds"] = sum(b - a > apart_s for a, b in zip(ts, ts[1:])) + bool(ts)
    return out


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
    win = out["window"]
    rode = (win.get("rides") or {}).get("prompts", 0)
    own = sum((win.get("own_prompts") or {}).values())
    out["engaged_share"] = round(rode / (rode + own), 4) if rode + own else None
    out["first_token_ms"] = first_token_ms(gen, w0, w1)
    # every round of the window, where `decode_occupancy` reads one dispatch in 32
    emits = [f for f in (e["fields"] or {} for e in gen._flight.snapshot(etype="emit"))
             if "t" in f and w0 <= f["t"] < w1]
    out["occupancy_ring"] = {
        "rounds": len(emits),
        "pct": round(100.0 * sum(f["rows"] for f in emits) / (len(emits) * gen.max_slots), 3),
    } if emits else None
    plan = (gen.warmup_stats().get("plan") or [])
    out["plan"] = {
        "steps": len(plan),
        "wall_s_by_phase": {
            ph: round(sum(st.get("wall_s") or 0.0 for st in plan if st["phase"] == ph), 2)
            for ph in sorted({st["phase"] for st in plan})},
        "mixed": [{k: st.get(k) for k in ("key", "status", "wall_s")}
                  for st in plan if st["phase"] == "mixed"],
    }
    got = rounds(run)
    if got is not None:
        out["rounds"] = got
    # what the int8 decode-attention arm streamed over the window (`AttnStream`):
    # the block in force, the heads a row of the cache holds (PR 55) and the
    # positions a row of the latent pair's rope keys holds (PR 58; None from a
    # program without the key), positions live over positions fetched
    a0, a1 = start.get("decode_attn") or {}, end.get("decode_attn")
    if a1:
        d = {k: a1[k] - a0.get(k, 0) for k in ("steps", "tokens_streamed", "tokens_live")}
        out["decode_attn"] = {
            "block_tokens": a1["block_tokens"], "heads_abreast": a1.get("heads_abreast"),
            "positions_abreast": a1.get("positions_abreast"), **d,
            "live_over_streamed": round(d["tokens_live"] / d["tokens_streamed"], 4)
            if d["tokens_streamed"] else None}
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1,
                    help="0: an UNTRACED run (the end-to-end metrics) with the `admission:` line before it")
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
        spec["end_to_end"].append({"name": EXTRAS, "unit": EXTRAS})  # what an untraced run reads
        return spec

    def reader(kind: str, name: str):
        if name == EXTRAS:
            return types.SimpleNamespace(read=extras)
        return load_reader("end_to_end" if name in END_TO_END else kind, name)

    bench_run.load_cell, bench_run.load_reader = cell_with_admission, reader
    return bench_run.main(["--workload", args.workload, "--seed", str(args.seed),
                           "--seconds", str(args.seconds), "--trace", str(args.trace)])


if __name__ == "__main__":
    sys.exit(main())
