#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, by a sweep on the chip in one process.

    python3 benchmark/sweep.py --workload chat_open --rates 1,2,3,4,6,8 --seconds 30

Boots and warms up as run.py does, then offers the cell's traffic at each rate
for one window and prints one line a rate: the share of requests whose first
token came within the program's own limit (TPU_TARGET_TTFT_MS, 2000 ms), the
tails, the tokens a second, and whether the backlog grew (requests in flight at
the window's end against its start). The knee is the highest rate at which the
backlog does not grow and at least 90% of requests meet the limit; the cell's
file then holds 0.8 of it. Not part of a check: the builder runs it when a cell
is defined, and writes its table into PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import reduce, run, trafficgen  # noqa: E402

LIMIT_MS = 2000.0


def in_flight(records: list[dict], t: float) -> int:
    return sum(1 for r in records if reduce.clock(r) <= t and (r["done"] is None or r["done"] > t))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=3_000_000_101)
    args = ap.parse_args(argv)
    spec = run.load_cell(run.ROOT, args.workload)
    device = run.require_tpu(int(spec["cell"]["chips"]))
    from llm_mcp_tpu.utils import config as ucfg

    ucfg.enable_compile_cache()
    compiles = run.CompileEvents()
    work_dir = os.path.join(run.ROOT, ".bench_work", f"sweep.{os.getpid()}")
    os.makedirs(work_dir, exist_ok=True)
    sut = run.boot(spec["config"])
    try:
        run.warm_up(sut, spec, work_dir, compiles)
        for k, rate in enumerate(float(x) for x in args.rates.split(",")):
            traffic = dict(spec["traffic"], rate_per_s=rate)
            plan = trafficgen.make_plan(traffic, args.seed + k, args.seconds, model=sut["model"])
            pre = plan["preroll_s"]
            window = (pre, pre + args.seconds)
            before = compiles.count()
            plan.update(port=sut["port"], t_start=time.monotonic() + 0.3,
                        stop_s=window[1], timeout_s=120.0)
            recs = run.finish_loadgen(run.run_loadgen(work_dir, plan, f"rate{k}"),
                                      work_dir, f"rate{k}", 400.0)
            ttft = reduce.ttfts_ms(recs, window, 120e3)
            gaps = reduce.gaps_ms(recs, window)
            print(json.dumps({
                "rate_per_s": rate, "device": device["kind"], "requests_due": len(ttft),
                "failed": sum(1 for r in recs if not reduce.ok(r)),
                "within_limit_share": sum(1 for v in ttft if v <= LIMIT_MS) / max(1, len(ttft)),
                "ttft_p50_ms": reduce.percentile(ttft, 0.5), "ttft_p95_ms": reduce.percentile(ttft, 0.95),
                "itl_p50_ms": reduce.percentile(gaps, 0.5), "itl_p95_ms": reduce.percentile(gaps, 0.95),
                "out_tokens_per_s": reduce.out_tokens_per_s(recs, window),
                "in_flight_at_start": in_flight(recs, window[0]),
                "in_flight_at_end": in_flight(recs, window[1]),
                "late_p95_ms": reduce.percentile(reduce.late_ms(recs, window), 0.95),
                "compiled_or_loaded": compiles.count() - before,
            }), flush=True)
    finally:
        sut["srv"].shutdown()
        shutil.rmtree(work_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
