#!/usr/bin/env python3
"""What a step program is made of: a kept profiler trace, split by program and
by operation.

    chiprun -- env BENCH_KEEP_TRACE=chiprun_out/t python3 benchmark/run.py \
        --workload kexaone_reason_closed --seed <n> --seconds 40 --trace 1
    python scripts/program_ops.py chiprun_out/t/kexaone_reason_closed.xplane.pb[.gz] \
        [--program jit_admit_fn] [--top 14]

The result line's `device_ops` sums an operation over the whole slice, whichever
program ran it. This reads the same two lines of the device plane (`XLA
Modules`, `XLA Ops`; benchmark/trace_reduce.py says what they hold) and gives,
for every program name and every distinct duration class of it (two admit
shapes are one name: runs are grouped where their durations lie within 12% of
each other), the runs, the mean ms a run, and the leaf operations inside a run
by kind and output shape, in ms a run. Leaf operations only: a `while` spans
its body's. The groups on top are by the patterns of `GROUPS` on an operation's
whole HLO text (its type and its operands' types), first match wins, and each
line says which took it. The defaults know the kernels by name and the
compiler's matmul fusions; what the expert layer does beside its products (the
gather, the weighing, the sum of a row's pairs) has shapes that other
operations share (8,192 pairs a prompt and 8,192 = 64 heads x 128 at
K-EXAONE's widths) and lands under `the rest` unless `--group "expert
layer=<regex>"` names the run's own pair dimension: PERF.md section 6, PR 44,
was summed by hand from the lines below the groups."""

from __future__ import annotations

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace_reduce import (  # noqa: E402
    CONTAINERS, DEVICE_PLANE, HLO_TEXT, MODULES_LINE, MOSAIC_TARGET, OPS_LINE, base_name, short_name)
from cut_xplane import load  # noqa: E402  (scripts/: this file's own directory)

# group -> pattern on the operation's HLO text; first match wins
GROUPS = {
    "attention": r"flash_prefill|decode_attn|append_kv",
    "expert layer": r"%grouped_|%sort",
    "dense products": r"convolution|kind=kOutput",
}


def op_key(text: str) -> str:
    m = HLO_TEXT.match(text)
    kind = base_name(short_name(text))
    if MOSAIC_TARGET in text:
        kind += " (pallas)"
    return f"{kind} {m.group('type') if m else ''}".strip()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--program", default="", help="only programs whose name holds this")
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--group", action="append", default=[], metavar="NAME=REGEX")
    args = ap.parse_args()
    groups = {**GROUPS, **dict(g.split("=", 1) for g in args.group)}
    pats = [(g, re.compile(p)) for g, p in groups.items()]

    def group_of(text: str) -> str:
        return next((g for g, pat in pats if pat.search(text)), "the rest")

    data = load(args.trace)
    tpu = next(p for p in data.planes if DEVICE_PLANE.match(p.name))
    lines = {ln.name: sorted(ln.events, key=lambda e: e.start_ns) for ln in tpu.lines
             if ln.name in (OPS_LINE, MODULES_LINE)}
    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in lines[OPS_LINE]
           if not CONTAINERS.match(short_name(e.name))]
    classes: dict[str, list[list]] = {}  # program -> [[ms summed, runs, {(operation, group): ms summed}]]
    k = 0
    for m in lines[MODULES_LINE]:
        name = re.sub(r"\(.*", "", m.name)
        if args.program not in name:
            continue
        a, b = m.start_ns, m.start_ns + m.duration_ns
        while k < len(ops) and ops[k][0] < a:
            k += 1
        inside, j = {}, k
        while j < len(ops) and ops[j][0] < b:
            key = (op_key(ops[j][2]), group_of(ops[j][2]))
            inside[key] = inside.get(key, 0.0) + (min(ops[j][1], b) - ops[j][0]) / 1e6
            j += 1
        ms = m.duration_ns / 1e6
        for c in classes.setdefault(name, []):
            if abs(c[0] / c[1] - ms) <= 0.12 * ms:
                c[0] += ms
                c[1] += 1
                for key, v in inside.items():
                    c[2][key] = c[2].get(key, 0.0) + v
                break
        else:
            classes[name].append([ms, 1, dict(inside)])
    for name, cs in classes.items():
        for total, runs, inside in sorted(cs, key=lambda c: -c[0]):
            print(f"{name}: {runs} runs at {total / runs:.3f} ms")
            grouped: dict[str, float] = {}
            for (_, g), v in inside.items():
                grouped[g] = grouped.get(g, 0.0) + v / runs
            for g, v in sorted(grouped.items(), key=lambda kv: -kv[1]):
                print(f"    [{g}] {v:.3f} ms a run")
            for (op, g), v in sorted(inside.items(), key=lambda kv: -kv[1])[:args.top]:
                print(f"    {v / runs:8.3f} ms  {op}  [{g}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
