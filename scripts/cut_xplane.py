#!/usr/bin/env python3
"""Cut a few milliseconds out of a profiler trace into a text-proto fixture
that `jax.profiler.ProfileData.from_text_proto` reads back.

    python scripts/cut_xplane.py <trace.xplane.pb[.gz]> <out.xspace.txt> --at-ms 1204.5 --ms 12

Keeps, of the first TPU plane, the `XLA Modules` and `XLA Ops` lines, and of
the host plane every line with an event in the cut (or only the lines that
hold an event whose name starts with one of `--host-prefix`). Events across
an edge are clipped to it; times restart at 1 ms; stats are dropped (the
benchmark's reduction reads names and times only). `--at-ms` counts from the
first device operation of the trace; `breakdown.idle_gaps` of the traced run
says which gaps a cut should hold.
"""

from __future__ import annotations

import argparse
import gzip
import sys


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n") + '"'


def cut_line(events, a_ns: float, b_ns: float, ids: dict[str, int]):
    out = []
    for e in events:
        s, t = e.start_ns, e.start_ns + e.duration_ns
        if t <= a_ns or s >= b_ns or e.duration_ns <= 0:
            continue
        s, t = max(s, a_ns), min(t, b_ns)
        mid = ids.setdefault(e.name, len(ids) + 1)
        out.append((mid, round((s - a_ns) * 1e3), round((t - s) * 1e3)))
    return out


def plane_text(pid: int, name: str, lines: list[tuple[str, list]], ids: dict[str, int]) -> list[str]:
    out = ["planes {", f"  id: {pid}", f"  name: {quote(name)}"]
    for k, (lname, events) in enumerate(lines, 1):
        out += ["  lines {", f"    id: {k}", f"    name: {quote(lname)}", "    timestamp_ns: 1000000"]
        out += [f"    events {{ metadata_id: {m} offset_ps: {o} duration_ps: {d} }}" for m, o, d in events]
        out.append("  }")
    out += [f"  event_metadata {{ key: {i} value {{ id: {i} name: {quote(n)} }} }}" for n, i in ids.items()]
    out.append("}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--at-ms", type=float, default=0.0)
    ap.add_argument("--ms", type=float, default=12.0)
    ap.add_argument("--host-prefix", action="append", default=[],
                    help="keep only host lines with an event whose name starts so (repeatable)")
    args = ap.parse_args()

    data = load(args.trace)
    tpu = next(p for p in data.planes if p.name.startswith("/device:TPU:"))
    dev = {ln.name: list(ln.events) for ln in tpu.lines if ln.name in ("XLA Modules", "XLA Ops")}
    t0 = min(e.start_ns for e in dev["XLA Ops"])
    a, b = t0 + args.at_ms * 1e6, t0 + (args.at_ms + args.ms) * 1e6
    ids: dict[str, int] = {}
    text = plane_text(1, tpu.name, [(n, cut_line(dev[n], a, b, ids)) for n in ("XLA Modules", "XLA Ops")], ids)
    host = next((p for p in data.planes if p.name == "/host:CPU"), None)
    if host is not None:
        ids, lines = {}, []
        for ln in host.lines:
            inside = [e for e in ln.events if e.start_ns < b and e.start_ns + e.duration_ns > a]
            if not inside or (args.host_prefix and not any(
                    e.name.startswith(tuple(args.host_prefix)) for e in inside)):
                continue
            lines.append((ln.name, cut_line(inside, a, b, ids)))
        text += plane_text(2, host.name, lines, ids)
    with open(args.out, "w") as f:
        f.write("\n".join(text) + "\n")
    print(f"{args.out}: {args.ms} ms from {args.at_ms} ms, {sum(len(ln[1]) for ln in lines) if host else 0} host events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
