"""What the per-layer readers take from the program's own spans and names:
its host annotations in the trace (`engine.*`, `embed.*`:
`jax.profiler.TraceAnnotation`s, which land on the host plane's line of the
thread that made them), device operations by kernel name inside the runs of
one step program, and the perf observatory's timestamped samples cut by the
window. The planes are read once a run and kept in the run's dict. A program
without the annotation, the kernel name or the sample window gives nothing."""

from __future__ import annotations

import bisect

from benchmark import trace_reduce


def planes(run: dict):
    """(chips, host) of the run's trace as `trace_reduce.read_planes` gives
    them, or None for a run without a trace."""
    if "_planes" not in run:
        path = run.get("trace_path") or (
            trace_reduce.find_xplane(run["trace"]["dir"]) if run.get("trace", {}).get("dir") else None)
        run["_planes"] = trace_reduce.read_planes(path) if path else None
    return run["_planes"]


def host_seconds(host: dict, names: set[str]) -> dict[str, float]:
    """Seconds by annotation name, summed over every host line (an
    annotation's arguments, `rid=12`, are stats of the event, not part of its
    name)."""
    out: dict[str, float] = {}
    for events in host.values():
        for name, a, b in events:
            if name in names:
                out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def program_runs(chips, program: str, whole: bool = False) -> list[tuple[float, float]]:
    """[start, end) of every run of one step program, over all chips; with
    `whole`, less the runs the slice's edges cut (`trace_reduce.whole_runs`)."""
    return sorted((a, b) for _i, _ops, mods in chips
                  for name, a, b in (trace_reduce.whole_runs(mods) if whole else mods)
                  if trace_reduce.program_name(name) == program)


def kernel_seconds(chips, program: str, prefix: str,
                   whole: bool = True) -> tuple[float, int, set[str]]:
    """Device seconds of the leaf operations whose HLO name starts with
    `prefix`, inside WHOLE runs of `program` (a run the slice's edge cut holds
    part of its kernels and would count as one; `whole=False` takes those too,
    for a look at a recorded cut); the number of those runs; and the names
    found."""
    runs = program_runs(chips, program, whole)
    starts = [a for a, _ in runs]
    total, found = 0.0, set()
    for _i, ops, _mods in chips:
        for text, a, b in ops:
            if prefix not in text[:80]:  # the name leads the HLO text
                continue
            name = trace_reduce.short_name(text)
            if not name.startswith(prefix):
                continue
            k = bisect.bisect_right(starts, a) - 1
            if k >= 0 and b <= runs[k][1] + 1e3:  # a program may end a little after its last op
                total += (b - a) / 1e9
                found.add(trace_reduce.base_name(name))
    return total, len(runs), found


def decode_attn_s(run: dict) -> float | None:
    """Device seconds a run of the decode step program spends in the kernels
    named `decode_attn*` (the whole / blocked / paged arms sit in `lax.cond`
    branches and only the arm taken runs: they are summed)."""
    from benchmark import counters

    got = planes(run)
    if got is None:
        return None
    total, rounds, found = kernel_seconds(got[0], counters.DECODE_PROGRAM, "decode_attn")
    return total / rounds if found and rounds else None


def window_samples(run: dict, kind: str) -> list[float]:
    """Seconds of the perf observatory's samples of one kind stamped inside
    the window."""
    samples = getattr(run["sut"]["gen"]._perf, "samples", None)
    w0, w1 = run["window_abs"]
    return [v for t, v in samples(kind) if w0 <= t < w1] if samples else []
