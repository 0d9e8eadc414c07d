"""What the admission readers share (`admit_program_share`, `admit_rows_mean`,
`admit_pad_waste_pct`, `event_gap_admit_share`, `event_gap_admit_ms`,
`slot_vacant_ms`, `slot_vacant_queued_ms`): the window's difference of the
program's `perf_stats()["admit"]` sums, its `event_gap` samples with what the
device ran between the two events, and the runs of the admit program in the
trace beside the `engine.admit.dispatch` annotations that caused them. A
program without the block, the sample fields or the annotation gives None."""

from __future__ import annotations

import statistics

from benchmark import counters, spans, trace_reduce

ADMIT_PROGRAM = "jit_admit_fn"  # the engine's batched admission, as the trace names it
DISPATCH_SPAN = "engine.admit.dispatch"  # its dispatch on the host plane, argument `aid`
READ_NEAR_NS = 3e6  # a blocked read returns this close to its program's end


def admit_delta(run: dict, *path: str) -> float | None:
    """End minus start of one sum of the program's `admit` block."""
    return counters.delta(run, "perf", "admit", *path)


def admit_ratio(run: dict, over: tuple[str, ...], under: tuple[str, ...]) -> float | None:
    """The window's difference of one sum over another's; None without either."""
    a, b = admit_delta(run, *over), admit_delta(run, *under)
    return a / b if a is not None and b else None


def vacancy_ms(run: dict, *parts: str) -> float | None:
    """Milliseconds a vacancy of the window, of the parts named."""
    count = admit_delta(run, "vacancy", "count")
    seconds = [admit_delta(run, "vacancy", p) for p in parts]
    if not count or None in seconds:
        return None
    return 1e3 * sum(seconds) / count


def gap_samples(run: dict) -> list[tuple] | None:
    """The window's `event_gap` samples as the program wrote them, (t,
    seconds, admit programs between the two events, their padded tokens).
    None where the program writes no such fields, and where the window lost
    samples: `samples_evicted` rose since the window's start and the oldest
    sample still held is younger than the window's start."""
    perf = run["sut"]["gen"]._perf
    evicted = getattr(perf, "samples_evicted", None)
    before = run["start"].get("perf", {}).get("samples_evicted")
    if evicted is None or before is None:
        return None
    got = perf.samples("event_gap", whole=True)
    w0, w1 = run["window_abs"]
    if evicted["event_gap"] > before["event_gap"] and (not got or got[0][0] >= w0):
        return None
    return [s for s in got if w0 <= s[0] < w1]


def gap_split(run: dict) -> tuple[list[float], list[float]] | None:
    """Seconds of the window's gaps with no admit program between the two
    events, and of those with one or more."""
    got = gap_samples(run)
    if not got:
        return None
    return [s[1] for s in got if s[2] < 1], [s[1] for s in got if s[2] >= 1]


def dispatches(run: dict) -> list[tuple[int, float, float]] | None:
    """(aid, start_ns, end_ns) of every `engine.admit.dispatch` annotation in
    the run's trace, in order; None for a run without a trace. The planes as
    `spans.planes` reads them hold names and times; the argument is a stat
    of the event, so the file is read once more."""
    if "_admit_dispatches" not in run:
        path = run.get("trace_path") or (
            trace_reduce.find_xplane(run["trace"]["dir"]) if run.get("trace", {}).get("dir") else None)
        run["_admit_dispatches"] = _read_dispatches(path) if path else None
    return run["_admit_dispatches"]


def _read_dispatches(path: str) -> list[tuple[int, float, float]]:
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == DISPATCH_SPAN:
                    aid = dict(e.stats).get("aid")
                    if aid is not None:
                        out.append((int(aid), e.start_ns, e.start_ns + e.duration_ns))
    return sorted(out, key=lambda d: d[1])


def admit_runs(run: dict) -> tuple[list[tuple[float, float]], list[tuple[int, float, float]]] | None:
    """The runs of the admit program in the slice and the dispatches
    annotated in it, where they agree in number to within the two at the
    slice's edges (a run whose dispatch came before the slice, a dispatch
    whose run came after it). None otherwise: a program without the
    annotation, or a trace the two cannot both be read from."""
    got = spans.planes(run)
    disp = dispatches(run)
    if got is None or not disp:
        return None
    runs = spans.program_runs(got[0], ADMIT_PROGRAM)
    if not runs or abs(len(runs) - len(disp)) > 2:
        return None
    return runs, disp


def ring(run: dict, etype: str) -> dict[int, dict]:
    """The flight ring's events of one kind that carry an `aid`, by it."""
    events = run["sut"]["gen"]._flight.snapshot(etype=etype)
    return {f["aid"]: f for f in (e["fields"] or {} for e in events) if "aid" in f}


def run_aids(run: dict, runs: list, disp: list, progs: dict[int, dict]) -> dict[int, int]:
    """{index of a run in `runs`: the aid of the admission it is}. The device
    runs admissions in the order they were dispatched, so one run whose aid is
    known places them all. A dispatch's ring event is stamped
    time.monotonic() as its annotation closes, which puts the ring's clock on
    the trace's; a read that had to wait for the device (`admit_read`,
    `blocked`) returns as its program ends. Every such read that finds a run
    ending beside it must name the same shift, or nothing is placed. `progs`
    is the ring's `admit_prog` events by aid."""
    reads = ring(run, "admit_read")
    offsets = [progs[aid]["t"] * 1e9 - end for aid, _a, end in disp if aid in progs]
    if not offsets:
        return {}
    offset = statistics.median(offsets)
    ends = [b for _a, b in runs]
    shifts = set()
    for aid, read in reads.items():
        if not read.get("blocked"):
            continue
        at = read["t"] * 1e9 - offset
        k = min(range(len(ends)), key=lambda i: abs(ends[i] - at))
        if abs(ends[k] - at) <= READ_NEAR_NS:
            shifts.add(aid - k)
    if len(shifts) != 1:
        return {}
    shift = shifts.pop()
    return {k: k + shift for k in range(len(runs))}


def runs_by_shape(run: dict, runs: list, disp: list) -> dict[str, list[float]]:
    """Device milliseconds of each run of the admit program in the slice
    (`admit_runs`), by the shape its ring event states ("rows_padded:bucket",
    "?" for a run that cannot be placed or whose event the ring no longer
    holds)."""
    progs = ring(run, "admit_prog")
    aids = run_aids(run, runs, disp, progs)
    out: dict[str, list[float]] = {}
    for k, (a, b) in enumerate(runs):
        f = progs.get(aids.get(k, -1))
        shape = f"{f['rows_padded']}:{f['bucket']}" if f else "?"
        out.setdefault(shape, []).append((b - a) / 1e6)
    return out
