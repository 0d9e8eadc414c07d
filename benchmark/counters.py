"""Shared arithmetic of the per-layer readers: counters as the difference
between the window's edges, waterfall rows of the window's requests, and for a
reader that sets a count beside the trace's device times the same run cut to
the traced slice (`slice_of`)."""

from __future__ import annotations

from benchmark import reduce


def delta(run: dict, *path: str) -> float | None:
    """End minus start of one counter, by its path in the snapshots."""
    a, b = run["start"], run["end"]
    for key in path:
        if not isinstance(a, dict) or key not in a or key not in b:
            return None
        a, b = a[key], b[key]
    return float(b) - float(a)


def slice_of(run: dict) -> dict | None:
    """The run as the traced slice saw it: `start` and `end` are the counters
    read at the slice's two edges and `window` is the slice, on the records'
    clock. A `device_trace` reader takes the rows and the live tokens it sets
    beside the slice's device times from THIS run, so that both sides are the
    same rounds: rows averaged over the whole window against a time averaged
    over the slice read 105.5% of a roofline where the slice's rounds were
    unlike the window's (PERF.md section 6, PR 47). A `program_counter` reader
    keeps the window. None for a run without a slice: nothing to set beside a
    device time."""
    cut = run.get("slice")
    return dict(run, **cut) if cut else None


def plain_rows(cut: dict) -> float | None:
    """Mean decode rows of the PLAIN rounds the engine dispatched in the traced
    slice (`run.ring_rounds`: the flight ring's `decode` events, every round;
    the perf observatory samples one dispatch in 32, which in a slice is one or
    none). They are the rounds whose device time `decode_round_s` and
    `spans.kernel_seconds` read, and a plain round is the fuller one: no prompt
    waits while every slot is taken."""
    rows = [n for kind, n, _t in cut.get("rounds", ()) if kind == "decode"]
    return sum(rows) / len(rows) if rows else None


def window_rows(run: dict) -> list[tuple[dict, dict]]:
    """(client record, server waterfall row) of every request due inside the
    window whose row was seen, matched by the trace id the client sent."""
    rows = run["waterfall_rows"]
    return [(r, rows[r["trace"]]) for r in run["records"]
            if reduce.ok(r) and reduce.in_window(reduce.clock(r), run["window"])
            and r.get("trace") in rows]


def window_compiles(run: dict) -> float:
    """Executables built inside the window: the larger of the program's count
    of first dispatches (CompileLedger) and JAX's own count of compile
    requests, which see the same event from two sides. Should be 0."""
    ledger = delta(run, "ledger", "entries") or 0.0
    return max(ledger, float(run["window_compiles"]))


def mean_live_tokens(run: dict, samples: int = 64) -> float:
    """Cached tokens summed over the sequences in flight, averaged over the
    run's window (the traced slice, of a run `slice_of` cut to it): a request's
    context grows from its prompt to prompt + completion between its first and
    last delta."""
    w0, w1 = run["window"]
    total = 0.0
    for k in range(samples):
        t = w0 + (k + 0.5) * (w1 - w0) / samples
        for r in run["records"]:
            if not reduce.ok(r):
                continue
            a, b = reduce.stream_span(r)
            if a <= t <= b:
                total += r["prompt_tokens"] + r["completion_tokens"] * ((t - a) / (b - a) if b > a else 1.0)
    return total / samples


DECODE_PROGRAM = "jit_decode_chunk_fn"  # the engine's PLAIN decode step program, as the trace names it
# Every program that is one round of the engine's loop: the plain round, and the
# round whose first step carries queued prompts through its weight pass
# (`engine.mixed_round_fn`). A reader that counts the rounds the host served
# takes all of them; one that means a plain round's bytes or kernels keeps
# DECODE_PROGRAM.
ROUND_PROGRAMS = (DECODE_PROGRAM, "jit_mixed_round_fn")


def decode_round_s(run: dict) -> float | None:
    """Mean device seconds of one WHOLE run of the plain decode step program in
    the trace (`trace_reduce.whole_runs`: a run the slice's edge cut is no
    round's time); a mixed round is another program and is left out."""
    tr = run.get("trace_reduced")
    runs = tr["whole_runs"].get(DECODE_PROGRAM) if tr else None
    return runs[1] if runs else None
