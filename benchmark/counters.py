"""Shared arithmetic of the per-layer readers: counters as the difference
between the window's edges, waterfall rows of the window's requests."""

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
    window: a request's context grows from its prompt to prompt + completion
    between its first and last delta."""
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
    """Mean device seconds of one run of the plain decode step program in the
    trace; a mixed round is another program and is left out."""
    tr = run.get("trace_reduced")
    runs = tr["module_runs"].get(DECODE_PROGRAM) if tr else None
    return runs[1] if runs else None
