"""Prefill compute a thousand true prompt tokens: the waterfall's cumulative
prefill_compute seconds over the scheduler's true prefill tokens, both as
differences over the window."""
from benchmark import counters

NAME, UNIT, BETTER, SOURCE = "prefill_ms_per_ktok", "ms", "lower", "program_span"
LAYER, MOVES = "step programs", "ttft_p95_ms"


def read(run: dict):
    s = counters.delta(run, "waterfall", "stage_s", "prefill_compute")
    tok = counters.delta(run, "scheduler", "prefill_true_tokens")
    return 1e6 * s / tok if s is not None and tok else None
