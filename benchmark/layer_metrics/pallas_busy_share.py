"""Device time inside Mosaic (Pallas) custom calls over device busy time,
from the profiler's trace of the middle of the window."""

NAME, UNIT, BETTER, SOURCE = "pallas_busy_share", "%", "lower", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    tr = run.get("trace_reduced")
    return 100.0 * tr["mosaic_s"] / tr["busy_s"] if tr and tr["busy_s"] else None
