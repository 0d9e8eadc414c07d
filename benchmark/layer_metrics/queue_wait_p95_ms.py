"""95th percentile over the window's requests of admit_wait + prefill_queue
from the engine's latency waterfall: time a request waited for a slot and for
its turn in the token budget."""
from benchmark import counters, reduce

NAME, UNIT, BETTER, SOURCE = "queue_wait_p95_ms", "ms", "lower", "program_span"
LAYER, MOVES = "admission and scheduler", "ttft_p95_ms"


def read(run: dict):
    v = [row["admit_wait_ms"] + row["prefill_queue_ms"] for _r, row in counters.window_rows(run)]
    return reduce.percentile(v, 0.95) if v else None
