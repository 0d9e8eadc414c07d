"""Responses with status 429 among the window's requests."""
from benchmark import reduce

NAME, UNIT, BETTER, SOURCE = "shed_429", "count", "lower", "program_counter"
LAYER, MOVES = "HTTP and router", "ttft_p95_ms"


def read(run: dict):
    return float(sum(1 for r in run["records"]
                     if r.get("status") == 429 and reduce.in_window(reduce.clock(r), run["window"])))
