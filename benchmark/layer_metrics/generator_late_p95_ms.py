"""How late the load generator sent the window's requests, by its own clock:
a starved generator must not read as a fast server."""
from benchmark import reduce

NAME, UNIT, BETTER, SOURCE = "generator_late_p95_ms", "ms", "lower", "host_clock"
LAYER, MOVES = "load generator", "ttft_p95_ms"


def read(run: dict):
    v = reduce.late_ms(run["records"], run["window"])
    return reduce.percentile(v, 0.95) if v else None
