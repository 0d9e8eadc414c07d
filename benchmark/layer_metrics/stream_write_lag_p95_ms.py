"""p95 of the time from the engine's put of a text event to the moment the
HTTP handler has written that event's SSE frame to the socket, over the
frames written inside the window: the handler's share of a reader's gap."""
from benchmark import reduce, spans

NAME, UNIT, BETTER, SOURCE = "stream_write_lag_p95_ms", "ms", "lower", "program_span"
LAYER, MOVES = "HTTP and router", "itl_p95_ms"


def read(run: dict):
    v = spans.window_samples(run, "stream_lag")
    return 1e3 * reduce.percentile(v, 0.95) if v else None
