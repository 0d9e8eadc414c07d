"""Median over the window's requests of the client's time from sending to the
first delta, minus the same request's admit_wait + prefill_queue +
prefill_compute in the engine's latency waterfall: what HTTP, the router and
the SSE writer add."""
from benchmark import counters, reduce

NAME, UNIT, BETTER, SOURCE = "http_overhead_ms", "ms", "lower", "program_span"
LAYER, MOVES = "HTTP and router", "ttft_p95_ms"


def read(run: dict):
    v = [(reduce.stream_span(r)[0] - r["sent"]) * 1e3
         - (row["admit_wait_ms"] + row["prefill_queue_ms"] + row["prefill_compute_ms"])
         for r, row in counters.window_rows(run)]
    return reduce.percentile(v, 0.5) if v else None
