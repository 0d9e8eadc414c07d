"""95th percentile over all gaps between successive content-bearing SSE events
of all streams, counted where the later event fell inside the window: what a
reader watching the stream sees. With the byte tokenizer an event holds 1-4
tokens; the engine's per-token figure stands beside it as a layer metric."""
from benchmark import reduce

NAME, UNIT, BETTER, SOURCE = "itl_p95_ms", "ms", "lower", "host_clock"


def read(run: dict):
    v = reduce.gaps_ms(run["records"], run["window"])
    if not v:
        return None
    print(f"itl_ms: median {reduce.percentile(v, 0.5):.3f}, p95 {reduce.percentile(v, 0.95):.3f}, "
          f"n={len(v)}", flush=True)
    return reduce.percentile(v, 0.95)
