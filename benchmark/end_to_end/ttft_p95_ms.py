"""95th percentile, over requests due inside the window, of the time from when
the request was DUE to its first content delta. A failed or shed request counts
as the request timeout, over any limit."""
from benchmark import reduce

NAME, UNIT, BETTER, SOURCE = "ttft_p95_ms", "ms", "lower", "host_clock"


def read(run: dict):
    v = reduce.ttfts_ms(run["records"], run["window"], run["miss_ms"])
    if not v:
        return None
    print(f"ttft_ms: median {reduce.percentile(v, 0.5):.3f}, p95 {reduce.percentile(v, 0.95):.3f}, "
          f"n={len(v)}", flush=True)
    return reduce.percentile(v, 0.95)
