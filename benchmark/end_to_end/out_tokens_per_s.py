"""Completion tokens streamed inside the window over its seconds, from each
request's `usage`; a request that straddles an edge counts pro rata by the
part of its first-to-last-delta interval inside."""
from benchmark import reduce

NAME, UNIT, BETTER, SOURCE = "out_tokens_per_s", "tokens/s", "higher", "host_clock"


def read(run: dict):
    return reduce.out_tokens_per_s(run["records"], run["window"])
