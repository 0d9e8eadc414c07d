"""Input texts embedded inside the window over its seconds; a request that
straddles an edge counts pro rata by the part of its sent-to-answered interval
inside."""
from benchmark import reduce

NAME, UNIT, BETTER, SOURCE = "embeddings_per_s", "1/s", "higher", "host_clock"


def read(run: dict):
    return reduce.embeddings_per_s(run["records"], run["window"])
