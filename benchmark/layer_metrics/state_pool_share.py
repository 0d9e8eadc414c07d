"""Bytes of the recurrent state pool that belong to live slots (seated or
mid-prefill) over the pool's bytes, mean of the window's two edges."""
from benchmark import solar_bytes

NAME, UNIT, BETTER, SOURCE = "state_pool_share", "%", "higher", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    edges = [solar_bytes.pool(run, e) for e in ("start", "end")]
    if not all(edges) or not edges[0]["bytes"]:
        return None
    return 100.0 * sum(e["live_bytes"] / e["bytes"] for e in edges) / len(edges)
