"""The engine's own rolling per-token inter-token latency, 95th percentile, at
the window's end (not drained: drain_itl belongs to the metrics bridge)."""

NAME, UNIT, BETTER, SOURCE = "engine_itl_p95_ms", "ms", "lower", "program_span"
LAYER, MOVES = "step programs", "itl_p95_ms"


def read(run: dict):
    itl = run["end"].get("perf", {}).get("itl", {})
    return itl.get("p95_ms") if itl.get("samples") else None
