"""Executables compiled, loaded or first dispatched inside the window of a
chat cell. Should be 0: each one is a stall of seconds in some stream."""
from benchmark import counters

NAME, UNIT, BETTER, SOURCE = "window_compiles.serve", "count", "lower", "program_counter"
LAYER, MOVES = "step programs", "itl_p95_ms"


def read(run: dict):
    return counters.window_compiles(run)
