"""Executables compiled or loaded inside the window of an embedding cell.
Should be 0."""
from benchmark import counters

NAME, UNIT, BETTER, SOURCE = "window_compiles.embed", "count", "lower", "program_counter"
LAYER, MOVES = "step programs", "embeddings_per_s"


def read(run: dict):
    return counters.window_compiles(run)
