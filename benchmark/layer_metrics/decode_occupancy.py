"""Rows in a sampled decode round over the engine's slots: tokens over
decode_chunk over samples of the perf observatory's `decode` phase."""
from benchmark import counters

NAME, UNIT, BETTER, SOURCE = "decode_occupancy", "%", "higher", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"
PHASES = ("decode", "fused", "fused_rag")


def read(run: dict):
    gen = run["sut"]["gen"]
    tokens = sum(counters.delta(run, "perf", "phases", p, "tokens") or 0.0 for p in PHASES)
    samples = sum(counters.delta(run, "perf", "phases", p, "samples") or 0.0 for p in PHASES)
    if not samples:
        return None
    return 100.0 * tokens / gen.decode_chunk / samples / gen.max_slots
