"""p95 of the gap between a stream's successive text events as the engine
puts them on its queue, over the events put inside the window. Whole gaps,
one a round and stream (`engine_itl_p95_ms` spreads a gap over the round's
tokens): what a reader would see if the HTTP handler added nothing."""
from benchmark import reduce, spans

NAME, UNIT, BETTER, SOURCE = "engine_event_gap_p95_ms", "ms", "lower", "program_span"
LAYER, MOVES = "step programs", "itl_p95_ms"


def read(run: dict):
    v = spans.window_samples(run, "event_gap")
    return 1e3 * reduce.percentile(v, 0.95) if v else None
