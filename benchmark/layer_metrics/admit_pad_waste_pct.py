"""Share of the tokens the window's admit programs computed that were padding:
1 - `admit.true_tokens` / `admit.padded_tokens`, end minus start. Both pads
count: rows to the next power of two, lengths to the prompt bucket."""
from benchmark import admit_spans

NAME, UNIT, BETTER, SOURCE = "admit_pad_waste_pct", "%", "lower", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    true = admit_spans.admit_ratio(run, ("true_tokens",), ("padded_tokens",))
    return None if true is None else 100.0 * (1.0 - true)
