"""Token-expert pairs that landed on the experts held here over rows routed,
over the window's decode steps and layers, as a share of what the published
router gives this share in expectation (experts a token x held / published):
1.0 says the share is the router's own, and not a router cut to the held."""
from benchmark import solar_bytes

NAME, UNIT, BETTER, SOURCE = "moe_local_pairs_per_row", "ratio", "higher", "program_counter"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    got, book = solar_bytes.decode_counts(run), solar_bytes.experts(run)
    if not got or not book.get("router"):
        return None
    cfg = run["sut"]["gen"].cfg
    expected = cfg.experts_per_tok * book["held"] / book["router"]
    pairs = sum(r[solar_bytes.PAIRS] for r in got) / sum(r[solar_bytes.ROWS] for r in got)
    return pairs / expected
