"""The fullest held expert's rows over the mean over the held experts, summed
over the window's decode steps and layers: 1.0 is an even load; what a grouped
product waits for is its largest group."""
from benchmark import solar_bytes

NAME, UNIT, BETTER, SOURCE = "moe_load_max_over_mean", "ratio", "lower", "program_counter"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    got, book = solar_bytes.decode_counts(run), solar_bytes.experts(run)
    pairs = sum(r[solar_bytes.PAIRS] for r in got) if got else 0.0
    if not pairs or not book.get("held"):
        return None
    return sum(r[solar_bytes.FULLEST] for r in got) * book["held"] / pairs
