"""Distinct held experts a decode step and layer touched, over the experts
held, over the window's decode steps and expert layers (`ExpertCounts`): 100
says a step reads every bank, whatever its rows. With 64 rows x 4 choices over
32 experts it is about 100; it moves when a later change moves the batch or the
experts held."""
from benchmark import solar_bytes

NAME, UNIT, BETTER, SOURCE = "moe_experts_touched_share", "%", "lower", "program_counter"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    got, book = solar_bytes.decode_counts(run), solar_bytes.experts(run)
    if not got or not book or not book.get("held"):
        return None
    calls = sum(r[solar_bytes.CALLS] for r in got)
    return 100.0 * sum(r[solar_bytes.TOUCHED] for r in got) / (calls * book["held"])
