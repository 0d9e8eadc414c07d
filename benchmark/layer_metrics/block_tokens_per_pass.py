"""Tokens a row gains a forward pass of a block round: the positions the
denoising passes filled a row (`unmasked` over `rows`: a block's L less what a
first block held of its prompt) over the passes a round ran, denoising and
commit (`passes + commits` over `rounds`), by the program's own counters
(`perf_stats()["blocks"]`) over the window. 0.8 where every block of 4 takes 4
passes and a commit; 2.0 where one pass fills it (a greedy request). What the
mechanism yields, before what the scheduler loses (rows past their EOS or
`max_tokens`: `decode_token_yield`)."""
from benchmark import sdar_bytes

NAME, UNIT, BETTER, SOURCE = "block_tokens_per_pass", "tokens", "higher", "program_counter"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    got = sdar_bytes.blocks(run)
    if not got or not got["rows"] or not (got["passes"] + got["commits"]):
        return None
    return (got["unmasked"] / got["rows"]) / ((got["passes"] + got["commits"]) / got["rounds"])
