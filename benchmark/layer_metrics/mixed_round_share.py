"""Rounds of the window that carried prompts over all its rounds:
`rounds` of the `mixed_*` rows of `perf_stats()["rounds"]["by_program"]` over
every row's, end minus start, every round fetched in the window. A mixed round
costs a surcharge over a plain one (`mixed_round_ms` less `decode_round_ms`),
so tokens a second fall by the surcharge's share of a round times this. None
from a program without the account, or a window without rounds."""
from benchmark import round_account

NAME, UNIT, BETTER, SOURCE = "mixed_round_share", "%", "lower", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    rows = round_account.by_program(run)
    total = sum(r["rounds"] for r in rows.values()) if rows else 0
    if not total:
        return None
    return 100.0 * round_account.mixed(rows) / total
