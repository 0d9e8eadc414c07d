"""Share of the window's gaps between a stream's successive text events that
had one or more admit programs dispatched between the rounds that brought
them (`samples("event_gap")`, whole). Above 5 the p95 gap holds an admit
program, below it does not: the threshold `itl_p95_ms` turns on."""
from benchmark import admit_spans

NAME, UNIT, BETTER, SOURCE = "event_gap_admit_share", "%", "lower", "program_span"
LAYER, MOVES = "admission and scheduler", "itl_p95_ms"


def read(run: dict):
    got = admit_spans.gap_split(run)
    if got is None:
        return None
    without, behind = got
    return 100.0 * len(behind) / (len(without) + len(behind))
