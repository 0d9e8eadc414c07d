"""What an admit program costs the streams behind it, on the host's clock:
the median of the window's gaps with one or more admit programs between their
two events less the median of those with none."""
import statistics

from benchmark import admit_spans

NAME, UNIT, BETTER, SOURCE = "event_gap_admit_ms", "ms", "lower", "program_span"
LAYER, MOVES = "step programs", "itl_p95_ms"


def read(run: dict):
    got = admit_spans.gap_split(run)
    if got is None or not got[0] or not got[1]:
        return None
    without, behind = got
    return 1e3 * (statistics.median(behind) - statistics.median(without))
