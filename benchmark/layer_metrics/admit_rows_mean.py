"""Prompts an admit program carried, mean over the window: the program's
`admit.prompts` over `admit.programs`, end minus start. Every program reads
all the weights whatever it carries, so fuller programs are fewer."""
from benchmark import admit_spans

NAME, UNIT, BETTER, SOURCE = "admit_rows_mean", "count", "higher", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    return admit_spans.admit_ratio(run, ("prompts",), ("programs",))
