"""Milliseconds a slot stood empty between two requests, free to seated, mean
over the vacancies a batched admission closed in the window: all three parts
of the program's `admit.vacancy` (cooling behind the rounds in flight, no
request there, a request queued and no admit program dispatched yet)."""
from benchmark import admit_spans

NAME, UNIT, BETTER, SOURCE = "slot_vacant_ms", "ms", "lower", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    return admit_spans.vacancy_ms(run, "cooling_s", "no_request_s", "queued_s")
