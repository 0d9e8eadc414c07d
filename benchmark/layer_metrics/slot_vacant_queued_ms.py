"""The engine's own part of a slot's empty time: milliseconds a vacancy in
which the slot was cool, its next request already queued, and no admit
program dispatched yet (`admit.vacancy.queued_s` over `.count`)."""
from benchmark import admit_spans

NAME, UNIT, BETTER, SOURCE = "slot_vacant_queued_ms", "ms", "lower", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    return admit_spans.vacancy_ms(run, "queued_s")
