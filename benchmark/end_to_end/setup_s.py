"""Process start to the start of the measured window: engine, weights from the
seed, server, warm-up traffic, the wait for the warm-up zoo and the pre-roll.
The first run in a checkout also compiles."""

NAME, UNIT, BETTER, SOURCE = "setup_s", "s", "lower", "host_clock"


def read(run: dict):
    return run["setup_s"]
