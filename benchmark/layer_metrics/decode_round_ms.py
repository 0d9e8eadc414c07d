"""Mean device time of one decode round (`decode_chunk` = 4 steps in one
program), from the trace: the `XLA Modules` events of the decode step program,
which the trace names after its traced function. The perf observatory's
sampled wall is not read: with two rounds in flight its `block_until_ready`
also waits for the round before (208 ms where the trace shows 130; v5e, PR 23).
A PLAIN round (`counters.DECODE_PROGRAM`): a mixed round (`jit_mixed_round_fn`,
whose first step also carries queued prompts) is another program with another
length, left out so that this stays the time of 4 steps over the batch alone."""
from benchmark import counters

NAME, UNIT, BETTER, SOURCE = "decode_round_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "step programs", "itl_p95_ms"


def read(run: dict):
    mean_s = counters.decode_round_s(run)
    return 1e3 * mean_s if mean_s else None
