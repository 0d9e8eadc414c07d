"""Device milliseconds a decode round spends in the compiler's `copy*`
operations: the `XLA Ops` events whose HLO name starts with `copy` inside runs
of the decode step program, over the number of those runs. A copy moves bytes
the step's arithmetic never asked for: an operand re-laid out for a kernel and
laid back. In `granite_decode_closed` it is the int8 KV cache of heads of 64,
which the chip lays out with positions minor and the attention kernels read
with the head's 64 values minor (`copy s8[4,64,17,1024,64]`, PERF.md section 7):
what a cache layout of its own for narrow heads would take off the round.
None where the slice holds no such operation inside a round."""
from benchmark import counters, spans

NAME, UNIT, BETTER, SOURCE = "decode_copy_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    got = spans.planes(run)
    if got is None:
        return None
    total, rounds, found = spans.kernel_seconds(got[0], counters.DECODE_PROGRAM, "copy")
    return 1e3 * total / rounds if found and rounds else None
