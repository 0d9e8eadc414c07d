"""Device milliseconds a decode round spends in the decode attention kernels:
the `XLA Ops` events of every Mosaic kernel whose name starts with
`decode_attn`, inside runs of the decode step program, over the number of
those runs. 36 layers x 4 steps a run. Plain rounds alone: a mixed round
(`jit_mixed_round_fn`) runs the same kernel over the same rows, but its calls
are left out with their run, so that ms a run stays ms of one program."""
from benchmark import spans

NAME, UNIT, BETTER, SOURCE = "decode_attn_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    s = spans.decode_attn_s(run)
    return 1e3 * s if s else None
