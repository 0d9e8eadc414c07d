"""Device milliseconds a block round spends in its passes' attention: the leaf
`XLA Ops` under the scopes `block.denoise` or `block.commit` and `attn` (the
attention half of a layer in a block pass: the q/k/v products, head norms and
rope, the read of the past rows out of the int8 cache, scores, softmax, context
and the output product), inside whole runs of the block round's program, over
the number of those runs. The XLA arm (the bucketed chunk's attention): no
Pallas kernel runs a block's attention yet, so there is no kernel's name to
read and no roofline share of its own. None where the trace's operations carry
no scope or the slice holds no whole block round."""
from benchmark import sdar_bytes

NAME, UNIT, BETTER, SOURCE = "block_attn_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    s = sdar_bytes.attn_round_s(run)
    return 1e3 * s if s else None
