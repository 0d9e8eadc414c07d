"""Device milliseconds a decode round spends in the grouped expert kernels: the
`XLA Ops` events named `grouped_swiglu*` and `grouped_down*` inside whole runs of
the plain decode step program, over the number of those runs. 12 expert layers x
4 steps a run in `lfm2_decode_closed`, 256 pairs a call in groups of about 8
rows. The kernels' calls inside a round that carries a prompt are left out with
their run."""
from benchmark import lfm2_bytes

NAME, UNIT, BETTER, SOURCE = "moe_grouped_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    gen = run["sut"]["gen"]
    s = lfm2_bytes.grouped_round_s(run) if gen is not None and lfm2_bytes.is_ours(gen) else None
    return 1e3 * s if s else None
