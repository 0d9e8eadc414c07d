"""Device milliseconds a decode round spends in the one-step state kernel: the
`XLA Ops` events named `kda_decode_step*` inside runs of the decode step
program, over the number of those runs. 3 KDA layers x 4 steps a run."""
from benchmark import solar_bytes

NAME, UNIT, BETTER, SOURCE = "kda_decode_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    s = solar_bytes.kernel_round_s(run)
    return 1e3 * s if s else None
