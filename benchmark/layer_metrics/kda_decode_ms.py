"""Device milliseconds a decode round spends in the one-step state kernel: the
`XLA Ops` events named `kda_decode_step*` inside runs of the decode step
program, over the number of those runs. 3 KDA layers x 4 steps a run. Plain
rounds alone: the kernel's calls inside a mixed round (`jit_mixed_round_fn`;
none runs with recurrent layers today) would be left out with their run, not
added to these."""
from benchmark import solar_bytes

NAME, UNIT, BETTER, SOURCE = "kda_decode_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    s = solar_bytes.kernel_round_s(run)
    return 1e3 * s if s else None
