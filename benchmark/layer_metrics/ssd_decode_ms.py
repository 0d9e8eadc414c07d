"""Device milliseconds a decode round spends in the one-step state kernel of
the Mamba-2 layers: the `XLA Ops` events named `ssd_decode_step*` inside runs
of the decode step program, over the number of those runs. 36 state-space
layers x 4 steps a run in `granite_decode_closed`. Plain rounds alone: the
kernel's calls inside a mixed round (`jit_mixed_round_fn`; none runs with
recurrent layers today) would be left out with their run, not added to these."""
from benchmark import granite_bytes

NAME, UNIT, BETTER, SOURCE = "ssd_decode_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    s = granite_bytes.kernel_round_s(run)
    return 1e3 * s if s else None
