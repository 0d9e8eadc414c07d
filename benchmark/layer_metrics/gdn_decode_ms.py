"""Device milliseconds a decode round spends in the one-step state kernel of
the Gated DeltaNet layers: the `XLA Ops` events named `gdn_decode_step*` inside
runs of the decode step program, over the number of those runs. 15 linear
layers x 4 steps a run in `olmo_hybrid_decode_closed`. Plain rounds alone: the
kernel's calls inside a mixed round (`jit_mixed_round_fn`; none runs with
recurrent layers today) would be left out with their run, not added to these."""
from benchmark import olmo_hybrid_bytes

NAME, UNIT, BETTER, SOURCE = "gdn_decode_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    s = olmo_hybrid_bytes.kernel_round_s(run)
    return 1e3 * s if s else None
