"""Device milliseconds a decode round spends in the decode attention's window
arm: the `XLA Ops` events named `decode_attn_win*` inside runs of the decode
step program, over the number of those runs. 4 window layers x 4 steps a run in
`kexaone_reason_closed`. A part of `decode_attn_ms`, which sums every kernel
named `decode_attn*`. Plain rounds alone."""
from benchmark import kexaone_bytes

NAME, UNIT, BETTER, SOURCE = "win_attn_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    s = kexaone_bytes.kernel_round_s(run)
    return 1e3 * s if s else None
