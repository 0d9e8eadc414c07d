"""Share of the HBM roofline the GLOBAL layers' decode attention reaches where
window layers run beside them: each live sequence's whole fill at the window's
mean, int8 K and V with their scales (kexaone_bytes.py), once a global layer and
step, decode_chunk steps, over the chip's published bytes a second, over the
device time a round of the kernels named `decode_attn*` that are not the window
arm (`decode_attn_q8_blocked` in `kexaone_reason_closed`). Bound by memory.
`decode_attn_roofline` is not this: `peaks.kv_row_bytes` counts every layer of
the configuration, and four of this one's five read a ring."""
from benchmark import counters, kexaone_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "full_attn_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # the fill of the slice's own rounds, beside the slice's time
    s, need = kexaone_bytes.full_round_s(run), kexaone_bytes.full_step_bytes(cut) if cut else None
    if not s or not need:
        return None
    gen = run["sut"]["gen"]
    return 100.0 * gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"] / s
