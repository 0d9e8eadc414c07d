"""Share of the HBM roofline the decode attention's window arm reaches: each
live sequence's last `sliding_window` positions, int8 K and V with their scales
(kexaone_bytes.py), once a window layer and step, decode_chunk steps, over the
chip's published bytes a second, over the arm's device time a round in the
trace. Bound by memory. What a ring holds beyond the window, and a parked row's
ring, are streamed and not counted: they are the arm's to lose."""
from benchmark import counters, kexaone_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "win_attn_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # the fill of the slice's own rounds, beside the slice's time
    s, need = kexaone_bytes.kernel_round_s(run), kexaone_bytes.win_step_bytes(cut) if cut else None
    if not s or not need:
        return None
    gen = run["sut"]["gen"]
    return 100.0 * gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"] / s
