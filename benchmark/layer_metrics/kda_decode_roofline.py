"""Share of the HBM roofline the one-step state kernel reaches: each live
row's state read and written and the step's operands (solar_bytes.py), once a
KDA layer and step, decode_chunk steps, over the chip's published bytes a
second, over the kernel's device time a round in the trace. Bound by memory:
a row's state is 4 MB a layer for 2 MFLOP."""
from benchmark import counters, peaks, solar_bytes

NAME, UNIT, BETTER, SOURCE = "kda_decode_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # the rows of the slice's own rounds, beside the slice's time
    s, rows = solar_bytes.kernel_round_s(run), solar_bytes.live_rows(cut) if cut else None
    if not s or not rows:
        return None
    gen = run["sut"]["gen"]
    need = gen.decode_chunk * solar_bytes.kernel_step_bytes(gen.cfg, rows)
    return 100.0 * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"] / s
