"""Share of the HBM roofline the Mamba-2 state kernel reaches: each live row's
LOGICAL state [64, 128, 64] float32 read and written and the step's operands
(granite_bytes.py), once a state-space layer and step, decode_chunk steps, over
the chip's published bytes a second, over the kernel's device time a round in
the trace. Bound by memory: a row's state is 2.1 MB a layer for 2.1 MFLOP."""
from benchmark import counters, granite_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "ssd_decode_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # the rows of the slice's own rounds, beside the slice's time
    s, rows = granite_bytes.kernel_round_s(run), granite_bytes.live_rows(cut) if cut else None
    gen = run["sut"]["gen"]
    if not s or not rows or not getattr(gen.cfg, "ssm_heads", 0):
        return None
    need = gen.decode_chunk * granite_bytes.kernel_step_bytes(gen.cfg, rows)
    return 100.0 * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"] / s
