"""Share of the HBM roofline the Gated DeltaNet state kernel reaches: each live
row's LOGICAL state read and written and the step's operands
(olmo_hybrid_bytes.py), once a linear layer and step, decode_chunk steps, over
the chip's published bytes a second, over the kernel's device time a round in
the trace. Bound by memory: a row's state is 2.2 MB a layer for 1.1 MFLOP."""
from benchmark import counters, olmo_hybrid_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "gdn_decode_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # the rows of the slice's own rounds, beside the slice's time
    s, rows = olmo_hybrid_bytes.kernel_round_s(run), olmo_hybrid_bytes.live_rows(cut) if cut else None
    if not s or not rows:
        return None
    gen = run["sut"]["gen"]
    need = gen.decode_chunk * olmo_hybrid_bytes.kernel_step_bytes(gen.cfg, rows)
    return 100.0 * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"] / s
