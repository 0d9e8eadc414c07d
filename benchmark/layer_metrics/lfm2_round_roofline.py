"""Share of the HBM roofline a decode round of the gated-short-convolution
expert configuration reaches: the least bytes decode_chunk steps must move
(every weight but the expert banks once a step with the tied table counted
once, the banks of the experts the step's rows touched by the program's counter,
the live rows' tails read and written, the live int8 KV at the window's mean
fill: lfm2_bytes.py) over the chip's published bytes a second, over the round's
device time in the trace. Bound by memory: a step at 64 rows reads 9.3 GB for
about 0.2 TFLOP. The share of the whole step that bounds a later claim in this
cell. WHOLE plain rounds alone (`counters.DECODE_PROGRAM`,
`trace_reduce.whole_runs`), with the rows, the touched experts and the fill of
the slice's own rounds (`counters.slice_of`); a round that carries a prompt
(`jit_mixed_round_fn`) is left out, its prompts' bytes are not counted."""
from benchmark import counters, lfm2_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "lfm2_round_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # rows, touched experts and fill of the slice's own rounds
    mean_s, need = counters.decode_round_s(run), lfm2_bytes.decode_step_bytes(cut) if cut else None
    if not mean_s or not need:
        return None
    gen = run["sut"]["gen"]
    least_s = gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / mean_s
