"""Share of the HBM roofline a decode round of the window-and-global
configuration reaches: the least bytes decode_chunk steps must move (non-expert
weights once a step, the head's slice with them, the banks of the held experts
the step's rows touched by the program's counter, the global layer's live int8
rows at the window's mean fill, min(fill, window) rows a window layer:
kexaone_bytes.py) over the chip's published bytes a second, over the round's
device time in the trace. Bound by memory: a step at 64 rows does about 0.5
TFLOP against 7 GB. WHOLE plain rounds alone (`counters.DECODE_PROGRAM`,
`trace_reduce.whole_runs`), with the experts the slice's steps touched and the
slice's fill (`counters.slice_of`): this configuration runs no mixed round."""
from benchmark import counters, kexaone_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "kexaone_round_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # rows, touched experts and fill of the slice's own rounds
    mean_s, need = counters.decode_round_s(run), kexaone_bytes.decode_step_bytes(cut) if cut else None
    if not mean_s or not need:
        return None
    gen = run["sut"]["gen"]
    least_s = gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / mean_s
