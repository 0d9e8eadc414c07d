"""Share of the HBM roofline a decode round of the state-space hybrid
configuration reaches: the least bytes decode_chunk steps must move (every
weight once a step, the tied table once as the head, the live rows of the state
pool read and written, the live int8 KV: granite_bytes.py) over the chip's
published bytes a second, over the round's device time in the trace. Bound by
memory: a step at 64 rows does about 0.4 TFLOP against 16 GB. The share of the
whole step that bounds a later claim in this cell. WHOLE plain rounds alone
(`counters.DECODE_PROGRAM`, `trace_reduce.whole_runs`): the bytes are a plain
round's, with the rows of the slice's own plain rounds and the slice's fill
(`counters.slice_of`). Since PR 42 most of this cell's rounds carry a prompt
(`jit_mixed_round_fn`): they are left out, their prompts' bytes are not counted."""
from benchmark import counters, granite_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "granite_round_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # rows, touched experts and fill of the slice's own rounds
    mean_s, need = counters.decode_round_s(run), granite_bytes.decode_step_bytes(cut) if cut else None
    if not mean_s or not need:
        return None
    gen = run["sut"]["gen"]
    least_s = gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / mean_s
