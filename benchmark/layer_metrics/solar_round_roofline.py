"""Share of the HBM roofline a decode round of the hybrid configuration
reaches: the least bytes decode_chunk steps must move (non-expert weights once
a step, the banks of the held experts the step's rows touched by the program's
counter, the live rows of the state pool read and written, the live int8 KV:
solar_bytes.py) over the chip's published bytes a second, over the round's
device time in the trace. Bound by memory: a step at 64 rows does about 0.3
TFLOP against 7 GB. WHOLE plain rounds alone (`counters.DECODE_PROGRAM`,
`trace_reduce.whole_runs`): the bytes are a plain round's, with the rows of the
slice's own plain rounds, the experts its steps touched and its fill
(`counters.slice_of`). Since PR 42 most of this cell's rounds carry a prompt
(`jit_mixed_round_fn`): they are left out, their prompts' bytes are not counted."""
from benchmark import counters, peaks, solar_bytes

NAME, UNIT, BETTER, SOURCE = "solar_round_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # rows, touched experts and fill of the slice's own rounds
    mean_s, need = counters.decode_round_s(run), solar_bytes.decode_step_bytes(cut) if cut else None
    if not mean_s or not need:
        return None
    gen = run["sut"]["gen"]
    least_s = gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / mean_s
