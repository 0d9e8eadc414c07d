"""Share of the HBM roofline a decode round of the state-space hybrid
configuration reaches: the least bytes decode_chunk steps must move (every
weight once a step, the tied table once as the head, the live rows of the state
pool read and written, the live int8 KV: granite_bytes.py) over the chip's
published bytes a second, over the round's device time in the trace. Bound by
memory: a step at 64 rows does about 0.4 TFLOP against 16 GB. The share of the
whole step that bounds a later claim in this cell. Plain rounds alone
(`counters.DECODE_PROGRAM`): the bytes are a plain round's, and this
configuration runs no mixed round (`memory.RECURRENT_OFF["mixed_round"]`)."""
from benchmark import counters, granite_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "granite_round_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    mean_s, need = counters.decode_round_s(run), granite_bytes.decode_step_bytes(run)
    if not mean_s or not need:
        return None
    gen = run["sut"]["gen"]
    least_s = gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / mean_s
