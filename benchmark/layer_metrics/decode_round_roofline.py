"""Share of the HBM roofline a decode round reaches: the bytes decode_chunk
steps must read (the int8 weights once a step, and the live int8 KV rows with
their scales at the traced slice's mean fill, from shapes: peaks.py) over the
chip's published bytes a second, over the round's device time in the trace.
Bound by memory: a step at 32 rows does 0.5 TOP against 8 GB. Plain rounds
alone: a mixed round (`jit_mixed_round_fn`) also moves its prompts' rows and
writes their KV, bytes this count does not hold, so it is left out."""
from benchmark import counters, peaks

NAME, UNIT, BETTER, SOURCE = "decode_round_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    gen = run["sut"]["gen"]
    mean_s, cut = counters.decode_round_s(run), counters.slice_of(run)
    if not mean_s or cut is None:
        return None
    need = peaks.decode_round_bytes(gen.params, gen.cfg, gen.kv_quant, counters.mean_live_tokens(cut))
    least_s = gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / mean_s
