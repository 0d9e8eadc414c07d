"""Share of the HBM roofline the decode attention kernels reach: the bytes
they must read in a round (the live int8 KV rows with their scales at the
traced slice's mean fill, once a step, decode_chunk steps: peaks.py) over the
chip's published bytes a second, over the kernels' device time a round in the
trace. Bound by memory; the queries and outputs are left out (32 x 4096 bf16 a
layer against 8 MB of cache)."""
from benchmark import counters, peaks, spans

NAME, UNIT, BETTER, SOURCE = "decode_attn_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    s, cut = spans.decode_attn_s(run), counters.slice_of(run)
    if not s or cut is None:
        return None
    gen = run["sut"]["gen"]
    need = peaks.kv_row_bytes(gen.cfg, gen.kv_quant) * counters.mean_live_tokens(cut)
    least_s = gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / s
