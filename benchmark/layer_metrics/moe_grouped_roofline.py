"""Share of the HBM roofline the grouped expert kernels reach: the touched
experts' banks once a layer and step, each held pair's row in and its float32
row out (lfm2_bytes.py), decode_chunk steps, over the chip's published bytes a
second, over the kernels' device time a round in the trace. Bound by memory: a
bank of 22 MB is read for the 8 rows of its group. The reader also prints the
share of the chip's bfloat16 peak the same calls issue at whole row tiles of 128
(every visit multiplies 128 rows, whatever it holds), so that the next reader of
the line sees how near 8 rows a group in a tile of 128 stands to the compute
ridge."""
from benchmark import counters, lfm2_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "moe_grouped_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # the touched experts and pairs of the slice's own rounds
    gen = run["sut"]["gen"]
    if not cut or gen is None or not lfm2_bytes.is_ours(gen):
        return None
    s, need = lfm2_bytes.grouped_round_s(run), lfm2_bytes.grouped_step_bytes(cut)
    if not s or not need:
        return None
    chip = peaks.peaks(run["device"]["kind"])
    flops = lfm2_bytes.grouped_tile_flops(cut)
    print(f"moe_grouped: {1e3 * s:.3f} ms a round, {gen.decode_chunk * need / 1e9:.2f} GB a round; "
          f"at row tiles of {lfm2_bytes.ROW_TILE} the calls issue "
          f"{100.0 * gen.decode_chunk * flops / chip['bf16_flops'] / s:.1f}% of the bfloat16 peak",
          flush=True)
    return 100.0 * gen.decode_chunk * need / chip["hbm_bytes_per_s"] / s
