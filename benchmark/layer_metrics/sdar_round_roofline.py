"""Share of the HBM roofline a block round of the block-diffusion expert-share
configuration reaches: the least bytes of the passes the counter says ran (a
denoising pass: every weight but the expert banks once with the embedding table
left out, the banks of the held experts the pass's rows touched by the program's
counter, the live int8 KV rows at the slice's mean fill; the commit pass the same
less the head, its block's rows written: sdar_bytes.py) over the chip's published
bytes a second, over the round's device time in the trace. Bound by memory: a
pass of 256 rows reads about 10 GB for 1.5 TFLOP. The share of the whole round
that bounds a later claim in this cell. WHOLE block rounds alone
(`sdar_bytes.PROGRAM`, `trace_reduce.whole_runs`), with the passes, the touched
experts and the positions of the slice's own rounds (`counters.slice_of`)."""
from benchmark import counters, peaks, sdar_bytes

NAME, UNIT, BETTER, SOURCE = "sdar_round_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # passes, touched experts and positions of the slice's own rounds
    gen = run["sut"]["gen"]
    if not cut or not sdar_bytes.is_ours(gen):
        return None
    mean_s, need = sdar_bytes.round_s(run), sdar_bytes.round_bytes(cut)
    if not mean_s or not need:
        return None
    n = sdar_bytes.passes_a_round(cut)
    print(f"sdar round: {1e3 * mean_s:.2f} ms, {need / 1e9:.2f} GB a round of {n[0]:.2f} denoising "
          f"passes and {n[1]:.2f} commits, a denoising pass {sdar_bytes.pass_bytes(cut) / 1e9:.2f} GB",
          flush=True)
    return 100.0 * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"] / mean_s
