"""Share of the HBM roofline a decode round of the latent-attention expert-share
configuration reaches: the least bytes decode_chunk steps must move (every weight
but the expert banks once a step with the embedding table left out, the banks of
the held experts the step's rows touched by the program's counter, the latent
rows of the live positions by the program's count, each row's new position
written: joyai_bytes.py) over the chip's published bytes a second, over the
round's device time in the trace. Bound by memory: a step at 64 rows reads about
8 GB for 0.3 TFLOP. The share of the whole step that bounds a later claim in this
cell. WHOLE plain rounds alone (`counters.DECODE_PROGRAM`,
`trace_reduce.whole_runs`), with the rows, the touched experts and the positions
of the slice's own rounds (`counters.slice_of`). The reader also logs the round's
ms in the grouped expert kernels and in the latent attention kernel."""
from benchmark import counters, joyai_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "joyai_round_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # rows, touched experts and positions of the slice's own rounds
    gen = run["sut"]["gen"]
    if not cut or gen is None or not joyai_bytes.is_ours(gen):
        return None
    mean_s, need = counters.decode_round_s(run), joyai_bytes.decode_step_bytes(cut)
    if not mean_s or not need:
        return None
    grouped = joyai_bytes.kernel_round_s(run, joyai_bytes.GROUPED)
    attn = joyai_bytes.kernel_round_s(run)
    print(f"joyai round: {1e3 * mean_s:.2f} ms, {gen.decode_chunk * need / 1e9:.2f} GB a round of "
          f"{gen.decode_chunk} steps, of them latent rows {joyai_bytes.latent_step_bytes(cut) / 1e9:.3f} GB a "
          f"step; grouped expert kernels {1e3 * (grouped or 0.0):.2f} ms, latent attention "
          f"{1e3 * (attn or 0.0):.2f} ms a round", flush=True)
    least_s = gen.decode_chunk * need / peaks.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / mean_s
