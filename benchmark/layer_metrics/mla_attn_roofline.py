"""Share of its roofline the latent decode-attention kernel reaches
(`decode_attn_mla_q8_whole`, or the `_blocked` / `_paged` arm where the dispatcher
takes one): what the calls of every layer must move and issue in decode_chunk
steps at the slice's fill (the live positions' int8 latent rows, a row's queries
in and its context out; every head's scores and context against each live
position: joyai_bytes.py), as the LARGER of the byte time at the chip's published
bytes a second and the MXU time at its published int8 peak (the latent products
are s8 x s8; the rope part, a ninth of them, is counted at the same peak), over
the kernel's device time a round in the trace. At 64 rows of a few hundred
positions the bytes bound it. The whole-S arm streams a row's every position
whatever its fill, so at a mean fill of a third of the cache it cannot pass a
third: the reader logs the fill beside the share."""
from benchmark import counters, joyai_bytes, peaks

NAME, UNIT, BETTER, SOURCE = "mla_attn_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "Pallas kernels", "out_tokens_per_s"


def read(run: dict):
    cut = counters.slice_of(run)  # the positions and rows of the slice's own rounds
    gen = run["sut"]["gen"]
    if not cut or gen is None or not joyai_bytes.is_ours(gen):
        return None
    s, need, ops = (joyai_bytes.kernel_round_s(run), joyai_bytes.attn_step_bytes(cut),
                    joyai_bytes.attn_step_ops(cut))
    if not s or not need or not ops:
        return None
    chip = peaks.peaks(run["device"]["kind"])
    byte_s, mxu_s = need / chip["hbm_bytes_per_s"], ops / chip["int8_ops"]
    fill = joyai_bytes.latent_positions_a_step(cut) / (gen.max_slots * gen.max_seq_len)
    print(f"mla_attn: {1e3 * s:.3f} ms a round of {gen.decode_chunk} steps; a step's least "
          f"{1e6 * byte_s:.0f} us by bytes, {1e6 * mxu_s:.0f} us by the MXU; live positions "
          f"{100.0 * fill:.1f}% of the cache's", flush=True)
    return 100.0 * gen.decode_chunk * max(byte_s, mxu_s) / s
