"""What the readers of the latent-attention expert-share cell
(`joyai_decode_closed`: latent attention with a low-rank query in every layer
over an int8 latent cache, a leading dense layer, 16 of 256 routed experts held
beside a shared expert, embedding and head apart) share: the bytes and the
operations a decode step must move and issue, computed from shapes, from the
program's expert counters and from its count of the latent positions a step
reads (`perf_stats()["decode_attn"]`: `tokens_live` over `steps`, rows x their
lengths), and the latent decode-attention kernel's device time in the trace.
LOGICAL bytes: what ANY implementation must read and write, whatever an arm
streams beyond a row's fill or a tile pads; with every held expert touched the
weights' part is the parameter count less the embedding table times the item size
(tests/benchmark/test_bench_joyai.py holds it to that). A program without the
counters or the kernel's name, or a configuration without such layers (the parent
commit, any other cell), gives None everywhere."""

from __future__ import annotations

from benchmark import counters, peaks, solar_bytes, spans
from benchmark.lfm2_bytes import bank_bytes, one_expert_bytes, touched_a_step  # the banks' bytes: any held experts'

KERNEL = "decode_attn_mla_q8"  # `_whole`, `_blocked`, `_paged`: the arm taken, as the trace names it
GROUPED = "grouped_"  # `grouped_swiglu` and `grouped_down`


def is_ours(gen) -> bool:
    cfg = gen.cfg
    return bool(getattr(cfg, "kv_lora_rank", 0) and getattr(cfg, "q_lora_rank", 0) and cfg.n_experts)


def latent_row_bytes(cfg, kv_quant: str, scale_bytes: int = 2) -> int:
    """One cached position over all layers: the latent and the rope key, int8
    payload with one scale each a position, or the model's two bytes a value."""
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return cfg.n_layers * (width + 2 * scale_bytes if kv_quant == "int8" else 2 * width)


def latent_positions_a_step(run: dict) -> float | None:
    """Latent positions ONE layer's attention reads in a decode step, summed over
    the step's rows (each row's length), by the program's own count between the
    run's edges (the traced slice's, of a run `counters.slice_of` cut to it)."""
    a = (run.get("start") or {}).get("perf", {}).get("decode_attn")
    b = (run.get("end") or {}).get("perf", {}).get("decode_attn")
    if not a or not b or b["steps"] <= a["steps"]:
        return None
    return (b["tokens_live"] - a["tokens_live"]) / (b["steps"] - a["steps"])


def latent_step_bytes(run: dict) -> float | None:
    """The latent rows every layer's attention must read in one step."""
    gen = run["sut"]["gen"]
    positions = latent_positions_a_step(run)
    if positions is None or gen is None or not is_ours(gen):
        return None
    return positions * latent_row_bytes(gen.cfg, gen.kv_quant)


def decode_step_bytes(run: dict) -> float | None:
    """The least one decode step reads and writes: every weight outside the
    expert banks once (the embedding table left out, a row a sequence:
    peaks.decode_weight_bytes), the banks of the held experts the step's rows
    touched (by the program's counter, layer by layer), the latent rows of the
    live positions read and each row's new position written."""
    gen = run["sut"]["gen"]
    got, rows, latent = solar_bytes.decode_counts(run), solar_bytes.live_rows(run), latent_step_bytes(run)
    if not got or not rows or latent is None:
        return None
    return (peaks.decode_weight_bytes(gen.params) - bank_bytes(gen)
            + touched_a_step(got) * one_expert_bytes(gen)
            + latent + rows * latent_row_bytes(gen.cfg, gen.kv_quant))


def kernel_round_s(run: dict, prefix: str = KERNEL) -> float | None:
    """Device seconds a whole run of the plain decode step program spends in the
    kernels whose name starts with `prefix`."""
    got = spans.planes(run)
    if got is None:
        return None
    total, rounds, found = spans.kernel_seconds(got[0], counters.DECODE_PROGRAM, prefix)
    return total / rounds if found and rounds else None


def attn_step_bytes(run: dict) -> float | None:
    """What the latent decode attention of every layer must move in one step: the
    live positions' latent rows, and a row's absorbed and rope queries in and its
    context out (the model's type)."""
    gen = run["sut"]["gen"]
    latent, rows = latent_step_bytes(run), solar_bytes.live_rows(run)
    if latent is None or not rows:
        return None
    cfg, item = gen.cfg, gen.params["embed"].dtype.itemsize
    return latent + cfg.n_layers * rows * cfg.n_heads * (2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim) * item


def attn_step_ops(run: dict) -> float | None:
    """Operations the same calls must issue: every head's scores against each
    live position's latent and rope key, and its context out of the latents."""
    gen = run["sut"]["gen"]
    positions = latent_positions_a_step(run)
    if positions is None or gen is None or not is_ours(gen):
        return None
    cfg = gen.cfg
    return cfg.n_layers * positions * cfg.n_heads * 2.0 * (2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim)
