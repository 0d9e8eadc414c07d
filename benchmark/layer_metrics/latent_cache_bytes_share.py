"""Share of a decode step's bytes that are latent cache rows: the live
positions' int8 latents and rope keys of every layer (the program's count of the
positions a step reads, `perf_stats()["decode_attn"]`, times 580 bytes a position
and layer) over everything the step must move (joyai_bytes.decode_step_bytes:
weights outside the banks, the touched banks, those rows), over the window's
decode steps. The number that says how far this cell stands from a long-context
one: at a few hundred positions a row the latent CACHE is a few per cent of the
step and the latent attention's WEIGHTS a quarter; at 8k positions a row the
cache would be half."""
from benchmark import joyai_bytes

NAME, UNIT, BETTER, SOURCE = "latent_cache_bytes_share", "%", "higher", "program_counter"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    gen = run["sut"]["gen"]
    if gen is None or not joyai_bytes.is_ours(gen):
        return None
    latent, need = joyai_bytes.latent_step_bytes(run), joyai_bytes.decode_step_bytes(run)
    return 100.0 * latent / need if latent is not None and need else None
