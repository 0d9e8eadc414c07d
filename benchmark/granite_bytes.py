"""What the readers of the state-space hybrid cell (`granite_decode_closed`:
Mamba-2 layers with a per-slot state, attention layers, a dense feed-forward,
the embedding table tied to the head) share: the rows a decode step carries,
from the perf observatory's phases as `gdn_decode_roofline` reads them
(olmo_hybrid_bytes.live_rows), and the bytes a step must move, computed from
shapes: LOGICAL bytes, what the mathematics reads and writes, whatever the
pool's layout pads or the kernel's operands repeat. A program without the
state kernel under this name, or a configuration without state-space layers
(the parent commit, any other cell), gives None everywhere."""

from __future__ import annotations

from benchmark import counters, olmo_hybrid_bytes, peaks, solar_bytes, spans

KERNEL = "ssd_decode_step"  # the one-step state kernel, as the trace names it here
live_rows = olmo_hybrid_bytes.live_rows


def ssm_layers(cfg) -> int:
    return cfg.n_layers - cfg.n_attn_layers


def kernel_row_bytes(cfg) -> int:
    """One step of one state-space layer on one sequence, as the state kernel
    must move it: the float32 state [H, N, P] read and written, B and C [N]
    once a row (one group for every head), dt x [H, P] in and y [H, P] out, one
    decay a head."""
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return 4 * (2 * H * N * P + 2 * N + 2 * H * P + H)


def kernel_step_bytes(cfg, rows: float) -> float:
    """What `ssd_decode_step` itself must move in one step of every state-space layer."""
    return ssm_layers(cfg) * rows * kernel_row_bytes(cfg)


def state_step_bytes(cfg, rows: float) -> float:
    """One step of every state-space layer on `rows` sequences: the kernel's
    bytes and the convolution tails (taps-1 rows of x | B | C, bfloat16) read
    and written."""
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    tails = 2 * (cfg.ssm_conv - 1) * (H * P + 2 * N) * 2
    return ssm_layers(cfg) * rows * (kernel_row_bytes(cfg) + tails)


def decode_step_bytes(run: dict) -> float | None:
    """The least one decode step reads and writes: every weight once (the tied
    table once, as the head: peaks.decode_weight_bytes), the state pool's live
    rows read and written with their tails, the live KV rows at the mean fill
    of the run's window (a roofline reader hands the run over cut to the traced
    slice)."""
    rows = live_rows(run)
    gen = run["sut"]["gen"]
    if not rows or not getattr(gen.cfg, "ssm_heads", 0):
        return None
    return (peaks.decode_weight_bytes(gen.params) + state_step_bytes(gen.cfg, rows)
            + solar_bytes.kv_row_bytes(gen.cfg, gen.kv_quant) * counters.mean_live_tokens(run))


def kernel_round_s(run: dict) -> float | None:
    """Device seconds a run of the decode step program spends in the kernel."""
    got = spans.planes(run)
    if got is None:
        return None
    total, rounds, found = spans.kernel_seconds(got[0], counters.DECODE_PROGRAM, KERNEL)
    return total / rounds if found and rounds else None
