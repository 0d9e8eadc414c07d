"""What the readers of the dense hybrid cell (`olmo_hybrid_decode_closed`: Gated
DeltaNet layers with a per-slot state, full attention layers, a dense
feed-forward) share: the rows a decode step carries, from the perf
observatory's phases as `decode_occupancy` reads them (this configuration has
no expert counters to take them from, as solar_bytes.py does), and the bytes a
step must move, computed from shapes: LOGICAL bytes, what the mathematics
reads and writes, whatever the pool's layout pads or the kernel's operands
repeat. A program without the state kernel under this name (the parent commit,
or any other configuration) gives None everywhere."""

from __future__ import annotations

from benchmark import counters, peaks, solar_bytes, spans

KERNEL = "gdn_decode_step"  # the one-step state kernel, as the trace names it here
PHASES = ("decode", "fused", "fused_rag")  # the perf observatory's decode rounds


def live_rows(run: dict) -> float | None:
    """Sequences a decode step carries. Of a run cut to the traced slice
    (`counters.slice_of`): the rows of the plain rounds dispatched in it
    (`counters.plain_rows`). Of a whole window: averaged over its sampled
    rounds, tokens over decode_chunk over samples."""
    if "rounds" in run:
        return counters.plain_rows(run)
    tokens = sum(counters.delta(run, "perf", "phases", p, "tokens") or 0.0 for p in PHASES)
    samples = sum(counters.delta(run, "perf", "phases", p, "samples") or 0.0 for p in PHASES)
    if not samples or not tokens:
        return None
    return tokens / run["sut"]["gen"].decode_chunk / samples


def linear_layers(cfg) -> int:
    return cfg.n_layers - cfg.n_attn_layers


def kernel_row_bytes(cfg) -> int:
    """One step of one linear layer on one sequence, as the state kernel must
    move it: the float32 state [H, dk, dv] read and written, q and k [H, dk],
    v [H, dv] in and o [H, dv] out, one decay and one beta a head."""
    H, dk, dv = cfg.lin_heads, cfg.lin_head_dim, cfg.lin_dv
    return 4 * (2 * H * dk * dv + 2 * H * dk + 2 * H * dv + 2 * H)


def kernel_step_bytes(cfg, rows: float) -> float:
    """What `gdn_decode_step` itself must move in one step of every linear layer."""
    return linear_layers(cfg) * rows * kernel_row_bytes(cfg)


def state_step_bytes(cfg, rows: float) -> float:
    """One step of every linear layer on `rows` sequences: the kernel's bytes
    and the convolution tails (taps-1 rows of x [Wq | Wk | Wv], bfloat16) read
    and written."""
    H, dk, dv = cfg.lin_heads, cfg.lin_head_dim, cfg.lin_dv
    tails = 2 * (cfg.lin_conv - 1) * H * (2 * dk + dv) * 2
    return linear_layers(cfg) * rows * (kernel_row_bytes(cfg) + tails)


def decode_step_bytes(run: dict) -> float | None:
    """The least one decode step reads and writes: every weight once (the
    embedding table left out, as peaks.py does), the state pool's live rows
    read and written with their tails, the live KV rows at the mean fill of the
    run's window (a roofline reader hands the run over cut to the traced slice)."""
    rows = live_rows(run)
    if not rows:
        return None
    gen = run["sut"]["gen"]
    if not getattr(gen.cfg, "recurrent", False) or getattr(gen.cfg, "n_experts", 0):
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
