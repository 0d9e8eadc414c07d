"""What the readers of the window-and-global cell (`kexaone_reason_closed`:
window attention layers on rings beside one global layer's full-length cache, a
leading dense layer, an expert share) have in common: the KV cache's block by
kind of layer at the window's edges (`perf_stats()["kv_kinds"]`), the positions
each kind of layer must read, and the bytes a decode step must move, computed
from shapes and from the program's counters. LOGICAL bytes: what the
mathematics reads, whatever a ring holds beyond the window. A program without
the block or without the window arm's kernel (the parent commit, any other
configuration) gives None everywhere."""

from __future__ import annotations

from benchmark import counters, peaks, reduce, solar_bytes, spans

KERNEL = "decode_attn_win"  # the decode attention's window arm, as the trace names it
BANKS = ("w1e", "w3e", "w2e")


def kinds(run: dict, edge: str = "end") -> dict | None:
    """The KV cache by kind of layer at one edge of the window, where the
    program keeps window layers on rings."""
    got = (run.get(edge) or {}).get("perf", {}).get("kv_kinds")
    return got if got and "window" in got else None


def position_bytes(cfg, kv_quant: str, scale_bytes: int = 2) -> int:
    """K and V of one cached position of ONE layer."""
    hd = cfg.resolved_head_dim
    return 2 * cfg.n_kv_heads * ((hd + scale_bytes) if kv_quant == "int8" else hd * 2)


def mean_live_positions(run: dict, cap: int = 0, samples: int = 64) -> float:
    """Cached positions a layer must read, summed over the sequences in flight
    and averaged over the window: a sequence's fill (`counters.mean_live_tokens`
    is this with no cap), or with `cap` what a window layer sees of it."""
    w0, w1 = run["window"]
    total = 0.0
    for k in range(samples):
        t = w0 + (k + 0.5) * (w1 - w0) / samples
        for r in run["records"]:
            if not reduce.ok(r):
                continue
            a, b = reduce.stream_span(r)
            if a <= t <= b:
                fill = r["prompt_tokens"] + r["completion_tokens"] * ((t - a) / (b - a) if b > a else 1.0)
                total += min(fill, cap) if cap else fill
    return total / samples


def window_layers(cfg) -> int:
    return cfg.n_layers - cfg.n_attn_layers


def win_step_bytes(run: dict) -> float | None:
    """What the window arm must read in one step of every window layer: each
    live sequence's last `sliding_window` positions (fewer while it is
    shorter), K and V with their scales."""
    gen = run["sut"]["gen"]
    window = getattr(gen.cfg, "sliding_window", 0)
    if not kinds(run) or not window:
        return None
    return (window_layers(gen.cfg) * position_bytes(gen.cfg, gen.kv_quant)
            * mean_live_positions(run, cap=window))


def full_step_bytes(run: dict) -> float | None:
    """What the global layers' decode attention must read in one step: each
    live sequence's whole fill at the window's mean, K and V with their scales."""
    gen = run["sut"]["gen"]
    if not kinds(run):
        return None
    return (gen.cfg.n_attn_layers * position_bytes(gen.cfg, gen.kv_quant)
            * counters.mean_live_tokens(run))


def decode_step_bytes(run: dict) -> float | None:
    """The least one decode step reads: every weight outside the expert banks
    once (the head's slice with them; the embedding table left out, as peaks.py
    does), the banks of the held experts the step's rows touched (by the
    program's counter, expert layer by expert layer), the global layers' live
    rows at the mean fill of the run's window (a roofline reader hands the run
    over cut to the traced slice), and of a window layer min(fill, window) rows
    a sequence."""
    got, win, full = solar_bytes.decode_counts(run), win_step_bytes(run), full_step_bytes(run)
    if not got or win is None or full is None:
        return None
    gen = run["sut"]["gen"]
    cfg, layers = gen.cfg, gen.params["layers"]
    banks = sum(peaks.tree_bytes(layers[k]) for k in BANKS)
    one_expert = banks / (len(got) * cfg.n_experts)
    touched = sum(r[solar_bytes.TOUCHED] / r[solar_bytes.CALLS] for r in got)
    return peaks.decode_weight_bytes(gen.params) - banks + touched * one_expert + full + win


def kernel_round_s(run: dict) -> float | None:
    """Device seconds a run of the decode step program spends in the window arm."""
    got = spans.planes(run)
    if got is None:
        return None
    total, rounds, found = spans.kernel_seconds(got[0], counters.DECODE_PROGRAM, KERNEL)
    return total / rounds if found and rounds else None


def full_round_s(run: dict) -> float | None:
    """Device seconds a run of the decode step program spends in the decode
    attention kernels OTHER than the window arm: the global layers' (the
    blocked or the whole-S arm, whichever a step took)."""
    win, every = kernel_round_s(run), spans.decode_attn_s(run)
    return every - win if win and every and every > win else None
