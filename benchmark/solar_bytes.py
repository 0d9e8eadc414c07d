"""What the readers of the hybrid (recurrent-state, expert-share) cells share:
the state pool's and the expert counts' blocks of `perf_stats()` at the window's
edges, and the bytes a decode step must move, computed from shapes and from
those counters. peaks.py's functions count the dense int8 decoder; these count
a configuration whose layers are of unlike kinds. A program without the blocks
(the parent commit, or any other configuration) gives None everywhere."""

from __future__ import annotations

from benchmark import counters, peaks, spans

KERNEL = "kda_decode_step"  # the one-step state kernel, as the trace names it
ROWS, PAIRS, TOUCHED, FULLEST, CALLS = range(5)  # moe.moe_share_ffn's counts


def pool(run: dict, edge: str = "end") -> dict | None:
    """The state pool's block at one edge of the window."""
    return (run.get(edge) or {}).get("perf", {}).get("state_pool")


def experts(run: dict, edge: str = "end") -> dict | None:
    """The expert counts' block at one edge of the window."""
    return (run.get(edge) or {}).get("perf", {}).get("experts")


def decode_counts(run: dict) -> list[list[float]] | None:
    """[layer][count] of the expert layer over the window's DECODE steps:
    rows routed, pairs on held experts, held experts touched, the fullest
    held expert's rows, calls. None without the counters or without a step."""
    a, b = experts(run, "start"), experts(run, "end")
    if not a or not b:
        return None
    out = [[float(y) - float(x) for x, y in zip(row_a, row_b)]
           for row_a, row_b in zip(a["counts"][0], b["counts"][0])]
    return out if out and all(row[CALLS] > 0 for row in out) else None


def live_rows(run: dict) -> float | None:
    """Sequences a decode step carries: of a run cut to the traced slice
    (`counters.slice_of`) the rows of the plain rounds dispatched in it
    (`counters.plain_rows`); of a whole window, averaged over its decode steps
    by the expert layer's counts."""
    if "rounds" in run:
        return counters.plain_rows(run)
    got = decode_counts(run)
    return sum(r[ROWS] / r[CALLS] for r in got) / len(got) if got else None


def state_step_bytes(cfg, rows: float) -> float:
    """One step of every KDA layer on `rows` sequences: each state read and
    written (float32), the convolution tails read and written, and the
    kernel's operands (q, k, alpha, v, beta broadcast in; o out)."""
    H, d = cfg.lin_heads, cfg.lin_head_dim
    n_kda = cfg.n_layers - cfg.n_attn_layers
    state = 2 * H * d * d * 4
    tails = 2 * (cfg.lin_conv - 1) * 3 * H * d * 2
    return n_kda * rows * (state + tails + kernel_operand_bytes(cfg))


def kernel_operand_bytes(cfg) -> int:
    H, d = cfg.lin_heads, cfg.lin_head_dim
    return 6 * H * d * 4


def kernel_step_bytes(cfg, rows: float) -> float:
    """What `kda_decode_step` itself must move in one step of every KDA layer."""
    H, d = cfg.lin_heads, cfg.lin_head_dim
    n_kda = cfg.n_layers - cfg.n_attn_layers
    return n_kda * rows * (2 * H * d * d * 4 + kernel_operand_bytes(cfg))


def kv_row_bytes(cfg, kv_quant: str, scale_bytes: int = 2) -> int:
    """K and V of one cached token over the layers that own cache rows."""
    heads = cfg.n_kv_heads * cfg.n_attn_layers * 2
    hd = cfg.resolved_head_dim
    return heads * (hd + scale_bytes) if kv_quant == "int8" else heads * hd * 2


def decode_step_bytes(run: dict) -> float | None:
    """The least one decode step reads and writes: every weight outside the
    expert banks once (the embedding table left out, as peaks.py does), the
    banks of the held experts the step's rows touched (by the counter, layer
    by layer), the state pool's live rows, the live KV rows; all over the run's
    window (a roofline reader hands the run over cut to the traced slice)."""
    got = decode_counts(run)
    if not got:
        return None
    gen = run["sut"]["gen"]
    cfg, layers = gen.cfg, gen.params["layers"]
    banks = sum(peaks.tree_bytes(layers[k]) for k in ("w1e", "w3e", "w2e"))
    one_expert = banks / (cfg.n_layers * cfg.n_experts)
    touched = sum(r[TOUCHED] / r[CALLS] for r in got)  # experts a step, summed over layers
    rows = live_rows(run)
    if not rows:
        return None
    return (peaks.decode_weight_bytes(gen.params) - banks + touched * one_expert
            + state_step_bytes(cfg, rows)
            + kv_row_bytes(cfg, gen.kv_quant) * counters.mean_live_tokens(run))


def kernel_round_s(run: dict) -> float | None:
    """Device seconds a run of the decode step program spends in the kernel."""
    got = spans.planes(run)
    if got is None:
        return None
    total, rounds, found = spans.kernel_seconds(got[0], counters.DECODE_PROGRAM, KERNEL)
    return total / rounds if found and rounds else None
