"""What the readers of the gated-short-convolution expert cell
(`lfm2_decode_closed`: convolution layers whose per-slot state is a tail,
attention layers, two leading dense layers, every routed expert of a layer held
whole, the embedding table tied to the head) share: the bytes a decode step must
move, computed from shapes and from the program's expert counters, and the
grouped expert kernels' device time in the trace. LOGICAL bytes: what ANY
implementation must read and write, whatever a row tile pads or a kernel
re-reads; with every expert touched the weights' part is the parameter count
times the item size (tests/benchmark/test_bench_lfm2.py holds it to that). A
program without the counters or the kernels' names, or a configuration without
such layers (the parent commit, any other cell), gives None everywhere."""

from __future__ import annotations

from benchmark import counters, peaks, solar_bytes, spans

KERNELS = "grouped_"  # `grouped_swiglu` and `grouped_down`, as the trace names them
BANKS = ("w1e", "w3e", "w2e")
ROW_TILE = 128  # rows of a visit of the grouped kernels (kernels/grouped.py)


def conv_layers(cfg) -> int:
    return cfg.n_layers - cfg.n_attn_layers


def is_ours(gen) -> bool:
    return bool(getattr(gen.cfg, "conv_taps", 0) and gen.cfg.n_experts)


def tail_step_bytes(cfg, rows: float, itemsize: int = 2) -> float:
    """One step of every convolution layer on `rows` sequences: the tail
    (taps-1 values of B * x at the model's width) read and written."""
    return conv_layers(cfg) * rows * 2 * (cfg.conv_taps - 1) * cfg.dim * itemsize


def bank_bytes(gen) -> int:
    """Every expert bank of every expert layer, as the device holds them."""
    return sum(peaks.tree_bytes(gen.params["layers"][k]) for k in BANKS)


def one_expert_bytes(gen) -> float:
    layers = gen.params["layers"]
    return bank_bytes(gen) / (layers["w1e"].shape[0] * gen.cfg.n_experts)


def touched_a_step(got) -> float:
    """Held experts a decode step touched, summed over the expert layers."""
    return sum(r[solar_bytes.TOUCHED] / r[solar_bytes.CALLS] for r in got)


def decode_step_bytes(run: dict) -> float | None:
    """The least one decode step reads and writes: every weight outside the
    expert banks once (the tied table once, as the head:
    peaks.decode_weight_bytes), the banks of the experts the step's rows touched
    (by the program's counter, layer by layer), the live rows' tails read and
    written, the live int8 KV rows at the mean fill of the run's window (a
    roofline reader hands the run over cut to the traced slice)."""
    gen = run["sut"]["gen"]
    got, rows = solar_bytes.decode_counts(run), solar_bytes.live_rows(run)
    if not got or not rows or not is_ours(gen):
        return None
    return (peaks.decode_weight_bytes(gen.params) - bank_bytes(gen)
            + touched_a_step(got) * one_expert_bytes(gen)
            + tail_step_bytes(gen.cfg, rows, gen.params["embed"].dtype.itemsize)
            + solar_bytes.kv_row_bytes(gen.cfg, gen.kv_quant) * counters.mean_live_tokens(run))


def grouped_round_s(run: dict) -> float | None:
    """Device seconds a whole run of the plain decode step program spends in
    the grouped expert kernels (`grouped_swiglu*`, `grouped_down*`)."""
    got = spans.planes(run)
    if got is None:
        return None
    total, rounds, found = spans.kernel_seconds(got[0], counters.DECODE_PROGRAM, KERNELS)
    return total / rounds if found and rounds else None


def grouped_step_bytes(run: dict) -> float | None:
    """What the grouped kernels of every expert layer must move in one step: the
    touched experts' banks once, each held pair's row in (the model's type) and
    its float32 row out. The product between the two kernels is no traffic an
    implementation must have."""
    gen = run["sut"]["gen"]
    got = solar_bytes.decode_counts(run)
    if not got or not is_ours(gen):
        return None
    pairs = sum(r[solar_bytes.PAIRS] / r[solar_bytes.CALLS] for r in got)
    item = gen.params["layers"]["w1e"].dtype.itemsize
    return touched_a_step(got) * one_expert_bytes(gen) + pairs * gen.cfg.dim * (item + 4)


def grouped_tile_flops(run: dict) -> float | None:
    """Operations the grouped kernels issue in one step at whole row tiles: a
    visit (one touched expert's rows in one tile; a group that straddles a tile
    edge is visited twice, so touched + tiles - 1 a layer at most) multiplies
    `ROW_TILE` rows by the expert's three matrices, whatever rows it holds."""
    gen = run["sut"]["gen"]
    got = solar_bytes.decode_counts(run)
    if not got or not is_ours(gen):
        return None
    cfg = gen.cfg
    visits = 0.0
    for r in got:
        pairs = r[solar_bytes.PAIRS] / r[solar_bytes.CALLS]
        visits += r[solar_bytes.TOUCHED] / r[solar_bytes.CALLS] + max(0.0, -(-pairs // ROW_TILE) - 1)
    return visits * ROW_TILE * 6.0 * cfg.dim * (cfg.moe_ffn_hidden or cfg.ffn_hidden)
