"""Token-timeline perf observatory: ITL/TPOT, goodput, and rooflines.

TTFT histograms say how fast the *first* token arrives; the compile ledger
says what cold dispatches cost; neither explains a steady-state regression.
This module is the third observability layer (tracing.py = per-request,
recorder.py = post-mortem, perf.py = *explanation*), with three coupled
parts:

1. **Token timelines** — the engine feeds every emission round's
   (wall gap, tokens learned) pair here, yielding per-token inter-token
   latency (ITL, a.k.a. TPOT) p50/p95/p99 over a rolling window, and a
   goodput accountant that classifies each finished request against the
   joint TTFT + ITL SLO (`TPU_TARGET_TTFT_MS` / `TPU_TARGET_ITL_MS`):
   `goodput_tok_per_s` counts only tokens from SLO-conforming requests,
   the metric DistServe/Sarathi-class serving work optimizes for, vs the
   raw tok/s the dashboard has always shown.

2. **Phase attribution** — every Nth dispatch (`TPU_PERF_SAMPLE`, dynamic;
   0 disables) the engine reports {host staging, device compute, scheduler
   wait} walls per dispatch phase. The CompileLedger times only *first*
   dispatches; this is the steady-state complement. A decode round's
   sample is counted at its dispatch. Nothing blocks for its device
   seconds (with two rounds in flight a `block_until_ready` at dispatch
   waits for the round before as well, and serialises the pipeline): EVERY
   round whose device time can be told where it ends (the first read that
   waited for its program: its fetch, or for a round that carried prompts
   the read of their first tokens), as the interval since the previous
   round's end when the two were dispatched back to back and the host
   waited for both, gives its seconds, rows and tokens together
   (`observe_device`) to the row of its step program in `RoundAccount`, the
   one book of rounds, and the roofline's token rate and the rows it is
   evaluated at are that book's totals. A
   sampled round that can be told adds its seconds to the phase's
   `device_s` too. The synchronous prefill-family dispatches time their
   own sync.

3. **Rooflines** — analytical FLOPs and HBM-byte cost models per cache
   layout (bf16/int8 × GQA/MLA, including the fused int8 layout's scale
   pseudo-head rows and the paged path's block-table gathers) turn the
   sampled decode device time into MFU/MBU gauges against the chip peaks
   (`CHIP_PEAKS`, keyed by the device kind the engine reports; a kind
   that is not in the table gets no utilization, only counts). The live
   `decode_mbu` number is ROADMAP item 5's "layers_gbps toward 650"
   microbench, continuously measured on the serve path. All four layouts
   are evaluated against the same measured token rate — the non-active
   rows are the what-if column (what would this traffic cost under the
   other cache layouts); `active` marks the one the engine actually runs.

Like tracing.py and recorder.py this module is stdlib-only and must never
import `executor`, `api`, `jax`, or `numpy` — the engine imports *us* and
hands plain scalars in (`tests/test_perf.py` pins the contract).

`DISPATCH_PHASES` below is the registry of record for the serve path's
steady-state dispatch phases: the lint in tests/test_perf.py asserts every
phase string the engine feeds `_compile_obs` is either listed here (and
therefore has a recorder etype and a cost model) or in
`AUX_COMPILE_PHASES` (compile-ledger-only paths with no steady-state
cadence to sample).
"""

from __future__ import annotations

import gc
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from itertools import islice
from typing import Any

__all__ = [
    "AUX_COMPILE_PHASES",
    "AdmitAccount",
    "BlockAccount",
    "CACHE_LAYOUTS",
    "DISPATCH_PHASES",
    "ModelShape",
    "PerfObservatory",
    "RoundAccount",
    "WARMUP_PHASES",
    "decode_flops_per_token",
    "decode_hbm_bytes_per_token",
    "kv_bytes_per_token",
    "layout_name",
    "phase_cost",
    "prefill_flops_per_token",
]

# Steady-state dispatch phases: every one has a CompileLedger phase string,
# a flight-recorder etype, and a cost model in PHASE_COSTS (lint-enforced).
DISPATCH_PHASES = (
    "admit", "chunk", "cnstep", "decode", "fused", "fused_rag", "mixed",
    "pf_rag", "verify",
)
# Compile-ledger-only phases: rare, data-dependent dispatches (COW block
# copies, pool offload staging, host-payload pool puts on the fleet
# prefix-tier import path, preemption restore) with no steady-state
# cadence worth sampling — the ledger's first-dispatch wall is the story.
AUX_COMPILE_PHASES = ("cow", "pool_put", "pool_put_host", "restore")
# Warmup-plannable subset of the dispatch surface (executor/warmup.py):
# phases whose jit argument shapes are a pure function of the engine's
# config (so an AOT lower().compile() can be synthesized from a shape key
# alone, without live traffic). fused/fused_rag/verify depend on the live
# fill mix and speculation state — the planner lists their ledger-observed
# keys but marks them unplannable (they compile on first real dispatch).
WARMUP_PHASES = ("admit", "chunk", "decode", "mixed", "pf_rag")

CACHE_LAYOUTS = ("gqa_bf16", "gqa_int8", "mla_bf16", "mla_int8")

DEFAULT_PERF_SAMPLE = 32
# Timestamped sample windows (observe_sample / samples), values in seconds:
# event_gap — between a stream's successive text events as the engine puts
#   them on its queue, whole (a round's tokens arrive as ONE event, so this
#   is what a reader of the stream sees, where the ITL window spreads the
#   gap over the round's tokens);
# stream_lag — from the engine's put of a text event to the moment the HTTP
#   handler has written its SSE frame to the socket.
# An event_gap sample also says what the device ran between the two events:
# the admit programs dispatched between the rounds that brought them, and
# those programs' padded tokens (`samples(kind, whole=True)`).
SAMPLE_KINDS = ("event_gap", "stream_lag")
# The largest cell (64 streams, a round and its admit programs every 47 ms)
# puts 1,300 gaps a second: 52,000 in its 40 s, 56,000 with the drain, 80,000
# since boot when the benchmark reads them (v5e, PR 37; at 32,768 the first
# 18 s of the 40 were gone by then). 131,072 holds window and drain with rounds
# twice as fast; a full window of four numbers a sample is about 20 MB a kind.
# What is pushed out is counted (`samples_evicted`), so a reader knows when
# its window has lost its start.
SAMPLE_WINDOW = 131072
DEFAULT_TARGET_ITL_MS = 0.0  # no ITL SLO unless configured
# Published per-chip peaks keyed by JAX `device_kind`: (bf16 TFLOP/s, HBM
# GB/s). Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s). A device that is not in the table has no utilization — never a
# default: an MBU against another chip's bandwidth is a wrong number.
CHIP_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v5 lite": (197.0, 819.0),
}


def chip_peaks(device_kind: str) -> tuple[float, float]:
    """(peak bf16 TFLOP/s, peak HBM GB/s) of one chip of `device_kind`.
    Raises for a kind the table does not hold."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)}): add it to "
            "telemetry/perf.py:CHIP_PEAKS with its source"
        ) from None


_SCALE_BYTES = 4  # per-(head, token) quantization scale, f32


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def layout_name(mla: bool, int8: bool) -> str:
    return ("mla" if mla else "gqa") + ("_int8" if int8 else "_bf16")


@dataclass(frozen=True)
class ModelShape:
    """The scalar facts the cost models need, decoupled from ModelConfig so
    this module never imports the models package (which pulls jax)."""

    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    param_count: int
    # MLA latent dims; 0 when the model is plain GQA
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0

    @classmethod
    def from_config(cls, cfg: Any) -> "ModelShape":
        """Duck-typed: accepts any object with ModelConfig's fields."""
        hd = getattr(cfg, "head_dim", 0) or cfg.dim // cfg.n_heads
        return cls(
            dim=cfg.dim,
            n_layers=cfg.n_layers,
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=hd,
            param_count=int(cfg.param_count()),
            kv_lora_rank=getattr(cfg, "kv_lora_rank", 0) or 0,
            qk_rope_head_dim=getattr(cfg, "qk_rope_head_dim", 0) or 0,
        )


# -- cost models -------------------------------------------------------------


def _fused_scale_bytes(n_kv_heads: int, head_dim: int) -> int:
    """Per-token bytes of the fused int8 layout's packed scales: one f32
    scale per (k|v, kv-head, token), packed into pseudo-head rows of the
    payload's row width in int8 lanes — storage rounds up to whole rows, so
    the cost is the padded row width, not the scalars. A row is a head wide,
    or the 128 lanes where narrower heads lie abreast in it
    (kernels/attention.py:kv_heads_abreast, restated: this module imports
    nothing of jax; tests/test_perf.py holds the two to each other)."""
    raw = 2 * n_kv_heads * _SCALE_BYTES
    hd = max(1, head_dim)
    abreast = 128 // hd if hd < 128 and 128 % hd == 0 else 1
    width = hd * (abreast if n_kv_heads % abreast == 0 else 1)
    rows = -(-raw // width)
    return rows * width


def kv_bytes_per_token(shape: ModelShape, layout: str) -> float:
    """KV-cache bytes ONE token occupies across all layers under `layout`.
    GQA stores k+v per kv-head; MLA stores one shared latent row
    (kv_lora_rank + rope key dims). int8 layouts add per-token scales —
    for fused GQA int8, padded to pseudo-head row granularity."""
    L = shape.n_layers
    if layout.startswith("mla"):
        latent = shape.kv_lora_rank + shape.qk_rope_head_dim
        if layout.endswith("int8"):
            return float(L * (latent + _SCALE_BYTES))
        return float(L * latent * 2)  # bf16 latents
    per_tok = 2 * shape.n_kv_heads * shape.head_dim
    if layout.endswith("int8"):
        return float(
            L * (per_tok + _fused_scale_bytes(shape.n_kv_heads, shape.head_dim))
        )
    return float(L * per_tok * 2)  # bf16 k+v


def decode_flops_per_token(shape: ModelShape, layout: str, ctx: float) -> float:
    """FLOPs to decode one token at mean context `ctx`: 2 FLOPs per weight
    (every parameter does one MAC) plus attention. GQA attention is QK^T +
    PV over the context (2 matmuls × 2 FLOPs/MAC per head); MLA's absorbed
    decode form runs both against the latent cache, so the per-head width
    is (kv_lora_rank + rope) for scores and kv_lora_rank for values.
    Layout quantization changes bytes, not FLOPs."""
    weights = 2.0 * shape.param_count
    if layout.startswith("mla"):
        score_w = shape.kv_lora_rank + shape.qk_rope_head_dim
        attn = 2.0 * shape.n_layers * shape.n_heads * ctx * (
            score_w + shape.kv_lora_rank
        )
    else:
        attn = 4.0 * shape.n_layers * shape.n_heads * shape.head_dim * ctx
    return weights + attn


def decode_hbm_bytes_per_token(
    shape: ModelShape,
    layout: str,
    ctx: float,
    rows: float,
    *,
    paged: bool = False,
    block_tokens: int = 16,
    weight_bytes_per_param: float = 1.0,
) -> float:
    """HBM bytes moved per decoded token: the full weight stream amortized
    over the batch rows (one stream serves every row of a step), the KV
    read of the row's whole context, the one-token KV append, and — paged —
    the block-table index gathers (one i32 per block per layer, the
    indirection the kernels' scalar-prefetch path reads)."""
    rows = max(1.0, rows)
    weights = shape.param_count * weight_bytes_per_param / rows
    kv_tok = kv_bytes_per_token(shape, layout)
    kv_read = ctx * kv_tok
    kv_write = kv_tok
    table = 0.0
    if paged:
        table = shape.n_layers * 4.0 * (ctx / max(1, block_tokens))
    return weights + kv_read + kv_write + table


def prefill_flops_per_token(shape: ModelShape, layout: str, ctx: float) -> float:
    """Prefill costs the same weight FLOPs per token; causal attention over
    a prompt averages half the final context per token."""
    return decode_flops_per_token(shape, layout, ctx / 2.0)


def _prefill_cost(shape, layout, ctx, rows, paged, block_tokens):
    flops = prefill_flops_per_token(shape, layout, ctx)
    # prefill is compute-bound: weights stream once per chunk, KV is
    # written (not read back) for every token
    byts = (
        shape.param_count / max(1.0, rows * max(ctx, 1.0))
        + kv_bytes_per_token(shape, layout)
    )
    return flops, byts


def _decode_cost(shape, layout, ctx, rows, paged, block_tokens):
    return (
        decode_flops_per_token(shape, layout, ctx),
        decode_hbm_bytes_per_token(
            shape, layout, ctx, rows, paged=paged, block_tokens=block_tokens
        ),
    )


# Registry of record: one analytical (flops, bytes) model per steady-state
# dispatch phase. verify is decode-shaped (one fused step over the drafted
# tokens); the prefill family shares the chunk model.
PHASE_COSTS = {
    "admit": _prefill_cost,
    "chunk": _prefill_cost,
    "pf_rag": _prefill_cost,
    "cnstep": _decode_cost,  # one masked decode step: decode-shaped
    "decode": _decode_cost,
    "fused": _decode_cost,
    "fused_rag": _decode_cost,
    "mixed": _decode_cost,  # a decode round whose first step carries prompts
    "verify": _decode_cost,
}


def phase_cost(
    phase: str,
    shape: ModelShape,
    layout: str,
    *,
    ctx: float,
    rows: float,
    paged: bool = False,
    block_tokens: int = 16,
) -> tuple[float, float]:
    """(flops_per_token, hbm_bytes_per_token) for one dispatch phase."""
    return PHASE_COSTS[phase](shape, layout, ctx, rows, paged, block_tokens)


def _pctl(vals: list[float], q: float) -> float:
    """Nearest-rank percentile (matches engine.ttft_percentiles)."""
    if not vals:
        return 0.0
    n = len(vals)
    return vals[max(0, min(n - 1, int(n * q + 0.5) - 1))]


class AdmitAccount:
    """What admission cost, in sums since boot (`perf_stats()["admit"]`): a
    reader takes the difference over its window. Written by the engine's
    thread alone, read from any.

    `program` books one admission the engine dispatched as a device program
    of its own: `kind` "batch" (whole prompts through `admit_fn`), "cached"
    (a prefix hit's rows copied into their slots) or "chunk" (a chunk group
    run by itself, nothing decoding). `read` books the read of a batch's
    first tokens. `vacancy` books one slot's empty time, free to seated, in
    three parts on one clock: `cooling_s` (free, fenced until the rounds in
    flight at the free were fetched), `no_request_s` (cool, and the request
    that took the slot had not arrived), `queued_s` (cool, the request
    waiting, no admit program dispatched yet: the engine's own part).

    `ride` books a batch of whole prompts that rode a decode round's first
    step (the engine's `mixed_round_fn`) and took no program: `rides`
    {rounds, prompts, true_tokens, padded_tokens (the round's rung)}. `own`
    books, for every batch that DID take an admit program, why it did not
    ride: `own` {reason: programs} and `own_prompts` {reason: prompts}, the
    reasons "no active rows", "compact", "reads at once", "over the cap",
    "other" (and, in records from before a recurrent configuration's
    admissions rode, "recurrent"). `programs`, `prompts` and the mark stay
    programs of their own alone."""

    SUMS = ("programs", "prompts", "rows_padded", "true_tokens",
            "padded_tokens", "queued_sum", "reads", "reads_blocked",
            "reads_at_once")
    VACANCY = ("count", "cooling_s", "no_request_s", "queued_s")
    RIDES = ("rounds", "prompts", "true_tokens", "padded_tokens")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        for k in self.SUMS:
            setattr(self, k, 0)
        self.by_shape: dict[str, int] = {}  # "rows_padded:bucket" -> programs
        self.held_by: dict[str, int] = {}  # why a batch closed -> programs
        self._vacancy = {**dict.fromkeys(self.VACANCY, 0.0), "count": 0}
        self._rides = dict.fromkeys(self.RIDES, 0)
        self._own: dict[str, int] = {}  # reason -> programs
        self._own_prompts: dict[str, int] = {}  # reason -> prompts

    def ride(self, prompts: int, true_tokens: int, padded_tokens: int) -> None:
        """Book one batch that rode a decode round."""
        r = self._rides
        with self._lock:
            r["rounds"] += 1
            r["prompts"] += prompts
            r["true_tokens"] += true_tokens
            r["padded_tokens"] += padded_tokens

    def own(self, reason: str, prompts: int) -> None:
        """Book why a batch took an admit program of its own."""
        with self._lock:
            self._own[reason] = self._own.get(reason, 0) + 1
            self._own_prompts[reason] = self._own_prompts.get(reason, 0) + prompts

    def program(self, kind: str, rows: int, rows_padded: int, bucket: int,
                true_tokens: int, queued: int, held_by: str = "") -> int:
        """Book one admission dispatched; returns its `aid`, the count of
        admissions dispatched up to and including it."""
        shape = f"{rows_padded}:{bucket}"
        if kind != "batch":
            shape = f"{kind} {shape}"
        with self._lock:
            self.programs += 1
            self.prompts += rows
            self.rows_padded += rows_padded
            self.true_tokens += true_tokens
            self.padded_tokens += rows_padded * bucket
            self.queued_sum += queued
            self.by_shape[shape] = self.by_shape.get(shape, 0) + 1
            if held_by:
                self.held_by[held_by] = self.held_by.get(held_by, 0) + 1
            return self.programs

    def mark(self) -> tuple[int, int]:
        """(admissions dispatched so far, their padded tokens): a place in
        the device's order; the difference of two is what was dispatched
        between them."""
        return self.programs, self.padded_tokens

    def read(self, blocked: bool, at_once: bool) -> None:
        self.reads += 1
        self.reads_blocked += blocked
        self.reads_at_once += at_once

    def vacancy(self, t_free: float, t_cool: float, t_arrived: float,
                t_seat: float) -> tuple[float, float, float]:
        """One vacancy closed at `t_seat`; the three parts sum to seat less
        free whatever the order of the four times."""
        t_cool = min(max(t_cool, t_free), t_seat)
        no_request = min(max(t_arrived - t_cool, 0.0), t_seat - t_cool)
        parts = (t_cool - t_free, no_request, t_seat - t_cool - no_request)
        v = self._vacancy
        with self._lock:
            v["count"] += 1
            v["cooling_s"] += parts[0]
            v["no_request_s"] += parts[1]
            v["queued_s"] += parts[2]
        return parts

    def stats(self) -> dict[str, Any]:
        with self._lock:
            return {
                **{k: getattr(self, k) for k in self.SUMS},
                "by_shape": dict(self.by_shape),
                "held_by": dict(self.held_by),
                "vacancy": dict(self._vacancy),
                "rides": dict(self._rides),
                "own": dict(self._own),
                "own_prompts": dict(self._own_prompts),
            }


# An interval between two retirements of the engine's in-flight queue that is
# longer than this (and than twice the retiring program's mean told device
# seconds) is a stall: a round is 36-122 ms in every cell, and 200 ms is the
# threshold builders' scripts have walked the ring with since PR 38.
# A device trace names a program after its traced function (`jit_<name>`). The
# engine's PLAIN round has ONE name in a trace whatever the configuration's
# round is made of: `decode_chunk` one-token steps (`engine.decode_chunk_fn`)
# or, for a configuration that generates by diffusion over blocks, a block's
# denoising passes and its commit (`engine.block_round_fn`, which takes this
# name where it is jitted). Whoever reads "the plain round" off a trace reads
# `jit_` + this; `perf_stats()["rounds"]["by_program"]` tells the two apart
# (`plain`, `block`), as the ring does (`decode`, `block`).
# doc/observability.md, "The plain round's name in a trace".
PLAIN_ROUND_TRACE_NAME = "decode_chunk_fn"

STALL_S = 0.2
STALL_ROWS = 16  # the newest stalls kept whole

# Seconds this process has spent inside Python's collector, on one pair of
# stamps: (when the collection in progress began or 0.0, the seconds of those
# that have ended), swapped whole. A collection holds the GIL whichever thread
# set it off, so it is the engine thread's time too; but it lets go of it in
# destructors that release it (a device buffer's), and the loop can then come
# by before the collection's end is stamped: the reader counts the part of a
# collection in progress, or a stall would give its seconds to the next
# interval (PR 54: stalls of 0.33-0.40 s with `gc_s` 0.000 in windows whose
# collections summed to 0.39-0.48 s).
_gc = {"v": (0.0, 0.0)}


def _on_gc(phase: str, info: dict) -> None:
    t0, s = _gc["v"]
    now = time.perf_counter()
    _gc["v"] = (now, s) if phase == "start" else (0.0, s + (now - t0 if t0 else 0.0))


def gc_seconds() -> float:
    """Seconds inside `gc` collections since the first `RoundAccount` of the
    process was made, the one in progress up to now."""
    t0, s = _gc["v"]
    return s + (time.perf_counter() - t0 if t0 else 0.0)


class RoundAccount:
    """Every round of the engine's loop, in sums since boot
    (`perf_stats()["rounds"]`): a reader takes the difference over its window.
    Written by the engine's thread alone, read from any.

    `by_program` has one row a step program the loop dispatches as a round,
    under the key the engine knows it by at dispatch: `plain`, `mixed_<rung>`
    (a round whose first step carried prompts, by its packed length), `fused`,
    `fused_rag`. `fetched` books a round at its fetch: `rounds`, `rows`,
    `row_steps` (rows x decode_chunk); the prompts a mixed round carried are
    `AdmitAccount`'s `rides`. `told` books the device seconds of a round that
    could tell them (`told`, `device_s`, and the rows and tokens of those
    same rounds: `told_rows`, `told_tokens`). `delivered` books the tokens
    its emission handed on.

    `retired` is handed every retirement of the in-flight queue (a round's
    end, the read of an admit program) and books a STALL where the interval
    since the retirement before it, the chain unbroken between (`unchain`), is
    longer than `STALL_S` and than twice the retiring program's mean told
    device seconds: `count`, `seconds`, `excess_s` (the interval less that
    mean), `by_phase` {phase: [count, excess_s]} under the loop phase that
    held most of the interval's host seconds, or `first_dispatch` where a
    shape was dispatched for the first time inside it, and `gc_s`, the part of
    the stalls' seconds inside Python's collector; the newest `STALL_ROWS`
    whole in `recent` (`t` on time.monotonic(); `wait_s` the blocked read that
    closed it). The account's own `gc_s` sums the collector's seconds over
    every interval, stall or not."""

    ROW = ("rounds", "rows", "row_steps", "delivered", "told", "told_rows",
           "told_tokens", "device_s")
    TOLD = ("told", "told_rows", "told_tokens", "device_s")

    def __init__(self, lock: threading.Lock | None = None) -> None:
        self._lock = lock or threading.Lock()
        self._rows: dict[str, dict[str, float]] = {}
        self._stalls = {"count": 0, "seconds": 0.0, "excess_s": 0.0,
                        "longest_s": 0.0, "gc_s": 0.0}
        self._by_phase: dict[str, list] = {}
        self._recent: deque[dict[str, Any]] = deque(maxlen=STALL_ROWS)
        self.gc_s = 0.0
        # the retirement before: (its time, the loop's seconds by phase then,
        # first dispatches so far, the collector's seconds so far); None
        # where the chain has broken since (unchain)
        self._prev: tuple | None = None
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def _row(self, prog: str) -> dict[str, float]:
        row = self._rows.get(prog)
        if row is None:
            row = self._rows[prog] = {
                k: 0.0 if k.endswith("_s") else 0 for k in self.ROW}
        return row

    def fetched(self, prog: str, rows: int, row_steps: int) -> None:
        with self._lock:
            row = self._row(prog)
            row["rounds"] += 1
            row["rows"] += rows
            row["row_steps"] += row_steps

    def told(self, prog: str, device_s: float, rows: int, tokens: int) -> None:
        with self._lock:
            row = self._row(prog)
            row["told"] += 1
            row["told_rows"] += max(0, rows)
            row["told_tokens"] += max(0, tokens)
            row["device_s"] += max(0.0, device_s)

    def delivered(self, prog: str, tokens: int) -> None:
        with self._lock:
            self._row(prog)["delivered"] += tokens

    def unchain(self) -> None:
        """The next retirement closes no interval: the loop found nothing in
        flight and nothing to dispatch (a wait for requests is no stall), or
        ran a device program to its end that is no retirement (a standalone
        chunk group, a constrained or a speculative round, an admission read
        at once: the device was busy, and those seconds are no stall either)."""
        self._prev = None

    def retired(self, prog: str, rid: int, now: float, wait_s: float,
                phase_s: dict[str, float], firsts: int) -> dict[str, Any] | None:
        """One retirement at `now` (the engine's time.perf_counter()), its
        blocked read `wait_s` long, with the loop's seconds by phase up to
        `now` and the count of first dispatches so far. Returns the stall's
        row where the interval it closes was one."""
        gc_now = gc_seconds()
        prev, self._prev = self._prev, (now, phase_s, firsts, gc_now)
        if prev is None:
            return None
        p_t, p_phase, p_firsts, p_gc = prev
        seconds, gc_s = now - p_t, gc_now - p_gc
        with self._lock:
            self.gc_s += gc_s
            row = self._rows.get(prog)
            mean = row["device_s"] / row["told"] if row and row["told"] else 0.0
            if seconds <= max(STALL_S, 2.0 * mean):
                return None
            host = {k: v - p_phase.get(k, 0.0) for k, v in phase_s.items()}
            phase = ("first_dispatch" if firsts != p_firsts
                     else max(host, key=host.get) if host else "")
            st = self._stalls
            st["count"] += 1
            st["seconds"] += seconds
            st["excess_s"] += seconds - mean
            st["longest_s"] = max(st["longest_s"], seconds)
            st["gc_s"] += gc_s
            by = self._by_phase.setdefault(phase, [0, 0.0])
            by[0] += 1
            by[1] += seconds - mean
            stall = {"t": time.monotonic(), "seconds": round(seconds, 6),
                     "excess_s": round(seconds - mean, 6), "phase": phase,
                     "program": prog, "rid": rid, "gc_s": round(gc_s, 6),
                     "wait_s": round(wait_s, 6)}
            self._recent.append(stall)
        return stall

    def totals(self) -> dict[str, float]:
        """The told rounds of every program together: the roofline's rate."""
        with self._lock:
            return {k: sum(r[k] for r in self._rows.values()) for k in self.TOLD}

    def stats(self) -> dict[str, Any]:
        with self._lock:
            return {
                "by_program": {k: dict(r) for k, r in self._rows.items()},
                "stalls": {**self._stalls,
                           "by_phase": {k: list(v) for k, v in self._by_phase.items()},
                           "recent": [dict(r) for r in self._recent]},
                "gc_s": self.gc_s,
            }


class BlockAccount:
    """The block rounds of a configuration that generates by diffusion over
    blocks (`cfg.block_len`; engine.block_round_fn), in sums since boot
    (`perf_stats()["blocks"]`): a reader takes the difference over its window.
    Written by the engine's thread alone, read from any.

    `fetched` books a round at its fetch: `rounds`, `rows` (a row is one
    sequence's block), `passes` (the denoising passes the round's program ran:
    its loop ends when no live row holds a mask, so the most any row took),
    `commits` (one a round), `unmasked` (positions a denoising pass filled,
    over the rows), `remainder_tokens` (positions a first block held fixed: the
    prompt's last P mod L tokens) and `by_passes` {passes: blocks that took so
    many}. `delivered` books what the round's emission handed on. `off` counts
    the times each feature such a configuration runs without would have
    engaged (`memory.BLOCK_OFF`, the one list). `attn` is the book of what the
    passes' attention streams and by which arm
    (`kernels/attention.py:BlockAttnStream`, the engine's)."""

    SUMS = ("rounds", "rows", "passes", "commits", "unmasked", "remainder_tokens", "delivered")

    def __init__(self, lock: threading.Lock, off: Any, attn: Any) -> None:
        self._lock = lock
        self._sums = dict.fromkeys(self.SUMS, 0)
        self._by_passes: dict[int, int] = {}
        self.off = dict.fromkeys(off, 0)
        self._attn = attn

    def fetched(self, row_passes: list[int], unmasked: int, remainder: int,
                starts: list[int], batch_rows: int) -> None:
        """One round: each live row's denoising passes, the positions they
        filled and the positions that were the prompt's; where each live row's
        block started and the rows of the batch it went out in."""
        with self._lock:
            s = self._sums
            self._attn.fetched(starts, batch_rows, max(row_passes, default=0) + 1)
            s["rounds"] += 1
            s["rows"] += len(row_passes)
            s["passes"] += max(row_passes, default=0)
            s["commits"] += 1
            s["unmasked"] += unmasked
            s["remainder_tokens"] += remainder
            for n in row_passes:
                self._by_passes[n] = self._by_passes.get(n, 0) + 1

    def delivered(self, tokens: int) -> None:
        with self._lock:
            self._sums["delivered"] += tokens

    def note_off(self, feature: str) -> None:
        self.off[feature] += 1

    def stats(self) -> dict[str, Any]:
        with self._lock:
            return {**self._sums,
                    "by_passes": {str(k): v for k, v in sorted(self._by_passes.items())},
                    "off": dict(self.off), "attn": self._attn.stats()}


class PerfObservatory:
    """Per-process-engine perf state: ITL window, goodput ledger, sampled
    phase attribution, and the roofline evaluation. All writers are the
    engine thread; readers (API, dashboard, benchmark/) take the same small lock
    the writers do, so snapshots are internally consistent."""

    def __init__(
        self,
        shape: ModelShape | None = None,
        *,
        active_layout: str = "gqa_bf16",
        paged: bool = False,
        block_tokens: int = 16,
        weight_bytes_per_param: float = 1.0,
        target_ttft_ms: float | None = None,
        target_itl_ms: float | None = None,
        itl_window: int = 4096,
        device_kind: str = "",
    ):
        self.shape = shape
        # what the engine's device calls itself (jax device_kind): keys the
        # roofline's peaks. "" (a bare observatory) and the CPU have none.
        self.device_kind = device_kind
        self.active_layout = active_layout
        self.paged = paged
        self.block_tokens = max(1, int(block_tokens))
        self.weight_bytes_per_param = weight_bytes_per_param
        self.target_ttft_ms = (
            _env_float("TPU_TARGET_TTFT_MS", 0.0)
            if target_ttft_ms is None else target_ttft_ms
        )
        self.target_itl_ms = (
            _env_float("TPU_TARGET_ITL_MS", DEFAULT_TARGET_ITL_MS)
            if target_itl_ms is None else target_itl_ms
        )
        self._lock = threading.Lock()
        # rolling per-token ITL seconds (percentile window) + a fresh queue
        # the Prometheus bridge drains exactly once per sample
        self._itl = deque(maxlen=max(64, itl_window))
        self._itl_fresh = deque(maxlen=8192)
        self._itl_count = 0
        self._itl_sum_s = 0.0
        # one generic timestamped window: kind -> (time.monotonic(), value),
        # so that a reader can cut by its own window (SAMPLE_KINDS)
        self._samples = {k: deque(maxlen=SAMPLE_WINDOW) for k in SAMPLE_KINDS}
        # samples pushed out of a full window since boot, a kind
        self.samples_evicted = {k: 0 for k in SAMPLE_KINDS}
        # goodput ledger: lifetime counters + a rolling (ts, tokens, good)
        # window for the live tok/s split
        self.finished_requests = 0
        self.good_requests = 0
        self.finished_tokens = 0
        self.good_tokens = 0
        self._finish_window = deque(maxlen=4096)
        # per-tenant goodput ledgers (model zoo tenancy): tenant id ->
        # the same lifetime counters + rolling window as the engine-wide
        # ledger, plus the tenant's shed (429) count. Empty until a
        # request actually carries a tenant id — the single-tenant path
        # allocates nothing here.
        self._tenants: dict[str, dict[str, Any]] = {}
        # sampled phase attribution {phase: {host_s, device_s, wait_s,
        # samples, tokens}} — tokens only for the decode family (the MFU/MBU
        # denominator); dispatch counters drive the every-Nth cadence
        self._phases = {
            p: {"host_s": 0.0, "device_s": 0.0, "wait_s": 0.0,
                "samples": 0, "tokens": 0}
            for p in DISPATCH_PHASES
        }
        self._dispatches = {p: 0 for p in DISPATCH_PHASES}
        # the one book of rounds, by step program: every round's counts, and
        # of those whose device time could be told, sampled or not, seconds
        # and tokens of the SAME rounds, the roofline's measured token rate
        # (observe_device)
        self.rounds = RoundAccount(self._lock)
        # the block rounds' own book, for a configuration that has them
        # (`count_blocks`): None for every other
        self.blocks: BlockAccount | None = None
        # live decode-shape EMAs feeding the roofline (mean context, rows)
        self._ctx_ema = 0.0
        self._rows_ema = 0.0

    def count_blocks(self, off: Any, attn: Any) -> BlockAccount:
        """Open the block rounds' book (once, where the engine is built):
        `stats()["blocks"]` from here on; `off` the features to count, `attn`
        the book of the passes' attention."""
        self.blocks = BlockAccount(self._lock, off, attn)
        return self.blocks

    # -- sampling cadence --------------------------------------------------

    @property
    def sample_every(self) -> int:
        """Dynamic (like TPU_FLIGHT): flip TPU_PERF_SAMPLE on a live
        process. 0 disables sampling entirely."""
        return _env_int("TPU_PERF_SAMPLE", DEFAULT_PERF_SAMPLE)

    def should_sample(self, phase: str) -> bool:
        """True on every Nth dispatch of `phase`. The caller must skip
        first dispatches (those belong to the CompileLedger — a compile
        wall in the steady-state attribution would swamp it)."""
        n = self.sample_every
        c = self._dispatches.get(phase)
        if c is None:
            return False
        self._dispatches[phase] = c + 1
        return n > 0 and (c + 1) % n == 0

    # -- token timelines ---------------------------------------------------

    def observe_itl(self, gap_s: float, n_tokens: int) -> float:
        """One emission round for one request: `n_tokens` arrived
        `gap_s` after the request's previous emission (or its first
        token). Tokens learned in one fetch share the gap evenly — the
        engine only syncs once per round, so a finer split would be
        fiction. Returns the per-token ITL in seconds."""
        if n_tokens <= 0:
            return 0.0
        itl = max(0.0, gap_s) / n_tokens
        with self._lock:
            # cap the fan-out so one giant coalesced round can't flood the
            # percentile window with identical samples
            for _ in range(min(n_tokens, 64)):
                self._itl.append(itl)
                self._itl_fresh.append(itl)
            self._itl_count += n_tokens
            self._itl_sum_s += max(0.0, gap_s)
        return itl

    def itl_percentiles(self) -> dict[str, float]:
        with self._lock:
            vals = sorted(self._itl)
            n = self._itl_count
        return {
            "p50_ms": _pctl(vals, 0.50) * 1e3,
            "p95_ms": _pctl(vals, 0.95) * 1e3,
            "p99_ms": _pctl(vals, 0.99) * 1e3,
            "samples": float(n),
        }

    def drain_itl(self) -> list[float]:
        """ITL samples (seconds) since the last drain — the metrics bridge
        observes each into llmtpu_itl_seconds exactly once."""
        with self._lock:
            vals = list(self._itl_fresh)
            self._itl_fresh.clear()
        return vals

    # -- timestamped samples -----------------------------------------------

    def observe_sample(self, kind: str, value_s: float, *also: float) -> None:
        """One sample of a SAMPLE_KINDS window, stamped time.monotonic(),
        with what the writer knows `also` (an event_gap's admit programs and
        their padded tokens). Written by the engine thread and the HTTP
        handlers' threads. A full window pushes its oldest sample out and
        counts it."""
        win = self._samples.get(kind)
        if win is not None:
            with self._lock:
                if len(win) == win.maxlen:
                    self.samples_evicted[kind] += 1
                win.append((time.monotonic(), max(0.0, value_s), *also))

    def samples(self, kind: str, whole: bool = False) -> list[tuple]:
        """(time.monotonic(), seconds) of every sample the window holds;
        `whole` gives each sample as it was written, (t, seconds, *also)."""
        with self._lock:
            got = list(self._samples.get(kind, ()))
        return got if whole else [s[:2] for s in got]

    def sample_percentiles(self, kind: str) -> dict[str, float]:
        """Over the newest samples only (the ITL window's size), and only
        those are copied under the lock, which the engine thread takes on
        every text event: the dashboard asks often, a reader with a window
        of its own cuts `samples()` itself."""
        with self._lock:
            win = self._samples[kind]
            vals = [s[1] for s in islice(reversed(win), self._itl.maxlen)]
        vals.sort()
        return {
            "p50_ms": _pctl(vals, 0.50) * 1e3,
            "p95_ms": _pctl(vals, 0.95) * 1e3,
            "samples": float(len(vals)),
        }

    # -- goodput accounting ------------------------------------------------

    def finish_request(
        self, ttft_ms: float, itl_mean_ms: float, tokens: int,
        tenant: str = "",
    ) -> bool:
        """Classify one finished request against the joint SLO. A target of
        0 means that axis is unconstrained (matching TTFTBurnDetector's
        no-SLO convention). Returns whether the request was good. A
        non-empty `tenant` also lands the request in that tenant's ledger
        (per-tenant goodput for the zoo scheduler and /v1/debug/perf)."""
        good = (
            (self.target_ttft_ms <= 0 or ttft_ms <= self.target_ttft_ms)
            and (self.target_itl_ms <= 0 or itl_mean_ms <= self.target_itl_ms)
        )
        with self._lock:
            self.finished_requests += 1
            self.finished_tokens += tokens
            if good:
                self.good_requests += 1
                self.good_tokens += tokens
            self._finish_window.append((time.time(), tokens, good))
            if tenant:
                t = self._tenant_locked(tenant)
                t["finished_requests"] += 1
                t["finished_tokens"] += tokens
                if good:
                    t["good_requests"] += 1
                    t["good_tokens"] += tokens
                t["window"].append((time.time(), tokens, good))
        return good

    def _tenant_locked(self, tenant: str) -> dict[str, Any]:
        """Ledger for `tenant`, created on first touch. Caller holds the
        lock."""
        t = self._tenants.get(tenant)
        if t is None:
            t = {
                "finished_requests": 0, "good_requests": 0,
                "finished_tokens": 0, "good_tokens": 0, "shed": 0,
                "window": deque(maxlen=1024),
            }
            self._tenants[tenant] = t
        return t

    def note_tenant_shed(self, tenant: str, n: int = 1) -> None:
        """A per-tenant admission 429: quota or capacity shed charged to
        `tenant`'s ledger (surfaced in /v1/debug/perf and the
        llmtpu_tenant_shed_total metric)."""
        if not tenant:
            return
        with self._lock:
            self._tenant_locked(tenant)["shed"] += int(n)

    def tenant_goodput(self, window_s: float = 60.0) -> dict[str, dict[str, float]]:
        """Per-tenant goodput split, same shape as `goodput()` per entry
        plus the tenant's shed count. Empty dict when no request ever
        carried a tenant id."""
        now = time.time()
        out: dict[str, dict[str, float]] = {}
        with self._lock:
            for name, t in self._tenants.items():
                rows = [r for r in t["window"] if now - r[0] <= window_s]
                ftok, gtok = t["finished_tokens"], t["good_tokens"]
                out[name] = {
                    "goodput_tok_per_s": sum(
                        tok for _, tok, g in rows if g
                    ) / window_s,
                    "raw_finished_tok_per_s": sum(
                        tok for _, tok, _ in rows
                    ) / window_s,
                    "good_requests": float(t["good_requests"]),
                    "finished_requests": float(t["finished_requests"]),
                    "good_tokens": float(gtok),
                    "finished_tokens": float(ftok),
                    "goodput_ratio": (gtok / ftok) if ftok else 1.0,
                    "shed": float(t["shed"]),
                }
        return out

    def tenant_goodput_ratios(self) -> dict[str, float]:
        """Lifetime goodput_ratio per tenant — the SLO-debt signal the
        engine's preemption victim selection reads every preempt
        decision (cheap: no window scan)."""
        with self._lock:
            return {
                name: (t["good_tokens"] / t["finished_tokens"])
                if t["finished_tokens"] else 1.0
                for name, t in self._tenants.items()
            }

    def goodput(self, window_s: float = 60.0) -> dict[str, float]:
        now = time.time()
        with self._lock:
            rows = [r for r in self._finish_window if now - r[0] <= window_s]
            fin, good_r = self.finished_requests, self.good_requests
            ftok, gtok = self.finished_tokens, self.good_tokens
        raw = sum(t for _, t, _ in rows) / window_s
        good = sum(t for _, t, g in rows if g) / window_s
        return {
            "goodput_tok_per_s": good,
            "raw_finished_tok_per_s": raw,
            "good_requests": float(good_r),
            "finished_requests": float(fin),
            "good_tokens": float(gtok),
            "finished_tokens": float(ftok),
            "goodput_ratio": (gtok / ftok) if ftok else 1.0,
            "target_ttft_ms": self.target_ttft_ms,
            "target_itl_ms": self.target_itl_ms,
        }

    # -- sampled phase attribution ----------------------------------------

    def observe_phase(
        self,
        phase: str,
        host_s: float,
        device_s: float,
        wait_s: float = 0.0,
        *,
        tokens: int = 0,
        rows: int = 0,
        ctx_mean: float = 0.0,
    ) -> None:
        rec = self._phases.get(phase)
        if rec is None:
            return
        with self._lock:
            rec["host_s"] += max(0.0, host_s)
            rec["device_s"] += max(0.0, device_s)
            rec["wait_s"] += max(0.0, wait_s)
            rec["samples"] += 1
            rec["tokens"] += max(0, tokens)
            if rows > 0:
                self._rows_ema = (
                    rows if self._rows_ema == 0.0
                    else 0.8 * self._rows_ema + 0.2 * rows
                )
            if ctx_mean > 0:
                self._ctx_ema = (
                    ctx_mean if self._ctx_ema == 0.0
                    else 0.8 * self._ctx_ema + 0.2 * ctx_mean
                )

    def observe_device(
        self, phase: str, prog: str, device_s: float, rows: int, tokens: int,
        sampled: bool,
    ) -> None:
        """A decode round of step program `prog` (`RoundAccount`'s key) whose
        device seconds could be told where it ended, with the rows and tokens
        of that same round. `sampled`: `observe_phase` counted this round at
        its dispatch, with no device seconds yet."""
        rec = self._phases.get(phase)
        if rec is None:
            return
        self.rounds.told(prog, device_s, rows, tokens)
        if sampled:
            with self._lock:
                rec["device_s"] += max(0.0, device_s)

    def phase_attribution(self) -> dict[str, dict[str, float]]:
        with self._lock:
            return {
                p: {
                    "host_s": round(r["host_s"], 6),
                    "device_s": round(r["device_s"], 6),
                    "wait_s": round(r["wait_s"], 6),
                    "samples": float(r["samples"]),
                    "tokens": float(r["tokens"]),
                }
                for p, r in self._phases.items()
            }

    # -- roofline ----------------------------------------------------------

    def roofline(self) -> dict[str, Any]:
        """FLOPs and HBM bytes per token for every cache layout at the live
        decode shape and, where `device_kind` has published peaks, MFU/MBU
        against them. The measured token rate comes from the sampled device
        walls; the four layouts share it so the non-active rows read as
        what-ifs."""
        peaks = CHIP_PEAKS.get(self.device_kind)
        told = self.rounds.totals()
        # the measured rate, and the rows it was measured at, are those of
        # the rounds whose device time could be told, of every step program
        # (the sampled rows EMA stands in until one has been): a round that
        # carried prompts counts its decode rows' tokens and its whole
        # seconds, and the account's rows by program say what each kind took
        tok_s = told["told_tokens"] / told["device_s"] if told["device_s"] > 0 else 0.0
        ctx = self._ctx_ema or 1.0
        rows = (
            told["told_rows"] / told["told"] if told["told_rows"]
            else self._rows_ema or 1.0
        )
        out: dict[str, Any] = {
            "device_kind": self.device_kind,
            "device_tok_per_s": round(tok_s, 1),
            # lifetime sums behind the rate: a reader with a window of its
            # own takes end minus start
            "device_rounds": float(told["told"]),
            "device_s": round(told["device_s"], 6),
            "device_tokens": float(told["told_tokens"]),
            "ctx_mean": round(ctx, 1),
            "rows_mean": round(rows, 2),
            "active_layout": self.active_layout,
            "layouts": {},
        }
        if peaks is not None:
            out["peak_tflops"], out["peak_hbm_gbps"] = peaks
        if self.shape is None:
            return out
        for layout in CACHE_LAYOUTS:
            wb = (
                self.weight_bytes_per_param
                if layout == self.active_layout else
                (1.0 if layout.endswith("int8") else 2.0)
            )
            flops, byts = (
                decode_flops_per_token(self.shape, layout, ctx),
                decode_hbm_bytes_per_token(
                    self.shape, layout, ctx, rows,
                    paged=self.paged, block_tokens=self.block_tokens,
                    weight_bytes_per_param=wb,
                ),
            )
            row = {
                "flops_per_token": flops,
                "hbm_bytes_per_token": byts,
                "arith_intensity": flops / byts if byts else 0.0,
                "active": layout == self.active_layout,
            }
            if peaks is not None:
                row["mfu"] = flops * tok_s / (peaks[0] * 1e12)
                row["mbu"] = byts * tok_s / (peaks[1] * 1e9)
            out["layouts"][layout] = row
        if peaks is not None:
            # utilization only against the peaks of the chip that ran it;
            # counts (flops, bytes) above are from shapes and hold anywhere
            act = out["layouts"][self.active_layout]
            out["decode_mfu"] = round(act["mfu"], 4)
            out["decode_mbu"] = round(act["mbu"], 4)
        return out

    # -- the /v1/debug/perf document --------------------------------------

    def stats(self) -> dict[str, Any]:
        return {
            "sample_every": float(self.sample_every),
            "itl": self.itl_percentiles(),
            **{k: self.sample_percentiles(k) for k in SAMPLE_KINDS},
            "samples_evicted": dict(self.samples_evicted),
            "itl_mean_ms": (
                self._itl_sum_s / self._itl_count * 1e3
                if self._itl_count else 0.0
            ),
            "goodput": self.goodput(),
            "tenants": self.tenant_goodput(),
            "phases": self.phase_attribution(),
            "roofline": self.roofline(),
            "rounds": self.rounds.stats(),
            **({} if self.blocks is None else {"blocks": self.blocks.stats()}),
        }
