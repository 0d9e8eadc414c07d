"""Mixture-of-Experts feed-forward layers, in two forms (TPU-first).

Which form a configuration's expert layer takes follows from what its preset
states, with no switch (`share_form`):

  - **`moe_share_ffn`: the dropless share** of ONE member of an expert-parallel
    group. The decoder of unlike layers (models/hybrid.py) has no other form;
    the latent-attention family (models/mla.py) and the dense family
    (models/llama.py: whole-prompt prefill, bucketed chunk and a block round's
    passes) take it where the preset states a share of the published experts
    (`n_router_experts`) or a sigmoid router. Every (row, chosen expert) pair
    that lands on a held expert is kept, sorted in front and multiplied by the
    repo's grouped kernels (kernels/grouped.py) over a window of them; the
    banks go in STACKED over the layers and the layer's work is counted
    (`ExpertCounts`, `perf_stats()["experts"]`).
  - **`moe_ffn`: GShard-style capacity dispatch**, for the presets that state
    no share (Mixtral-class models in the dense family, DeepSeek-V2's softmax
    presets in the latent family), below.

The reference has no MoE (no model execution at all — Ollama serves Mixtral
et al. as opaque names in the catalog, `discovery.go:526-551`). Here MoE is a
real sharded subsystem so Mixtral-class models run in-process.

TPU-first design choices of the capacity dispatch:

  - **Dense dispatch via one-hot matmuls** (Switch/GShard formulation): the
    token→expert routing is expressed as two einsums against a [T, E, C]
    dispatch tensor instead of gather/scatter — everything is static-shaped,
    maps onto the MXU, and GSPMD turns the dispatch einsums into the
    all-to-all when experts are sharded on the `ep` mesh axis.
  - **Stacked expert weights** `[L, E, D, F]`: one batched matmul per layer
    (`ecd,edf->ecf`) instead of E separate matmuls — large MXU tiles, and the
    `E` dim shards cleanly with `P("ep")`.
  - **Capacity-bounded**: each expert processes at most C tokens per step
    (`C = ceil(T·k/E · capacity_factor)`); overflow tokens are dropped from
    that expert (their gate mass is simply lost, residual carries them) —
    the standard trade that keeps shapes static under jit.
  - Router math in float32 (softmax over expert logits), expert FFN in the
    model dtype.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.grouped import ROW_TILE, grouped_ffn
from .configs import ModelConfig


def share_form(cfg: ModelConfig) -> bool:
    """Whether the dense and latent-attention families run a configuration's
    expert layer as `moe_share_ffn` (dropless, counted, the banks handed over
    stacked) and not as `moe_ffn`'s capacity dispatch: where it states a share
    of the published experts (`n_router_experts`) or a sigmoid router, neither
    of which `moe_ffn` computes. From what the preset states, no switch. (The
    decoder of unlike layers, models/hybrid.py, has no other form.)"""
    return bool(cfg.n_experts and (cfg.n_router_experts or cfg.router_score == "sigmoid"))


BANKS = ("w1e", "w3e", "w2e")  # never sliced by layer: moe_share_ffn says why


def expert_stack(cfg: ModelConfig, layers: dict[str, Any]) -> tuple[dict | None, dict]:
    """(banks, rest) of the stacked layers. In the share form the expert banks
    go to `moe_share_ffn` whole with the layer's index and a layer scan slices
    the rest alone (a slice of a stack that feeds a grouped kernel is copied out
    every step: `moe_share_ffn`); else (None, everything)."""
    if not share_form(cfg):
        return None, layers
    return ({n: layers[n] for n in BANKS}, {n: v for n, v in layers.items() if n not in BANKS})


def expert_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    """Static per-expert token capacity for a T-token step."""
    c = math.ceil(n_tokens * cfg.experts_per_tok / cfg.n_experts * cfg.capacity_factor)
    return max(1, min(c, n_tokens))


def route(
    cfg: ModelConfig, router_logits: jnp.ndarray, bias: jnp.ndarray | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The one routing function: (gates [T, k] f32, experts [T, k] int32).

    Scores are a softmax over the router's columns, or with
    `router_score == "sigmoid"` an independent sigmoid of each; the top k are
    chosen greedily over ALL columns (by score + `bias` when the family has a
    selection bias: it chooses and does not weigh). The gates are the chosen
    scores, renormalised to sum to 1 with `norm_topk_prob`, then scaled by
    `routed_scaling_factor` (DeepSeek-V2 scales its raw softmax mass and does
    not renormalise; K-EXAONE states both: renormalised, then times 2.5)."""
    k = cfg.experts_per_tok
    logits = router_logits.astype(jnp.float32)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top_g, top_i = jax.lax.top_k(scores, k)
    else:
        _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        top_g = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.norm_topk_prob and k > 1:
        top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)  # renormalize
    if cfg.routed_scaling_factor != 1.0:
        top_g = top_g * cfg.routed_scaling_factor
    return top_g, top_i


# A window of the grouped form holds this many times the pairs a call of T rows
# lands here in expectation (T k E / Er), rounded up to whole row tiles. It is
# no capacity: held pairs beyond a window take another turn of `_grouped`'s
# loop. At an even router T = 1024 rows hold 1024 +- 29 pairs, so one turn
# nearly always; a window of twice the mean cost 0.4 ms a layer more in the
# gather, the weighing and the sum of rows that hold nothing (v5e, PR 44). A
# decode step's 64 rows get one row tile of 128 for their 47-64 held pairs; a
# smaller tile for them was not built: in Solar's traced rounds the kernels
# stream about 22 touched banks in 0.92 ms a layer, nine tenths of the chip's
# 819 GB/s, and sort, gather, weighing and sum are 0.04 ms (v5e, PR 45).
WINDOW_OVER_MEAN = 1.25


def window_rows(n_rows: int, k: int, held: int, router: int) -> int:
    """Rows of one window of the grouped form: what a call of `n_rows` rows,
    `k` choices a row, lands on `held` of `router` experts in expectation,
    times `WINDOW_OVER_MEAN`, at most all its pairs, in whole row tiles."""
    pairs = n_rows * k
    mean = -(-pairs * held // router)
    rows = min(pairs, math.ceil(mean * WINDOW_OVER_MEAN))
    return -(-rows // ROW_TILE) * ROW_TILE


def _grouped(x, stacks, first, flat_e, sizes, w, rows_a_window):
    """The pairs held HERE sorted in front, expert by expert, and only they
    gathered, multiplied (`kernels/grouped.py`, one group an expert: gate and
    up in one pass, then down) and summed: work and weight traffic follow the
    pairs that landed on this member and the experts they touched, not T k and
    not E. A window of `rows_a_window` sorted pairs at a time, as many windows
    as the held pairs fill (one, unless the router lands far more here than
    its share: `window_rows`); every held pair is in exactly one, whatever the
    router does. `stacks` are G >= E groups of which [first, first + E) have
    rows."""
    T, D = x.shape
    TK = flat_e.shape[0]
    k, C = TK // T, rows_a_window
    order = jnp.argsort(flat_e, stable=True)  # [T k] held pairs first, grouped by expert
    ends = jnp.cumsum(sizes)
    held = ends[-1]

    def window(i, y):
        at = i * C
        idx = at + jnp.arange(C, dtype=jnp.int32)
        pair = order[jnp.minimum(idx, TK - 1)]
        rows = pair // k
        xs = jnp.take(x, rows, axis=0)  # [C, D]
        # [E] each expert's rows of this window, back to back from its row 0
        lo, hi = jnp.clip(ends - sizes - at, 0, C), jnp.clip(ends - at, 0, C)
        ys = grouped_ffn(xs, *stacks, lo, hi, first)
        # a row behind the last held pair weighs 0 whatever the kernel left there
        ys = jnp.where((idx < held)[:, None], ys * w[pair][:, None], 0.0)
        return y.at[rows].add(ys)

    return jax.lax.fori_loop(0, (held + C - 1) // C, window, jnp.zeros((T, D), jnp.float32))


def moe_share_ffn(
    cfg: ModelConfig,
    lp: dict[str, Any],
    x: jnp.ndarray,  # [T, D]
    valid: jnp.ndarray | None = None,  # [T] bool: rows that are tokens
    banks: dict[str, Any] | None = None,  # the STACKED w1e, w3e, w2e [L, E, ..]
    layer: jnp.ndarray | int = 0,  # which of the stack's layers, with `banks`
    prompt: jnp.ndarray | None = None,  # [T] bool: rows that are a prompt's (a mixed step)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless expert layer of ONE member of an expert-parallel group.

    The router scores all `cfg.router_width` published experts (`router`
    [D, Er], optional `router_bias` [Er]); this member holds experts
    [0, E) of them (`w1e`, `w3e` [E, D, F], `w2e` [E, F, D]) and returns
    sum over a row's chosen e < E of gate_e * SwiGLU_e(x), plus the shared
    expert. What the other members' experts would add is NOT here and nothing
    stands in for it. With Er == E it is the whole layer.

    Every (row, chosen expert) pair is kept, whatever the call's rows (a decode
    step's 64, a mixed step's 64 + 256, a prompt's thousand): the pairs held
    here are sorted in front, expert by expert, and they alone are gathered, go
    through grouped products and are summed (`_grouped`). Routing in float32,
    bfloat16 operands and float32 accumulation in all three products, gate x
    up, the gates and the sum over a row's pairs in float32.

    A caller inside a layer scan hands over the expert banks STACKED over the
    layers (`banks`, with `layer`) and not this layer's slice: the grouped
    product is a kernel of its own to the TPU's compiler, and a slice of a
    stack that feeds one is copied out first (three banks a layer and step,
    1.2 GB at the published size). The stack goes in whole, as L x E groups
    of which this layer's E are named by index.

    Returns (y [T, D], counts int32 [5]): rows routed, pairs on held experts,
    distinct held experts touched, the fullest held expert's rows, and 1 (a
    call) — what the engine's counters sum per layer (executor/memory.py:
    StatePool). A call whose rows are of both phases (a mixed step: decode rows
    and prompt tokens through ONE pass over the banks) says which are which
    with `prompt` and gets the counts of each, [2, 5]: the decode rows' under
    [0], the prompt tokens' under [1], by masks on the router's choice."""
    T, D = x.shape
    k = cfg.experts_per_tok
    E = (banks or lp)["w1e"].shape[-3]
    # The router's product in float32: of the router's 8 choices among 320
    # scores, the 8th and the 9th lie about 0.05 apart in the logit, and a
    # bfloat16 product rounds a logit of 2 by up to 0.008: one row in five
    # changed an expert a layer, and a served token lay up to 0.05 of its
    # row's max |logit| under the reference's choice (v5e, PR 32).
    logits = jnp.dot(x.astype(jnp.float32), lp["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gates, experts = route(cfg, logits, lp.get("router_bias"))
    held = experts < E
    if valid is not None:
        held = held & valid[:, None]
    flat_e = jnp.where(held, experts, E).reshape(-1)  # absent/padding → group E
    hit = flat_e[:, None] == jnp.arange(E, dtype=flat_e.dtype)[None, :]  # [T k, E]
    sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)  # [E] rows of each held expert
    w = jnp.where(held, gates, 0.0).reshape(-1)  # [T k] a pair's weight, 0 off this member
    if banks is None:
        stacks, first = (lp["w1e"], lp["w3e"], lp["w2e"]), 0
    else:
        L = banks["w1e"].shape[0]
        stacks = tuple(banks[n].reshape(L * E, *banks[n].shape[2:]) for n in ("w1e", "w3e", "w2e"))
        first = jnp.asarray(layer, jnp.int32) * E
    y = _grouped(x, stacks, first, flat_e, sizes, w, window_rows(T, k, E, lp["router"].shape[-1]))
    y = y.astype(x.dtype)
    if "w1s" in lp:
        from .quant import qdot

        sg = jax.nn.silu(qdot(x, lp["w1s"]))
        y = y + qdot(sg * qdot(x, lp["w3s"]), lp["w2s"])
    def counted(rows, sizes):
        return jnp.stack([
            jnp.asarray(rows, jnp.int32), jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32),
            jnp.max(sizes), jnp.int32(1),
        ])

    if prompt is None:
        return y, counted(T if valid is None else jnp.sum(valid, dtype=jnp.int32), sizes)
    live = jnp.ones((T,), bool) if valid is None else valid
    of_prompts = jnp.sum(hit & jnp.repeat(prompt, k)[:, None], axis=0, dtype=jnp.int32)
    return y, jnp.stack([
        counted(jnp.sum(live & ~prompt, dtype=jnp.int32), sizes - of_prompts),
        counted(jnp.sum(live & prompt, dtype=jnp.int32), of_prompts)])


def moe_dispatch(
    cfg: ModelConfig,
    router_logits: jnp.ndarray,
    capacity: int,
    valid: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Build (dispatch [T, E, C] model-dtype 0/1, combine [T, E, C] f32 gates).

    Top-k routing with normalized gates; position-in-expert assigned by
    cumulative count with slot-0 priority (GShard), tokens beyond capacity
    dropped.

    `valid` ([T] bool) excludes rows from routing entirely: bucket-padding
    tokens must not consume expert capacity ahead of real tokens (the
    cumsum priority is positional, so garbage rows earlier in the flattened
    batch would otherwise steal slots and change real tokens' outputs).
    """
    T, E = router_logits.shape
    k = cfg.experts_per_tok
    top_g, top_i = route(cfg, router_logits)  # [T, k]

    dispatch = jnp.zeros((T, E, capacity), dtype=jnp.float32)
    combine = jnp.zeros((T, E, capacity), dtype=jnp.float32)
    prev_count = jnp.zeros((E,), dtype=jnp.int32)
    for j in range(k):  # k is tiny and static (1-2 typically)
        mask_j = jax.nn.one_hot(top_i[:, j], E, dtype=jnp.int32)  # [T, E]
        if valid is not None:
            mask_j = mask_j * valid.astype(jnp.int32)[:, None]
        pos_j = jnp.cumsum(mask_j, axis=0) - 1 + prev_count[None, :]  # [T, E]
        prev_count = prev_count + jnp.sum(mask_j, axis=0)
        keep = (pos_j < capacity) & (mask_j > 0)  # [T, E]
        slot = jax.nn.one_hot(jnp.clip(pos_j, 0, capacity - 1), capacity)  # [T,E,C]
        sel = jnp.where(keep[..., None], slot, 0.0)
        dispatch = dispatch + sel
        combine = combine + sel * top_g[:, j][:, None, None]
    return dispatch, combine


def moe_ffn(
    cfg: ModelConfig,
    lp: dict[str, Any],
    x: jnp.ndarray,
    capacity: int | None = None,
    valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Sparse FFN over flattened tokens x: [T, D] → [T, D].

    lp holds this layer's "router" [D, E], "w1e"/"w3e" [E, D, F],
    "w2e" [E, F, D] (sliced from the stacked [L, ...] tree by the caller's
    scan). With `P("ep")` on the E dim, GSPMD inserts the token all-to-all
    around the batched expert matmuls.

    `capacity=T` makes the layer dropless — decode passes this (a [B, E, B]
    dispatch over engine slots is tiny, and dropping tokens at decode time
    would silently degrade generations); prefill uses the capacity factor to
    bound the batched expert matmul at large T.
    """
    T, D = x.shape
    C = capacity if capacity is not None else expert_capacity(cfg, T)
    logits = jnp.einsum("td,de->te", x, lp["router"])  # router in f32 below
    dispatch, combine = moe_dispatch(cfg, logits, C, valid=valid)

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)  # [E, C, D]
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, lp["w1e"]))
    up = jnp.einsum("ecd,edf->ecf", xe, lp["w3e"])
    ye = jnp.einsum("ecf,efd->ecd", gate * up, lp["w2e"])  # [E, C, D]
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye)  # [T, D]
    if "w1s" in lp:
        # DeepSeek shared experts: a dense always-on gated MLP added to the
        # routed output (never dropped, no dispatch). qdot so int8-quantized
        # shared weights flow through like any dense linear.
        from .quant import qdot

        sg = jax.nn.silu(qdot(x, lp["w1s"]))
        y = y + qdot(sg * qdot(x, lp["w3s"]), lp["w2s"])
    return y


def init_moe_layer_params(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype, n_layers: int | None = None
) -> dict[str, jnp.ndarray]:
    """Stacked [L, ...] MoE weights (Mixtral-style all-MoE, or the MoE block
    of a DeepSeek first-dense split — `n_layers` overrides the stack depth).

    Routed experts use cfg.moe_ffn_hidden when set (DeepSeek's routed width
    is far narrower than its dense layer-0 FFN); `n_shared_experts` adds the
    always-on shared gated MLP (hidden = n_shared x moe width)."""
    L = cfg.n_layers if n_layers is None else n_layers
    D, E = cfg.dim, cfg.n_experts
    F = cfg.moe_ffn_hidden or cfg.ffn_hidden
    keys = jax.random.split(key, 7)

    def w(k, shape, fan_in):
        return (
            jax.random.normal(k, shape, dtype=jnp.float32) * (fan_in**-0.5)
        ).astype(dtype)

    out = {
        "router": w(keys[0], (L, D, cfg.router_width), D),
        "w1e": w(keys[1], (L, E, D, F), D),
        "w3e": w(keys[2], (L, E, D, F), D),
        "w2e": w(keys[3], (L, E, F, D), F),
    }
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        out["w1s"] = w(keys[4], (L, D, Fs), D)
        out["w3s"] = w(keys[5], (L, D, Fs), D)
        out["w2s"] = w(keys[6], (L, Fs, D), Fs)
    return out
