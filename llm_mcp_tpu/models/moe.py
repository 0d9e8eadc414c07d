"""Mixture-of-Experts FFN with GShard-style capacity dispatch (TPU-first).

The reference has no MoE (no model execution at all — Ollama serves Mixtral
et al. as opaque names in the catalog, `discovery.go:526-551`). Here MoE is a
real sharded subsystem so Mixtral-class models run in-process.

TPU-first design choices:

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

from .configs import ModelConfig


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


# Rows of a call at or under which `moe_share_ffn` visits each touched expert
# once instead of grouping (row, expert) pairs. The crossing on the v5e at the
# published widths (scripts/moe_crossing.py, ms a layer, expert-major against
# grouped; PERF.md section 6, PR 34): 1.6 against 3.6 at 64 rows, 2.1 / 4.6 at
# 128, 2.5 / 5.0 at 256, 4.1 / 5.7 at 512, 5.8 / 6.3 at 768, 7.6 / 7.1 at
# 1024, 14.9 / 10.4 at 2048. Up to a few hundred rows a touched bank's 31.5 MB
# bound the dense product and the grouped kernel spends its time on groups far
# smaller than its tiles; from about a thousand rows the arithmetic of every
# row against every touched expert (forty times that of the rows that chose
# it, at an even router over 320) costs more than the grouping, and loses.
EXPERT_MAJOR_MAX_ROWS = 768


def share_form(n_rows: int) -> str:
    """Which form `moe_share_ffn` takes for a call of `n_rows` rows (padding
    included: the static shape it is traced at): "expert_major" or "grouped".
    The shape alone decides; the engine's counter asks the same function."""
    return "expert_major" if n_rows <= EXPERT_MAJOR_MAX_ROWS else "grouped"


def _grouped(x, stacks, first, flat_e, sizes, w):
    """Pairs sorted by expert, the held experts' rows through grouped products
    (`jax.lax.ragged_dot`, one group an expert: work and weight traffic follow
    the pairs that landed here and the experts they touched, not E); pairs of
    absent experts sort behind the last group, where a ragged product yields
    zeros. `stacks` are G >= E groups of which [first, first + E) have rows."""
    T, D = x.shape
    k = flat_e.shape[0] // T
    E = sizes.shape[0]
    w1, w3, w2 = stacks
    order = jnp.argsort(flat_e, stable=True)  # [T k] pairs grouped by expert
    rows = order // k
    xs = jnp.take(x, rows, axis=0)  # [T k, D]
    groups = sizes
    if w1.shape[0] != E:
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((w1.shape[0],), jnp.int32), sizes, (first,))
    gate = jax.nn.silu(jax.lax.ragged_dot(xs, w1, groups))
    up = jax.lax.ragged_dot(xs, w3, groups)
    ys = jax.lax.ragged_dot((gate * up).astype(x.dtype), w2, groups)
    w = w[order]
    # a pair of an absent expert weighs 0 whatever its row of `ys` holds
    ys = jnp.where(w[:, None] > 0, ys.astype(jnp.float32) * w[:, None], 0.0)
    return jnp.zeros((T, D), jnp.float32).at[rows].add(ys)


def _expert_major(x, stacks, first, hit, sizes, w):
    """One dense product of ALL rows against each held expert that some row
    chose, weighted by the row's gate for it (0 for a row that did not choose
    it), one touched expert after another in ONE traced loop body; an expert
    nobody chose is not read. The bank is indexed in the stack, by group,
    inside the loop: the product reads it in place."""
    T, D = x.shape
    E = sizes.shape[0]
    w1, w3, w2 = stacks
    # [E, T] a row's gate for each held expert
    dense = jnp.sum(jnp.where(hit, w[:, None], 0.0).reshape(T, -1, E), axis=1).T
    touched = jnp.argsort(sizes == 0, stable=True)  # the touched experts' ids first
    f32 = jnp.float32

    def one(i, y):
        e = touched[i]
        g = first + e
        gate = jax.nn.silu(jnp.dot(x, w1[g], preferred_element_type=f32))
        up = jnp.dot(x, w3[g], preferred_element_type=f32)
        ye = jnp.dot((gate * up).astype(x.dtype), w2[g], preferred_element_type=f32)
        return y + ye * dense[e][:, None]

    return jax.lax.fori_loop(0, jnp.sum(sizes > 0, dtype=jnp.int32), one, jnp.zeros((T, D), f32))


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

    Every (row, chosen expert) pair is kept, in one of two forms of the same
    sum, chosen where the function is traced by the call's row count alone
    (`share_form`): up to some hundreds of rows (a decode round, an admit
    program) each touched expert is visited once with all rows
    (`_expert_major`); above that (the larger chunks of a long prompt) the
    pairs are sorted by expert and go through grouped products (`_grouped`).
    Same routing, same float32 accumulation, same counts.

    A caller inside a layer scan hands over the expert banks STACKED over the
    layers (`banks`, with `layer`) and not this layer's slice: the grouped
    product is a kernel of its own to the TPU's compiler, and a slice of a
    stack that feeds one is copied out first (three banks a layer and step,
    1.2 GB at the published size). The stack goes in whole, as L x E groups
    of which only this layer's E have rows; the expert-major form indexes it
    by (layer, expert) one bank at a time.

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
    if share_form(T) == "expert_major":
        y = _expert_major(x, stacks, first, hit, sizes, w)
    else:
        y = _grouped(x, stacks, first, flat_e, sizes, w)
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
