"""Llama-family causal decoder, pure-JAX functional, designed for XLA/TPU.

Replaces the reference's delegated Ollama `/api/generate`/`/api/chat` execution
(`worker/llm_worker/main.py:222-243`, `core/internal/api/handlers.go:2427-2587`)
with an in-process model. TPU-first choices:

  - **Scan over layers** with stacked per-layer weights (leading dim L): one
    layer's XLA program compiled once, not L times — fast compiles and a small
    executable even at 32+ layers.
  - **Static shapes everywhere**: batch = engine slots, sequence = cache
    capacity; per-slot progress is carried in `lengths` (int32) and masking,
    never in array shapes — so jit compiles once per (batch, bucket).
  - **KV cache layout [L, B, Hkv, S, hd]**: heads before sequence so the
    trailing (S, hd) dims match native TPU (sublane, lane) tiling — the
    Pallas kernels stream K/V at full HBM bandwidth (kernels/attention.py).
  - **bfloat16 weights/activations, float32 softmax and logits.**
  - Sampling is fused into the decode step (see ops/sampling.py) so only [B]
    token ids leave the device per step.
  - `attn_impl="pallas"` routes attention through the fused flash kernels;
    "xla" uses einsum contractions (GQA) that XLA maps onto the MXU. Both
    paths share every other op, and tests assert they agree.

A configuration that generates by diffusion over BLOCKS (`cfg.block_len`: SDAR)
runs here too: positions lie in blocks of L, a query sees the keys of its own and
of every earlier block (`_in_block`: `prefill_masks`, the prompt kernel's edge
blocks, `_chunk_attention`, `packed_prompt_attn`), logits are unshifted, and a
block is filled by `block_denoise` (a pass over the bucketed chunk's machinery
that writes no cache, the sampler with its probability, `block_unmask`) and
committed by `block_pass(commit=True)`; executor/engine.py:block_round_fn loops
them. Where the preset states a share of the published experts the feed-forward
is `moe.moe_share_ffn` with the banks stacked (`moe.expert_stack`, `_ffn`) and the
counts ride the cache pair's second member (`init_kv_cache`).

Layout conventions:
  params["layers"][name]: [L, ...] stacked weights
  KV cache: k, v: [L, B, Hkv, S, hd]
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.attention import (
    _interpret,
    _note_fall,
    _q8_step_rows,
    append_kv_bf16,
    append_kv_q8,
    block_attend_q8,
    blocked_arm_fits,
    ctx_apart,
    decode_attend_bf16,
    decode_attend_q8,
    flash_prefill_attention,
    fused_kv,
    fused_q8_heads,
    kv_abreast,
    kv_heads_abreast,
    paged_gather,
    q8_block_tokens,
    q_abreast,
    ragged_prefill_attend_bf16,
    ragged_prefill_attend_q8,
    rope_put,
)
from ..ops.norms import rms_norm as _rms_norm
from ..ops.rope import rope_tables, apply_rope
from .configs import ModelConfig
from .moe import expert_stack, init_moe_layer_params, moe_ffn, moe_share_ffn, share_form
from .quant import (
    embed_lookup,
    logits_head,
    pack_scales,
    qdot,
    scale_pack_width,
)

Params = dict[str, Any]


def init_llama_params(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16,
    _dispatch: bool = True,
) -> Params:
    """Random-init weights with fan-in scaling (used when no checkpoint is
    supplied; real weights load via models/weights.py). MLA configs
    (kv_lora_rank > 0) dispatch to models/mla.py, which reuses this body
    for the shared embed/FFN/norm structure via _dispatch=False."""
    if _dispatch and cfg.kv_lora_rank:
        from .mla import init_mla_params

        return init_mla_params(cfg, key, dtype=dtype)
    if _dispatch and cfg.gqa_layers:  # layers of unlike kinds: models/hybrid.py
        from .hybrid import init_hybrid_params

        return init_hybrid_params(cfg, key, dtype=dtype)
    hd = cfg.resolved_head_dim
    L, D, H, Hkv, F, V = (
        cfg.n_layers,
        cfg.dim,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.ffn_hidden,
        cfg.vocab_size,
    )
    keys = jax.random.split(key, 8)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dtype)

    # norm weights init to 1 - offset so an offset-norm family (Gemma's
    # x * (1 + w)) starts at the same identity scale as plain RMSNorm.
    norm_init = jnp.full((L, D), 1.0 - cfg.norm_weight_offset, dtype=dtype)
    layers: Params = {"attn_norm": norm_init, "ffn_norm": norm_init}
    if not cfg.kv_lora_rank:
        # GQA projections — MLA configs (reached with _dispatch=False from
        # init_mla_params) build their factorized attention instead; at
        # 8B-class shapes the discarded GQA weights would be a ~4 GB
        # init-time transient
        layers.update(
            {
                "wq": w(keys[1], (L, D, H * hd), D),
                "wk": w(keys[2], (L, D, Hkv * hd), D),
                "wv": w(keys[3], (L, D, Hkv * hd), D),
                "wo": w(keys[4], (L, H * hd, D), H * hd),
            }
        )
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, H * hd), dtype=dtype)
        layers["bk"] = jnp.zeros((L, Hkv * hd), dtype=dtype)
        layers["bv"] = jnp.zeros((L, Hkv * hd), dtype=dtype)
    if cfg.qk_norm:
        # Qwen3 per-head q/k RMSNorm: one [hd] weight vector per layer; OLMo's
        # runs over the whole projection width (cfg.qk_norm_whole)
        nq, nk = qk_norm_widths(cfg)
        layers["q_norm"] = jnp.ones((L, nq), dtype=dtype)
        layers["k_norm"] = jnp.ones((L, nk), dtype=dtype)
    if cfg.post_norms:
        layers["post_attn_norm"] = norm_init
        layers["post_ffn_norm"] = norm_init
    if cfg.n_experts:
        layers.update(init_moe_layer_params(cfg, keys[5], dtype))
    else:
        layers.update(
            {
                "w1": w(keys[5], (L, D, F), D),
                "w3": w(keys[6], (L, D, F), D),
                "w2": w(keys[7], (L, F, D), F),
            }
        )
    params: Params = {
        "embed": w(keys[0], (V, D), D),
        "layers": layers,
        "final_norm": jnp.full((D,), 1.0 - cfg.norm_weight_offset, dtype=dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(jax.random.fold_in(key, 99), (D, V), D)
    return params


def init_kv_cache(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    dtype: jnp.dtype = jnp.bfloat16,
    quantized: bool = False,
) -> dict[str, Any]:
    """KV cache buffers. `quantized=True` stores int8 payloads with
    per-(token, head) scales — decode is cache-bandwidth-bound once weights
    are int8, so halving KV bytes buys ~25-40% step time at 8B/B≥32 and
    doubles the (batch × context) that fits beside the weights.

    Quantized GQA entries use the FUSED single-payload layout:

        cache["k"] = {"q": int8 [L, B, 2*Hkv/P + p, S, P*hd],
                      "s": dtype [L, B, 2*Hkv, S]}
        cache["v"] = {}   (V rides cache["k"]'s head axis)

    A payload row holds P heads ABREAST (`kernels/attention.py:
    kv_heads_abreast`): 1 at heads of 128 or wider, and where a head is
    narrower than the 128 lanes and divides them as many as fill them (two
    heads of 64; head p*R + r in lanes [p*hd, (p+1)*hd) of row r, R = Hkv/P),
    so that the minor dimension is whole lanes and the chip lays the array
    out as the kernels read it. At a minor dimension of 64 it laid positions
    minor instead, and every step program copied the whole cache to the
    kernels' layout and back (PERF.md section 6, PR 55). P is a function of the
    shape alone, and every reader takes it off the two members' shapes
    (`fused_q8_heads`). Payload rows [0, R) are K, [R, 2*R) are V, and — when
    the scale bytes fit one row (p = 1, `models/quant.py:scale_pack_width`)
    — row 2*R carries the per-position dequant scales BIT-PACKED into
    int8 lanes. One scale a (position, head) either way: the plain "s" array
    does not know of P. The fusion is what lets the blocked decode kernel issue ONE
    DMA per (row, block) cell instead of the r05 layout's four (kq/ks/vq/vs
    as separate arrays — kernels/attention.py:_attend_q8_blocked_kernel);
    the plain "s" array is dual-written for every consumer that wants
    arithmetic scales (whole-S kernel, XLA einsum paths, chunked prefill).
    The seq axis stays axis 3 in both members — the engine's slot machinery
    (inserts, parking, snapshots) indexes [:, slot, :, pos] unchanged.

    Plain entries are a bare [L,B,Hkv,S,hd] array per side. All forms flow
    through `llama_decode_step` (jit treats them as pytrees).

    MLA configs store latents instead (models/mla.py:init_mla_cache) in the
    same (k, v) pair convention; quantized=True there stores int8 latents
    (a further capacity trade on top of the latent cache's ~3.6x size
    advantage; decode pays a dequant-then-dot on the XLA path)."""
    if cfg.kv_lora_rank:
        from .mla import init_mla_cache

        return init_mla_cache(cfg, batch, max_seq, dtype=dtype, quantized=quantized)
    if cfg.gqa_layers:  # KV rows for the GQA layers, recurrent state for the rest
        from .hybrid import init_hybrid_cache

        return init_hybrid_cache(cfg, batch, max_seq, dtype, quantized)
    hd = cfg.resolved_head_dim
    Hkv = cfg.n_kv_heads
    shape = (cfg.n_layers, batch, Hkv, max_seq, hd)
    if quantized:
        P = kv_heads_abreast(Hkv, hd)
        p = scale_pack_width(Hkv, P * hd, dtype)
        pair = {
            "k": {
                "q": jnp.zeros(
                    (cfg.n_layers, batch, 2 * Hkv // P + p, max_seq, P * hd), dtype=jnp.int8
                ),
                "s": jnp.zeros(
                    (cfg.n_layers, batch, 2 * Hkv, max_seq), dtype=dtype
                ),
            },
            "v": {},
        }
    else:
        pair = {"k": jnp.zeros(shape, dtype=dtype), "v": jnp.zeros(shape, dtype=dtype)}
    if share_form(cfg):
        # the expert layer's counts ride the pair's second member, beside the
        # V rows (none in the fused form): as a latent pair's ride beside its
        # rope keys (models/mla.py; models/hybrid.py says what they are)
        pair["v"] = {"v": pair["v"], "moe": jnp.zeros((2, cfg.n_layers, 5), jnp.int32)}
    return pair


def quantize_kv(kv: jnp.ndarray, scale_dtype=None) -> dict[str, jnp.ndarray]:
    """Quantize a bf16 K or V block to the int8 cache form over its last
    (head_dim) axis: per-(…, token, head) symmetric scales, like the cache's
    write path. Used when inserting prefill KV into a quantized cache."""
    f = kv.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=-1)
    s = amax / 127.0
    q = jnp.where(
        s[..., None] > 0, jnp.round(f / jnp.maximum(s, 1e-30)[..., None]), 0.0
    ).astype(jnp.int8)
    return {"q": q, "s": s.astype(scale_dtype or kv.dtype)}


def fuse_prompt_kv(
    kh: jnp.ndarray,  # [..., Hkv, S, hd] bf16 K rows (head-major)
    vh: jnp.ndarray,  # [..., Hkv, S, hd]
    scale_dtype=None,
) -> dict[str, jnp.ndarray]:
    """Quantize a prompt's K/V rows into the FUSED cache entry
    (`init_kv_cache`): one int8 payload carrying K heads | V heads, P abreast
    in a row as the cache of this shape holds them, | the optional bit-packed
    scale pseudo-head, plus the plain "s" scales. The engine's cache "v"
    member is the empty dict — callers pair the returned dict with `{}`."""
    hd = kh.shape[-1]
    Hkv = kh.shape[-3]
    P = kv_heads_abreast(Hkv, hd)
    kq = quantize_kv(kh, scale_dtype=scale_dtype)
    vq = quantize_kv(vh, scale_dtype=scale_dtype)
    s = jnp.concatenate([kq["s"], vq["s"]], axis=-2)  # [..., 2*Hkv, S]
    pay = jnp.concatenate(
        [kv_abreast(kq["q"], P), kv_abreast(vq["q"], P)], axis=-3
    )  # [..., 2*Hkv/P, S, P*hd]
    if scale_pack_width(Hkv, P * hd, s.dtype):
        pay = jnp.concatenate([pay, pack_scales(s, P * hd)], axis=-3)
    return {"q": pay, "s": s}


def _cache_shape(cache) -> tuple[int, ...]:
    return cache["q"].shape if isinstance(cache, dict) else cache.shape


def _norm(cfg: ModelConfig, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """RMSNorm with the family's weight convention: llama scales by w,
    Gemma by (1 + w) (norm_weight_offset)."""
    if cfg.norm_weight_offset:
        w = w + jnp.asarray(cfg.norm_weight_offset, dtype=w.dtype)
    return _rms_norm(x, w, cfg.norm_eps)


def _sub_in(cfg: ModelConfig, h: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """What a sub-layer (mixing or feed-forward) reads: the normed residual
    stream under `norm_placement` "input", the stream itself under "output"
    (the norm then sits on the sub-layer's output: `_attn_residual`,
    `_ffn_residual`, with the same weight `w`)."""
    return _norm(cfg, h, w) if cfg.norm_placement == "input" else h


def _sub_out(cfg: ModelConfig, y: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """A sub-layer's output as it joins the residual stream: normed with the
    same `w` under `norm_placement` "output", as it is under "input"."""
    return _norm(cfg, y, w) if cfg.norm_placement == "output" else y


def _residual(cfg: ModelConfig, h: jnp.ndarray, out: jnp.ndarray) -> jnp.ndarray:
    """A sub-layer's output onto the residual stream, times
    `residual_multiplier` where the family has one (Granite)."""
    if cfg.residual_multiplier != 1.0:
        out = out * jnp.asarray(cfg.residual_multiplier, dtype=out.dtype)
    return h + out


def qk_norm_widths(cfg: ModelConfig) -> tuple[int, int]:
    """Lengths of a layer's q_norm and k_norm vectors: the whole projection
    (OLMo, `qk_norm_whole`) or one head (Qwen3)."""
    hd = cfg.resolved_head_dim
    return (cfg.n_heads * hd, cfg.n_kv_heads * hd) if cfg.qk_norm_whole else (hd, hd)


def _act(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.gelu(x) if cfg.act == "gelu" else jax.nn.silu(x)


def _softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(x / cap) * cap if cap else x


def _qkv(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    """Q/K/V projections (+ family bias / qk-norm) on [..., D] activations;
    outputs stay flat [..., H*hd] / [..., Hkv*hd] — callers reshape for
    their layout. This is the single seam every attention path (prefill,
    chunked prefill, both decode steps) goes through, so per-family query/
    key transforms live here exactly once."""
    if "wqkv" in lp:
        # single-chip fused projection (models/quant.py:fuse_layer_weights):
        # one qdot quantizes the activation row once and reads one contiguous
        # int8 weight block instead of three — bitwise-identical outputs,
        # fewer per-matmul dispatch/epilogue round trips in the layer scan
        hd = cfg.resolved_head_dim
        nq, nk = cfg.n_heads * hd, cfg.n_kv_heads * hd
        qkv = qdot(x, lp["wqkv"])
        if cfg.qkv_bias:
            qkv = qkv + lp["bqkv"]
        q = qkv[..., :nq]
        k = qkv[..., nq : nq + nk]
        v = qkv[..., nq + nk :]
    else:
        q = qdot(x, lp["wq"])
        k = qdot(x, lp["wk"])
        v = qdot(x, lp["wv"])
        if cfg.qkv_bias:
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
    if cfg.qk_norm and cfg.qk_norm_whole:
        # OLMo: RMSNorm over the whole projection width, one weight vector
        # each ([H hd] and [Hkv hd]) a layer
        q = _rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = _rms_norm(k, lp["k_norm"], cfg.norm_eps)
    elif cfg.qk_norm:
        # Qwen3: per-head RMSNorm over head_dim, applied pre-rope. Weights
        # are one [hd] vector per layer, shared across heads.
        hd = cfg.resolved_head_dim
        q = _rms_norm(
            q.reshape(*q.shape[:-1], -1, hd), lp["q_norm"], cfg.norm_eps
        ).reshape(q.shape)
        k = _rms_norm(
            k.reshape(*k.shape[:-1], -1, hd), lp["k_norm"], cfg.norm_eps
        ).reshape(k.shape)
    return q, k, v


def _attn_residual(
    cfg: ModelConfig, lp: Params, ctx: jnp.ndarray, h: jnp.ndarray,
    x: jnp.ndarray | None = None,
):
    """Output projection (+ optional post-attention norm) and residual add.
    A layer with an output gate (`wg`, cfg.attn_gate) multiplies the heads'
    output by sigmoid(x W_gate), x being the layer's input (`_sub_in`). Under
    `norm_placement` "output" the layer's one norm sits here."""
    if "wg" in lp:
        ctx = ctx * jax.nn.sigmoid(qdot(x, lp["wg"]).astype(jnp.float32)).astype(ctx.dtype)
    out = qdot(ctx, lp["wo"])
    if cfg.post_norms:
        out = _norm(cfg, out, lp["post_attn_norm"])
    return _residual(cfg, h, _sub_out(cfg, out, lp["attn_norm"]))


@jax.named_scope("ffn")
def _ffn_residual(
    cfg: ModelConfig,
    lp: Params,
    h: jnp.ndarray,
    moe_capacity: int = 0,
    moe_valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The FFN half of a decoder layer (norm by `norm_placement`, MoE or
    gated-MLP, optional post-norm, residual add) on [..., D] activations —
    shared by prefill, chunked prefill, and decode so layer semantics live in
    one place."""
    x = _sub_in(cfg, h, lp["ffn_norm"])
    # dispatch on THIS LAYER's params, not cfg: DeepSeek-style models carry
    # a dense prologue (params["dense_layers"], cfg.first_dense_layers)
    # through the same layer function as their MoE stack
    if "router" in lp:
        lead = x.shape[:-1]
        flat = x.reshape(-1, x.shape[-1])
        fvalid = moe_valid.reshape(-1) if moe_valid is not None else None
        out = (
            moe_ffn(cfg, lp, flat, capacity=moe_capacity, valid=fvalid)
            if moe_capacity
            else moe_ffn(cfg, lp, flat, valid=fvalid)
        )
        out = out.reshape(*lead, -1)
    elif "w13" in lp:
        # single-chip fused gate|up (models/quant.py:fuse_layer_weights) —
        # same w8a8 epilogue-fusion move as wqkv
        g13 = qdot(x, lp["w13"])
        F = g13.shape[-1] // 2
        gate = _act(cfg, g13[..., :F])
        out = qdot(gate * g13[..., F:], lp["w2"])
    else:
        gate = _act(cfg, qdot(x, lp["w1"]))
        up = qdot(x, lp["w3"])
        out = qdot(gate * up, lp["w2"])
    if cfg.post_norms:
        out = _norm(cfg, out, lp["post_ffn_norm"])
    return _residual(cfg, h, _sub_out(cfg, out, lp["ffn_norm"]))


def _ffn(cfg: ModelConfig, lp: Params, banks: Params | None, li, h, valid=None, prompt=None):
    """Feed-forward half of layer `li` and residual add on [..., D]: (h, counts
    [5] of the expert layer in the share form (`banks`: `moe.expert_stack`),
    None for every other feed-forward); with `prompt` [N] (a mixed step's rows:
    which are a prompt's) the counts of each phase, [2, 5]. The one share-form
    layer of the three families (models/hybrid.py and models/mla.py call this)."""
    if banks is None:
        return _ffn_residual(cfg, lp, h, moe_valid=valid), None
    with jax.named_scope("ffn"):
        x = _sub_in(cfg, h, lp["ffn_norm"])
        y, counts = moe_share_ffn(
            cfg, lp, x.reshape(-1, x.shape[-1]),
            valid=None if valid is None else valid.reshape(-1), banks=banks, layer=li,
            prompt=prompt)
        return _residual(cfg, h, _sub_out(cfg, y.reshape(h.shape), lp["ffn_norm"])), counts


def _rows(cache_v: Any) -> Any:
    """The rows of the cache pair's second member (a dense pair's V rows, a
    latent pair's rope keys: models/mla.py): the member itself, or its "v" where
    the expert counts ride beside them (`init_kv_cache`, `mla.init_mla_cache`)."""
    return cache_v["v"] if isinstance(cache_v, dict) and "moe" in cache_v else cache_v


def _second(cache_v: Any, new_v: Any, phase: int, counts) -> Any:
    """The pair's second member after a call: the new rows, and where the
    member carries the expert counts, this call's [L, 5] added onto the running
    sums (decode steps and a block round's passes under 0, prefills under 1)."""
    if counts is None or not (isinstance(cache_v, dict) and "moe" in cache_v):
        return new_v
    return {"v": new_v, "moe": cache_v["moe"].at[phase].add(counts)}


def _in_block(cfg: ModelConfig, q_pos: jnp.ndarray, k_pos: jnp.ndarray) -> jnp.ndarray:
    """Which keys a query sees of positions at or after its own, beside itself:
    none in a causal decoder; with `cfg.block_len` those of its own block."""
    if not cfg.block_len:
        return k_pos <= q_pos
    return k_pos // cfg.block_len <= q_pos // cfg.block_len


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer attention window sizes, [L] int32 (0 = global attention): the
    family's published list (`cfg.sliding_windows`; a fixed period is written
    out by `configs.periodic_windows`), zeros where nothing slides."""
    wins = cfg.sliding_windows or (0,) * cfg.n_layers
    assert len(wins) == cfg.n_layers, (cfg.name, len(wins), cfg.n_layers)
    return jnp.asarray(wins, dtype=jnp.int32)


def _embed_in(cfg: ModelConfig, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    h = embed_lookup(params["embed"], tokens)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.dim**0.5, dtype=h.dtype)
    if cfg.embed_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embed_multiplier, dtype=h.dtype)
    return h


@jax.named_scope("head")
def _logits(cfg: ModelConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    h = _norm(cfg, h, params["final_norm"])
    src = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = logits_head(src, h, tied=cfg.tie_embeddings)
    if cfg.logits_divisor != 1.0:
        logits = logits / jnp.asarray(cfg.logits_divisor, dtype=logits.dtype)
    return _softcap(logits, cfg.logit_softcap)


def prefill_masks(
    cfg: ModelConfig, S: int, lengths: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(cos [1,S,hd/2], sin, mask [B,S,S]) shared by all prefill layers."""
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]  # [1, S]
    cos, sin = rope_tables(cfg, cfg.resolved_head_dim, positions)
    # Causal + padding mask, computed once: [B, S, S] would be big at long S,
    # so use [1, S, S] causal and fold padding via key-validity [B, 1, S].
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))[None]  # [1, S, S]
    if cfg.block_len:  # causal between blocks, whole inside one
        pos = jnp.arange(S, dtype=jnp.int32)
        causal = _in_block(cfg, pos[:, None], pos[None, :])[None]
    valid_k = (jnp.arange(S)[None, :] < lengths[:, None])[:, None, :]  # [B, 1, S]
    return cos, sin, causal & valid_k


def prefill_layer(
    cfg: ModelConfig,
    lp: Params,  # this layer's weights (un-stacked)
    h: jnp.ndarray,  # [B, S, D]
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    mask: jnp.ndarray,  # [B, S, S]
    lengths: jnp.ndarray,  # [B]
    attn_impl: str = "xla",
    window: jnp.ndarray | int = 0,  # this layer's sliding window (0 = global)
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """One decoder layer over a full prompt. Shared by the scan in
    `llama_prefill` and the stage loop in parallel/pipeline.py."""
    S = h.shape[1]
    h, kv = prefill_attn(cfg, lp, h, cos, sin, mask, lengths, attn_impl, window)
    h = _ffn_residual(
        cfg, lp, h,
        moe_valid=jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None],
    )
    return h, kv


def prefill_attn(
    cfg: ModelConfig, lp: Params, h: jnp.ndarray, cos, sin, mask, lengths,
    attn_impl: str = "xla", window: jnp.ndarray | int = 0, rope: bool | None = None,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """The attention half of `prefill_layer`: (h after the residual add,
    (kh, vh) head-major prompt K/V). models/hybrid.py runs it for its GQA and
    window layers; a family without rope (cfg.use_rope False), or a layer that
    does not rotate (`rope` False), skips the rotation."""
    B, S, _ = h.shape
    hd = cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    G = H // Hkv
    neg = jnp.float32(-1e30)
    window = jnp.asarray(window, dtype=jnp.int32)

    with jax.named_scope("attn"):
        x = _sub_in(cfg, h, lp["attn_norm"])
        q, k, v = _qkv(cfg, lp, x)
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, Hkv, hd)
        v = v.reshape(B, S, Hkv, hd)
        if cfg.use_rope if rope is None else rope:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

        # Cache layout: heads before sequence (see module docstring).
        kh = k.transpose(0, 2, 1, 3)  # [B, Hkv, S, hd]
        vh = v.transpose(0, 2, 1, 3)

        if attn_impl == "pallas":
            qh = q.transpose(0, 2, 1, 3)  # [B, H, S, hd]
            ctx = flash_prefill_attention(
                qh,
                kh,
                vh,
                lengths,
                window=window,
                softcap=cfg.attn_softcap,
                scale=cfg.attn_scale,
                **({"block_len": cfg.block_len} if cfg.block_len else {}),
            )
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        else:
            qg = q.reshape(B, S, Hkv, G, hd)
            scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
            scores = _softcap(scores * cfg.attn_scale, cfg.attn_softcap)
            m = mask
            if cfg.sliding_window:
                # q_pos - k_pos < window; window == 0 disables (global layer)
                diff = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]  # [S, S]
                m = m & ((window == 0) | (diff < window))[None]
            scores = jnp.where(m[:, None, None, :, :], scores, neg)
            probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
            ctx = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(B, S, H * hd)
        h = _attn_residual(cfg, lp, ctx, h, x)
    return h, (kh, vh)


def llama_prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32 (right-padded prompts)
    lengths: jnp.ndarray,  # [B] int32 true prompt lengths
    attn_impl: str = "xla",
    quant_kv: bool = False,
) -> tuple[jnp.ndarray, Any, Any]:
    """Causal self-attention over fresh prompts (no past KV).

    Returns (last_logits [B, V] f32, k [L, B, Hkv, S, Dh], v [...]) — the
    prompt KV to be inserted into the engine cache at the request's slot.

    `quant_kv=True` quantizes each layer's K/V INSIDE the scan into the
    FUSED cache entry form (`fuse_prompt_kv` — K|V|packed-scale payload +
    plain scales, paired with `{}` for v), so the stacked ys are int8
    pytrees and the full bf16 prompt KV never materializes in HBM — at 8B a
    batch-8 × 256-bucket admission would otherwise stack ~1 GB of bf16 KV
    before the engine's quantize step, enough memory pressure to collapse
    serving throughput. Fusing here means every engine insert path receives
    cache-layout-ready rows and never re-derives the packed scale bytes.
    """
    if cfg.kv_lora_rank:  # MLA family: latent cache, query-blocked prefill
        from .mla import mla_prefill

        return mla_prefill(cfg, params, tokens, lengths, quant_kv=quant_kv)
    if cfg.gqa_layers:
        from .hybrid import hybrid_prefill

        return hybrid_prefill(
            cfg, params, tokens, lengths, attn_impl=attn_impl, quant_kv=quant_kv)
    B, S = tokens.shape
    h = _embed_in(cfg, params, tokens)  # [B, S, D]
    cos, sin, mask = prefill_masks(cfg, S, lengths)
    banks, stack = expert_stack(cfg, params["layers"])

    def layer(h, xs):
        lp, win = xs
        h, (kh, vh) = prefill_layer(
            cfg, lp, h, cos, sin, mask, lengths, attn_impl, window=win
        )
        if quant_kv:
            return h, (fuse_prompt_kv(kh, vh), {})
        return h, (kh, vh)

    def share_layer(carry, xs):
        # the share form: the attention half as above, then the dropless
        # expert layer over the prompts' own tokens, its banks stacked
        h, li = carry
        lp, win = xs
        h, (kh, vh) = prefill_attn(cfg, lp, h, cos, sin, mask, lengths, attn_impl, win)
        h, counts = _ffn(cfg, lp, banks, li, h,
                         valid=jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None])
        kv = (fuse_prompt_kv(kh, vh), {}) if quant_kv else (kh, vh)
        return (h, li + 1), (*kv, counts)

    if banks is None:
        h, (ks, vs) = jax.lax.scan(layer, h, (stack, layer_windows(cfg)))
    else:
        (h, _), (ks, vs, counts) = jax.lax.scan(
            share_layer, (h, jnp.int32(0)), (stack, layer_windows(cfg)))
        # the call's expert counts [L, 5] beside the rows, as hybrid_prefill
        # hands them over: the engine adds them once (`hybrid.add_counts`)
        vs = {"v": vs, "moe": counts}

    last = jnp.take_along_axis(
        h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]  # [B, D]
    return _logits(cfg, params, last), ks, vs


def llama_encode(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32 right-padded
    lengths: jnp.ndarray,  # [B] int32 true lengths
    attn_impl: str = "xla",
) -> jnp.ndarray:
    """The causal decoder run as a TEXT ENCODER: hidden state at each
    sequence's last valid position, final-normed and L2-normalized —
    [B, D] unit vectors. This is how decoder-architecture embedding models
    (Qwen3-Embedding: a Qwen3 causal LM with last-token pooling) serve
    through EmbeddingEngine; the bidirectional mean/cls-pooling families
    stay on models/embedder.py. The reference only reaches any embedder
    through Ollama's /api/embed proxy (handlers.go:1942-2015)."""
    h = _embed_in(cfg, params, tokens)  # [B, S, D]
    cos, sin, mask = prefill_masks(cfg, tokens.shape[1], lengths)

    def layer(h, xs):
        lp, win = xs
        h, _ = prefill_layer(
            cfg, lp, h, cos, sin, mask, lengths, attn_impl, window=win
        )
        return h, None

    h, _ = jax.lax.scan(layer, h, (params["layers"], layer_windows(cfg)))
    last = jnp.take_along_axis(
        h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]  # [B, D]
    e = _norm(cfg, last, params["final_norm"]).astype(jnp.float32)
    return e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True), 1e-9)


def _decode_step_q8(
    cfg: ModelConfig,
    params: Params,
    cache_k: dict,
    cache_v: dict,
    tokens: jnp.ndarray,  # [Ba] int32 (compact batch when slot_ids is given)
    lengths: jnp.ndarray,  # [Ba] int32
    slot_ids: jnp.ndarray | None = None,  # [Ba] int32 cache rows (None = 1:1)
    paged: dict | None = None,  # {"tbl","k","v"} physical paging operand
) -> tuple[jnp.ndarray, dict, dict]:
    """Decode step for the int8 cache on the pallas path.

    Structure matters more than arithmetic here: carrying the cache through
    the layer scan and scattering each layer's one-token K/V row costs XLA a
    full cache-payload copy PER LAYER (14.2 ms of a 37.5 ms step at 8B
    B=112 S=1024 — the single largest line item in the decode budget).
    Instead the cache is a scan-invariant operand read by `decode_attend_q8`
    (which overrides this step's position with the exact in-register
    vectors, so correctness never depends on the append having happened),
    the per-layer K/V stack out as scan ys ([L, Ba, Hkv, hd] — 3.7 MB), and
    ONE `append_kv_q8` call rewrites just the 32-row tiles in place.
    Measured: 37.5 -> ~24 ms/step.

    With `slot_ids` the batch axis is COMPACT: row i computes the forward
    pass for cache row slot_ids[i] (slot compaction — at low occupancy the
    weights pass and sampling shrink to the active rows instead of paying
    for every parked slot; the kernels follow the indirection via scalar
    prefetch, so cache traffic also shrinks on the blocked path).
    """
    # fused cache: axis 2 of "q" is 2*Hkv/P + p and its rows P*hd wide — take
    # Hkv and hd from cfg
    L, B, _, S, _ = _cache_shape(cache_k)
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    Ba = tokens.shape[0]
    H = cfg.n_heads
    h = _embed_in(cfg, params, tokens)  # [Ba, D]
    cos, sin = rope_tables(cfg, hd, lengths)  # [Ba, hd/2]

    def layer(carry, xs):
        lp, win = xs
        h, li = carry
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q = q.reshape(Ba, H, hd)
            k = k.reshape(Ba, Hkv, hd)
            v = v.reshape(Ba, Hkv, hd)
            q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
            k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
            qg = q.reshape(Ba, Hkv, H // Hkv, hd)
            ctx = decode_attend_q8(
                qg, k, v, cache_k, cache_v, li, lengths,
                slot_ids=slot_ids, scale=cfg.attn_scale,
                block_tables=None if paged is None else paged["tbl"],
                pool_k=None if paged is None else paged["k"],
            ).reshape(Ba, H * hd)
            h = _attn_residual(cfg, lp, ctx, h)
        h = _ffn_residual(cfg, lp, h, moe_capacity=Ba)
        return (h, li + 1), (k, v)

    (h, _), (knew, vnew) = jax.lax.scan(
        layer,
        (h, jnp.int32(0)),
        (params["layers"], layer_windows(cfg)),
    )
    with jax.named_scope("kv_append"):
        new_k, new_v = append_kv_q8(cache_k, cache_v, knew, vnew, lengths, slot_ids=slot_ids)
    return _logits(cfg, params, h), new_k, new_v


def mixed_step_supported(cfg: ModelConfig) -> bool:
    """Whether a mixed step serves this family. `mixed_step_q8`: the ones whose
    decode step is `_decode_step_q8` with a dense feed-forward (global
    attention, no score softcap, rope, no latent cache, no routed experts).
    `hybrid.hybrid_mixed_step`: a stack with recurrent layers, with rope on its
    attention layers or without, whatever its feed-forward; a stack of window and
    global layers keeps rings, which the mixed step does not write, and takes
    `admit_fn`. A configuration that generates by diffusion over blocks
    (`cfg.block_len`: routed experts in the dense family) has no decode step for
    a prompt to ride: it answers False here and its admissions take `admit_fn`."""
    if cfg.kv_lora_rank or cfg.sliding_window or cfg.attn_softcap:
        return False
    if cfg.recurrent:
        return True
    return not (cfg.n_experts or cfg.attn_gate or not cfg.use_rope)


def packed_prompt_attn(
    cfg: ModelConfig,
    q: jnp.ndarray,  # [T, H, hd] roped
    k: jnp.ndarray,  # [T, Hkv, hd] roped
    v: jnp.ndarray,  # [T, Hkv, hd]
    rowids: jnp.ndarray,  # [T] int32, sorted; pads carry the row count
    positions: jnp.ndarray | None = None,  # [T] int32: each token's place in its prompt
) -> jnp.ndarray:
    """Causal self-attention of several whole prompts packed back to back:
    `prefill_attn`'s product under a segment-and-causal mask, in the
    precision `flash_prefill_attention` computes in (float32 scores, softmax
    and weighted sum; the context rounded to the activations' type once), so
    a riding prompt's K/V and first token are admit_fn's to float32 rounding.
    A token sees the tokens of its own prompt at or before it (packed order
    is position order inside a prompt), and with `cfg.block_len` those of its
    own block of positions too (`positions` says where a token lies in its
    prompt); pads see each other, finite garbage nobody reads."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(T, Hkv, H // Hkv, hd).astype(jnp.float32) * cfg.attn_scale
    scores = jnp.einsum("qhgd,khd->hgqk", qg, k.astype(jnp.float32))
    t = jnp.arange(T, dtype=jnp.int32)
    mask = (rowids[:, None] == rowids[None, :]) & (t[None, :] <= t[:, None])
    if cfg.block_len:
        mask = (rowids[:, None] == rowids[None, :]) & _in_block(
            cfg, positions[:, None], positions[None, :])
    scores = jnp.where(mask[None, None], scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("hgqk,khd->qhgd", probs, v.astype(jnp.float32))
    return ctx.astype(q.dtype).reshape(T, H * hd)


def write_prompt_rows(
    cache: jnp.ndarray,  # [L, B, Hx, S, *rest]: one plane of the KV cache
    new: jnp.ndarray,  # [L, Hx, T, *rest]: whole prompts' rows packed, head-major
    slots: jnp.ndarray,  # [R] int32: the cache row of each prompt
    offsets: jnp.ndarray,  # [R + 1] int32: packed boundaries
    counts: jnp.ndarray | None = None,  # [R] int32: with padding BETWEEN the prompts,
    #   each one's tokens; `offsets` [R] is then where each starts
) -> jnp.ndarray:
    """Land whole packed prompts at position 0 of their slots, IN PLACE:
    `ragged_write_rows` for rows that all start at 0, where one window a
    prompt covers every layer (R updates a plane where the general form
    unrolls R x L; half the seconds to lower and compile the mixed round for
    the described v5e, and as there no second copy of the cache). An empty
    row (offsets equal) selects nothing and writes back what it read."""
    L, B, Hx, S = cache.shape[:4]
    W = min(S, new.shape[2])  # a prompt holds at most T tokens
    ntail = cache.ndim - 4
    win = jnp.arange(W, dtype=jnp.int32).reshape((1, 1, 1, W) + (1,) * ntail)
    # the window may run past the packed buffer's end: doubled, never selected
    twice = jnp.concatenate([new, new], axis=2)
    for r in range(slots.shape[0]):
        rows = jax.lax.dynamic_slice(
            twice, (0, 0, offsets[r]) + (0,) * ntail, (L, Hx, W) + cache.shape[4:]
        )[:, None]  # [L, 1, Hx, W, *rest]
        at = (0, slots[r], 0, 0) + (0,) * ntail
        cur = jax.lax.dynamic_slice(cache, at, (L, 1, Hx, W) + cache.shape[4:])
        keep = win < (offsets[r + 1] - offsets[r] if counts is None else counts[r])
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.where(keep, rows.astype(cache.dtype), cur), at
        )
    return cache


def mixed_step_q8(
    cfg: ModelConfig,
    params: Params,
    cache_k: dict,
    cache_v: dict,
    tokens: jnp.ndarray,  # [B] int32, the full batch's last tokens
    lengths: jnp.ndarray,  # [B] int32 (a parked row carries S)
    p_tokens: jnp.ndarray,  # [T] int32: whole prompts packed back to back
    p_rowids: jnp.ndarray,  # [T] int32: prompt of each token, sorted; pads = R
    p_positions: jnp.ndarray,  # [T] int32: position in its prompt; pads = S
    p_slots: jnp.ndarray,  # [R] int32: the cache row each prompt takes
    p_last_idx: jnp.ndarray,  # [R] int32: packed index of each prompt's last token
    paged: dict | None = None,  # the decode rows' paging operand (`_decode_step_q8`);
    #   a fresh prompt's rows go to its slot's own arena rows, as admit_fn's do
) -> tuple[jnp.ndarray, dict, dict]:
    """`_decode_step_q8` for the full batch with admitted prompts riding the
    same pass over the weights: each layer runs ONE `_qkv`, ONE output
    projection and ONE feed-forward over the B decode rows and the T prompt
    tokens stacked. `qdot` scales activations a row, so a row's products do
    not depend on what shares the matmul: the decode rows get what the plain
    step gives them and the prompt tokens what `llama_prefill` gives them. In
    between the decode rows attend through the cache as ever and the prompt
    tokens attend causally over their own fresh K/V (position 0 on: they read
    no cache, so their numerics do not depend on the quantised rows). After
    the scan the decode rows' K/V is appended, then the prompts' rows land in
    their slots in the cache's fused form (`fuse_prompt_kv`, the scales
    `admit_fn`'s insert writes; `write_prompt_rows`); the prompts' slots are parked rows of the
    decode batch, so the append writes nothing live there first.

    Returns (logits [B + R, V]: the decode rows, then each prompt's last
    token; new_k, new_v)."""
    L, B, _, S, _ = _cache_shape(cache_k)
    Hkv, H, hd = cfg.n_kv_heads, cfg.n_heads, cfg.resolved_head_dim
    T = p_tokens.shape[0]
    R = p_slots.shape[0]
    N = B + T
    p_rowids = jnp.asarray(p_rowids, dtype=jnp.int32)
    h = _embed_in(cfg, params, jnp.concatenate([tokens, p_tokens]))  # [N, D]
    cos, sin = rope_tables(cfg, hd, jnp.concatenate([lengths, p_positions]))

    def layer(carry, xs):
        lp, win = xs
        h, li = carry
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q = apply_rope(q.reshape(N, 1, H, hd), cos[:, None], sin[:, None])[:, 0]
            k = apply_rope(k.reshape(N, 1, Hkv, hd), cos[:, None], sin[:, None])[:, 0]
            v = v.reshape(N, Hkv, hd)
            ctx_d = decode_attend_q8(
                q[:B].reshape(B, Hkv, H // Hkv, hd), k[:B], v[:B],
                cache_k, cache_v, li, lengths, scale=cfg.attn_scale,
                block_tables=None if paged is None else paged["tbl"],
                pool_k=None if paged is None else paged["k"],
            ).reshape(B, H * hd)
            ctx_p = packed_prompt_attn(cfg, q[B:], k[B:], v[B:], p_rowids, p_positions)
            h = _attn_residual(cfg, lp, jnp.concatenate([ctx_d, ctx_p]), h)
        h = _ffn_residual(cfg, lp, h)
        with jax.named_scope("kv_append"):
            fused = fuse_prompt_kv(
                k[B:].transpose(1, 0, 2), v[B:].transpose(1, 0, 2),
                scale_dtype=cache_k["s"].dtype,
            )  # {"q": [2 Hkv / P + p, T, P hd], "s": [2 Hkv, T]}
        return (h, li + 1), (k[:B], v[:B], fused["q"], fused["s"])

    (h, _), (knew, vnew, pq, ps) = jax.lax.scan(
        layer, (h, jnp.int32(0)), (params["layers"], layer_windows(cfg)),
    )
    with jax.named_scope("kv_append"):
        new_k, new_v = append_kv_q8(cache_k, cache_v, knew, vnew, lengths)
        offsets = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.sum(p_rowids[None, :] < jnp.arange(1, R + 1, dtype=jnp.int32)[:, None],
                    axis=1, dtype=jnp.int32),
        ])  # [R + 1] packed boundaries; an unused row is empty and writes nothing
        new_k = {
            "q": write_prompt_rows(new_k["q"], pq, p_slots, offsets),
            "s": write_prompt_rows(new_k["s"], ps, p_slots, offsets),
        }
    last = jnp.take(h[B:], jnp.clip(p_last_idx, 0, T - 1), axis=0)  # [R, D]
    return _logits(cfg, params, jnp.concatenate([h[:B], last])), new_k, new_v


def _decode_step_bf16(
    cfg: ModelConfig,
    params: Params,
    cache_k: jnp.ndarray,  # [L, B, Hkv, S, hd]
    cache_v: jnp.ndarray,
    tokens: jnp.ndarray,  # [Ba] int32 (compact batch when slot_ids is given)
    lengths: jnp.ndarray,  # [Ba] int32
    slot_ids: jnp.ndarray | None = None,  # [Ba] int32 cache rows (None = 1:1)
    paged: dict | None = None,  # {"tbl","k","v"} physical paging operand
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Decode step for the bf16 cache on the pallas path — the structure
    that made the q8 path fast (`_decode_step_q8`), applied to the split
    bf16 cache: the cache rides the layer scan as a scan-INVARIANT operand
    (no per-layer scatter), `decode_attend_bf16` overrides this step's
    position with the exact in-register vectors, the per-layer K/V rows
    stack out as scan ys, and ONE `append_kv_bf16` call rewrites just the
    16-row tiles in place after the scan. Replaces the old in-scan sliced
    kernel (the since-removed `decode_attention_cache` + per-layer carry
    scatter) that `resolve_decode_impl` used to reject in favor of XLA."""
    L, B, Hkv, S, hd = _cache_shape(cache_k)
    Ba = tokens.shape[0]
    H = cfg.n_heads
    h = _embed_in(cfg, params, tokens)  # [Ba, D]
    cos, sin = rope_tables(cfg, hd, lengths)  # [Ba, hd/2]

    def layer(carry, xs):
        lp, win = xs
        h, li = carry
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q = q.reshape(Ba, H, hd)
            k = k.reshape(Ba, Hkv, hd)
            v = v.reshape(Ba, Hkv, hd)
            q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
            k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
            qg = q.reshape(Ba, Hkv, H // Hkv, hd)
            ctx = decode_attend_bf16(
                qg, k, v, cache_k, cache_v, li, lengths,
                slot_ids=slot_ids, scale=cfg.attn_scale,
                block_tables=None if paged is None else paged["tbl"],
                pool_k=None if paged is None else paged["k"],
                pool_v=None if paged is None else paged["v"],
            ).reshape(Ba, H * hd)
            h = _attn_residual(cfg, lp, ctx, h)
        h = _ffn_residual(cfg, lp, h, moe_capacity=Ba)
        return (h, li + 1), (k, v)

    (h, _), (knew, vnew) = jax.lax.scan(
        layer,
        (h, jnp.int32(0)),
        (params["layers"], layer_windows(cfg)),
    )
    with jax.named_scope("kv_append"):
        new_k, new_v = append_kv_bf16(
            cache_k, cache_v, knew, vnew, lengths, slot_ids=slot_ids
        )
    return _logits(cfg, params, h), new_k, new_v


def _chunk_attention(
    cfg: ModelConfig, params: Params, cache_k: Any, tokens, slots, starts, nvalid,
    skey: int = 0, paged: dict | None = None, ring: int = 0,
):
    """What the layers of a bucketed chunk share: (h0 [A, C, D], attend, write).
    `attend(h, ck_all, cv_all, li, lp, win, rope) -> (h, kh, vh)` is the
    attention half of a layer reading cache layer `li` (past rows from the
    PRE-write cache, the chunk's own K/V from registers; `rope` False: a layer
    that does not rotate); `write(ck_all, cv_all, kh, vh, li)` lands the chunk's
    rows. `llama_prefill_chunk_batch` scans them with the dense or routed FFN
    between; models/hybrid.py runs them for its GQA layers, and once more for
    its window layers with `ring` their window: `cache_k` is then the RING
    [Lw, B, .., R, hd], position p at index p mod R. The past segment is
    the whole ring under a mask of each row's own positions, the window is in
    both masks, and `write` lands each row's last R VALID positions at their
    wrapped indices (a padding row of a ragged chunk would replace a live one).

    With `cfg.block_len` the chunk's own segment is masked by block (a query
    sees the VALID keys of its own block of positions too; the past lies in
    earlier blocks whole), and a block round's passes (`block_pass`) come
    through here: `slots` None says that row a IS cache row a (the whole batch
    in order: the past rows are the layer's slice, no row is gathered), and
    `write(..., keep=[A] bool)` leaves a row's cache as it stands where `keep`
    is false (a parked row, whose start lies at the cache's end)."""
    quantized = isinstance(cache_k, dict)
    # fused quantized cache: axis 2 of "q" is 2*Hkv/P + p and its rows P*hd
    # wide — take Hkv and hd from cfg, P from the cache
    L, B, _, S, _ = _cache_shape(cache_k)
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    P = fused_q8_heads(cache_k)[2] if quantized else 1
    H = cfg.n_heads
    G = H // Hkv
    A, C = tokens.shape
    Sk = S if ring else min(skey, S) if skey else S
    assert not ring or paged is None, "a ring is never paged"
    neg = jnp.float32(-1e30)
    in_order = slots is None
    slots = jnp.arange(A, dtype=jnp.int32) if in_order else jnp.asarray(slots, dtype=jnp.int32)
    starts = jnp.asarray(starts, dtype=jnp.int32)
    assert not in_order or (A == B and paged is None and not ring), (A, B)

    # Block-indirect past reads: gather each slot's PAST rows through its
    # block table (shared prefix blocks resolve to pool rows) instead of a
    # contiguous slice. Only the first ceil(Sk/bt) table entries matter —
    # the gather is bounded by the same static skey bucket as before.
    ptbl = None
    if paged is not None:
        nbs_full = paged["tbl"].shape[1]
        bt = S // nbs_full
        nsel = max(1, -(-Sk // bt))
        ptbl = jnp.take(paged["tbl"], slots, axis=0)[:, :nsel]

    h = _embed_in(cfg, params, tokens)  # [A, C, D]
    q_pos = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [A, C]
    cos, sin = rope_tables(cfg, hd, q_pos)  # [A, C, hd/2]
    key_pos = jnp.arange(Sk, dtype=jnp.int32)  # [Sk]
    # past segment: cache rows strictly before each chunk's start
    past_mask = key_pos[None, None, :] < starts[:, None, None]  # [A, 1|C, Sk]
    past_mask = jnp.broadcast_to(past_mask, (A, C, Sk))
    # self segment: causal within the chunk
    c_idx = jnp.arange(C, dtype=jnp.int32)
    self_mask = jnp.broadcast_to(
        (c_idx[None, :] <= c_idx[:, None])[None], (A, C, C)
    )
    if cfg.block_len:
        # by block, on absolute positions; a padding key of a ragged chunk
        # must not be seen by the valid queries of its block
        self_mask = _in_block(cfg, q_pos[:, :, None], q_pos[:, None, :]) & (
            c_idx[None, None, :] < jnp.asarray(nvalid, jnp.int32)[:, None, None])
    if ring:
        # index j holds the last position before the chunk that wraps onto it
        # (negative: never written by this sequence), seen inside the window
        ring_pos = _ring_positions(starts, S)[:, None, :]  # [A, 1, R]
        past_mask = (ring_pos >= 0) & (q_pos[:, :, None] - ring_pos < ring)  # [A, C, R]
        self_mask = self_mask & (c_idx[:, None] - c_idx[None, :] < ring)[None]

    def attend(h, ck_all, cv_all, li, lp, win, rope=None):
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q, k = q.reshape(A, C, H, hd), k.reshape(A, C, Hkv, hd)
            if cfg.use_rope if rope is None else rope:
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            v = v.reshape(A, C, Hkv, hd)
            kh = k.transpose(0, 2, 1, 3)  # [A, Hkv, C, hd]
            vh = v.transpose(0, 2, 1, 3)
            # everything below is in the cache's own arrangement, P heads
            # abreast in a row (P = 1: a head a row): the past rows are read as
            # they lie, [A, R, Sk, W], and a query group's rows hold zeros
            # outside its head's lanes (`q_abreast`). Pulled apart to heads of
            # 64, the past rows wanted positions minor, and the compiler re-laid
            # the whole cache for them at the loop's edge (described-chip
            # compile, PR 55)
            qg = q_abreast(q.reshape(A, C, Hkv, G, hd), P)  # [A, C, R, P*G, W]
            kw, vw = kv_abreast(kh, P), kv_abreast(vh, P)  # [A, R, C, W]

            # ---- reads first: the past rows from the PRE-write cache ----
            if quantized:
                # FUSED layout: K heads [0,Hkv) and V heads [Hkv,2Hkv) share one
                # payload — one slice per slot covers both (the packed-scale
                # pseudo-head past 2*Hkv is never read here; the plain "s" rows
                # carry the arithmetic scales)
                if ptbl is not None:
                    pays = paged_gather(
                        jax.lax.dynamic_index_in_dim(ck_all["q"], li, 0, keepdims=False),
                        jax.lax.dynamic_index_in_dim(paged["k"]["q"], li, 0, keepdims=False),
                        ptbl, nbs=nbs_full,
                    )[:, : 2 * Hkv // P, :Sk]  # [A, 2*Hkv/P, Sk, P*hd] int8
                    srows = paged_gather(
                        jax.lax.dynamic_index_in_dim(ck_all["s"], li, 0, keepdims=False),
                        jax.lax.dynamic_index_in_dim(paged["k"]["s"], li, 0, keepdims=False),
                        ptbl, nbs=nbs_full,
                    )[:, : 2 * Hkv, :Sk]  # [A, 2*Hkv, Sk]
                else:
                    if in_order:
                        # the whole batch in order: the layer's payload as it lies.
                        # (The scales keep their row-by-row slices below: cut
                        # whole out of the stack, the compiler re-laid ALL
                        # layers' scales every layer of every pass, 43% of a
                        # block round; PERF.md section 6, PR 59)
                        pays = jax.lax.dynamic_slice(
                            ck_all["q"], (li, 0, 0, 0, 0), (1, A, 2 * Hkv // P, Sk, P * hd))[0]
                    else:
                        pays = jnp.stack(
                            [
                                jax.lax.dynamic_slice(
                                    ck_all["q"], (li, slots[a], 0, 0, 0),
                                    (1, 1, 2 * Hkv // P, Sk, P * hd),
                                )[0, 0]
                                for a in range(A)
                            ]
                        )  # [A, 2*Hkv/P, Sk, P*hd] int8
                    srows = jnp.stack(
                        [
                            jax.lax.dynamic_slice(
                                ck_all["s"], (li, slots[a], 0, 0), (1, 1, 2 * Hkv, Sk)
                            )[0, 0]
                            for a in range(A)
                        ]
                    )  # [A, 2*Hkv, Sk]
                krows, vrows = pays[:, : Hkv // P], pays[:, Hkv // P :]  # [A, R, Sk, W]
                ksr, vsr = srows[:, :Hkv], srows[:, Hkv:]
            elif ptbl is not None:
                krows = paged_gather(
                    jax.lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False),
                    jax.lax.dynamic_index_in_dim(paged["k"], li, 0, keepdims=False),
                    ptbl, nbs=nbs_full,
                )[:, :, :Sk]
                vrows = paged_gather(
                    jax.lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False),
                    jax.lax.dynamic_index_in_dim(paged["v"], li, 0, keepdims=False),
                    ptbl, nbs=nbs_full,
                )[:, :, :Sk]
            elif in_order:
                krows = jax.lax.dynamic_slice(ck_all, (li, 0, 0, 0, 0), (1, A, Hkv, Sk, hd))[0]
                vrows = jax.lax.dynamic_slice(cv_all, (li, 0, 0, 0, 0), (1, A, Hkv, Sk, hd))[0]
            else:
                krows = jnp.stack(
                    [
                        jax.lax.dynamic_slice(
                            ck_all, (li, slots[a], 0, 0, 0), (1, 1, Hkv, Sk, hd)
                        )[0, 0]
                        for a in range(A)
                    ]
                )  # [A, Hkv, Sk, hd]
                vrows = jnp.stack(
                    [
                        jax.lax.dynamic_slice(
                            cv_all, (li, slots[a], 0, 0, 0), (1, 1, Hkv, Sk, hd)
                        )[0, 0]
                        for a in range(A)
                    ]
                )

            # past scores (dequant post-dot when the cache is int8)
            s_past = jnp.einsum(
                "achgd,ahsd->ahgcs", qg, krows.astype(h.dtype)
            ).astype(jnp.float32)
            if quantized:
                s_past = s_past * _scales_by_row(ksr.astype(jnp.float32), P, G)
            # self scores: exact, from in-register bf16 K
            s_self = jnp.einsum("achgd,ahtd->ahgct", qg, kw).astype(jnp.float32)
            s_past = _softcap(s_past * cfg.attn_scale, cfg.attn_softcap)
            s_self = _softcap(s_self * cfg.attn_scale, cfg.attn_softcap)

            pm, sm = past_mask, self_mask
            if cfg.sliding_window and not ring:  # a ring's masks hold its window
                pm = pm & (
                    (win == 0)
                    | (q_pos[:, :, None] - key_pos[None, None, :] < win)
                )
                sm = sm & ((win == 0) | (c_idx[None, :] - c_idx[:, None] > -win))
            s_past = jnp.where(pm[:, None, None, :, :], s_past, neg)
            s_self = jnp.where(sm[:, None, None, :, :], s_self, neg)

            # joint softmax over [past | self]
            s = jnp.concatenate([s_past, s_self], axis=-1)  # [A, Hkv, G, C, Sk+C]
            probs = jax.nn.softmax(s, axis=-1)
            p_past, p_self = probs[..., :Sk], probs[..., Sk:]
            if quantized:
                p_past = p_past * _scales_by_row(vsr.astype(jnp.float32), P, G)
            ctx = jnp.einsum(
                "ahgcs,ahsd->achgd", p_past.astype(h.dtype), vrows.astype(h.dtype)
            ) + jnp.einsum("ahgct,ahtd->achgd", p_self.astype(h.dtype), vw)
            ctx = ctx_apart(ctx, P).reshape(A, C, H * hd)
            h = _attn_residual(cfg, lp, ctx, h, x)
        return h, kh, vh

    def write(ck_all, cv_all, kh, vh, li, keep=None):
        def put(plane, rows, a, tail):
            # row a's rows at its start; with `keep`, what stands there where false
            new = rows[a][None, None].astype(plane.dtype)
            at = (li, slots[a], 0, starts[a]) + (0,) * tail
            if keep is not None:
                new = jnp.where(keep[a], new, jax.lax.dynamic_slice(plane, at, new.shape))
            return jax.lax.dynamic_update_slice(plane, new, at)

        def put_kept(plane, rows, a, tail):
            # a block's few positions of row a into the fused int8 cache (a block
            # pass's commit: `keep` is given), in `ragged_write_rows`'s form: the
            # window of a tile's positions that holds them (32 rows of the int8
            # payload, 128 lanes of the plain scales) is read, the new rows
            # selected into it and the window written back. An update of
            # [rows, 4, W] is laid out heads-minor (4 positions pad a tile of 32
            # eightfold), and once no slice of a layer's payload stood in the
            # program to say otherwise (the block pass reads it through a Mosaic
            # call), the compiler re-laid the WHOLE cache to suit it, in and out,
            # every layer; updates of [2 Hkv, 4] did the same to all layers'
            # scales (described-chip compile, PR 60; chip trace, PR 59)
            Wn = min(S, 32 if tail else 128)
            new = rows[a][None, None].astype(plane.dtype)  # [1, 1, rows, C(, W)]: positions at axis 3
            lo = jnp.clip(starts[a], 0, S - Wn)
            at = (li, slots[a], 0, lo) + (0,) * tail
            whole = jax.lax.dynamic_update_slice(
                jnp.zeros(new.shape[:3] + (Wn,) + new.shape[4:], plane.dtype), new,
                (0, 0, 0, starts[a] - lo) + (0,) * tail)
            pos = lo + jnp.arange(Wn, dtype=jnp.int32)
            lands = (pos >= starts[a]) & (pos < starts[a] + C) & keep[a]
            lands = lands.reshape((1, 1, 1, Wn) + (1,) * tail)
            cur = jax.lax.dynamic_slice(plane, at, whole.shape)
            return jax.lax.dynamic_update_slice(plane, jnp.where(lands, whole, cur), at)

        with jax.named_scope("kv_append"):
            if ring:
                # the chunk's row that index j holds after it; negative: an
                # older position, which the ring keeps
                src = _ring_positions(starts + nvalid, S) - starts[:, None]  # [A, R]
                take = jnp.clip(src, 0, C - 1)

                def turn(plane, rows):  # plane [Lw, B, Hx, R, ..], rows [A, Hx, C, ..]
                    for a in range(A):
                        at = (li, slots[a]) + (0,) * (plane.ndim - 2)
                        cur = jax.lax.dynamic_slice(plane, at, (1, 1, *plane.shape[2:]))[0, 0]
                        new = jnp.take(rows[a], take[a], axis=1).astype(plane.dtype)
                        kept = (src[a] >= 0).reshape((1, S) + (1,) * (plane.ndim - 4))
                        plane = jax.lax.dynamic_update_slice(
                            plane, jnp.where(kept, new, cur)[None, None], at)
                    return plane

                if quantized:
                    fused = fuse_prompt_kv(kh, vh, scale_dtype=ck_all["s"].dtype)
                    ck_all = {"q": turn(ck_all["q"], fused["q"]), "s": turn(ck_all["s"], fused["s"])}
                else:
                    ck_all, cv_all = turn(ck_all, kh), turn(cv_all, vh)
            elif quantized:
                # write the chunk's rows in cache layout: fused payload
                # (K|V|packed scales) + plain scales, so later readers — decode
                # kernels included — see a consistent fused entry
                fused = fuse_prompt_kv(kh, vh, scale_dtype=ck_all["s"].dtype)
                to = put if keep is None else put_kept
                if in_order:
                    # A block's few positions a row. The payload rows land row by
                    # row in place; the plain scales as ONE update of the layer's
                    # [A, 2 Hkv, S] (a megabyte read, a select a position, written back):
                    # 64 updates of [2 Hkv, 4] made the compiler lay ALL layers'
                    # scales out heads-minor and back, twice a layer, 120 ms of a
                    # 276 ms round (chip trace, PERF.md section 6, PR 59)
                    pay = ck_all["q"]
                    for a in range(A):
                        pay = to(pay, fused["q"], a, 1)
                    cur = jax.lax.dynamic_slice(ck_all["s"], (li, 0, 0, 0), (1, A, 2 * Hkv, S))[0]
                    off = jnp.arange(S, dtype=jnp.int32)[None, :] - starts[:, None]  # [A, S]
                    for j in range(C):  # selects, not a gather: a gather of [A, 2 Hkv, S] took 6.7 ms a layer
                        at_j = off == j if keep is None else (off == j) & keep[:, None]
                        cur = jnp.where(at_j[:, None, :], fused["s"][:, :, j : j + 1], cur)
                    ck_all = {"q": pay, "s": jax.lax.dynamic_update_slice(
                        ck_all["s"], cur[None], (li, 0, 0, 0))}
                else:
                    for a in range(A):
                        ck_all = {"q": to(ck_all["q"], fused["q"], a, 1),
                                  "s": to(ck_all["s"], fused["s"], a, 0)}
            else:
                for a in range(A):
                    ck_all = put(ck_all, kh, a, 1)
                    cv_all = put(cv_all, vh, a, 1)
        return ck_all, cv_all

    return h, attend, write


def _scales_by_row(ss: jnp.ndarray, abreast: int, group: int) -> jnp.ndarray:
    """Dequant scales [A, Hkv, Sk] as they multiply a chunk's scores
    [A, Hkv / P, P*G, C, Sk] of P heads abreast (`q_abreast`'s rows: head
    p*R + r's G rows are rows [p*G, (p+1)*G) of row r). The kernels' twin
    (`kernels/attention.py:_scales_by_row`) spreads them with a select, having
    no reshape of a tile's sublanes; here a reshape does."""
    if abreast == 1:
        return ss[:, :, None, None, :]
    A, Hkv, Sk = ss.shape
    rows = ss.reshape(A, abreast, Hkv // abreast, Sk).transpose(0, 2, 1, 3)  # [A, R, P, Sk]
    return jnp.repeat(rows, group, axis=2)[:, :, :, None, :]


def _ring_positions(ends: jnp.ndarray, ring_len: int) -> jnp.ndarray:
    """[A, R]: the position that index j of a ring of R holds once a sequence
    has written positions [0, end): the last one below `end` that is j modulo
    R; negative where the sequence has not reached j yet."""
    last = jnp.asarray(ends, jnp.int32)[:, None] - 1
    return last - (last - jnp.arange(ring_len, dtype=jnp.int32)[None, :]) % ring_len


def llama_prefill_chunk_batch(
    cfg: ModelConfig,
    params: Params,
    cache_k: Any,  # [L, B, Hkv, S, hd] engine cache (or int8 {"q","s"} pytree)
    cache_v: Any,
    tokens: jnp.ndarray,  # [A, C] int32 — right-padded chunks, one per slot
    slots: jnp.ndarray,  # [A] int32 — engine slots (distinct, or duplicated row 0 padding)
    starts: jnp.ndarray,  # [A] int32 — absolute position of each chunk's first token
    nvalid: jnp.ndarray,  # [A] int32 — valid tokens per chunk
    skey: int = 0,  # STATIC bound on the PAST key range (0 = whole S); >= max(starts)
    all_logits: bool = False,  # STATIC: logits at every chunk position, not just the last
    paged: dict | None = None,  # {"tbl","k","v"} physical paging operand
) -> tuple[jnp.ndarray, Any, Any]:
    """Batched chunked prefill: one bounded chunk for up to A slots' prompts
    in a single dispatch, written straight into the engine cache.

    Three TPU-first structural choices (each measured against the naive
    form on a v5e chip at 8B):

    - **Batched over slots**: the chunk weight pass dominates chunk cost
      (~65 ms at 8B int8); A prompts amortize it A-fold. A serial admission
      path starves the continuous batch — most slots sit idle waiting to
      prefill (measured 102 tok/s vs ~1.9 k tok/s decode capacity at B=64).
    - **Read-past-then-write**: the chunk attends the slot's PAST rows
      [0, starts) read from the pre-write cache, and its own K/V from
      registers (exact bf16, even when the cache is int8 — the same
      semantics as the decode kernel's current-position override). All cache
      writes happen after the reads: write-after-read updates in place,
      while the read-after-write form costs XLA defensive copies.
    - **Static buckets everywhere**: C and `skey` are compile-time buckets
      (pow2), positions/slots are traced scalars — one executable per
      (A, C, skey) serves every admission forever.

    Padding rows past `nvalid` in a ragged final chunk are written but never
    attended (causal mask; valid q rows never reach garbage columns) and are
    overwritten in place by later decode steps. Engine interleaving:
    executor/engine.py:_stage_prefill_group (token-budget scheduler,
    executor/scheduler.py). The reference never faces any of
    this — it proxies Ollama (`core/internal/api/handlers.go:2427-2587`).

    Returns (logits [A, V] f32 at each row's last valid position — or
    [A, C, V] at every position when `all_logits` (the speculative-decoding
    verify path scores each drafted token against the position before it) —
    new_cache_k, new_cache_v).
    """
    if cfg.kv_lora_rank:  # MLA family: absorbed chunked prefill over latents
        from .mla import mla_prefill_chunk_batch

        return mla_prefill_chunk_batch(
            cfg, params, cache_k, cache_v, tokens, slots, starts, nvalid,
            skey=skey, all_logits=all_logits, paged=paged,
        )
    if cfg.gqa_layers:
        from .hybrid import hybrid_prefill_chunk_batch

        return hybrid_prefill_chunk_batch(
            cfg, params, cache_k, cache_v, tokens, slots, starts, nvalid,
            skey=skey, all_logits=all_logits, paged=paged,
        )
    A, C = tokens.shape
    c_idx = jnp.arange(C, dtype=jnp.int32)
    h, attend, write = _chunk_attention(
        cfg, params, cache_k, tokens, slots, starts, nvalid, skey=skey, paged=paged)
    banks, stack = expert_stack(cfg, params["layers"])
    pair_v, cache_v = cache_v, _rows(cache_v)

    def layer(carry, xs):
        lp, win = xs
        h, ck_all, cv_all, li = carry
        h, kh, vh = attend(h, ck_all, cv_all, li, lp, win)
        h, counts = _ffn(cfg, lp, banks, li, h, valid=c_idx[None, :] < nvalid[:, None])
        # ---- writes last: in-place (write-after-read) ----
        ck_all, cv_all = write(ck_all, cv_all, kh, vh, li)
        return (h, ck_all, cv_all, li + 1), counts

    (h, new_k, new_v, _), counts = jax.lax.scan(
        layer,
        (h, cache_k, cache_v, jnp.int32(0)),
        (stack, layer_windows(cfg)),
    )
    new_v = _second(pair_v, new_v, 1, counts)
    if all_logits:
        return _logits(cfg, params, h), new_k, new_v  # [A, C, V]
    last = jnp.take_along_axis(
        h, (nvalid - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]  # [A, D]
    return _logits(cfg, params, last), new_k, new_v


def llama_prefill_chunk(
    cfg: ModelConfig,
    params: Params,
    cache_k: Any,
    cache_v: Any,
    tokens: jnp.ndarray,  # [C] int32 — single slot's chunk
    slot: jnp.ndarray,
    start: jnp.ndarray,
    nvalid: jnp.ndarray,
    skey: int = 0,
    paged: dict | None = None,
) -> tuple[jnp.ndarray, Any, Any]:
    """Single-slot wrapper over `llama_prefill_chunk_batch` (A=1)."""
    return llama_prefill_chunk_batch(
        cfg,
        params,
        cache_k,
        cache_v,
        tokens[None, :],
        jnp.asarray(slot, dtype=jnp.int32)[None],
        jnp.asarray(start, dtype=jnp.int32)[None],
        jnp.asarray(nvalid, dtype=jnp.int32)[None],
        skey=skey,
        paged=paged,
    )


def block_attn_arm(cfg: ModelConfig, cache_k: Any, attn_impl: str) -> tuple[str, str]:
    """(arm, why not the kernel) of a block pass's attention, from what the
    code can observe: "pallas" (`kernels/attention.py:block_attend_q8`) for
    the fused int8 cache in rows of whole lanes with a block size that divides
    them, where the kernels were asked for and nothing caps or windows the
    scores; "xla" (`_chunk_attention`'s) for anything else. `cache_k` may be
    shapes: the engine asks for its book (`perf_stats()["blocks"]["attn"]`)."""
    if attn_impl != "pallas":
        return "xla", f"attn_impl={attn_impl}"
    if not isinstance(cache_k, dict):
        return "xla", "no int8 cache"
    if cfg.attn_softcap or cfg.sliding_window:
        return "xla", "softcap or sliding window"
    rows, S, W = cache_k["q"].shape[2:]
    if not blocked_arm_fits(W, _interpret()):
        return "xla", f"rows of {W} lanes"
    if not q8_block_tokens(rows, S, W):
        return "xla", f"S={S}: no block size"
    return "pallas", ""


def _block_attend(cfg: ModelConfig, shape: tuple[int, int], slots, starts):
    """`attend` of a block pass on the kernel's arm, in `_chunk_attention`'s
    place and with its signature: the same attention half of a layer
    (`_sub_in`, `_qkv`, rope, `_attn_residual`) around ONE call that reads the
    stacked cache as it lies. Row a is cache row `slots[a]` (None: row a); no
    mask is built here: the past is [0, start) and the block sees itself whole."""
    A, L = shape
    Hkv, hd, H = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_heads
    starts = jnp.asarray(starts, dtype=jnp.int32)
    cos, sin = rope_tables(cfg, hd, starts[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :])

    def attend(h, ck_all, cv_all, li, lp, win, rope=None):
        del cv_all, win  # the fused cache holds V beside K; nothing slides here
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q, k = q.reshape(A, L, H, hd), k.reshape(A, L, Hkv, hd)
            if cfg.use_rope if rope is None else rope:
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            kh = k.transpose(0, 2, 1, 3)  # [A, Hkv, L, hd]
            vh = v.reshape(A, L, Hkv, hd).transpose(0, 2, 1, 3)
            ctx = block_attend_q8(
                q.reshape(A, L, Hkv, H // Hkv, hd), kh, vh, ck_all, li, starts,
                slot_ids=slots, scale=cfg.attn_scale)
            h = _attn_residual(cfg, lp, ctx.reshape(A, L, H * hd), h, x)
        return h, kh, vh

    return attend


def block_pass(
    cfg: ModelConfig,
    params: Params,
    cache_k: Any,
    cache_v: Any,
    tokens: jnp.ndarray,  # [A, L] int32: a block a row (masks, fixed and unmasked tokens)
    slots: jnp.ndarray | None,  # [A] int32 cache rows; None: row a is cache row a
    starts: jnp.ndarray,  # [A] int32: each block's first position, a multiple of L
    live: jnp.ndarray,  # [A] bool: rows that hold a sequence (a parked row routes nothing)
    commit: bool,  # STATIC: write the block's keys and values, return no logits
    skey: int = 0,  # STATIC bound on the past key range (0 = the whole cache row)
    attn_impl: str = "xla",  # STATIC: "pallas" asks for the kernel (`block_attn_arm`)
) -> tuple[jnp.ndarray | None, Any, Any]:
    """One pass of a block round (`cfg.block_len`; executor/engine.py:
    block_round_fn) over the bucketed chunk's machinery: the L positions of
    each row's block against the cache of every earlier block and, whole,
    against each other. A DENOISING pass (`commit` False) writes nothing (the
    layer scan does not even carry the cache) and returns the logits at every
    position [A, L, V], UNSHIFTED: the row at a position that holds the mask is
    the distribution of that position's own token. The COMMIT pass runs the
    block's final tokens, writes their keys and values at [start, start + L) of
    each live row and returns no logits (the head is not run). Either way the
    second member's expert counts, where it carries them, take the pass's under
    phase 0. The attention is the kernel's where `block_attn_arm` says so
    (`_block_attend`: the row's live blocks alone leave HBM), else the
    bucketed chunk's, which reads every row whole; the embedding and `write`
    are the chunk's on both."""
    A, L = tokens.shape
    nvalid = jnp.full((A,), L, jnp.int32)
    h, attend, write = _chunk_attention(cfg, params, cache_k, tokens, slots, starts, nvalid, skey=skey)
    arm, why = block_attn_arm(cfg, cache_k, attn_impl)
    if arm == "pallas":
        attend = _block_attend(cfg, (A, L), slots, starts)
    else:
        _note_fall("block_attn_q8", why, _interpret())
    banks, stack = expert_stack(cfg, params["layers"])
    rows_v = _rows(cache_v)
    valid = jnp.broadcast_to(live[:, None], (A, L))
    xs = (stack, layer_windows(cfg))

    if not commit:
        def layer(carry, xs):
            lp, win = xs
            h, li = carry
            h, _, _ = attend(h, cache_k, rows_v, li, lp, win)
            h, counts = _ffn(cfg, lp, banks, li, h, valid=valid)
            return (h, li + 1), counts

        (h, _), counts = jax.lax.scan(layer, (h, jnp.int32(0)), xs)
        return _logits(cfg, params, h), cache_k, _second(cache_v, rows_v, 0, counts)

    def commit_layer(carry, xs):
        lp, win = xs
        h, ck_all, cv_all, li = carry
        h, kh, vh = attend(h, ck_all, cv_all, li, lp, win)
        h, counts = _ffn(cfg, lp, banks, li, h, valid=valid)
        ck_all, cv_all = write(ck_all, cv_all, kh, vh, li, keep=live)
        return (h, ck_all, cv_all, li + 1), counts

    (_, new_k, new_v, _), counts = jax.lax.scan(
        commit_layer, (h, cache_k, rows_v, jnp.int32(0)), xs)
    return None, new_k, _second(cache_v, new_v, 0, counts)


def block_unmask(
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [A, L] int32 before the pass
    x0: jnp.ndarray,  # [A, L] int32: the pass's sample at every position
    p: jnp.ndarray,  # [A, L] float32: each sample's probability after the request's filters
) -> jnp.ndarray:
    """A denoising pass's unmask rule: the block with the positions due this
    pass filled from `x0`. Of a row's positions that still hold the mask, n =
    L / denoise_steps are due: the n of highest `p` (`low_confidence_static`);
    under `low_confidence_dynamic` every one whose `p` passes the threshold
    where at least n do, else those n. A position that holds a token keeps it;
    a row with fewer than n masks left fills them all."""
    L = tokens.shape[1]
    due = L // cfg.denoise_steps
    masked = tokens == cfg.mask_token_id
    conf = jnp.where(masked, p, -jnp.inf)
    # a position's rank by confidence among its row's (ties: the earlier first)
    rank = jnp.argsort(jnp.argsort(-conf, axis=1, stable=True), axis=1, stable=True)
    take = masked & (rank < due)
    if cfg.unmask_rule == "low_confidence_dynamic":
        high = masked & (p > cfg.unmask_threshold)
        take = jnp.where(jnp.sum(high, axis=1, keepdims=True) >= due, high, take)
    return jnp.where(take, x0, tokens)


def block_denoise(
    cfg: ModelConfig, params: Params, cache_k: Any, cache_v: Any,
    tokens: jnp.ndarray, slots, starts, live,
    key: jax.Array, temp, topk, topp,  # the pass's draw; [A] sampling parameters a row
    allowed: jnp.ndarray | None = None,  # [V] bool: ids the sampler may emit
    skey: int = 0,
    attn_impl: str = "xla",
) -> tuple[jnp.ndarray, Any, jnp.ndarray]:
    """One denoising pass of a block round and its unmask rule: (the block
    after the pass [A, L], the second member with the pass's expert counts,
    the pass's logits [A, L, V]). Every position is sampled with its row's
    parameters (`ops/sampling.py:sample_tokens_p`: the token AND its
    probability after temperature, top-k and top-p; greedy is top-1 at
    probability 1, so a greedy row fills its whole block in its first pass
    under the dynamic rule); only the due masked ones are kept."""
    from ..ops.sampling import sample_tokens_p

    A, L = tokens.shape
    with jax.named_scope("block.denoise"):
        logits, _, cache_v = block_pass(
            cfg, params, cache_k, cache_v, tokens, slots, starts, live, commit=False, skey=skey,
            attn_impl=attn_impl)
    with jax.named_scope("block.unmask"):
        lg = logits if allowed is None else jnp.where(allowed, logits, -jnp.inf)
        x0, p = sample_tokens_p(
            lg.reshape(A * L, -1), key, jnp.repeat(temp, L), jnp.repeat(topk, L),
            jnp.repeat(topp, L), active=jnp.repeat(live, L))
        new = block_unmask(cfg, tokens, x0.reshape(A, L), p.reshape(A, L))
    return new, cache_v, logits


def ragged_write_rows(
    cache: jnp.ndarray,  # [L, B, Hx, S, *rest] — one plane of the KV cache
    new: jnp.ndarray,  # [L, Hx, T, *rest] — the packed chunk's rows, head-major
    slots: jnp.ndarray,  # [R] int32 — cache row per descriptor row
    starts: jnp.ndarray,  # [R] int32 — first position each row writes
    offsets: jnp.ndarray,  # [R+1] int32 — packed row boundaries
) -> jnp.ndarray:
    """Land a packed ragged chunk in the cache, every layer, IN PLACE.

    Descriptor row r's tokens are packed contiguously ([offsets[r],
    offsets[r+1])) and go to contiguous positions of one slot, so each
    (row, layer) is ONE window: read the [Hx, W, ..] window of the slot that
    covers the positions, select the new rows into it, write it back with a
    dynamic_update_slice. R and L are static, so the chain unrolls. Pads
    and empty rows select nothing and write back what they read.

    Why this shape and no other. Each alternative was compiled for a
    described v5e at 8B int8, where one extra copy of the cache is 4.25 GB
    and the step program no longer fits the chip:
      - a scatter (`cache.at[:, slot, :, pos].set`), and any loop that
        carries the cache through single-row updates, get a full second
        copy of the cache in temp space;
      - a straight-line chain of window updates compiles to temp ~0 only
        while the update has the cache's own physical layout. An update
        that carries the layer axis (the scan stacks its outputs with L
        next to the lanes), token-major rows swapped into place, or a
        gather's output each made the compiler re-lay the WHOLE cache out
        to suit the small operand instead.
    Hence: head-major rows (as `fuse_prompt_kv` makes them), one layer per
    update, contiguous slices and no gather."""
    L, B, Hx, S = cache.shape[:4]
    T = new.shape[2]
    R = slots.shape[0]
    # the latent pair's int8 rope keys lie P positions abreast in a row of
    # whole lanes (`rope_abreast`) and the packed rows apart: `rope_put`
    # selects a window's rows into the slot's row as the loop below does, ONE
    # update a descriptor row for all layers (its update is a select's output
    # in the cache's own layout, so the layer axis costs nothing there, and a
    # row's mask is made once and not L times: 160 of them in a program of
    # four rows cost every packed shape 3-5 s more to lower, PR 58)
    P = cache.shape[4] // new.shape[3] if cache.ndim == 5 else 1
    S *= P
    W = min(S, T)  # a row holds at most T tokens
    ntail = cache.ndim - 4
    win = jnp.arange(W, dtype=jnp.int32)
    # the packed index landing at window offset i is t0 + i: contiguous, so
    # a dynamic_slice of the rows doubled along T (t0 may be negative when
    # the window was clamped back from the end of the slot; what wraps
    # around is never selected) — a gather would do, but its output layout
    # is the compiler's, and the cache gets re-laid-out to match it
    twice = jnp.concatenate([new, new], axis=2)
    for r in range(R):
        n = offsets[r + 1] - offsets[r]
        a = jnp.clip(starts[r], 0, S - W)  # window start (dynamic_slice's clamp)
        pos = a + win  # [W] cache positions the window covers
        t0 = jnp.mod(offsets[r] + a - starts[r], T)
        hit = (pos >= starts[r]) & (pos < starts[r] + n)
        if P > 1:
            rows = jax.lax.dynamic_slice(twice, (0, 0, t0, 0), (L, Hx, W) + new.shape[3:])
            cache = rope_put(cache, rows[:, None], (0, slots[r]), a, keep=hit)
            continue
        keep = hit.reshape((1, 1, 1, W) + (1,) * ntail)
        for l in range(L):
            rows = jax.lax.dynamic_slice(
                twice, (l, 0, t0) + (0,) * ntail, (1, Hx, W) + cache.shape[4:]
            )[None]  # [1, 1, Hx, W, *rest]
            at = (l, slots[r], 0, a) + (0,) * ntail
            cur = jax.lax.dynamic_slice(cache, at, (1, 1, Hx, W) + cache.shape[4:])
            cache = jax.lax.dynamic_update_slice(
                cache, jnp.where(keep, rows.astype(cache.dtype), cur), at
            )
    return cache


def llama_prefill_chunk_ragged(
    cfg: ModelConfig,
    params: Params,
    cache_k: Any,  # [L, B, Hkv, S, hd] engine cache (or fused int8 {"q","s"})
    cache_v: Any,
    tokens: jnp.ndarray,  # [T] int32 — PACKED chunks, rows back-to-back
    rowids: jnp.ndarray,  # [T] int32 — descriptor row per token, SORTED
    #   ascending; pad tokens carry rowid == R
    positions: jnp.ndarray,  # [T] int32 — absolute rope/write position per
    #   token; pad tokens carry S (their cache scatters DROP)
    slots: jnp.ndarray,  # [R] int32 — engine slot per descriptor row
    starts: jnp.ndarray,  # [R] int32 — cached-prefix length per row
    last_idx: jnp.ndarray,  # [R] int32 — packed index of each row's LAST
    #   token this chunk (0 for unused rows — never sampled by the engine)
    skey: int = 0,  # STATIC past bound for the XLA arm (kernel arm ignores
    #   it — past trips are data-dependent, so 0 keeps ONE executable)
    paged: dict | None = None,  # {"tbl","k","v"} physical paging operand
    impl: str | None = None,  # "kernel" | "xla": the ENGINE's resolved choice
    #   (a sharded engine must say "xla" — a Mosaic kernel cannot be
    #   partitioned by GSPMD); None asks kernels/attention.py's resolver
) -> tuple[jnp.ndarray, Any, Any]:
    """Ragged chunked prefill: the packed-descriptor twin of
    `llama_prefill_chunk_batch`. Instead of [A, C] bucket-padded rows, up to
    R rows' chunks pack back-to-back into one [T] token buffer — compute is
    spent on real tokens only, and because T and R are static while every
    descriptor (rowids, positions, offsets, starts, tables) is data, ONE
    executable per (T, layout) serves every fill mix where the bucketed path
    mints one per (A, bucket, skey). Attention runs through the ragged
    paged-native kernels (`kernels/attention.py:ragged_prefill_attend_*`):
    the cached prefix streams block-indirect through the PR 10 tables, the
    chunk's own K/V stays exact bf16 from registers, and masks derive from
    the packed row boundaries. Same read-past-then-write discipline as the
    bucketed path; writes are positional scatters (`mode="drop"` — pad
    tokens carry position S and vanish, the parked-slot OOB convention).

    Sliding-window and softcap families are NOT supported — the engine's
    ragged eligibility gate routes them to the bucketed path.

    Returns (logits [R, V] f32 at each row's `last_idx` token, new_k, new_v).
    """
    if cfg.kv_lora_rank:  # MLA family: absorbed ragged prefill over latents
        from .mla import mla_prefill_chunk_ragged

        return mla_prefill_chunk_ragged(
            cfg, params, cache_k, cache_v, tokens, rowids, positions,
            slots, starts, last_idx, skey=skey, paged=paged, impl=impl,
        )
    if cfg.sliding_window or cfg.attn_softcap or cfg.recurrent:
        raise NotImplementedError(
            "ragged prefill covers global-attention, no-softcap families; "
            "the engine gates others to the bucketed path"
        )
    quantized = isinstance(cache_k, dict)
    L, B, _, S, _ = _cache_shape(cache_k)
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    H = cfg.n_heads
    G = H // Hkv
    T = tokens.shape[0]
    R = slots.shape[0]
    slots = jnp.asarray(slots, dtype=jnp.int32)
    starts = jnp.asarray(starts, dtype=jnp.int32)
    rowids = jnp.asarray(rowids, dtype=jnp.int32)
    positions = jnp.asarray(positions, dtype=jnp.int32)
    # packed row boundaries from the sorted rowids: offsets[r] = first packed
    # index of row r; offsets[R] = total real tokens
    offsets = jnp.concatenate(
        [
            jnp.zeros((1,), jnp.int32),
            jnp.sum(
                (rowids[None, :] < jnp.arange(1, R + 1, dtype=jnp.int32)[:, None]),
                axis=1,
                dtype=jnp.int32,
            ),
        ]
    )  # [R+1]
    moe_valid = rowids < R  # [T]
    btbl = paged["tbl"] if paged is not None else None

    h = _embed_in(cfg, params, tokens)  # [T, D]
    cos, sin = rope_tables(cfg, hd, positions)  # [T, hd/2]

    # The cache is a scan-INVARIANT operand (the decode steps' structure,
    # `_decode_step_q8`): every layer reads it pre-append, the new rows stack
    # out as scan ys, and ONE scatter lands them after the scan. Reads never
    # see this chunk's writes either way — a row's past is positions
    # < starts[r], its writes are positions >= starts[r], and shared blocks
    # are full prefix blocks nobody writes — so hoisting the writes is
    # value-identical. What it buys: with the cache as a scan CARRY that a
    # Pallas call reads and a scatter then updates, XLA keeps a second copy
    # of the whole cache (the 8B int8 engine's ragged programs asked for
    # 18.05 GB of a 15.75 GB chip); read-only inside the loop, updated once
    # outside it, the donated buffer is rewritten in place.
    def layer(carry, lp):
        h, li = carry
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q = apply_rope(q.reshape(T, H, hd), cos, sin)
            k = apply_rope(k.reshape(T, Hkv, hd), cos, sin)
            v = v.reshape(T, Hkv, hd)
            qg = q.reshape(T, Hkv, G, hd)

            # ---- ragged attention over [cached past | packed self]
            if quantized:
                ctx = ragged_prefill_attend_q8(
                    qg, k, v, cache_k, li, rowids, offsets, slots, starts,
                    scale=cfg.attn_scale, skey=skey, block_tables=btbl,
                    pool=paged["k"] if paged is not None else None, impl=impl,
                )
            else:
                ctx = ragged_prefill_attend_bf16(
                    qg, k, v, cache_k, cache_v, li, rowids, offsets, slots, starts,
                    scale=cfg.attn_scale, skey=skey, block_tables=btbl,
                    pool_k=paged["k"] if paged is not None else None,
                    pool_v=paged["v"] if paged is not None else None, impl=impl,
                )
            ctx = ctx.reshape(T, H * hd)
            h = _attn_residual(cfg, lp, ctx, h)
        h = _ffn_residual(cfg, lp, h, moe_valid=moe_valid)

        # ---- this layer's rows, in the cache's own form and axis order
        with jax.named_scope("kv_append"):
            if quantized:
                fused = fuse_prompt_kv(
                    k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                    scale_dtype=cache_k["s"].dtype,
                )  # {"q": [2*Hkv/P+p, T, P*hd], "s": [2*Hkv, T]}
                new = (fused["q"], fused["s"])
            else:
                new = (
                    k.transpose(1, 0, 2).astype(cache_k.dtype),
                    v.transpose(1, 0, 2).astype(cache_v.dtype),
                )
        return (h, li + 1), new

    (h, _), (new_a, new_b) = jax.lax.scan(
        layer, (h, jnp.int32(0)), params["layers"]
    )  # new_*: [L, heads, T, ...]

    # ---- writes last, all layers at once (`ragged_write_rows`). Paging keeps
    # writes at identity arena homes — COW re-homing is host-side ledger
    # machinery, so the writes need no tables.
    with jax.named_scope("kv_append"):
        if quantized:
            new_k = {
                "q": ragged_write_rows(cache_k["q"], new_a, slots, starts, offsets),
                "s": ragged_write_rows(cache_k["s"], new_b, slots, starts, offsets),
            }
            new_v = cache_v
        else:
            new_k = ragged_write_rows(cache_k, new_a, slots, starts, offsets)
            new_v = ragged_write_rows(cache_v, new_b, slots, starts, offsets)
    last = jnp.take(h, jnp.clip(last_idx, 0, T - 1), axis=0)  # [R, D]
    return _logits(cfg, params, last), new_k, new_v


def llama_decode_step(
    cfg: ModelConfig,
    params: Params,
    cache_k: jnp.ndarray,  # [L, B, Hkv, S, Dh]
    cache_v: jnp.ndarray,
    tokens: jnp.ndarray,  # [Ba] int32 — last emitted token per batch row
    lengths: jnp.ndarray,  # [Ba] int32 — position to write (tokens already in cache)
    attn_impl: str = "xla",
    slot_ids: jnp.ndarray | None = None,  # [Ba] int32 cache rows (None = 1:1)
    paged: dict | None = None,  # {"tbl","k","v"} physical paging operand —
    #   block-indirect reads through executor/physical.py tables (None =
    #   contiguous). Writes are UNTOUCHED: decode always appends at private
    #   positions, and private blocks live at their identity homes.
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One batched autoregressive step for all slots.

    Writes this step's K/V at `lengths[b]`, attends over positions
    ≤ lengths[b], returns (logits [Ba, V] f32, new_cache_k, new_cache_v).
    Inactive slots simply produce garbage logits that the engine ignores —
    keeping the step shape-static (no data-dependent control flow under jit).

    With `slot_ids` the batch is COMPACT: row i serves cache row
    slot_ids[i] (reads attend that row, the K/V append scatters into it).
    The forward pass then sizes to the active rows only — the engine's slot
    compaction (executor/engine.py:_dispatch_decode) uses this so parked slots
    stop costing weights-pass FLOPs and sampling work.

    The caches may be int8-quantized ({"q", "s"} pytrees — see
    `init_kv_cache`): scales then fold into the attention einsums post-dot
    (QK scores scale by k's per-token scale; v's folds into the probs), so
    the HBM read is int8 payload + 1/head_dim of scales.
    """
    if cfg.kv_lora_rank:  # MLA family: absorbed decode over the latent cache
        from .mla import mla_decode_step

        return mla_decode_step(
            cfg, params, cache_k, cache_v, tokens, lengths,
            slot_ids=slot_ids, attn_impl=attn_impl, paged=paged,
        )
    if cfg.gqa_layers:  # one structure on either platform: the kernels' own
        from .hybrid import hybrid_decode_step

        return hybrid_decode_step(
            cfg, params, cache_k, cache_v, tokens, lengths,
            slot_ids=slot_ids, paged=paged,
        )
    quantized = isinstance(cache_k, dict)
    # fused quantized cache: axis 2 of "q" is 2*Hkv/P + p and its rows P*hd
    # wide — take Hkv and hd from cfg, P from the cache
    L, B, _, S, _ = _cache_shape(cache_k)
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    P = fused_q8_heads(cache_k)[2] if quantized else 1
    Ba = tokens.shape[0]
    H = cfg.n_heads
    G = H // Hkv

    # Sliding windows / score softcaps aren't implemented in the pallas
    # decode kernels; those families take the XLA path. Both cache dtypes
    # otherwise share the scan-invariant + post-scan-append structure:
    # int8 routes to the s8-MXU hybrid (decode_attend_q8), bf16 to its twin
    # (decode_attend_bf16) — both take cfg.attn_scale and follow slot_ids,
    # so query_pre_attn_scalar families and compacted batches stay on the
    # kernel path now.
    if attn_impl == "pallas" and (cfg.sliding_window or cfg.attn_softcap):
        attn_impl = "xla"

    if quantized and attn_impl == "pallas":
        # The TPU hot path takes a different structure: cache is a
        # scan-INVARIANT operand (no per-layer scatter — measured 14.2 ms of
        # a 37.5 ms step at 8B B=112) and the append happens once post-scan
        # via the in-place tile-rewrite kernel (kernels/attention.py:
        # append_kv_q8). decode_attend_q8 is built for pre-append caches: it
        # overrides position w with the exact new vectors.
        return _decode_step_q8(
            cfg, params, cache_k, cache_v, tokens, lengths,
            slot_ids=slot_ids, paged=paged,
        )
    if attn_impl == "pallas" and not quantized:
        # same structure for the bf16 cache (new: it used to take the
        # in-scan sliced kernel, which lost to XLA — the restructure wins)
        return _decode_step_bf16(
            cfg, params, cache_k, cache_v, tokens, lengths,
            slot_ids=slot_ids, paged=paged,
        )

    h = _embed_in(cfg, params, tokens)  # [Ba, D]
    cos, sin = rope_tables(cfg, hd, lengths)  # [Ba, hd/2]

    # row i of the compact batch scatters/gathers cache row rows[i]
    rows = jnp.arange(B, dtype=jnp.int32) if slot_ids is None else slot_ids
    b_idx = rows[:, None]  # [Ba, 1]
    h_idx = jnp.arange(Hkv)[None, :]  # [1, Hkv]
    w_idx = lengths[:, None]  # [Ba, 1] — broadcast with h_idx to [Ba, Hkv]
    key_pos = jnp.arange(S)[None, :]  # [1, S]
    attn_mask = key_pos <= lengths[:, None]  # [Ba, S]
    neg = jnp.float32(-1e30)

    def rowsel(x):
        # gather the compact batch's cache rows for the einsum attention
        # paths (identity when uncompacted — XLA elides the arange take)
        return x if slot_ids is None else jnp.take(x, slot_ids, axis=0)

    ptbl = None if paged is None else jnp.take(paged["tbl"], rows, axis=0)

    def csel(x_all, li, pool_all):
        # layer-select + row-select; block-indirect through the compacted
        # table when physical paging is live (subsumes rowsel: table row i
        # resolves slot rows[i]'s blocks, private ones to identity homes)
        x = jax.lax.dynamic_index_in_dim(x_all, li, 0, keepdims=False)
        if ptbl is None:
            return rowsel(x)
        p = jax.lax.dynamic_index_in_dim(pool_all, li, 0, keepdims=False)
        return paged_gather(x, p, ptbl)

    # The full cache rides the layer scan as CARRY, not xs/ys: as ys the
    # scan would materialize a fresh [L, B, Hkv, S, hd] stack every step — a
    # full-cache HBM write per token (measured 17 ms/step at B=32 S=1024 for
    # a 1B model, ~3x the roofline). As carry, the only cache writes are the
    # per-layer one-token scatters, which XLA performs in place on the
    # donated buffers inside the loop; step time becomes weights + one cache
    # READ, which is the decode minimum.
    def layer(carry, xs):
        lp, win = xs
        h, ck_all, cv_all, li = carry
        with jax.named_scope("attn"):
            x = _sub_in(cfg, h, lp["attn_norm"])
            q, k, v = _qkv(cfg, lp, x)
            q = q.reshape(Ba, H, hd)
            k = k.reshape(Ba, Hkv, hd)
            v = v.reshape(Ba, Hkv, hd)
            q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]  # [Ba, H, hd]
            k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]

            qg = q.reshape(Ba, Hkv, G, hd)
            # Append this step's K/V row to the carry, quantizing into the FUSED
            # layout when the cache is int8. The scatter happens BEFORE the
            # attention read: write-after-read on the carried buffer would cost
            # XLA a full-cache defensive copy (~10 ms at 8B B=64).
            if quantized:
                # the packed pseudo-head is kept consistent too: snapshots /
                # path switches must see one coherent fused entry
                pay, s_new = _q8_step_rows(ck_all, k, v)  # [Ba, Hf, P*hd], [Ba, 2*Hkv]
                hf_idx = jnp.arange(pay.shape[1])[None, :]
                hs_idx = jnp.arange(2 * Hkv)[None, :]
                ck_all = {
                    "q": ck_all["q"].at[li, b_idx, hf_idx, w_idx].set(pay),
                    "s": ck_all["s"].at[li, b_idx, hs_idx, w_idx].set(s_new),
                }
            else:
                ck_all = ck_all.at[li, b_idx, h_idx, w_idx].set(k.astype(ck_all.dtype))
                cv_all = cv_all.at[li, b_idx, h_idx, w_idx].set(v.astype(cv_all.dtype))

            if quantized:
                payl = csel(ck_all["q"], li, None if paged is None else paged["k"]["q"])
                ssl = csel(ck_all["s"], li, None if paged is None else paged["k"]["s"])
                ck, cv = fused_kv(payl, Hkv, P)
                ks, vs = ssl[:, :Hkv], ssl[:, Hkv:]
                # int8 K dot in compute dtype; per-key-token dequant scales the
                # SCORES (cheap [Ba,Hkv,G,S] multiply), not the K payload
                scores = jnp.einsum("bhgd,bhsd->bhgs", qg, ck.astype(h.dtype)).astype(
                    jnp.float32
                ) * ks.astype(jnp.float32)[:, :, None, :]
                scores = _softcap(scores * cfg.attn_scale, cfg.attn_softcap)
                m = attn_mask
                if cfg.sliding_window:
                    m = m & ((win == 0) | (key_pos > (lengths[:, None] - win)))
                scores = jnp.where(m[:, None, None, :], scores, neg)
                probs = jax.nn.softmax(scores, axis=-1)
                # v's dequant folds into the probs before the PV dot
                probs = (probs * vs.astype(jnp.float32)[:, :, None, :]).astype(h.dtype)
                ctx = jnp.einsum("bhgs,bhsd->bhgd", probs, cv.astype(h.dtype)).reshape(
                    Ba, H * hd
                )
            else:
                ck = csel(ck_all, li, None if paged is None else paged["k"])
                cv = csel(cv_all, li, None if paged is None else paged["v"])
                scores = jnp.einsum("bhgd,bhsd->bhgs", qg, ck).astype(jnp.float32)
                scores = _softcap(scores * cfg.attn_scale, cfg.attn_softcap)
                m = attn_mask
                if cfg.sliding_window:
                    m = m & ((win == 0) | (key_pos > (lengths[:, None] - win)))
                scores = jnp.where(m[:, None, None, :], scores, neg)
                probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
                ctx = jnp.einsum("bhgs,bhsd->bhgd", probs, cv).reshape(Ba, H * hd)
            h = _attn_residual(cfg, lp, ctx, h)
        h = _ffn_residual(cfg, lp, h, moe_capacity=Ba)  # dropless at decode
        return (h, ck_all, cv_all, li + 1), None

    (h, new_k, new_v, _), _ = jax.lax.scan(
        layer,
        (h, cache_k, cache_v, jnp.int32(0)),
        (params["layers"], layer_windows(cfg)),
    )
    return _logits(cfg, params, h), new_k, new_v


def llama_encode_packed(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,  # [R, S] int32: a row's texts back to back, then padding
    seg_lens: jnp.ndarray,  # [R, K] int32 lengths of a row's texts, 0 = unused place
) -> jnp.ndarray:
    """`llama_encode` over rows that hold SEVERAL texts each (sequence
    packing): [R, K, D] unit vectors, one per place, zeros at unused places.

    A text in a shared row is computed as it is alone in a padded row:
    positions restart at its first token, attention stays inside it (causal
    AND same text AND valid), and everything else a layer does is per token
    (norms, q/k norms, `qdot`'s activation scales; a masked score is exactly
    0 after the softmax). The sliding-window term of `prefill_layer` is a
    difference of positions in the row, which inside one text is the
    difference of its own positions. Lives at the end of the module so that
    no generation program's traced line moves (the persistent cache's key).
    """
    K = seg_lens.shape[1]
    seg_lens = seg_lens.astype(jnp.int32)
    ends = jnp.cumsum(seg_lens, axis=1)  # [R, K] one past each text's last token
    row_len = ends[:, -1]  # [R] valid tokens of the row
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]  # [1, S]
    # the place a token belongs to = texts that end at or before it; unused
    # places end where the row's texts end, so padding (and only it) reads K
    seg = jnp.sum(pos[:, :, None] >= ends[:, None, :], axis=-1)  # [R, S]
    starts = jnp.take_along_axis(ends - seg_lens, jnp.minimum(seg, K - 1), axis=1)
    cos, sin = rope_tables(cfg, cfg.resolved_head_dim, pos - starts)  # [R, S, hd/2]
    mask = (
        (pos[:, :, None] >= pos[:, None, :])
        & (seg[:, :, None] == seg[:, None, :])
        & (pos < row_len[:, None])[:, None, :]
    )  # [R, S, S]
    h = _embed_in(cfg, params, tokens)  # [R, S, D]

    def layer(h, xs):
        lp, win = xs
        h, _ = prefill_layer(cfg, lp, h, cos, sin, mask, row_len, "xla", window=win)
        return h, None

    h, _ = jax.lax.scan(layer, h, (params["layers"], layer_windows(cfg)))
    last = jnp.take_along_axis(h, jnp.maximum(ends - 1, 0)[:, :, None], axis=1)
    e = _norm(cfg, last, params["final_norm"]).astype(jnp.float32)  # [R, K, D]
    e = e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True), 1e-9)
    return jnp.where((seg_lens > 0)[:, :, None], e, 0.0)
