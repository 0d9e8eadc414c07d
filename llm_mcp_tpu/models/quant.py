"""Weight-only int8 quantization for the serving path.

TPU decode is HBM-bandwidth-bound: every step streams all weights once, so
halving weight bytes nearly halves step time. Per-output-channel symmetric
int8 (the standard weight-only scheme: negligible quality loss, no
activation calibration needed) stores each linear as `{"q": int8, "s":
bf16-scale}`; the matmul reads int8 from HBM and XLA fuses the int8→bf16
convert into the operand load, so VMEM/MXU still run bf16 × bf16 → f32.

Parity note: the reference's executor (Ollama/llama.cpp) serves q4/q8 GGUF
models by default — quantized inference is its normal operating mode, and
this module is that capability rebuilt TPU-style. (`worker/llm_worker/
main.py:222-243` merely proxies; quantization lived inside the native
dependency.)

Scales are per-OUTPUT-channel so dequantization commutes with the matmul:
    x @ (q * s[None, :]) == (x @ q) * s
which keeps the int8 tensor the only weight-sized HBM read.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]

# linear weights quantized inside each stacked layer pytree: [L, in, out]
LAYER_QUANT_KEYS = (
    "wq", "wk", "wv", "wo", "w1", "w2", "w3",
    # MLA factorization (models/mla.py): qdot consumes these transparently;
    # the absorbed decode dequantizes w_ukv once per step
    "wq_mla", "w_dkv", "w_ukv", "wo_mla",
    # DeepSeek shared experts — dense always-on linears (models/moe.py
    # routes them through qdot); the ROUTED expert banks stay unquantized
    "w1s", "w3s", "w2s",
    # single-chip fused layouts (fuse_layer_weights): wqkv = [wq|wk|wv],
    # w13 = [w1|w3] concatenated along the output axis post-quantization
    "wqkv", "w13",
)


def _quantize_slice(w: jnp.ndarray, axis: int) -> dict[str, jnp.ndarray]:
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": jnp.squeeze(scale, axis=axis).astype(w.dtype)}


def quantize_weight(w: jnp.ndarray, axis: int = -2) -> dict[str, jnp.ndarray]:
    """Symmetric per-output-channel int8: reduce |max| over the CONTRACTION
    axis (default -2 = the `in` dim of an [..., in, out] linear). Scales
    keep the weight's dtype, so f32 test models stay f32 end-to-end.

    Stacked [L, in, out] tensors are quantized one layer-slice at a time:
    the f32 working copy is 2x the bf16 weight, and at engine init the full
    bf16 tree is still resident — a whole-tensor upcast of e.g. Llama-8B's
    stacked FFN (3.8 GB bf16) would spike ~8 GB and OOM the exact
    single-chip deployments int8 exists to enable. Per-slice, the transient
    is 1/L of that."""
    if w.ndim >= 3:
        parts = [_quantize_slice(w[i], axis) for i in range(w.shape[0])]
        return {
            "q": jnp.stack([p["q"] for p in parts]),
            "s": jnp.stack([p["s"] for p in parts]),
        }
    return _quantize_slice(w, axis)


import os

_W8A8 = os.environ.get("LLM_MCP_TPU_W8A8", "1") != "0"


def qdot(x: jnp.ndarray, w) -> jnp.ndarray:
    """Matmul over the last axis of x; transparent for plain arrays.

    For quantized weights the default path quantizes the ACTIVATION rows to
    int8 too (w8a8): the MXU consumes the int8 weight payload directly
    (s8 x s8 -> s32), so the weight-sized HBM read is never converted
    elementwise. The convert path (`LLM_MCP_TPU_W8A8=0`) runs int8->bf16 on
    the VPU at ~1 elem/lane/cycle — about HBM byte rate — which nearly
    doubles decode step time at 8B (measured: ~17 ms/step floor vs ~11).
    Per-row activation scales x per-output-channel weight scales rescale the
    int32 accumulator, llama.cpp-q8_0 style.
    """
    if isinstance(w, dict):
        if _W8A8:
            xf = x.astype(jnp.float32)
            xa = jnp.maximum(
                jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0, 1e-30
            )
            x8 = jnp.round(xf / xa).astype(jnp.int8)
            y = jax.lax.dot_general(
                x8,
                w["q"],
                (((x8.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            return (y.astype(jnp.float32) * xa * w["s"].astype(jnp.float32)).astype(
                x.dtype
            )
        y = jnp.matmul(x, w["q"].astype(x.dtype))
        return y * w["s"].astype(y.dtype)
    return jnp.matmul(x, w)


def is_quantized(w) -> bool:
    return isinstance(w, dict) and "q" in w


def embed_lookup(embed, tokens: jnp.ndarray) -> jnp.ndarray:
    """Embedding rows for token ids; per-ROW scales when quantized. The
    activation dtype follows the scale dtype (model compute dtype)."""
    if isinstance(embed, dict):
        rows = embed["q"][tokens].astype(embed["s"].dtype)
        return rows * embed["s"][tokens][..., None]
    return embed[tokens]


def logits_head(embed_or_head, h: jnp.ndarray, tied: bool) -> jnp.ndarray:
    """Final projection to vocab logits (f32). For tied embeddings the table
    is [V, D] with per-V-row scales == per-output-channel of its transpose."""
    if isinstance(embed_or_head, dict):
        q, s = embed_or_head["q"], embed_or_head["s"]
        m = q.T if tied else q
        y = jnp.matmul(h, m.astype(h.dtype)).astype(jnp.float32)
        return y * s.astype(jnp.float32)
    head = embed_or_head.T if tied else embed_or_head
    return jnp.einsum("...d,dv->...v", h, head).astype(jnp.float32)


def quantize_params(params: Params) -> Params:
    """Quantize all dense linears (+ the embedding/LM head) of a Llama-family
    param tree in place-compatible form. Norm weights stay bf16 (tiny, and
    precision-sensitive); MoE expert banks stay unquantized (their dispatch
    einsums in models/moe.py have their own path) — on MoE models only the
    attention linears and embedding quantize."""
    def quant_block(block: Params) -> Params:
        b = dict(block)
        for k in LAYER_QUANT_KEYS:
            if k in b and not is_quantized(b[k]):
                b[k] = quantize_weight(b[k])
        return b

    out: Params = dict(params)
    out["layers"] = quant_block(params["layers"])
    if "dense_layers" in params:  # DeepSeek first-dense prologue stack
        out["dense_layers"] = quant_block(params["dense_layers"])
    if not is_quantized(params["embed"]):
        # per-row (vocab) scales: contraction axis for the tied head is D,
        # but the LOOKUP needs row scales; per-row also equals per-output-
        # channel of embed.T, which is exactly what the tied logits head
        # contracts against.
        out["embed"] = quantize_weight(params["embed"], axis=-1)
    if "lm_head" in params and not is_quantized(params["lm_head"]):
        out["lm_head"] = quantize_weight(params["lm_head"], axis=-2)
    return out


def qw_random(key, shape, fan_in, scale_axes, scale_dtype) -> dict:
    """Direct-int8 random weight: uniform int8 payload + constant
    per-output-channel scales. Uniform int8 draws have std ≈ 73.3, so
    fan_in**-0.5 / 73.3 matches the fan-in-scaled normal init's magnitude.
    Single source of truth for every direct-quantized init (the llama tree
    below, models/embedder.py's encoder tree)."""
    q = jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)
    s = jnp.full(scale_axes, (fan_in**-0.5) / 73.3, dtype=scale_dtype)
    return {"q": q, "s": s}


def init_llama_params_quantized(
    cfg, key: jax.Array, scale_dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    """Random-init a Llama-family param tree DIRECTLY in int8-quantized form
    (the tree shape `quantize_params` produces), never materializing the
    bf16 tree.

    Exists for models too large to init-then-quantize on one chip: 8B bf16
    is 16 GB — the whole HBM of a v5e — while the int8 tree it quantizes to
    is half that. Benchmarks and engine boots without a checkpoint use this
    for 8B-class configs. Uniform int8 draws have std ≈ 73.3, so the
    per-channel scale is fan_in**-0.5 / 73.3 to match `init_llama_params`'s
    fan-in-scaled normal init.
    """
    from .configs import ModelConfig  # noqa: F401 (type only)

    hd = cfg.resolved_head_dim
    L, D, H, Hkv, F, V = (
        cfg.n_layers,
        cfg.dim,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.ffn_hidden,
        cfg.vocab_size,
    )
    # DeepSeek first-dense split (see models/mla.py:init_mla_params): the
    # main stack holds L - k layers; a dense_layers prologue holds the rest
    k_dense = (
        cfg.first_dense_layers
        if (cfg.n_experts and getattr(cfg, "kv_lora_rank", 0))
        else 0
    )
    L = L - k_dense
    keys = jax.random.split(key, 24)
    kit = iter(keys)

    def qw(shape, fan_in, scale_axes):
        return qw_random(next(kit), shape, fan_in, scale_axes, scale_dtype)

    norm_init = jnp.full((L, D), 1.0 - cfg.norm_weight_offset, dtype=scale_dtype)
    layers: Params = {"attn_norm": norm_init, "ffn_norm": norm_init}
    def mla_attn_q(depth: int) -> Params:
        # the quantized analog of mla.py:_mla_attn_weights, depth-
        # parameterized so the main stack and the dense prologue share it
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        R = cfg.kv_lora_rank
        return {
            "wq_mla": qw((depth, D, H * (dn + dr)), D, (depth, H * (dn + dr))),
            "w_dkv": qw((depth, D, R + dr), D, (depth, R + dr)),
            "kv_norm": jnp.ones((depth, R), dtype=scale_dtype),
            "w_ukv": qw((depth, R, H * (dn + dv)), R, (depth, H * (dn + dv))),
            "wo_mla": qw((depth, H * dv, D), H * dv, (depth, D)),
        }

    if getattr(cfg, "kv_lora_rank", 0):
        # MLA factorized attention (models/mla.py), direct-int8 — the
        # latent down-projection's RMSNorm weight stays full precision
        if getattr(cfg, "q_lora_rank", 0):
            # same guard as init_mla_params: a silent dense-q tree would be
            # the wrong architecture for a V2/V3-layout config
            raise ValueError(
                "q_lora_rank > 0 (low-rank query path) is not implemented; "
                "use the dense-q MLA variant (q_lora_rank=0, V2-Lite style)"
            )
        layers.update(mla_attn_q(L))
    else:
        layers.update(
            {
                "wq": qw((L, D, H * hd), D, (L, H * hd)),
                "wk": qw((L, D, Hkv * hd), D, (L, Hkv * hd)),
                "wv": qw((L, D, Hkv * hd), D, (L, Hkv * hd)),
                "wo": qw((L, H * hd, D), H * hd, (L, D)),
            }
        )
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, H * hd), dtype=scale_dtype)
        layers["bk"] = jnp.zeros((L, Hkv * hd), dtype=scale_dtype)
        layers["bv"] = jnp.zeros((L, Hkv * hd), dtype=scale_dtype)
    if cfg.qk_norm:
        # Qwen3 per-head q/k RMSNorm weights stay full precision
        layers["q_norm"] = jnp.ones((L, hd), dtype=scale_dtype)
        layers["k_norm"] = jnp.ones((L, hd), dtype=scale_dtype)
    if cfg.post_norms:
        layers["post_attn_norm"] = norm_init
        layers["post_ffn_norm"] = norm_init
    if cfg.n_experts:
        # routed expert banks stay unquantized (quantize_params parity);
        # shared experts are dense linears and quantize like any other
        from .llama import init_moe_layer_params

        moe_p = init_moe_layer_params(cfg, next(kit), scale_dtype, n_layers=L)
        for sk in ("w1s", "w3s", "w2s"):
            if sk in moe_p:
                moe_p[sk] = quantize_weight(moe_p[sk])
        layers.update(moe_p)
    else:
        layers.update(
            {
                "w1": qw((L, D, F), D, (L, F)),
                "w3": qw((L, D, F), D, (L, F)),
                "w2": qw((L, F, D), F, (L, D)),
            }
        )
    params: Params = {
        "embed": {
            "q": jax.random.randint(next(kit), (V, D), -127, 128, dtype=jnp.int8),
            "s": jnp.full((V,), (D**-0.5) / 73.3, dtype=scale_dtype),
        },
        "layers": layers,
        "final_norm": jnp.full((D,), 1.0 - cfg.norm_weight_offset, dtype=scale_dtype),
    }
    if k_dense:
        dnorm = jnp.full((k_dense, D), 1.0 - cfg.norm_weight_offset, dtype=scale_dtype)
        params["dense_layers"] = {
            "attn_norm": dnorm,
            "ffn_norm": dnorm,
            **mla_attn_q(k_dense),
            "w1": qw((k_dense, D, F), D, (k_dense, F)),
            "w3": qw((k_dense, D, F), D, (k_dense, F)),
            "w2": qw((k_dense, F, D), F, (k_dense, D)),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = qw((D, V), D, (V,))
    return params


def quantized_specs(specs: Params) -> Params:
    """Map a param PartitionSpec tree (parallel/sharding.py:llama_param_specs)
    onto the quantized tree shape: `q` keeps the weight's spec, `s` drops the
    contracted axis (scales are per-output-channel, so their sharding is the
    weight's spec minus the reduced dim). Lets TP-sharded serving run int8 —
    the v5e-8 baseline config — instead of carving quantization out for
    meshes."""
    from jax.sharding import PartitionSpec as P

    def drop(spec, axis: int):
        t = list(spec)
        del t[axis]
        return P(*t)

    def quant_block_specs(block):
        b = dict(block)
        for k in LAYER_QUANT_KEYS:
            if k in b:
                b[k] = {"q": b[k], "s": drop(b[k], -2)}
        return b

    out: Params = dict(specs)
    out["layers"] = quant_block_specs(specs["layers"])
    if "dense_layers" in specs:
        out["dense_layers"] = quant_block_specs(specs["dense_layers"])
    out["embed"] = {"q": specs["embed"], "s": drop(specs["embed"], -1)}
    if "lm_head" in specs:
        out["lm_head"] = {"q": specs["lm_head"], "s": drop(specs["lm_head"], -2)}
    return out


def _concat_w(parts):
    """Concatenate linears along the OUTPUT axis, preserving quantization.

    Post-quantization concat is exact for the w8a8 path: `qdot` quantizes
    the activation row once per call (per-row amax over the shared input),
    so a fused s8xs8 dot produces bit-identical int32 columns to running
    the separate dots — the fusion only changes how many times the scan
    body launches a matmul and re-reads the activation, never the math."""
    if all(isinstance(p, dict) for p in parts):
        return {
            "q": jnp.concatenate([p["q"] for p in parts], axis=-1),
            "s": jnp.concatenate([p["s"] for p in parts], axis=-1),
        }
    if any(isinstance(p, dict) for p in parts):
        raise ValueError("cannot fuse mixed quantized/unquantized linears")
    return jnp.concatenate(parts, axis=-1)


def fuse_layer_weights(params: Params) -> Params:
    """Rewrite a layer stack for the single-chip decode hot path: the three
    QKV projections become one `wqkv` dot and the two gate/up FFN
    projections one `w13` dot. The layer `lax.scan` then issues 2 big
    matmuls instead of 5 small ones per block half, which raises achieved
    HBM bandwidth on the w8a8 pass (fewer kernel launches + activation
    re-reads per weight byte; no chip number on record for either form).

    Single-chip only: the fused output axis interleaves q|k|v head groups,
    which the `tp` axis of `llama_param_specs` cannot shard — the engine
    gates the call on `mesh is None`. MoE stacks keep w1/w3 unfused (they
    have none); MLA stacks fuse only w13. Consumers: `llama._qkv` /
    `llama._ffn_residual` detect "wqkv"/"w13" and split the fused output.
    """

    def fuse_block(block: Params) -> Params:
        b = dict(block)
        if all(k in b for k in ("wq", "wk", "wv")):
            b["wqkv"] = _concat_w([b.pop("wq"), b.pop("wk"), b.pop("wv")])
            if all(k in b for k in ("bq", "bk", "bv")):
                b["bqkv"] = jnp.concatenate(
                    [b.pop("bq"), b.pop("bk"), b.pop("bv")], axis=-1
                )
        if "w1" in b and "w3" in b:
            b["w13"] = _concat_w([b.pop("w1"), b.pop("w3")])
        return b

    out: Params = dict(params)
    out["layers"] = fuse_block(params["layers"])
    if "dense_layers" in params:
        out["dense_layers"] = fuse_block(params["dense_layers"])
    return out


def scale_pack_width(n_kv_heads: int, head_dim: int, scale_dtype) -> int:
    """Padded head rows needed to ride per-position dequant scales inside
    the int8 KV payload block: 1 when the 2*Hkv k+v scale bytes for one
    position fit a single head_dim lane row, else 0 (packing disabled —
    the blocked kernel falls back to a second scale DMA per cell)."""
    dt = jnp.dtype(scale_dtype)
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        # the in-kernel unpack rebuilds an f32 bit pattern from the bytes
        # (kernels/attention.py:_unpack_scale_lanes): exact for bf16 and
        # f32 scales only
        return 0
    return 1 if 2 * n_kv_heads * dt.itemsize <= head_dim else 0


_UINT_OF_WIDTH = {2: jnp.uint16, 4: jnp.uint32}


def pack_scales(s: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """Bit-pack per-position scales [..., Hs, T] into one int8 pseudo-head
    row [..., 1, T, head_dim] so the blocked attention kernel's single
    payload DMA carries the dequant scales with the int8 K/V rows.

    Layout per position (lane axis): Hs scales of `s.dtype`, each as its
    bytes from the least significant up, then zero padding to head_dim
    lanes. The byte order is spelled out with shifts (a width-changing
    bitcast would leave it to the backend); the kernel inverts it the same
    way after the block lands in VMEM (`_unpack_scale_lanes`)."""
    Hs, T = s.shape[-2], s.shape[-1]
    it = jnp.dtype(s.dtype).itemsize
    sw = jnp.swapaxes(s, -1, -2)  # [..., T, Hs]
    bits = jax.lax.bitcast_convert_type(sw, _UINT_OF_WIDTH[it])
    raw = jnp.stack(
        [(bits >> (8 * b)) & 0xFF for b in range(it)], axis=-1
    ).astype(jnp.uint8)  # [..., T, Hs, it]
    raw = jax.lax.bitcast_convert_type(raw, jnp.int8)
    raw = raw.reshape(*sw.shape[:-1], Hs * it)
    pad = [(0, 0)] * (raw.ndim - 1) + [(0, head_dim - Hs * it)]
    return jnp.pad(raw, pad)[..., None, :, :]  # [..., 1, T, head_dim]


def unpack_scales(row: jnp.ndarray, n_heads: int, scale_dtype) -> jnp.ndarray:
    """Invert `pack_scales` for one landed block: [..., T, head_dim] int8
    -> [..., n_heads, T] scales (the plain-JAX twin of the kernels'
    `_unpack_scale_lanes`; tests hold the two to each other)."""
    it = jnp.dtype(scale_dtype).itemsize
    uint = _UINT_OF_WIDTH[it]
    raw = row[..., : n_heads * it].reshape(*row.shape[:-1], n_heads, it)
    raw = jax.lax.bitcast_convert_type(raw, jnp.uint8).astype(uint)
    bits = jnp.zeros(raw.shape[:-1], uint)
    for b in range(it):
        bits = bits | (raw[..., b] << (8 * b))
    s = jax.lax.bitcast_convert_type(bits, scale_dtype)  # [..., T, n_heads]
    return jnp.swapaxes(s, -1, -2)


def quantized_bytes(params: Params) -> tuple[int, int]:
    """(bytes_quantized_tree, bytes_bf16_equivalent) for logging."""

    def nbytes(t) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(t))

    def bf16_bytes(t) -> int:
        return sum(x.size * 2 for x in jax.tree_util.tree_leaves(t))

    return nbytes(params), bf16_bytes(params)
