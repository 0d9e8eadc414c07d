"""Plain float32 reference forward for the dense GQA decoder family.

What the serving code is compared with: the same weights taken through the
textbook equations, one layer at a time, in float32 `jax.numpy` — no cache,
no scan, no kernels, no quantized dots, no mesh. It shares nothing with
models/llama.py but the parameter tree's names, so a bug there does not
cancel here. Covers what llama-3.x needs (RMSNorm, split-half RoPE with the
llama3 frequency scaling, GQA causal attention, SwiGLU, untied or tied head,
optional q/k/v biases); other families raise rather than guess.

`chip_smoke.py` holds what the engines serve to it: on one chip the greedy
tokens of the int8 engine (its tree read in place), with `--four-chips` a
tp=4 engine's weights read back through `fetch` onto a single device.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# float32 means float32: a TPU's default matmul precision rounds f32 operands
# to bf16
_HI = jax.lax.Precision.HIGHEST
_mm = partial(jnp.matmul, precision=_HI)
_einsum = partial(jnp.einsum, precision=_HI)


def _rope_inv_freq(cfg, hd: int) -> np.ndarray:
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    if cfg.rope_factor > 1.0 and cfg.rope_orig_max:
        if cfg.rope_type != "llama3":
            raise NotImplementedError(f"reference rope type {cfg.rope_type!r}")
        # Llama-3.1 frequency scaling: long wavelengths slow down by `factor`,
        # short ones stay, the band between interpolates
        wavelen = 2.0 * math.pi / inv
        low_wl = cfg.rope_orig_max / cfg.llama3_low_freq_factor
        high_wl = cfg.rope_orig_max / cfg.llama3_high_freq_factor
        smooth = (cfg.rope_orig_max / wavelen - cfg.llama3_low_freq_factor) / (
            cfg.llama3_high_freq_factor - cfg.llama3_low_freq_factor
        )
        mid = (1.0 - smooth) * inv / cfg.rope_factor + smooth * inv
        inv = np.where(wavelen > low_wl, inv / cfg.rope_factor,
                       np.where(wavelen < high_wl, inv, mid))
    return inv


def _rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x [T, heads, hd], split-half pairing (i, i + hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rms(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


# one fused pass: int8 in, float32 out, no float32 copy of the payload between
_multiply_out = jax.jit(
    lambda q, s: q.astype(jnp.float32) * s.astype(jnp.float32)[None, :])


def _linear(w: Any, li: int, fetch: Callable[[Any], Any]) -> jnp.ndarray:
    """Layer `li` of a stacked linear as float32 [in, out]: a plain array, or
    the int8 form {"q", "s"} (per-output-channel scales, models/quant.py)
    multiplied out."""
    if isinstance(w, dict):
        return _multiply_out(jnp.asarray(fetch(w["q"][li])), jnp.asarray(fetch(w["s"][li])))
    return jnp.asarray(fetch(w[li]), jnp.float32)


def llama_forward_layerwise(
    cfg,
    params: dict[str, Any],
    tokens: np.ndarray,  # [T] int32, one prompt, no padding
    fetch: Callable[[Any], Any] = lambda x: x,
    rows: np.ndarray | None = None,  # positions whose logits are wanted (all)
    cols: np.ndarray | None = None,  # token ids whose logits are wanted (all)
) -> jnp.ndarray:
    """Logits [T, V] (float32) at every position of `tokens` (row t is the
    distribution over token t+1; causal, so row t depends on tokens <= t);
    `rows` / `cols` cut the head to [len(rows), len(cols)] so a long prompt
    at a 128k vocabulary does not cost a [T, V] array.

    `fetch` brings one array of the tree to where this computes (identity
    for a host/one-device tree; a device_get + device_put for a sharded
    one). One linear is fetched, used and dropped before the next, so a tree
    that does not fit one device still goes through. Reads the tree as the
    engines keep it: int8 linears with their scales, and the one-chip
    engine's fused `wqkv` / `w13` columns split back by width."""
    if (cfg.kv_lora_rank or cfg.n_experts or cfg.sliding_window or cfg.attn_softcap
            or cfg.qk_norm or cfg.post_norms or cfg.norm_weight_offset
            or cfg.embed_scale or cfg.logit_softcap or cfg.act == "gelu"):
        raise NotImplementedError(f"no plain reference for {cfg.name!r} yet")
    f32 = jnp.float32
    T = int(tokens.shape[0])
    hd, H, Hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    G = H // Hkv
    scale = cfg.attn_scale or hd**-0.5

    ang = np.arange(T, dtype=np.float64)[:, None] * _rope_inv_freq(cfg, hd)[None, :]
    cos, sin = jnp.asarray(np.cos(ang), f32), jnp.asarray(np.sin(ang), f32)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def vec(name, li):
        return jnp.asarray(fetch(layers[name][li]), f32)

    embed = jax.tree.map(lambda a: jnp.asarray(fetch(a)), params["embed"])

    def table(idx):  # rows of the [V, D] table; int8 rows carry one scale each
        if isinstance(embed, dict):
            return embed["q"][idx].astype(f32) * embed["s"][idx].astype(f32)[:, None]
        return embed[idx].astype(f32)

    h = table(jnp.asarray(tokens))  # [T, D]
    layers = params["layers"]
    for li in range(cfg.n_layers):
        x = _rms(h, vec("attn_norm", li), cfg.norm_eps)
        if "wqkv" in layers:  # [wq | wk | wv] side by side
            qkv = _mm(x, _linear(layers["wqkv"], li, fetch))
            if cfg.qkv_bias:
                qkv = qkv + vec("bqkv", li)
            q, k, v = jnp.split(qkv, [H * hd, (H + Hkv) * hd], axis=-1)
        else:
            q, k, v = (_mm(x, _linear(layers[n], li, fetch)) for n in ("wq", "wk", "wv"))
            if cfg.qkv_bias:
                q, k, v = q + vec("bq", li), k + vec("bk", li), v + vec("bv", li)
        q = _rope(q.reshape(T, H, hd), cos, sin).reshape(T, Hkv, G, hd)
        k = _rope(k.reshape(T, Hkv, hd), cos, sin)
        v = v.reshape(T, Hkv, hd)
        heads = []
        for g in range(Hkv):  # one KV head at a time: [G, T, T] scores, not [H, T, T]
            s = _einsum("tgd,ud->gtu", q[:, g], k[:, g]) * scale
            s = jnp.where(causal[None], s, -jnp.inf)
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = p / jnp.sum(p, axis=-1, keepdims=True)
            heads.append(_einsum("gtu,ud->tgd", p, v[:, g]))
        ctx = jnp.stack(heads, axis=1).reshape(T, H * hd)
        h = h + _mm(ctx, _linear(layers["wo"], li, fetch))
        x = _rms(h, vec("ffn_norm", li), cfg.norm_eps)
        if "w13" in layers:  # [w1 | w3] side by side
            gate, up = jnp.split(_mm(x, _linear(layers["w13"], li, fetch)), 2, axis=-1)
        else:
            gate = _mm(x, _linear(layers["w1"], li, fetch))
            up = _mm(x, _linear(layers["w3"], li, fetch))
        silu = gate * (1.0 / (1.0 + jnp.exp(-gate)))
        h = h + _mm(silu * up, _linear(layers["w2"], li, fetch))
    out = _rms(h, jnp.asarray(fetch(params["final_norm"]), f32), cfg.norm_eps)
    if rows is not None:
        out = out[jnp.asarray(rows)]
    pick = slice(None) if cols is None else jnp.asarray(cols)
    if cfg.tie_embeddings:
        return _mm(out, table(pick).T)
    head = params["lm_head"]
    if isinstance(head, dict):
        return _mm(out, jnp.asarray(fetch(head["q"]))[:, pick].astype(f32)) * (
            jnp.asarray(fetch(head["s"]))[pick].astype(f32)[None, :])
    return _mm(out, jnp.asarray(fetch(head))[:, pick].astype(f32))
