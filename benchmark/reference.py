"""Plain float32 reference forward for the dense GQA decoder family, the
benchmark's own copy.

Copied from llm_mcp_tpu/models/reference.py (PR 21) so that a PR which changes
the program cannot move what `correct` is held to, with two additions that
qwen3 needs: per-head q/k RMSNorm before RoPE, and a pooled output (the hidden
state at the last token, final-normed and L2-normalised) for the embedding
configuration. The same weights go through the textbook equations one layer at
a time in float32 `jax.numpy`: no cache, no scan over layers, no kernels, no
quantized dots. It shares nothing with models/llama.py but the names of the
parameter tree.

Two departures from the original, both about cost on a chip that the engine
has filled: the layer is ONE jitted function that indexes the stacked tree
(one executable for every layer, found in the compile cache on the next run,
where the eager original compiled every operation for every new length), and
the feed-forward is computed in column blocks so that no float32 copy of a
whole int8 matrix is alive at once. Callers pad the sequence to a fixed length
(causal: what follows a row does not move it).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# float32 means float32: a TPU's default matmul precision rounds f32 operands
# to bf16
_HI = jax.lax.Precision.HIGHEST
_mm = partial(jnp.matmul, precision=_HI)
_einsum = partial(jnp.einsum, precision=_HI)
FFN_BLOCKS = 4

# The tolerances of this family (correctness.py reads them from the reference
# module a configuration names; this one is the default).
# Served tokens against the float32 forward, as a share of a row's max |logit|:
# int8 x int8 dots, int8 KV and bf16 activations over 36 layers on one side,
# float32 on the other. PR 21's v5e runs showed 0.0151 at worst on Llama-3.1-8B
# (32 layers, same kernels); this model showed 0.0 in four of six prompts and
# 0.016 and 0.028 in the others, the same in both runs of each (PR 23, v5e).
# Another request's logits or a lost KV row miss by the spread of the logits
# themselves (0.5 and more). About three times the worst seen.
SERVED_TOL_REL = 0.08
# Cosine distance between a served embedding and the reference's. The vector
# is one hidden state after 36 int8 x bf16 layers, cut to `dimensions` and
# normalised again; the v5e showed 0.0009-0.00125 over 28 inputs of 14 runs
# (PR 23), and the two inputs of a run themselves lie 0.24-0.33 apart: another
# input's vector, or a layer left out, misses by far. Four times the worst seen.
EMBED_TOL_COS = 0.005


def _rope_inv_freq(cfg, hd: int) -> np.ndarray:
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    if cfg.rope_factor > 1.0 and cfg.rope_orig_max:
        if cfg.rope_type != "llama3":
            raise NotImplementedError(f"reference rope type {cfg.rope_type!r}")
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


def _rope(x, cos, sin):
    """x [T, heads, hd], split-half pairing (i, i + hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rms(x, w, eps: float):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _at(leaf, li):
    return jax.lax.dynamic_index_in_dim(leaf, li, 0, keepdims=False)


def _linear(w, li, cols=slice(None)):
    """Layer `li` of a stacked linear as float32 [in, out[cols]]: a plain
    array, or the int8 form {"q", "s"} (per-output-channel scales) multiplied
    out."""
    f32 = jnp.float32
    if isinstance(w, dict):
        return _at(w["q"], li)[:, cols].astype(f32) * _at(w["s"], li)[cols].astype(f32)[None, :]
    return _at(w, li)[:, cols].astype(f32)


def check(cfg) -> None:
    """Raises for a configuration this family's equations do not cover."""
    if (cfg.kv_lora_rank or cfg.n_experts or cfg.sliding_window or cfg.attn_softcap
            or cfg.post_norms or cfg.norm_weight_offset or cfg.embed_scale
            or cfg.logit_softcap or cfg.act == "gelu"):
        raise NotImplementedError(f"no plain reference for {cfg.name!r} yet")


@partial(jax.jit, static_argnums=(0,))
def _layer(cfg, layers, li, h, cos, sin):
    """One decoder layer over h [T, D], float32."""
    f32 = jnp.float32
    T = h.shape[0]
    hd, H, Hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    G = H // Hkv
    scale = cfg.attn_scale or hd**-0.5
    causal = jnp.tril(jnp.ones((T, T), bool))

    def vec(name):
        return _at(layers[name], li).astype(f32)

    x = _rms(h, vec("attn_norm"), cfg.norm_eps)
    if "wqkv" in layers:  # [wq | wk | wv] side by side
        qkv = _mm(x, _linear(layers["wqkv"], li))
        if cfg.qkv_bias:
            qkv = qkv + vec("bqkv")
        q, k, v = jnp.split(qkv, [H * hd, (H + Hkv) * hd], axis=-1)
    else:
        q, k, v = (_mm(x, _linear(layers[n], li)) for n in ("wq", "wk", "wv"))
        if cfg.qkv_bias:
            q, k, v = q + vec("bq"), k + vec("bk"), v + vec("bv")
    q, k, v = q.reshape(T, H, hd), k.reshape(T, Hkv, hd), v.reshape(T, Hkv, hd)
    if cfg.qk_norm:  # qwen3: RMSNorm over head_dim, one [hd] weight a layer, before RoPE
        q = _rms(q, vec("q_norm"), cfg.norm_eps)
        k = _rms(k, vec("k_norm"), cfg.norm_eps)
    q = _rope(q, cos, sin).reshape(T, Hkv, G, hd)
    k = _rope(k, cos, sin)
    heads = []
    for g in range(Hkv):  # one KV head at a time: [G, T, T] scores, not [H, T, T]
        s = _einsum("tgd,ud->gtu", q[:, g], k[:, g]) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        heads.append(_einsum("gtu,ud->tgd", p, v[:, g]))
    ctx = jnp.stack(heads, axis=1).reshape(T, H * hd)
    h = h + _mm(ctx, _linear(layers["wo"], li))
    x = _rms(h, vec("ffn_norm"), cfg.norm_eps)
    F = cfg.ffn_hidden
    step = -(-F // FFN_BLOCKS)
    out = jnp.zeros_like(h)
    for lo in range(0, F, step):  # the hidden width in blocks
        hi = min(lo + step, F)
        if "w13" in layers:  # [w1 | w3] side by side
            gate = _mm(x, _linear(layers["w13"], li, slice(lo, hi)))
            up = _mm(x, _linear(layers["w13"], li, slice(F + lo, F + hi)))
        else:
            gate = _mm(x, _linear(layers["w1"], li, slice(lo, hi)))
            up = _mm(x, _linear(layers["w3"], li, slice(lo, hi)))
        silu = gate * (1.0 / (1.0 + jnp.exp(-gate)))
        w2 = layers["w2"]
        if isinstance(w2, dict):
            down = _at(w2["q"], li)[lo:hi].astype(f32) * _at(w2["s"], li).astype(f32)[None, :]
        else:
            down = _at(w2, li)[lo:hi].astype(f32)
        out = out + _mm(silu * up, down)
    return h + out


@jax.jit
def _embed_rows(embed, tokens):
    """Rows of the [V, D] table; int8 rows carry one scale each."""
    f32 = jnp.float32
    if isinstance(embed, dict):
        return embed["q"][tokens].astype(f32) * embed["s"][tokens].astype(f32)[:, None]
    return embed[tokens].astype(f32)


def hidden_states(cfg, params, tokens: np.ndarray):
    """Final-normed hidden states [T, D] (float32) of one unbatched sequence."""
    check(cfg)
    f32 = jnp.float32
    T = int(tokens.shape[0])
    ang = np.arange(T, dtype=np.float64)[:, None] * _rope_inv_freq(cfg, cfg.resolved_head_dim)[None, :]
    cos, sin = jnp.asarray(np.cos(ang), f32), jnp.asarray(np.sin(ang), f32)
    h = _embed_rows(params["embed"], jnp.asarray(tokens, jnp.int32))
    for li in range(cfg.n_layers):
        h = _layer(cfg, params["layers"], jnp.int32(li), h, cos, sin)
    return _rms(h, jnp.asarray(params["final_norm"], f32), cfg.norm_eps)


def logits(cfg, params, tokens: np.ndarray, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """Logits [len(rows), len(cols)]: row t is the distribution over token
    t+1, cut to the token ids `cols` so that a 152k vocabulary costs nothing."""
    f32 = jnp.float32
    out = hidden_states(cfg, params, tokens)[jnp.asarray(rows)]
    pick = jnp.asarray(cols)
    if cfg.tie_embeddings:
        return np.asarray(_mm(out, _embed_rows(params["embed"], pick).T), np.float32)
    head = params["lm_head"]
    if isinstance(head, dict):
        w = head["q"][:, pick].astype(f32) * head["s"][pick].astype(f32)[None, :]
    else:
        w = head[:, pick].astype(f32)
    return np.asarray(_mm(out, w), np.float32)


def pooled(cfg, params, tokens: np.ndarray, length: int, dimensions: int = 0) -> np.ndarray:
    """The embedding of one sequence: hidden state at its last real token,
    L2-normalised; with `dimensions`, cut to that width and normalised again
    (Matryoshka), as /v1/embeddings answers it."""
    e = np.asarray(hidden_states(cfg, params, tokens)[length - 1], np.float64)
    e = e / max(np.linalg.norm(e), 1e-9)
    if dimensions and 0 < dimensions < e.shape[0]:
        e = e[:dimensions]
        e = e / max(np.linalg.norm(e), 1e-9)
    return e.astype(np.float32)
