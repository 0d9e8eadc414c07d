"""The plain float32 reference (models/reference.py) against the serving
model's own prefill, on the CPU at tiny size: same weights, same tokens, every
position. The two share the parameter names and nothing else, so agreement
here is what lets `chip_smoke.py --four-chips` hold a sharded engine to the
reference on the chip."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.models.configs import resolve_config
from llm_mcp_tpu.models.llama import init_llama_params, llama_prefill
from llm_mcp_tpu.models.reference import llama_forward_layerwise

TOKENS = np.array([1, 5, 9, 200, 33, 7, 8, 100, 42, 17, 3], np.int32)


@pytest.mark.parametrize("rope", ["plain", "llama3"])
def test_reference_matches_prefill_at_every_position(rope):
    cfg = resolve_config("tiny-llm", "")
    if rope == "llama3":  # the scaling Llama-3.1 uses, at a tiny original max
        cfg = dataclasses.replace(
            cfg, rope_type="llama3", rope_factor=8.0, rope_orig_max=64)
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ref = llama_forward_layerwise(cfg, params, TOKENS)
    assert ref.shape == (len(TOKENS), cfg.vocab_size)
    for n in (1, 4, len(TOKENS)):  # causal: row n-1 only sees tokens < n
        toks = np.zeros((1, 16), np.int32)
        toks[0, :n] = TOKENS[:n]
        logits, _, _ = llama_prefill(
            cfg, params, jnp.asarray(toks), jnp.asarray([n], jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(ref[n - 1]), atol=2e-5)


def test_reference_reads_weights_through_fetch_one_layer_at_a_time():
    cfg = resolve_config("tiny-llm", "")
    params = init_llama_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    seen = []

    def fetch(x):
        seen.append(x.shape)
        return x

    llama_forward_layerwise(cfg, params, TOKENS[:4], fetch)
    stacked = {v.shape for v in params["layers"].values()}
    assert not stacked & set(seen), "a whole stacked [L, ...] tensor was fetched"
    assert len(seen) == 1 + cfg.n_layers * len(params["layers"]) + 1 + (
        0 if cfg.tie_embeddings else 1)


def test_reference_refuses_families_it_has_no_equations_for():
    with pytest.raises(NotImplementedError, match="no plain reference"):
        llama_forward_layerwise(resolve_config("gemma2-9b", ""), {}, TOKENS)


def test_reference_reads_the_one_chip_engines_tree():
    """int8 linears with their scales and the fused wqkv / w13 columns, as the
    one-chip engine keeps them: the same numbers as the tree multiplied out by
    hand, the head cut to the rows and columns asked for, and the serving
    prefill over the very same tree (int8 x int8 dots there) close by."""
    from llm_mcp_tpu.models.quant import fuse_layer_weights, quantize_params

    cfg = resolve_config("tiny-llm", "")
    plain = init_llama_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    served = fuse_layer_weights(quantize_params(plain))
    assert "wqkv" in served["layers"] and "w13" in served["layers"]

    def out(w):  # [.., in, out] int8 and its per-output-channel scales, multiplied out
        return w["q"].astype(jnp.float32) * w["s"].astype(jnp.float32)[..., None, :]

    q = quantize_params(plain)
    by_hand = dict(plain)
    by_hand["layers"] = {
        k: out(v) if isinstance(v, dict) else v for k, v in q["layers"].items()}
    by_hand["embed"] = q["embed"]["q"].astype(jnp.float32) * q["embed"]["s"][:, None]
    if "lm_head" in q:
        by_hand["lm_head"] = out(q["lm_head"])

    want = np.asarray(llama_forward_layerwise(cfg, by_hand, TOKENS))
    got = np.asarray(llama_forward_layerwise(cfg, served, TOKENS))
    np.testing.assert_allclose(got, want, atol=2e-5)

    rows, cols = np.array([3, 10]), np.array([0, 7, 200, 511])
    cut = np.asarray(llama_forward_layerwise(cfg, served, TOKENS, rows=rows, cols=cols))
    np.testing.assert_allclose(cut, want[rows][:, cols], atol=2e-5)

    toks = np.zeros((1, 16), np.int32)
    toks[0, : len(TOKENS)] = TOKENS
    logits, _, _ = llama_prefill(
        cfg, served, jnp.asarray(toks), jnp.asarray([len(TOKENS)], jnp.int32))
    err = float(np.max(np.abs(np.asarray(logits[0]) - want[-1])))
    assert err < 0.05 * float(np.max(np.abs(want[-1]))), err
