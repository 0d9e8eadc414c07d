"""The benchmark's float32 reference against the program's own model code at
a tiny size: the same weights through models/llama.py and through
benchmark/reference.py must agree to float32 rounding."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, reference  # noqa: E402
from llm_mcp_tpu.models import init_llama_params  # noqa: E402
from llm_mcp_tpu.models.configs import resolve_config  # noqa: E402
from llm_mcp_tpu.models.llama import llama_encode, llama_prefill  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    cfg = resolve_config("tiny-qwen3", "")
    params = init_llama_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    # the random tree's q/k norm weights are ones: move them, so that a
    # reference that skipped the per-head norm would be caught
    key = jax.random.PRNGKey(4)
    layers = dict(params["layers"])
    for i, name in enumerate(("q_norm", "k_norm")):
        layers[name] = 1.0 + 0.3 * jax.random.normal(jax.random.fold_in(key, i), layers[name].shape)
    return cfg, dict(params, layers=layers)


def test_logits_match_the_programs_prefill(tiny):
    cfg, params = tiny
    assert cfg.qk_norm
    rng = np.random.default_rng(0)
    n, S = 21, 32
    toks = np.zeros((1, S), np.int32)
    toks[0, :n] = rng.integers(1, cfg.vocab_size, n)
    want = np.asarray(llama_prefill(cfg, params, jnp.asarray(toks), jnp.asarray([n], jnp.int32),
                                    attn_impl="xla")[0], np.float32)[0]
    cols = np.arange(0, cfg.vocab_size, 3)
    got = reference.logits(cfg, params, toks[0], np.asarray([n - 1]), cols)[0]
    assert got.shape == (len(cols),)
    np.testing.assert_allclose(got, want[cols], atol=2e-4 * float(np.abs(want).max()))


def test_a_reference_without_qk_norm_would_be_caught(tiny):
    cfg, params = tiny
    toks = np.arange(1, 33, dtype=np.int32)
    cols = np.arange(cfg.vocab_size)
    with_norm = reference.logits(cfg, params, toks, np.asarray([31]), cols)
    without = reference.logits(dataclasses.replace(cfg, qk_norm=False), params, toks,
                               np.asarray([31]), cols)
    assert float(np.abs(with_norm - without).max()) > 0.05 * float(np.abs(with_norm).max())


def test_pooled_output_matches_the_programs_encoder(tiny):
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, pooling="last")
    rng = np.random.default_rng(1)
    S, lengths = 32, [19, 32]
    toks = np.zeros((2, S), np.int32)
    for b, n in enumerate(lengths):
        toks[b, :n] = rng.integers(1, cfg.vocab_size, n)
    want = np.asarray(llama_encode(cfg, params, jnp.asarray(toks), jnp.asarray(lengths, jnp.int32)))
    for b, n in enumerate(lengths):
        got = reference.pooled(cfg, params, toks[b], n)
        assert abs(float(np.linalg.norm(got)) - 1.0) < 1e-5
        assert 1.0 - float(got @ want[b]) < 1e-5
        cut = reference.pooled(cfg, params, toks[b], n, dimensions=16)
        ref = want[b][:16] / np.linalg.norm(want[b][:16])
        assert cut.shape == (16,) and 1.0 - float(cut @ ref) < 1e-5


def test_reference_reads_the_int8_tree():
    from llm_mcp_tpu.models.quant import init_llama_params_quantized

    cfg = resolve_config("tiny-qwen3", "")
    params = init_llama_params_quantized(cfg, jax.random.PRNGKey(0), scale_dtype=jnp.bfloat16)
    toks = np.arange(1, 33, dtype=np.int32)
    out = reference.logits(cfg, params, toks, np.asarray([7, 31]), np.arange(64))
    assert out.shape == (2, 64) and np.isfinite(out).all() and float(np.abs(out).max()) > 0
    # the bytes a decode round must read: every linear and the tied table once
    n_bytes = peaks.decode_weight_bytes(params)
    assert n_bytes == peaks.tree_bytes(params)  # tied head: the table is the head
    assert peaks.kv_row_bytes(cfg, "int8") == cfg.n_layers * cfg.n_kv_heads * 2 * (cfg.resolved_head_dim + 2)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
