"""Model-family coverage: Qwen2 (qkv bias), Mistral (sliding window), Gemma2
(gelu, (1+w)-norms, post-norms, embed scaling, soft-capping, alternating
window). One shared decoder serves all families (models/llama.py), the way
the reference's single Ollama runtime serves its whole catalog
(`discovery.go:482-560` just infers metadata per family name)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.models import get_config, init_llama_params, init_kv_cache
from llm_mcp_tpu.models.configs import MODEL_CONFIGS
from llm_mcp_tpu.models.llama import (
    layer_windows,
    llama_decode_step,
    llama_prefill,
)

from family import stepwise  # noqa: E402

# every model call of this file is ONE trace and ONE compile a (configuration, shape):
# called bare, a step dispatches its primitives one by one and lowers its kernels again
llama_decode_step, llama_prefill = map(stepwise, (llama_decode_step, llama_prefill))

FAMILIES = ["tiny-qwen", "tiny-qwen3", "tiny-mistral", "tiny-gemma"]


@pytest.fixture(scope="module", params=FAMILIES)
def fam(request):
    cfg = get_config(request.param)
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def test_decode_matches_prefill(fam):
    """Incremental decode == one-shot prefill for every family's extras
    (biases, post-norms, softcaps, windows all hit both paths)."""
    cfg, params = fam
    key = jax.random.PRNGKey(1)
    prompt = jax.random.randint(key, (1, 7), 3, cfg.vocab_size)
    lengths = jnp.array([7], dtype=jnp.int32)
    full_logits, _, _ = llama_prefill(cfg, params, prompt, lengths)

    l6 = jnp.array([6], dtype=jnp.int32)
    _, ks6, vs6 = llama_prefill(cfg, params, prompt[:, :6], l6)
    cache = init_kv_cache(cfg, batch=1, max_seq=16, dtype=jnp.float32)
    ck = cache["k"].at[:, :, :, :6].set(ks6)
    cv = cache["v"].at[:, :, :, :6].set(vs6)
    tok = jnp.array([int(prompt[0, 6])], dtype=jnp.int32)
    lens = jnp.array([6], dtype=jnp.int32)
    step_logits, _, _ = llama_decode_step(cfg, params, ck, cv, tok, lens)
    np.testing.assert_allclose(
        np.asarray(step_logits[0]), np.asarray(full_logits[0]), rtol=2e-4, atol=2e-4
    )


def test_flash_prefill_matches_xla(fam):
    """The pallas flash kernel (window + softcap path) agrees with the
    einsum reference for each family."""
    cfg, params = fam
    key = jax.random.PRNGKey(2)
    prompt = jax.random.randint(key, (2, 128), 3, cfg.vocab_size)
    lengths = jnp.array([128, 77], dtype=jnp.int32)
    lx, _, _ = llama_prefill(cfg, params, prompt, lengths, attn_impl="xla")
    lp, _, _ = llama_prefill(cfg, params, prompt, lengths, attn_impl="pallas")
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp), rtol=5e-3, atol=5e-3)


def test_sliding_window_limits_context():
    """A token far outside every layer's window cannot influence the last
    token's logits; a token inside it does."""
    cfg = get_config("tiny-mistral")  # window 64 on ALL layers
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    S = 128
    prompt = jax.random.randint(key, (1, S), 3, cfg.vocab_size)
    lengths = jnp.array([S], dtype=jnp.int32)
    base, _, _ = llama_prefill(cfg, params, prompt, lengths)

    # position 10 is > 64 tokens before the last query (127) — outside the
    # window of every layer, and (single-layer-hop) cannot leak through two
    # sliding layers either since 127 - 10 > 2*64 is false... use pos 0:
    # 127 - 0 = 127 < 2*64 = 128 could leak via layer stacking, so compare
    # against receptive-field math: L layers × window W gives reach L*(W-1).
    # tiny-mistral: 2 * 63 = 126 < 127 ⇒ position 0 is unreachable.
    changed = prompt.at[0, 0].set((prompt[0, 0] + 1) % cfg.vocab_size)
    out_far, _, _ = llama_prefill(cfg, params, changed, lengths)
    np.testing.assert_allclose(np.asarray(base), np.asarray(out_far), rtol=1e-5, atol=1e-5)

    # position 100 is inside the last token's window — must change logits
    changed_near = prompt.at[0, 100].set((prompt[0, 100] + 1) % cfg.vocab_size)
    out_near, _, _ = llama_prefill(cfg, params, changed_near, lengths)
    assert float(jnp.max(jnp.abs(out_near - base))) > 1e-4


def test_gemma_alternating_windows():
    cfg = get_config("tiny-gemma")
    wins = np.asarray(layer_windows(cfg))
    assert wins.tolist() == [64, 0]  # layer 0 sliding, layer 1 global
    mis = np.asarray(layer_windows(get_config("tiny-mistral")))
    assert mis.tolist() == [64, 64]
    lla = np.asarray(layer_windows(get_config("tiny-llm")))
    assert lla.tolist() == [0, 0]


def test_gemma_logit_softcap_bounds_logits():
    cfg = get_config("tiny-gemma")
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # scale up the embedding to force large pre-cap logits
    params = dict(params, embed=params["embed"] * 50.0)
    prompt = jnp.ones((1, 8), dtype=jnp.int32) * 5
    logits, _, _ = llama_prefill(cfg, params, prompt, jnp.array([8], jnp.int32))
    assert float(jnp.max(jnp.abs(logits))) <= cfg.logit_softcap + 1e-3


def test_qwen_bias_params_exist_and_matter():
    cfg = get_config("tiny-qwen")
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert set(params["layers"]) >= {"bq", "bk", "bv"}
    prompt = jnp.array([[7, 9, 11]], dtype=jnp.int32)
    lens = jnp.array([3], dtype=jnp.int32)
    base, _, _ = llama_prefill(cfg, params, prompt, lens)
    bumped = dict(params)
    bumped["layers"] = dict(params["layers"], bq=params["layers"]["bq"] + 1.0)
    out, _, _ = llama_prefill(cfg, bumped, prompt, lens)
    assert float(jnp.max(jnp.abs(out - base))) > 1e-4


def test_real_configs_resolve_and_count():
    for name, pb in [
        ("qwen2.5-7b", 7.6),
        ("qwen2.5-0.5b", 0.49),
        ("mistral-7b", 7.2),
        ("gemma2-9b", 9.24),
    ]:
        cfg = MODEL_CONFIGS[name]
        approx = cfg.param_count() / 1e9
        assert abs(approx - pb) / pb < 0.15, (name, approx)
    # alias resolution
    assert get_config("Qwen/Qwen2.5-7B-Instruct").name == "qwen2.5-7b"
    assert get_config("mistral:7b").name == "mistral-7b"
    assert get_config("gemma2:9b").name == "gemma2-9b"


def test_hf_roundtrip_families():
    """HF-name export → import reproduces the stacked tree for every family
    (exercises the Gemma2 norm-name remap and Qwen biases)."""
    from llm_mcp_tpu.models.weights import hf_to_llama_params, llama_to_hf_tensors

    for name in FAMILIES:
        cfg = get_config(name)
        params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        tensors = llama_to_hf_tensors(cfg, params)
        back = hf_to_llama_params(cfg, tensors)
        for k, v in params["layers"].items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(back["layers"][k]), err_msg=f"{name}:{k}"
            )
        np.testing.assert_array_equal(np.asarray(params["embed"]), back["embed"])


def test_deepseek_r1_distill_configs():
    """The reference's seeded local deepseek names (04_smart_routing.sql:20,
    35; discovery.go:510 thinking inference) resolve to real configs with
    plausible parameter counts, and qkv_bias follows the base family."""
    cfg = get_config("deepseek-r1:1.5b")
    assert cfg.name == "deepseek-r1-distill-qwen-1.5b"
    approx = cfg.param_count() / 1e9
    assert abs(approx - 1.78) / 1.78 < 0.15, approx
    assert get_config("deepseek-r1:8b").name == "deepseek-r1-distill-llama-8b"
    assert get_config("deepscaler:1.5b").name == "deepseek-r1-distill-qwen-1.5b"
    assert get_config(
        "deepseek-ai/DeepSeek-R1-Distill-Qwen-1.5B"
    ).name == "deepseek-r1-distill-qwen-1.5b"
    # size decides base architecture: 7b is the Qwen2.5 distill; sizes with
    # no in-repo config must FAIL, not silently resolve cross-family
    assert get_config("deepseek-r1:7b").name == "qwen2.5-7b"
    with pytest.raises(KeyError):
        get_config("deepseek-r1:14b")


def test_qwen3_qk_norm_params_exist_and_matter():
    """qk_norm (Qwen3): per-head RMSNorm weights exist, apply pre-rope in
    every path, and perturbing them moves the logits."""
    cfg = get_config("tiny-qwen3")
    assert cfg.resolved_head_dim == 64 and cfg.dim // cfg.n_heads == 32
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert set(params["layers"]) >= {"q_norm", "k_norm"}
    assert "bq" not in params["layers"]  # qwen3 dropped the qwen2 biases
    prompt = jnp.array([[7, 9, 11]], dtype=jnp.int32)
    lens = jnp.array([3], dtype=jnp.int32)
    base, _, _ = llama_prefill(cfg, params, prompt, lens)
    bumped = dict(params)
    bumped["layers"] = dict(
        params["layers"], k_norm=params["layers"]["k_norm"] * 3.0
    )
    out, _, _ = llama_prefill(cfg, bumped, prompt, lens)
    assert float(jnp.max(jnp.abs(out - base))) > 1e-4


def test_qwen3_hf_config_inferred():
    """A Qwen3-style config.json maps to qk_norm=True with the explicit
    head_dim (decoupled from dim // n_heads below 8B)."""
    from llm_mcp_tpu.models.configs import config_from_hf

    cfg = config_from_hf(
        {
            "model_type": "qwen3",
            "vocab_size": 512,
            "hidden_size": 128,
            "num_hidden_layers": 2,
            "num_attention_heads": 4,
            "num_key_value_heads": 2,
            "intermediate_size": 256,
            "head_dim": 64,
            "rope_theta": 1000000.0,
            "rms_norm_eps": 1e-6,
            "max_position_embeddings": 4096,
            "tie_word_embeddings": True,
        },
        name="qwen3-test",
    )
    assert cfg.qk_norm and not cfg.qkv_bias
    assert cfg.resolved_head_dim == 64
    assert cfg.rope_theta == 1000000.0


def test_engine_serves_qwen3():
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine(
        "tiny-qwen3", max_slots=2, max_seq_len=64, dtype=jnp.float32,
        decode_chunk=2, quant="int8", kv_quant="int8",
    ).start()
    try:
        a = eng.generate("qwen3 qk norm", max_tokens=6, temperature=0.0)
        b = eng.generate("qwen3 qk norm", max_tokens=6, temperature=0.0)
        assert a["text"] == b["text"]
        assert a["usage"]["completion_tokens"] >= 1
    finally:
        eng.shutdown()
