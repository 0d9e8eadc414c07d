"""DeepSeek-V2 family: yarn rope, shared-expert MoE, first-dense split, and
the HF checkpoint mapping (kv_a_proj_with_mqa / kv_b_proj / mlp.experts.* /
mlp.shared_experts.* incl. the rope-dim de-interleave).

`tiny-v2` exercises every V2 mechanism at toy size; `deepseek-v2-lite` is the
published checkpoint's real config (HF deepseek-ai/DeepSeek-V2-Lite).
Reference analog: the reference only catalogs deepseek names via Ollama
(`discovery.go:510`); here the architecture executes in-process.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.models import (
    get_config,
    init_kv_cache,
    init_llama_params,
    llama_decode_step,
    llama_prefill,
)
from llm_mcp_tpu.models.weights import (
    hf_to_llama_params,
    llama_to_hf_tensors,
    load_llama_checkpoint,
    write_safetensors,
    _rope_perm,
)

from family import stepwise  # noqa: E402

# every model call of this file is ONE trace and ONE compile a (configuration, shape):
# called bare, a step dispatches its primitives one by one and lowers its kernels again
llama_decode_step, llama_prefill = map(stepwise, (llama_decode_step, llama_prefill))

CFG = get_config("tiny-v2")


@pytest.fixture(scope="module")
def setup():
    params = init_llama_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    return CFG, params


def test_param_tree_structure(setup):
    cfg, params = setup
    assert "dense_layers" in params
    d, m = params["dense_layers"], params["layers"]
    # dense prologue: dense FFN, no router; MoE stack: routed + shared experts
    assert "w1" in d and "router" not in d
    assert d["w1"].shape == (cfg.first_dense_layers, cfg.dim, cfg.ffn_hidden)
    L_moe = cfg.n_layers - cfg.first_dense_layers
    assert m["router"].shape == (L_moe, cfg.dim, cfg.n_experts)
    assert m["w1e"].shape == (L_moe, cfg.n_experts, cfg.dim, cfg.moe_ffn_hidden)
    assert m["w1s"].shape == (
        L_moe, cfg.dim, cfg.n_shared_experts * cfg.moe_ffn_hidden
    )
    # both blocks carry their own MLA attention
    for blk in (d, m):
        for k in ("wq_mla", "w_dkv", "kv_norm", "w_ukv", "wo_mla"):
            assert k in blk, k


def test_yarn_rope_matches_reference_formula():
    """rope_tables must reproduce the published yarn recipe (HF
    DeepseekV2YarnRotaryEmbedding): blended inv_freq with the
    beta_fast/beta_slow linear ramp, mscale ratio on cos/sin."""
    from llm_mcp_tpu.ops.rope import rope_tables

    cfg = CFG
    dr = cfg.qk_rope_head_dim
    pos = np.arange(0, 200, 7, dtype=np.int32)
    cos, sin = rope_tables(cfg, dr, jnp.asarray(pos))

    # independent numpy re-derivation of the HF formula
    half = dr // 2
    freq_extra = 1.0 / (cfg.rope_theta ** (np.arange(half) / half))
    freq_inter = freq_extra / cfg.rope_factor

    def corr_dim(n_rot):
        return (dr * math.log(cfg.rope_orig_max / (n_rot * 2 * math.pi))) / (
            2 * math.log(cfg.rope_theta)
        )

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dr - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = freq_inter * ramp + freq_extra * (1 - ramp)

    def get_mscale(scale, m):
        return 0.1 * m * math.log(scale) + 1.0 if scale > 1 and m else 1.0

    msc = get_mscale(cfg.rope_factor, cfg.yarn_mscale) / get_mscale(
        cfg.rope_factor, cfg.yarn_mscale_all_dim
    )
    ang = pos[:, None].astype(np.float64) * inv_freq[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang) * msc, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang) * msc, rtol=1e-5, atol=1e-5)
    # and the attention-scale correction is live for this config
    assert abs(cfg.yarn_attn_mscale - get_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim) ** 2) < 1e-9


def test_decode_matches_prefill(setup):
    """Absorbed decode over the latent cache must agree step-for-step with a
    fresh expanded prefill — THROUGH the dense prologue, the MoE layers with
    shared experts, and the yarn rope."""
    cfg, params = setup
    B, S = 2, 32
    prompt = np.array(
        [[7, 8, 9, 10, 11, 0, 0, 0], [21, 22, 23, 0, 0, 0, 0, 0]], np.int32
    )
    lens = np.array([5, 3], np.int32)
    logits, cs, rs = llama_prefill(cfg, params, jnp.asarray(prompt), jnp.asarray(lens))
    cache = init_kv_cache(cfg, B, S, dtype=jnp.float32)
    ck = cache["k"].at[:, :, :, : prompt.shape[1]].set(cs)
    cv = cache["v"].at[:, :, :, : prompt.shape[1]].set(rs)

    seqs = [list(prompt[b, : lens[b]]) for b in range(B)]
    cur = jnp.asarray(np.argmax(np.asarray(logits), -1), jnp.int32)
    cur_lens = jnp.asarray(lens, jnp.int32)
    for step in range(4):
        dl, ck, cv = llama_decode_step(cfg, params, ck, cv, cur, cur_lens)
        for b in range(B):
            seqs[b].append(int(cur[b]))
        maxlen = max(len(s) for s in seqs)
        ref_toks = np.zeros((B, maxlen), np.int32)
        ref_lens = np.array([len(s) for s in seqs], np.int32)
        for b in range(B):
            ref_toks[b, : len(seqs[b])] = seqs[b]
        rl, _, _ = llama_prefill(cfg, params, jnp.asarray(ref_toks), jnp.asarray(ref_lens))
        da, ra = np.asarray(dl), np.asarray(rl)
        assert (np.argmax(da, -1) == np.argmax(ra, -1)).all(), step
        corr = np.corrcoef(da.ravel(), ra.ravel())[0, 1]
        # looser than the dense-MLA parity bound (0.999): top-k expert
        # selection amplifies f32-level differences between the absorbed and
        # expanded paths into a different (legitimate) expert choice on
        # near-tie router logits under random init
        assert corr > 0.995, (step, corr)
        cur = jnp.asarray(np.argmax(da, -1), jnp.int32)
        cur_lens = cur_lens + 1


def test_rope_perm_roundtrip():
    dr = CFG.qk_rope_head_dim
    perm, inv = _rope_perm(dr), _rope_perm(dr, inverse=True)
    x = np.arange(dr)
    np.testing.assert_array_equal(x[perm][inv], x)
    # de-interleave semantics: checkpoint col 2j lands at split-half col j
    assert perm[0] == 0 and perm[1] == 2 and perm[dr // 2] == 1


def test_hf_checkpoint_roundtrip_identical_logits(tmp_path):
    """Write tiny-v2 as an HF-layout DeepseekV2 checkpoint (the published
    names: kv_a_proj_with_mqa, kv_b_proj, mlp.gate, mlp.experts.*,
    mlp.shared_experts.*, dense mlp on layer 0), load it back through the
    full load_llama_checkpoint path, and require identical logits."""
    cfg = CFG
    params = init_llama_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    hf = llama_to_hf_tensors(cfg, params)
    # the published names must be present
    assert "model.layers.0.mlp.gate_proj.weight" in hf  # dense layer 0
    assert "model.layers.1.mlp.gate.weight" in hf  # MoE router
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" in hf
    assert "model.layers.1.mlp.shared_experts.gate_proj.weight" in hf
    assert "model.layers.1.self_attn.kv_a_proj_with_mqa.weight" in hf
    assert "model.layers.1.self_attn.kv_b_proj.weight" in hf
    q = hf["model.layers.0.self_attn.q_proj.weight"]
    assert q.shape == (
        cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), cfg.dim
    )

    back = hf_to_llama_params(cfg, hf)
    for grp in ("layers", "dense_layers"):
        for k, v in params[grp].items():
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(back[grp][k]), rtol=1e-6, err_msg=f"{grp}.{k}"
            )

    # full path through sharded safetensors files on disk
    names = sorted(hf)
    half = len(names) // 2
    write_safetensors(
        str(tmp_path / "model-00001-of-00002.safetensors"),
        {n: hf[n] for n in names[:half]},
    )
    write_safetensors(
        str(tmp_path / "model-00002-of-00002.safetensors"),
        {n: hf[n] for n in names[half:]},
    )
    loaded = load_llama_checkpoint(cfg, str(tmp_path), dtype=jnp.float32)
    tokens = jnp.array([[1, 5, 9, 4]], dtype=jnp.int32)
    lengths = jnp.array([4], dtype=jnp.int32)
    ref, _, _ = llama_prefill(cfg, params, tokens, lengths)
    got, _, _ = llama_prefill(cfg, loaded, tokens, lengths)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=1e-5)


def test_v2_lite_config_resolves():
    for name in ("deepseek-v2-lite", "deepseek-ai/DeepSeek-V2-Lite",
                 "deepseek-v2:lite"):
        cfg = get_config(name)
        assert cfg.name == "deepseek-v2-lite", name
    cfg = get_config("deepseek-v2-lite")
    # the published config.json numbers
    assert (cfg.n_layers, cfg.n_experts, cfg.experts_per_tok) == (27, 64, 6)
    assert (cfg.n_shared_experts, cfg.first_dense_layers) == (2, 1)
    assert (cfg.kv_lora_rank, cfg.qk_rope_head_dim) == (512, 64)
    assert cfg.rope_factor == 40.0 and cfg.rope_orig_max == 4096
    assert not cfg.norm_topk_prob
    # ~15.7B params within 5%
    assert abs(cfg.param_count() / 15.7e9 - 1.0) < 0.05


def test_engine_serves_tiny_v2_end_to_end():
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine(
        "tiny-v2", max_slots=2, max_seq_len=128, dtype=jnp.float32, decode_chunk=4
    ).start()
    try:
        out = eng.generate("deepseek structure", max_tokens=8, temperature=0.0)
        assert out["finish_reason"] in ("length", "stop")
        assert out["usage"]["completion_tokens"] >= 1
    finally:
        eng.shutdown()
