"""MoE (expert parallelism) and pipeline parallelism.

Both subsystems are new TPU-native surface (the reference routes Mixtral-class
names to external Ollama, `discovery.go:526-551`; it has no layer pipelining).
Equivalence is asserted against the single-device dense reference paths on the
virtual 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.models import (
    get_config,
    init_llama_params,
    llama_prefill,
    llama_decode_step,
    init_kv_cache,
    hf_to_llama_params,
    llama_to_hf_tensors,
)
from llm_mcp_tpu.models.moe import expert_capacity, moe_dispatch, moe_ffn
from llm_mcp_tpu.parallel.mesh import make_mesh, mesh_axis_sizes
from llm_mcp_tpu.parallel.sharding import llama_param_specs, shard_pytree
from llm_mcp_tpu.parallel.pipeline import pipeline_prefill, stack_stages

from family import stepwise  # noqa: E402

# every model call of this file is ONE trace and ONE compile a (configuration, shape):
# called bare, a step dispatches its primitives one by one and lowers its kernels again
llama_decode_step, llama_prefill = map(stepwise, (llama_decode_step, llama_prefill))

MOE = get_config("tiny-moe")
DENSE = get_config("tiny-llm")


@pytest.fixture(scope="module")
def moe_params():
    return init_llama_params(MOE, jax.random.PRNGKey(0), dtype=jnp.float32)


# ---------------------------------------------------------------------------
# MoE routing mechanics
# ---------------------------------------------------------------------------


def test_expert_capacity_static():
    assert expert_capacity(MOE, 8) == 8  # tiny-moe factor 2.0 ⇒ dropless C=T
    assert expert_capacity(get_config("mixtral-8x7b"), 64) == int(
        np.ceil(64 * 2 / 8 * 1.25)
    )
    assert expert_capacity(MOE, 1) == 1  # clamped to T


def test_dispatch_respects_topk_and_gates():
    T, E = 6, 4
    key = jax.random.PRNGKey(1)
    logits = jax.random.normal(key, (T, E))
    C = T  # capacity ample: nothing dropped
    dispatch, combine = moe_dispatch(MOE, logits, C)
    # every token lands in exactly k expert slots
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(dispatch, axis=(1, 2))), np.full(T, MOE.experts_per_tok)
    )
    # combine sums to 1 per token (renormalized top-k gates)
    np.testing.assert_allclose(np.asarray(jnp.sum(combine, axis=(1, 2))), np.ones(T), rtol=1e-6)
    # no expert slot double-booked
    assert np.asarray(jnp.max(jnp.sum(dispatch, axis=0))) <= 1.0 + 1e-6


def test_dispatch_drops_overflow():
    """With capacity 1, an expert chosen by many tokens keeps only the first."""
    T, E = 5, 4
    logits = jnp.zeros((T, E)).at[:, 0].set(10.0)  # all tokens want expert 0
    dispatch, _ = moe_dispatch(MOE, logits, 1)
    per_expert = np.asarray(jnp.sum(dispatch, axis=(0, 2)))
    assert per_expert[0] == 1.0  # only one token admitted to expert 0


def test_moe_ffn_matches_manual_dense_computation(moe_params):
    """With ample capacity, moe_ffn == explicit per-token top-k mixture."""
    lp = jax.tree.map(lambda x: x[0], moe_params["layers"])
    T = 4
    x = jax.random.normal(jax.random.PRNGKey(2), (T, MOE.dim), dtype=jnp.float32)

    big = MOE.__class__(**{**MOE.__dict__, "capacity_factor": 10.0})
    y = moe_ffn(big, lp, x)

    probs = jax.nn.softmax((x @ lp["router"]).astype(jnp.float32), axis=-1)
    top_g, top_i = jax.lax.top_k(probs, MOE.experts_per_tok)
    top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
    want = np.zeros((T, MOE.dim), dtype=np.float32)
    for t in range(T):
        for j in range(MOE.experts_per_tok):
            e = int(top_i[t, j])
            xe = x[t]
            ye = (jax.nn.silu(xe @ lp["w1e"][e]) * (xe @ lp["w3e"][e])) @ lp["w2e"][e]
            want[t] += float(top_g[t, j]) * np.asarray(ye)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# MoE end-to-end: prefill/decode consistency, ep sharding, HF mapping
# ---------------------------------------------------------------------------


def test_moe_decode_matches_prefill(moe_params):
    key = jax.random.PRNGKey(3)
    prompt = jax.random.randint(key, (1, 6), 3, MOE.vocab_size)
    lengths = jnp.array([6], dtype=jnp.int32)
    ref_logits, ks, vs = llama_prefill(MOE, moe_params, prompt, lengths)

    cache = init_kv_cache(MOE, 1, 16, dtype=jnp.float32)
    ck, cv = cache["k"], cache["v"]
    logits = None
    for pos in range(6):
        logits, ck, cv = llama_decode_step(
            MOE,
            moe_params,
            ck,
            cv,
            prompt[:, pos],
            jnp.array([pos], dtype=jnp.int32),
        )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-3, atol=2e-3
    )


def test_moe_sharded_ep_tp_matches_single_device(moe_params):
    """jit over a dp×ep×tp mesh with expert sharding == single-device."""
    mesh = make_mesh("dp=2,ep=2,tp=2")
    specs = llama_param_specs(MOE)
    # stacked layer axis rides pp (size-1 here), experts on ep, ffn on tp
    assert specs["layers"]["w1e"] == __import__("jax").sharding.PartitionSpec(
        "pp", "ep", None, "tp"
    )
    sharded = shard_pytree(moe_params, specs, mesh)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (4, 8), 3, MOE.vocab_size)
    lengths = jnp.array([8, 5, 8, 3], dtype=jnp.int32)

    ref, _, _ = jax.jit(lambda p, t, l: llama_prefill(MOE, p, t, l))(
        moe_params, prompt, lengths
    )
    with mesh:
        got, _, _ = jax.jit(lambda p, t, l: llama_prefill(MOE, p, t, l))(
            sharded, prompt, lengths
        )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_moe_hf_mapping_roundtrip(moe_params):
    hf = llama_to_hf_tensors(MOE, moe_params)
    assert "model.layers.0.block_sparse_moe.gate.weight" in hf
    assert "model.layers.1.block_sparse_moe.experts.3.w2.weight" in hf
    back = hf_to_llama_params(MOE, hf)
    for leaf_a, leaf_b in zip(
        jax.tree_util.tree_leaves(moe_params), jax.tree_util.tree_leaves(back)
    ):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


# ---------------------------------------------------------------------------
# Pipeline parallelism
# ---------------------------------------------------------------------------


def test_mesh_five_axes():
    sizes = mesh_axis_sizes("dp=2,pp=2,tp=2", 8)
    assert sizes == {"dp": 2, "pp": 2, "ep": 1, "sp": 1, "tp": 2}
    mesh = make_mesh("pp=2,tp=4")
    assert mesh.shape["pp"] == 2 and mesh.shape["tp"] == 4


def test_stack_stages_shapes():
    params = init_llama_params(DENSE, jax.random.PRNGKey(0), dtype=jnp.float32)
    st = stack_stages(params["layers"], 2)
    assert st["wq"].shape[0] == 2 and st["wq"].shape[1] == DENSE.n_layers // 2


@pytest.mark.parametrize("pp,m", [(2, 2), (2, 4)])
def test_pipeline_prefill_matches_reference(pp, m):
    params = init_llama_params(DENSE, jax.random.PRNGKey(5), dtype=jnp.float32)
    mesh = make_mesh(f"pp={pp}", devices=jax.devices()[:pp])
    B, S = 4, 8
    prompt = jax.random.randint(jax.random.PRNGKey(6), (B, S), 3, DENSE.vocab_size)
    lengths = jnp.array([8, 3, 6, 8], dtype=jnp.int32)

    ref_logits, ref_k, ref_v = llama_prefill(DENSE, params, prompt, lengths)
    got_logits, got_k, got_v = pipeline_prefill(
        DENSE, params, prompt, lengths, mesh, n_microbatches=m
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(ref_k), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(ref_v), rtol=2e-4, atol=2e-4)


def test_pipeline_prefill_moe():
    """pp composes with MoE layers (the Mixtral-class serving shape)."""
    params = init_llama_params(MOE, jax.random.PRNGKey(7), dtype=jnp.float32)
    mesh = make_mesh("pp=2", devices=jax.devices()[:2])
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 8), 3, MOE.vocab_size)
    lengths = jnp.array([8, 4], dtype=jnp.int32)
    ref_logits, _, _ = llama_prefill(MOE, params, prompt, lengths)
    got_logits, _, _ = pipeline_prefill(MOE, params, prompt, lengths, mesh)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(ref_logits), rtol=2e-3, atol=2e-3
    )
