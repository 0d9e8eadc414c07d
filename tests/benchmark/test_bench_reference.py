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


# -- the DeepSeek-V2 family's reference (benchmark/references/deepseek_v2.py) ----


@pytest.fixture(scope="module")
def v2():
    """The reference a configuration file names, loaded as run.py loads it, and
    tiny-v2 (a dense layer, two routed layers with shared experts, yarn)."""
    from benchmark import run as bench_run

    name, ref = bench_run.load_reference(
        {"reference": "deepseek_v2", "program": {"engine": "generation"}})
    assert name == "deepseek_v2"
    return ref, resolve_config("tiny-v2", "")


def v2_tree(cfg, kind, monkeypatch):
    if kind == "float32":
        params = init_llama_params(cfg, jax.random.PRNGKey(5), dtype=jnp.float32)
        # the random tree's latent norm weights are ones: move them, so that a
        # reference that skipped the norm would be caught
        for block in ("dense_layers", "layers"):
            shape = params[block]["kv_norm"].shape
            params[block] = dict(params[block], kv_norm=1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(block)), shape))
        return params
    from llm_mcp_tpu.models import quant

    # the program's weight-only int8 path (x @ q, then the scales): its default
    # also rounds the ACTIVATIONS to int8, which no float32 forward agrees with
    # to rounding
    monkeypatch.setattr(quant, "_W8A8", False)
    return quant.init_llama_params_quantized(cfg, jax.random.PRNGKey(5), scale_dtype=jnp.float32)


@pytest.mark.parametrize("kind", ["float32", "int8"])
def test_v2_reference_matches_the_programs_dropless_prefill_and_decode(v2, kind, monkeypatch):
    """Prefill logits at the last row, then three decode steps through the
    latent cache (absorbed projections, dropless by `moe_capacity` = rows),
    against the reference's one full forward of the whole sequence."""
    from llm_mcp_tpu.models import init_kv_cache
    from llm_mcp_tpu.models.llama import llama_decode_step

    ref, cfg = v2
    params = v2_tree(cfg, kind, monkeypatch)
    # capacity = ceil(T k / E x E / k) = T: the program's prefill drops nothing
    dropless = dataclasses.replace(cfg, capacity_factor=cfg.n_experts / cfg.experts_per_tok)
    rng = np.random.default_rng(2)
    n, S, steps = 27, 32, 3
    seq = [int(t) for t in rng.integers(1, cfg.vocab_size, n)]
    toks = np.zeros((1, S), np.int32)
    toks[0, :n] = seq
    logits, latents, rope_keys = llama_prefill(dropless, params, jnp.asarray(toks),
                                               jnp.asarray([n], jnp.int32))
    cache = init_kv_cache(cfg, 1, 64, dtype=jnp.float32)
    ck = cache["k"].at[:, :, :, :S].set(latents)
    cv = cache["v"].at[:, :, :, :S].set(rope_keys)
    served = [np.asarray(logits, np.float32)[0]]
    for _ in range(steps):
        seq.append(int(np.argmax(served[-1])))
        logits, ck, cv = llama_decode_step(cfg, params, ck, cv, jnp.asarray(seq[-1:], jnp.int32),
                                           jnp.asarray([len(seq) - 1], jnp.int32))
        served.append(np.asarray(logits, np.float32)[0])
    full = np.asarray(seq + [0] * (64 - len(seq)), np.int32)
    want = ref.logits(cfg, params, full, np.arange(n - 1, n + steps), np.arange(cfg.vocab_size))
    for k, got in enumerate(served):
        np.testing.assert_allclose(got, want[k], atol=2e-4 * float(np.abs(want[k]).max()),
                                   err_msg=f"row {k} (0 is the prefill's)")


def test_v2_programs_prefill_drops_tokens_at_its_default_capacity(v2):
    """PERF.md section 7's first debt of the `model_config` PR: at
    `capacity_factor` 1.25 an unpadded prompt overflows an expert and the
    program's prefill leaves the reference by a share of the logits; dropless,
    it agrees to rounding."""
    ref, cfg = v2
    params = init_llama_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    toks = np.random.default_rng(101).integers(1, cfg.vocab_size, (1, 64)).astype(np.int32)
    want = ref.logits(cfg, params, toks[0], np.asarray([63]), np.arange(cfg.vocab_size))[0]
    off = {}
    for factor in (1.25, cfg.n_experts / cfg.experts_per_tok):
        got = llama_prefill(dataclasses.replace(cfg, capacity_factor=factor), params,
                            jnp.asarray(toks), jnp.asarray([64], jnp.int32))[0]
        off[factor] = float(np.abs(np.asarray(got, np.float32)[0] - want).max() / np.abs(want).max())
    assert off[1.25] > 0.1 and off[2.0] < 2e-4, off


@pytest.mark.parametrize("left_out", ["shared_experts", "yarn_scale", "shared_rope_key"])
def test_a_v2_reference_without_a_mechanism_would_be_caught(v2, left_out, monkeypatch):
    ref, cfg = v2
    params = init_llama_params(cfg, jax.random.PRNGKey(5), dtype=jnp.float32)
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, 64).astype(np.int32)
    rows, cols = np.asarray([63]), np.arange(cfg.vocab_size)
    whole = ref.logits(cfg, params, toks, rows, cols)
    if left_out == "shared_experts":
        wrong = dataclasses.replace(cfg, n_shared_experts=0)
    elif left_out == "yarn_scale":  # the frequencies stay yarn's; both mscale terms go
        wrong = dataclasses.replace(cfg, yarn_mscale=0.0, yarn_mscale_all_dim=0.0)
    else:  # the position's rope key reaches the first head only
        def own_key(k_nope, k_rope, head):
            return jnp.concatenate([k_nope[:, head], k_rope * (head == 0)], axis=-1)

        monkeypatch.setattr(ref, "_head_keys", own_key)
        wrong = dataclasses.replace(cfg, name="tiny-v2-own-key")  # another jit key: traced anew
    without = ref.logits(wrong, params, toks, rows, cols)
    assert float(np.abs(whole - without).max()) > 0.05 * float(np.abs(whole).max())
    assert float(np.abs(whole - without).max()) > 250 * 2e-4 * float(np.abs(whole).max())


def test_v2_query_latent_branch_is_the_published_form(v2):
    """`q_lora_rank`: down, RMSNorm, up. The program has no such path, so the
    branch is held to the equations written out in numpy, as the routing in
    groups and the share are (below)."""
    ref, cfg = v2
    cfg = dataclasses.replace(cfg, q_lora_rank=24)
    rng = np.random.default_rng(4)
    width = cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    a, g, b = (rng.standard_normal(s).astype(np.float32)
               for s in ((2, cfg.dim, 24), (2, 24), (2, 24, width)))
    x = rng.standard_normal((5, cfg.dim)).astype(np.float32)
    stack = {"wq_a": jnp.asarray(a), "q_a_norm": jnp.asarray(g), "wq_b": jnp.asarray(b)}
    got = np.asarray(ref._queries(cfg, stack, jnp.int32(1), jnp.asarray(x)))
    c = x.astype(np.float64) @ a[1]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + cfg.norm_eps) * g[1]
    np.testing.assert_allclose(got.reshape(5, width), c @ b[1], rtol=2e-4, atol=2e-4)


def test_what_a_router_near_tie_costs_in_logits(v2, monkeypatch):
    """The reading behind deepseek_v2.SERVED_TOL_REL, kept small: at V2-Lite's
    routing (64 experts, top 6, raw gates, 1 + 26 layers) on tiny-v2's widths,
    every row whose 6th and 7th scores lie within 2**-6 of each other gets the
    7th expert for the 6th. The logits move, and the greedy token's regret
    stays under half the tolerance."""
    ref, base = v2
    base = dataclasses.replace(base, n_experts=64, experts_per_tok=6, n_layers=27)
    params = init_llama_params(base, jax.random.PRNGKey(3), dtype=jnp.float32)

    def near_ties_swapped(cfg, scores):
        k = cfg.experts_per_tok
        top, idx = jax.lax.top_k(scores, k + 1)
        tie = ((top[:, k - 1] - top[:, k]) < 2.0**-6 * top[:, k - 1])[:, None]
        idx = jnp.concatenate([idx[:, :k - 1], jnp.where(tie, idx[:, k:], idx[:, k - 1:k])], axis=1)
        top = jnp.concatenate([top[:, :k - 1], jnp.where(tie, top[:, k:], top[:, k - 1:k])], axis=1)
        chosen = idx[:, :, None] == jnp.arange(cfg.n_experts)[None, None, :]
        return jnp.sum(jnp.where(chosen, top[:, :, None] * cfg.routed_scaling_factor, 0.0), axis=1)

    rows, cols = np.arange(100, 128), np.arange(259)
    shift, regret = [], []
    for seed in range(3):
        toks = np.random.default_rng(seed).integers(1, 259, 128).astype(np.int32)
        want = ref.logits(base, params, toks, rows, cols)
        with monkeypatch.context() as patch:
            patch.setattr(ref, "_gates", near_ties_swapped)
            # another jit key, so that the layer is traced anew with the swap
            got = ref.logits(dataclasses.replace(base, name="swapped"), params, toks, rows, cols)
        scale = np.abs(want).max(axis=1)
        shift.append(float((np.abs(got - want).max(axis=1) / scale).max()))
        served = got.argmax(axis=1)
        regret.append(float(((want.max(axis=1) - want[np.arange(len(rows)), served]) / scale).max()))
    assert min(shift) > 0.01, shift  # the swaps are felt
    assert max(regret) < ref.SERVED_TOL_REL / 2, regret


# -- routing in groups and an expert share (PR 47: the yardstick before the program's path) --

# sha256 of the parent commit's logits (04b7733, before routing in groups) of tiny-v2 under
# PRNGKey(5), 64 tokens of default_rng(3), every row and column; and of a small float32 product
# at HIGHEST through exp on the machine that stored it: where this machine computes the canary
# bit for bit, it has to compute the logits so too
PARENT_LOGITS_SHA = "8bcc7e670c3f56799f5e9187621edca38324facfdfabd38ef0c5b51d6ebc7c81"
CANARY_SHA = "2bfc83b6757401ab44a6e09af9fe60618af36f064c024c6d7a3a4346f137086d"


def _sha(a) -> str:
    import hashlib

    return hashlib.sha256(np.ascontiguousarray(np.asarray(a, np.float32)).tobytes()).hexdigest()


def _parent_gates(cfg, scores):
    """`_gates` as the parent commit has it, letter for letter: greedy over all experts."""
    top, idx = jax.lax.top_k(scores, cfg.experts_per_tok)
    if cfg.norm_topk_prob and cfg.experts_per_tok > 1:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        top = top * cfg.routed_scaling_factor
    chosen = idx[:, :, None] == jnp.arange(cfg.n_experts)[None, None, :]
    return jnp.sum(jnp.where(chosen, top[:, :, None], 0.0), axis=1)  # [T, E]


def grouped(cfg, **fields):
    """The configuration with the fields a `model_config` PR would bring
    (`n_group`, `topk_group`): the reference reads them by `getattr`."""
    values = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    added = [(k, int, v) for k, v in fields.items() if k not in values]
    made = dataclasses.make_dataclass("Grouped", added, bases=(type(cfg),), frozen=True) if added else type(cfg)
    return made(**{**values, **fields})


def test_v2_at_one_group_is_bit_for_bit_the_forward_before_routing_in_groups(v2, monkeypatch):
    ref, cfg = v2
    params = init_llama_params(cfg, jax.random.PRNGKey(5), dtype=jnp.float32)
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, 64).astype(np.int32)
    rows, cols = np.arange(64), np.arange(cfg.vocab_size)
    got = ref.logits(cfg, params, toks, rows, cols)
    # the fields at 1 and 1 are the configuration without them
    one = ref.logits(grouped(cfg, n_group=1, topk_group=1), params, toks, rows, cols)
    assert np.array_equal(got, one)
    # against the parent's own selection on this machine, traced anew under another jit key
    monkeypatch.setattr(ref, "_gates", _parent_gates)
    parent = ref.logits(dataclasses.replace(cfg, name="tiny-v2-parent-gates"), params, toks, rows, cols)
    assert np.array_equal(got, parent)
    # and against the digest stored from the parent commit, where this machine rounds as that one did
    a = np.random.default_rng(0).standard_normal((64, 128)).astype(np.float32)
    b = np.random.default_rng(1).standard_normal((128, 96)).astype(np.float32)
    canary = jnp.exp(jnp.matmul(jnp.asarray(a), jnp.asarray(b), precision=jax.lax.Precision.HIGHEST) * 0.05)
    if _sha(canary) == CANARY_SHA:
        assert _sha(got) == PARENT_LOGITS_SHA
    assert ref.HELD["topk_method"](cfg) == "greedy" and ref.HELD["n_group"](cfg) == 1


def _numpy_gates(scores, n_group, topk_group, k, norm, scale):
    """ISSUE 47's equations in plain numpy, a row at a time: the best score of
    each group of consecutive experts, the `topk_group` best groups keep their
    scores and every other score is 0, the top k of what is left with their
    scores as gates, renormalised or scaled."""
    out = np.zeros_like(scores)
    per = scores.shape[1] // n_group
    for t, s in enumerate(scores):
        best = s.reshape(n_group, per).max(axis=1)
        kept = np.argsort(-best, kind="stable")[:topk_group]
        left = np.where(np.isin(np.arange(len(s)) // per, kept), s, 0.0)
        top = np.argsort(-left, kind="stable")[:k]
        out[t, top] = s[top] / s[top].sum() if norm and k > 1 else s[top] * scale
    return out


@pytest.mark.parametrize("norm", [False, True])
def test_v2_group_limited_routing_is_the_published_equations(v2, norm):
    """8 experts in 4 groups, 2 groups a token, 3 experts a token: the chosen
    experts and their gates are the numpy's, and no row's experts lie in more
    than `topk_group` groups, though a third of the rows' greedy top 3 do."""
    ref, base = v2
    cfg = grouped(dataclasses.replace(base, n_experts=8, experts_per_tok=3, norm_topk_prob=norm,
                                      routed_scaling_factor=16.0), n_group=4, topk_group=2)
    ref.check(cfg)
    rng = np.random.default_rng(11)
    logits = rng.standard_normal((256, 8)).astype(np.float32) * 2.0
    scores = np.exp(logits - logits.max(axis=1, keepdims=True))
    scores = (scores / scores.sum(axis=1, keepdims=True)).astype(np.float32)
    got = np.asarray(ref._gates(cfg, jnp.asarray(scores)))
    want = _numpy_gates(scores, 4, 2, 3, norm, 16.0)
    assert np.array_equal(got != 0, want != 0)  # the same experts, row for row
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    groups = [set(np.flatnonzero(row) // 2) for row in got]
    assert all((row != 0).sum() == 3 for row in got) and max(map(len, groups)) == 2
    greedy = np.asarray(ref._gates(dataclasses.replace(cfg, name="greedy-8"), jnp.asarray(scores)))
    assert np.array_equal(greedy, got)  # the name alone changes nothing
    free = np.asarray(ref._gates(grouped(cfg, n_group=1, topk_group=1), jnp.asarray(scores)))
    spread = [len(set(np.flatnonzero(row) // 2)) for row in free]
    assert 0.2 < np.mean(np.asarray(spread) == 3) < 0.6  # what the limit forbids does occur without it
    assert ref.HELD["topk_method"](cfg) == "group_limited_greedy"
    assert (ref.HELD["n_group"](cfg), ref.HELD["topk_group"](cfg)) == (4, 2)


def _ffn_stack(cfg, n_router, rng):
    D, Fm, E = cfg.dim, cfg.moe_ffn_hidden, cfg.n_experts
    w = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32) * shape[-2] ** -0.5)  # noqa: E731
    return {"router": w(1, D, n_router), "w1e": w(1, E, D, Fm), "w3e": w(1, E, D, Fm), "w2e": w(1, E, Fm, D),
            "w1s": w(1, D, 2 * Fm), "w3s": w(1, D, 2 * Fm), "w2s": w(1, 2 * Fm, D)}


def test_v2_four_shares_of_two_experts_add_up_to_the_uncut_layer(v2):
    """The guide's one test of a share: the router scores all 8 published
    experts and chooses groups and the top 3 over all of them in every share; a
    share applies its own two (one whole routing group, the first of ITS order)
    and leaves out what the absent would add; the shared experts are applied
    whole, so they are counted once. The four parts add up to the uncut layer."""
    ref, base = v2
    whole = grouped(dataclasses.replace(base, n_experts=8, experts_per_tok=3, n_shared_experts=2,
                                        routed_scaling_factor=16.0), n_group=4, topk_group=2)
    rng = np.random.default_rng(12)
    stack = _ffn_stack(whole, 8, rng)
    x = jnp.asarray(rng.standard_normal((48, whole.dim)).astype(np.float32))
    li = jnp.int32(0)
    uncut = np.asarray(ref._routed_ffn(whole, stack, li, x))
    parts = []
    for s in range(4):
        # this chip's order of the published experts: its own group first, the others after it
        order = np.roll(np.arange(8), -2 * s)
        share = grouped(dataclasses.replace(whole, n_experts=2, n_router_experts=8,
                                            n_shared_experts=2 if s == 0 else 0), n_group=4, topk_group=2)
        ref.check(share)
        assert ref.HELD["published.n_routed_experts"](share) == 8
        held = dict(stack, router=stack["router"][:, :, order],
                    **{k: stack[k][:, order[:2]] for k in ("w1e", "w3e", "w2e")})
        parts.append(np.asarray(ref._routed_ffn(share, held, li, x)))
    scale = float(np.abs(uncut).max())
    np.testing.assert_allclose(sum(parts), uncut, atol=2e-6 * scale, rtol=0)
    assert all(float(np.abs(p).max()) > 0.01 * scale for p in parts)  # every share adds its part
    assert float(np.abs(parts[0] - uncut).max()) > 0.05 * scale  # and one share alone is not the layer
    # a router cut to the held experts is another layer: every row would get its 3 experts from these 2
    with pytest.raises(NotImplementedError):
        ref.check(grouped(dataclasses.replace(whole, n_experts=3, n_router_experts=8), n_group=4, topk_group=2))
    with pytest.raises(NotImplementedError, match="selection bias"):
        ref._routed_ffn(whole, dict(stack, router_bias=jnp.zeros((1, 8))), li, x)


def test_each_reference_refuses_the_other_family(v2):
    ref, cfg = v2
    ref.check(cfg)
    reference.check(resolve_config("tiny-qwen3", ""))
    with pytest.raises(NotImplementedError):
        reference.check(cfg)
    with pytest.raises(NotImplementedError):
        ref.check(resolve_config("tiny-qwen3", ""))
