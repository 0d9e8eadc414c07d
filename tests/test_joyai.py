"""JoyAI-LLM-Flash's structure on `models/mla.py` at the tiny preset
(`tiny-joyai`: latent attention with a low-rank query in every layer, a leading
dense layer, a share of 4 of 16 routed experts with DeepSeek-V3's router beside a
shared expert), held to the plain reference `benchmark/references/joyai_flash.py`
on seeded float32 weights: LOGITS, not tokens, through every path a sequence can
take (whole prompt, bucketed chunks, packed chunks, decode) and both latent caches;
the low-rank query against numpy; the shares of the experts adding up to the uncut
layer; the counts member from the step programs to `perf_stats()["experts"]`; the
prediction module; the reference's controls."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.models import mla, moe
from llm_mcp_tpu.models.configs import get_config
from llm_mcp_tpu.models.llama import (
    init_kv_cache,
    init_llama_params,
    llama_decode_step,
    llama_prefill,
    llama_prefill_chunk_batch,
    llama_prefill_chunk_ragged,
)

from family import reference_for, reference_source, retrace, stepwise  # noqa: E402

# every model call of this file is ONE trace and ONE compile a (configuration, shape):
# called bare, a step dispatches its primitives one by one and lowers its kernels again
llama_decode_step, llama_prefill, llama_prefill_chunk_batch, llama_prefill_chunk_ragged = map(
    stepwise, (llama_decode_step, llama_prefill, llama_prefill_chunk_batch, llama_prefill_chunk_ragged))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 against float32, of logits whose largest is about 2: the program's
# absorbed, blocked and grouped products and the reference's expanded
# whole-sequence ones differ by rounding alone (3e-6 at worst, measured); every
# control but the two the configuration states moves a logit by 2e-2 and more
# (`test_the_controls_...`)
TOL = 1e-4
# through the int8 latent cache, by `_rel`'s MEDIAN over the rows: a past
# position's latent of 32 values and rope key of 16 are held to 1 part in 127 of
# the position's largest, which moves a row's logits by 0.006 of its largest in
# the median (measured; 0.005-0.015 over 16 decode steps). The largest row reads
# 0.09, and 0.42 under the reference's own int8 control: there the rounding moved
# a router's second choice and the row got another expert times 2.5, which a
# median leaves out and a maximum would not
TOL_INT8 = 0.03


def _rel(got, want):
    """A row's largest difference over the row's largest |logit|, a row."""
    return np.max(np.abs(got - want), axis=-1) / np.max(np.abs(want), axis=-1)


@pytest.fixture(scope="module")
def ref():
    return reference_for("joyai_flash")


def _unlike_ones(params, key=13):
    """Norm weights away from one (under ones a norm left out, or over the wrong
    width, would still agree) and a selection bias large enough to move choices."""
    keys = iter(jax.random.split(jax.random.PRNGKey(key), 32))

    def jitter(w):
        return w * (1.0 + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype))

    params = dict(params, final_norm=jitter(params["final_norm"]))
    for stack in ("layers", "dense_layers"):
        layers = params[stack]
        params[stack] = dict(layers, **{n: jitter(layers[n]) for n in (
            "attn_norm", "ffn_norm", "q_a_norm", "kv_norm")})
    params["layers"]["router_bias"] = 0.2 * jax.random.normal(
        next(keys), params["layers"]["router_bias"].shape, jnp.float32)
    return params


@pytest.fixture(scope="module")
def model(ref):
    """(cfg, params, tokens [96], the reference's logits at every position)."""
    with jax.default_matmul_precision("highest"):
        cfg = get_config("tiny-joyai")
        params = _unlike_ones(init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (96,), 3, 500))
        want = ref.logits(cfg, params, toks, np.arange(96), np.arange(cfg.vocab_size))
    return cfg, params, toks, want


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_reference_shares_no_code_with_the_program():
    assert "llm_mcp_tpu" not in reference_source("joyai_flash")  # its docstring names the files


def test_the_presets_are_the_published_structure(model):
    cfg, full = model[0], get_config("joyai-llm-flash-ep16")
    for c in (cfg, full):
        assert c.kv_lora_rank and c.q_lora_rank and c.first_dense_layers == 1 and c.mtp_layers == 1
        assert c.router_score == "sigmoid" and c.norm_topk_prob and c.routed_scaling_factor == 2.5
        assert c.n_shared_experts == 1 and c.rope_factor == 1.0 and not c.tie_embeddings
        assert moe.share_form(c) and c.n_experts * (16 if c is full else 4) == c.router_width
    assert (full.dim, full.n_layers, full.n_heads, full.vocab_size) == (2048, 40, 32, 129_280)
    assert (full.q_lora_rank, full.kv_lora_rank, full.qk_nope_head_dim, full.qk_rope_head_dim,
            full.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (full.n_experts, full.router_width, full.experts_per_tok, full.moe_ffn_hidden,
            full.ffn_hidden) == (16, 256, 8, 768, 7168)
    assert full.rope_theta == 32e6 and full.norm_eps == 1e-6 and full.resolved_head_dim == 64
    # the softmax presets keep the capacity dispatch: what they state decides, no switch
    assert not moe.share_form(get_config("tiny-v2")) and not moe.share_form(get_config("deepseek-v2-lite"))
    assert not moe.share_form(get_config("tiny-mla")) and not moe.share_form(get_config("tiny-moe"))


@pytest.mark.parametrize("held,want", [
    (16, 4_776_521_472),  # this chip's share: ISSUE 57's 4,776 M = 9.55 GB at 2 bytes
    (256, 48_942_542_592),  # the uncut model without its prediction module: "48B"
], ids=["ep16_share", "uncut_256"])
def test_param_count_is_exact(held, want):
    cfg = dataclasses.replace(get_config("joyai-llm-flash-ep16"), n_experts=held)
    D, H, Rq, R = 2048, 32, 1536, 512
    attn = D * Rq + Rq + Rq * H * 192 + D * (R + 64) + R + R * H * 256 + H * 128 * D
    assert attn == 26_345_472 + Rq + R  # ISSUE 57's 26.35 M and the two latent norms
    expert = 3 * D * 768
    expert_layer = attn + 2 * D + D * 256 + 256 + (held + 1) * expert
    dense_layer = attn + 2 * D + 3 * D * 7168
    by_hand = dense_layer + 39 * expert_layer + 2 * 129_280 * D + D
    assert cfg.param_count() == by_hand == want
    if held == 16:
        assert round(expert_layer / 1e6, 1) == 107.1 and round(dense_layer / 1e6, 1) == 70.4


def test_param_count_is_the_trees_size_and_the_low_rank_query_is_in_it(model):
    cfg, params = model[:2]
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))
    for stack, n in (("dense_layers", 1), ("layers", 3)):
        lp = params[stack]
        assert "wq_mla" not in lp
        assert lp["w_dq"].shape == (n, 64, 24) and lp["q_a_norm"].shape == (n, 24)
        assert lp["w_uq"].shape == (n, 24, 4 * 48)
    assert params["layers"]["router"].shape == (3, 64, 16) and params["layers"]["w1e"].shape == (3, 4, 64, 32)
    assert params["layers"]["router_bias"].shape == (3, 16) and "router" not in params["dense_layers"]
    # q_lora_rank 0 keeps the one dense product
    dense_q = init_llama_params(get_config("tiny-mla"), jax.random.PRNGKey(0), dtype=jnp.float32)
    assert "wq_mla" in dense_q["layers"] and "w_dq" not in dense_q["layers"]


def test_the_low_rank_query_against_numpy(model):
    cfg, params = model[:2]
    lp = jax.tree.map(lambda a: np.asarray(a[1], np.float64), params["layers"])
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (7, cfg.dim)), np.float64)
    down = x @ lp["w_dq"]
    c_q = down / np.sqrt(np.mean(down * down, axis=-1, keepdims=True) + cfg.norm_eps) * lp["q_a_norm"]
    want = c_q @ lp["w_uq"]  # [7, H dn | H dr]: the content columns of every head, then the rope ones
    qn, qr = mla._queries(cfg, jax.tree.map(lambda a: a[1], params["layers"]), jnp.asarray(x, jnp.float32))
    assert qn.shape == (7, 4, 32) and qr.shape == (7, 4, 16)
    assert np.max(np.abs(np.asarray(qn) - want[:, :128].reshape(7, 4, 32))) < 1e-5
    assert np.max(np.abs(np.asarray(qr) - want[:, 128:].reshape(7, 4, 16))) < 1e-5


def test_full_prefill_of_rows_of_unlike_lengths_is_dropless(model):
    cfg, params, toks, want = model
    assert 1.0 < np.max(np.abs(want)) < 8.0
    batch = np.zeros((4, 64), np.int32)
    lengths = [50, 30, 64, 1]
    for i, n in enumerate(lengths):
        batch[i, :n] = toks[:n]
    logits, ks, vs = llama_prefill(cfg, params, jnp.asarray(batch), jnp.asarray(lengths))
    for i, n in enumerate(lengths):
        assert np.max(np.abs(np.asarray(logits[i]) - want[n - 1])) < TOL, (i, n)
    assert ks.shape == (4, 4, 1, 64, 32) and vs["v"].shape == (4, 4, 1, 64, 16)
    counts = np.asarray(vs["moe"])  # [Le, 5]: rows, held pairs, touched, fullest, calls
    assert counts.shape == (3, 5) and (counts[:, 0] == sum(lengths)).all() and (counts[:, 4] == 1).all()
    assert (counts[:, 1] > 0).all() and (counts[:, 1] < 2 * sum(lengths)).all()  # a share of the pairs


def _decode(cfg, params, ck, cv, toks, start, n, slot, rows=2, attn_impl="xla"):
    """`n` decode steps of `toks[start:]` in `slot`; the logits a step."""
    step = jax.jit(lambda ck, cv, t, l: llama_decode_step(cfg, params, ck, cv, t, l, attn_impl=attn_impl))
    got = []
    for t in range(start, start + n):
        tokens = np.zeros(rows, np.int32)
        lengths = np.full(rows, 128, np.int32)  # the other rows parked
        tokens[slot], lengths[slot] = toks[t], t
        logits, ck, cv = step(ck, cv, jnp.asarray(tokens), jnp.asarray(lengths))
        got.append(np.asarray(logits[slot]))
    return np.stack(got), ck, cv


@pytest.mark.parametrize("quantized,attn_impl,tol", [
    (False, "xla", TOL), (True, "xla", TOL_INT8), (True, "pallas", TOL_INT8),
], ids=["bf16_cache", "int8_cache", "int8_cache_kernel_arm"])
def test_bucketed_chunks_then_decode_through_the_latent_cache(model, quantized, attn_impl, tol):
    """Two bucketed chunks of 32 into a used slot, then 16 decode steps, each
    position's logits against the reference's full forward; the counts member
    rides the pair's second member through both programs."""
    cfg, params, toks, want = model
    cache = init_kv_cache(cfg, 2, 128, dtype=jnp.float32, quantized=quantized)
    ck, cv = cache["k"], cache["v"]
    assert set(cv) == {"v", "moe"} and cv["moe"].shape == (2, 3, 5)
    slots, one = jnp.array([1]), toks[None, :].astype(np.int32)

    def close(got, rows):  # float caches: every logit; the int8 cache: the rows' median
        if not quantized:
            return np.max(np.abs(got - want[rows])) < tol
        return np.median(_rel(got, want[rows])) < tol

    for start in (0, 32):
        logits, ck, cv = llama_prefill_chunk_batch(
            cfg, params, ck, cv, jnp.asarray(one[:, start : start + 32]), slots,
            jnp.array([start]), jnp.array([32]), skey=64, all_logits=True)
        assert close(np.asarray(logits[0]), slice(start, start + 32)), start
    got, ck, cv = _decode(cfg, params, ck, cv, toks, 64, 16, slot=1, attn_impl=attn_impl)
    assert close(got, slice(64, 80))
    counts = np.asarray(cv["moe"])
    assert (counts[1, :, 0] == 64).all() and (counts[1, :, 4] == 2).all()  # two chunks of 32 rows
    assert (counts[0, :, 0] == 16).all() and (counts[0, :, 4] == 16).all()  # 16 steps of ONE live row
    assert (counts[0, :, 1] <= 2 * 16).all() and counts[0, :, 1].sum() > 0


def test_packed_chunks_agree_with_the_reference(model):
    """The ragged program: two prompts packed in one buffer, one continuing a
    cached prefix, through the expert share."""
    cfg, params, toks, want = model
    cache = init_kv_cache(cfg, 2, 128, dtype=jnp.float32)
    ck, cv = cache["k"], cache["v"]
    _, ck, cv = llama_prefill_chunk_batch(
        cfg, params, ck, cv, jnp.asarray(toks[None, :32].astype(np.int32)), jnp.array([0]),
        jnp.array([0]), jnp.array([32]), skey=64)
    # row 0: slot 0 continues at 32 with 16 tokens; row 1: slot 1 fresh with 24; 8 pads
    T = 48
    tokens = np.zeros(T, np.int32)
    tokens[:16], tokens[16:40] = toks[32:48], toks[:24]
    rowids = np.array([0] * 16 + [1] * 24 + [2] * 8, np.int32)
    positions = np.array(list(range(32, 48)) + list(range(24)) + [128] * 8, np.int32)
    logits, ck, cv = llama_prefill_chunk_ragged(
        cfg, params, ck, cv, jnp.asarray(tokens), jnp.asarray(rowids), jnp.asarray(positions),
        jnp.array([0, 1]), jnp.array([32, 0]), jnp.array([15, 39]), skey=64, impl="xla")
    assert np.max(np.abs(np.asarray(logits[0]) - want[47])) < TOL
    assert np.max(np.abs(np.asarray(logits[1]) - want[23])) < TOL
    counts = np.asarray(cv["moe"])
    assert (counts[1, :, 0] == 32 + 40).all() and (counts[1, :, 4] == 2).all()  # the pads route nothing


def test_the_shares_add_up_to_the_uncut_layer(model, ref):
    """Sixteen... here four shares of four experts: each member's part by the
    program's `moe_share_ffn` (banks rolled so that its experts are the held
    ones), the shared expert counted ONCE, against the reference's uncut layer
    over all 16 experts."""
    cfg, params = model[:2]
    uncut = dataclasses.replace(cfg, n_experts=16, n_router_experts=0)
    key = jax.random.PRNGKey(3)
    whole = moe.init_moe_layer_params(uncut, key, jnp.float32, 1)
    whole["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (1, 16), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, cfg.dim), jnp.float32)
    want = ref.held_part(uncut, whole, jnp.int32(0), x) + ref.shared_part(whole, jnp.int32(0), x)
    lp = {n: v[0] for n, v in whole.items()}
    routed = jnp.zeros_like(x)
    pairs = 0
    for member in range(4):
        # the router's columns rolled so that this member's experts come first,
        # as each member of the group sees the router's order from its own rank
        order = np.roll(np.arange(16), -4 * member)
        mine = dict(lp, router=lp["router"][:, order], router_bias=lp["router_bias"][order],
                    **{n: lp[n][4 * member : 4 * member + 4] for n in ("w1e", "w3e", "w2e")})
        shared = {n: mine.pop(n) for n in ("w1s", "w3s", "w2s")}
        y, counts = moe.moe_share_ffn(cfg, mine, x)
        routed = routed + y
        pairs += int(counts[1])
        part = ref.held_part(cfg, {n: v[None] for n, v in {**mine, **shared}.items()}, jnp.int32(0), x)
        assert np.max(np.abs(np.asarray(y - part))) < TOL, member  # the reference's share, member by member
    assert pairs == 40 * cfg.experts_per_tok  # every pair landed on exactly one member
    got = routed + ref.shared_part(whole, jnp.int32(0), x)
    assert np.max(np.abs(np.asarray(got - want))) < TOL
    # and the shared expert is inside each member's call where its leaves are
    y_with, _ = moe.moe_share_ffn(cfg, dict(lp, **{n: lp[n][:4] for n in ("w1e", "w3e", "w2e")}), x)
    y_bare, _ = moe.moe_share_ffn(cfg, {n: v for n, v in dict(
        lp, **{n: lp[n][:4] for n in ("w1e", "w3e", "w2e")}).items() if n not in ("w1s", "w3s", "w2s")}, x)
    assert np.max(np.abs(np.asarray(y_with - y_bare - ref.shared_part(whole, jnp.int32(0), x)))) < TOL


def test_mtp_logits_agree_with_the_reference(model, ref):
    cfg, params, toks, _ = model
    mtp = mla.init_mtp_params(cfg, jax.random.PRNGKey(9), jnp.float32)
    mtp = dict(mtp, hnorm=mtp["hnorm"] * 1.3, enorm=mtp["enorm"] * 0.7, final_norm=mtp["final_norm"] * 1.1)
    assert set(mtp) == {"hnorm", "enorm", "eh_proj", "layers", "final_norm"}
    assert mtp["eh_proj"].shape == (128, 64) and mtp["layers"]["w_uq"].shape == (1, 24, 192)
    assert mtp["layers"]["w1e"].shape == (1, 4, 64, 32) and mtp["layers"]["router_bias"].shape == (1, 16)
    seq = toks[:64]
    want = ref.mtp_logits(cfg, params, mtp, seq, np.arange(63), np.arange(cfg.vocab_size))
    h, _, _ = mla.mla_prefill(cfg, params, jnp.asarray(seq[None]), jnp.array([64]), hidden=True)
    assert h.shape == (1, 64, 64)
    nxt = np.append(seq[1:], 0)[None]
    got = mla.mtp_logits(cfg, params, mtp, h, jnp.asarray(nxt), jnp.array([64]))
    assert np.max(np.abs(np.asarray(got[0, :63]) - want)) < TOL


def test_the_controls_the_configuration_states_pass_and_the_others_do_not(model, ref):
    """By LOGITS at the tiny size (`_rel`'s median over 96 rows): bfloat16
    products and the int8 latent cache (both stated) stay within the int8
    tolerance of the float32 forward (0.013 and 0.018, measured); float8, the
    factor left out and the loader's permutation forgotten do not (0.45, 0.44,
    0.78)."""
    cfg, params, toks, want = model
    moved = {}
    try:
        for control in ref.CONTROLS:
            ref.LOWER = control
            retrace(ref)
            got = ref.logits(cfg, params, toks, np.arange(96), np.arange(cfg.vocab_size))
            moved[control] = float(np.median(_rel(got, want)))
    finally:
        ref.LOWER = None
        retrace(ref)
    assert moved["bf16"] < TOL_INT8 and moved["int8_latent"] < TOL_INT8, moved
    for control in ("fp8", "no_scale", "rope_halves"):
        assert moved[control] > 0.2, moved


def test_check_covers_this_family_alone(ref):
    ref.check(get_config("tiny-joyai"))
    ref.check(get_config("joyai-llm-flash-ep16"))
    for other in ("tiny-mla", "tiny-v2", "deepseek-v2-lite", "tiny-kexaone", "tiny-lfm2", "tiny-llm"):
        with pytest.raises(NotImplementedError):
            ref.check(get_config(other))
    for field, value in (("router_score", "softmax"), ("n_shared_experts", 2), ("rope_factor", 4.0),
                         ("norm_topk_prob", False), ("tie_embeddings", True), ("q_lora_rank", 0)):
        with pytest.raises(NotImplementedError):
            ref.check(dataclasses.replace(get_config("tiny-joyai"), **{field: value}))


@pytest.mark.parametrize("kv_quant", ["", "int8"], ids=["bf16_cache", "int8_cache"])
def test_the_engine_counts_the_expert_layer_on_a_latent_pair(kv_quant):
    """Through the normal path: the counts member reaches `ExpertCounts` from the
    admit, chunk and decode programs; the layout says what such a pair runs
    without; ragged prefill stays on."""
    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.executor.memory import COUNTED_OFF

    eng = GenerationEngine("tiny-joyai", max_slots=2, max_seq_len=256, dtype=jnp.float32,
                           prefill_chunk=32, kv_quant=kv_quant, prompt_cache_mb=64).start()
    try:
        lay = eng._layout
        assert lay.latent and lay.counted and lay.wrapped and not lay.slot_member and not lay.fused
        assert dict(lay.without) == COUNTED_OFF and eng._prefix_budget == 0 and eng._pool is None
        assert eng.ragged_prefill and eng._ride_off() == "other" and eng._state_pool is None
        assert set(eng._cv) == {"v", "moe"} and set(lay.kv_rows(eng._ck, eng._cv)) == {"k", "v"}
        first = eng.generate("a short prompt", max_tokens=8, temperature=0.0)
        assert first["usage"]["completion_tokens"] == 8
        out = eng.generate("a prompt longer than one chunk of thirty-two tokens " * 2,
                           max_tokens=5, temperature=0.0)
        assert out["usage"]["completion_tokens"] == 5
        ex = eng.perf_stats()["experts"]
        assert ex["held"] == 4 and ex["router"] == 16 and np.asarray(ex["counts"]).shape == (2, 3, 5)
        counts = np.asarray(ex["counts"])
        assert (counts[0, :, 4] > 0).all() and (counts[1, :, 4] >= 2).all()  # decode steps; prefills
        assert (counts[0, :, 0] >= 8 + 5 - 2).all() and counts[0, :, 1].sum() > 0
        # every prompt token was routed once, whichever program prefilled it
        assert counts[1, 0, 0] == first["usage"]["prompt_tokens"] + out["usage"]["prompt_tokens"]
    finally:
        eng.shutdown()
