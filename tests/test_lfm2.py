"""The gated short convolution as a layer kind of the hybrid decoder (a tail
and no matrix state), two leading dense layers of that kind, and routed experts
held whole (`Er == E`), at the tiny preset of LFM2-8B-A1B's shape (`tiny-lfm2`),
held to the plain reference `benchmark/references/lfm2_moe.py` on seeded float32
weights: logits, not tokens, through every path a sequence can take (whole
prompt, bucketed chunks, decode through cache and tails, a mixed step, a reused
slot), and the engine's slot life-cycle around a pool of kilobytes."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.models import hybrid, moe, shortconv
from llm_mcp_tpu.models.configs import get_config
from llm_mcp_tpu.models.kda import conv_chunk, conv_packed, conv_step
from llm_mcp_tpu.models.llama import (
    init_kv_cache,
    init_llama_params,
    llama_decode_step,
    llama_prefill,
    llama_prefill_chunk_batch,
)

from family import reference_for, reference_source, retrace, stepwise  # noqa: E402

# every model call of this file is ONE trace and ONE compile a (configuration, shape):
# called bare, a step dispatches its primitives one by one and lowers its kernels again
llama_decode_step, llama_prefill, llama_prefill_chunk_batch = map(
    stepwise, (llama_decode_step, llama_prefill, llama_prefill_chunk_batch))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 against float32, of logits whose largest is about 3: the program's
# chunked products and the reference's whole-sequence ones differ by rounding
# alone (2e-5 at worst, measured); a bfloat16 router product moves a logit by
# 1e-2 and more and float8 weights by 1 (`test_the_tolerance_refuses_...`)
TOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return reference_for("lfm2_moe")


def _unlike_ones(params, key=13):
    """Norm weights away from one (under ones a norm over the wrong width would
    still agree) and a selection bias large enough to move choices."""
    keys = iter(jax.random.split(jax.random.PRNGKey(key), 32))

    def jitter(w):
        return w * (1.0 + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype))

    params = dict(params, final_norm=jitter(params["final_norm"]))
    layers = params["layers"]
    params["layers"] = dict(
        layers, attn_norm=jitter(layers["attn_norm"]), ffn_norm=jitter(layers["ffn_norm"]),
        router_bias=0.2 * jax.random.normal(next(keys), layers["router_bias"].shape, jnp.float32))
    params["gqa"] = dict(params["gqa"], q_norm=jitter(params["gqa"]["q_norm"]),
                         k_norm=jitter(params["gqa"]["k_norm"]))
    params["first"] = [dict(lp, attn_norm=jitter(lp["attn_norm"]), ffn_norm=jitter(lp["ffn_norm"]))
                       for lp in params["first"]]
    return params


@pytest.fixture(scope="module")
def model(ref):
    """(cfg, params, tokens [96], the reference's logits at every position)."""
    with jax.default_matmul_precision("highest"):
        cfg = get_config("tiny-lfm2")
        params = _unlike_ones(init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (96,), 3, 500))
        want = ref.logits(cfg, params, toks, np.arange(96), np.arange(cfg.vocab_size))
    return cfg, params, toks, want


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_reference_shares_no_code_with_the_program():
    assert "llm_mcp_tpu" not in reference_source("lfm2_moe")  # its docstring names the files


def test_the_preset_is_the_published_shape(model):
    cfg = model[0]
    assert cfg.recurrent and cfg.recurrent_kind == "conv" and cfg.conv_taps == 3
    assert cfg.layer_period == ("gqa", "conv", "conv", "conv") and cfg.first_dense_layers == 2
    assert cfg.gqa_layers == (2, 6) and cfg.resolved_head_dim == 64 and cfg.router_width == cfg.n_experts
    assert "conv" in hybrid._RECURRENT and hybrid._RECURRENT["conv"].scope(cfg) == "conv"
    full = get_config("lfm2-8b-a1b-d14")
    assert full.layer_period == cfg.layer_period and full.gqa_layers == (2, 6, 10)
    assert (full.dim, full.n_heads, full.n_kv_heads, full.resolved_head_dim) == (2048, 32, 8, 64)
    assert (full.n_experts, full.router_width, full.experts_per_tok, full.moe_ffn_hidden) == (32, 32, 4, 1792)


@pytest.mark.parametrize("layers,attention,want", [
    (14, (2, 6, 10), 4_667_077_376),  # the cut: 121,655,296 + 4,411,202,304 + 134,219,776
    (24, (2, 6, 10, 14, 18, 21), 8_339_930_560),  # the published row: 8.34 B with ONE table
], ids=["cut_d14", "uncut_24"])
def test_param_count_is_exact(layers, attention, want):
    cfg = dataclasses.replace(get_config("lfm2-8b-a1b-d14"), n_layers=layers, gqa_layers=attention)
    conv_half, attn_half = 12_582_912 + 6_144 + 4_194_304, 10_485_888
    expert_ffn, dense_ffn, norms = 32 * 11_010_048 + 65_536 + 32, 44_040_192, 4_096
    by_hand = (2 * (conv_half + norms + dense_ffn)
               + len(attention) * attn_half + (layers - 2 - len(attention)) * conv_half
               + (layers - 2) * (norms + expert_ffn) + 134_217_728 + 2_048)
    assert cfg.param_count() == by_hand == want


def test_param_count_is_the_trees_size(model):
    cfg, params = model[:2]
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert len(params["first"]) == 2 and set(params["first"][0]) == {
        "attn_norm", "ffn_norm", "w_in", "conv_w", "w_out", "w1", "w3", "w2"}
    assert params["conv"]["w_in"].shape == (6, 128, 384) and params["gqa"]["wq"].shape == (2, 128, 256)


def test_full_prefill_of_rows_of_unlike_lengths(model):
    cfg, params, toks, want = model
    assert 1.0 < np.max(np.abs(want)) < 8.0
    batch = np.zeros((4, 64), np.int32)
    lengths = [50, 30, 64, 1]  # one token: every tap but the last reads the zero padding
    for i, n in enumerate(lengths):
        batch[i, :n] = toks[:n]
    logits, ks, vs = llama_prefill(cfg, params, jnp.asarray(batch), jnp.asarray(lengths))
    for i, n in enumerate(lengths):
        assert np.max(np.abs(np.asarray(logits[i]) - want[n - 1])) < TOL, (i, n)
    # a tail and no matrix state: 8 convolution layers (two leading), two taps back
    assert set(vs["state"]) == {"conv"} and vs["state"]["conv"].shape == (8, 4, 2 * 128)
    assert vs["moe"].shape == (8, 5) and ks.shape[0] == 2
    # row 3 (one token): its tail is [0, B * x of token 0]
    assert not np.asarray(vs["state"]["conv"][:, 3, :128]).any()
    assert np.asarray(vs["state"]["conv"][:, 3, 128:]).any()


def _decode(cfg, params, ck, cv, toks, start, n, slot, rows=2):
    """`n` decode steps of `toks[start:]` in `slot`; the logits a step."""
    step = jax.jit(lambda ck, cv, t, l: llama_decode_step(cfg, params, ck, cv, t, l))
    got = []
    for t in range(start, start + n):
        tokens = np.zeros(rows, np.int32)
        lengths = np.full(rows, 128, np.int32)  # the other rows parked
        tokens[slot], lengths[slot] = toks[t], t
        logits, ck, cv = step(ck, cv, jnp.asarray(tokens), jnp.asarray(lengths))
        got.append(np.asarray(logits[slot]))
    return np.stack(got), ck, cv


def test_chunked_prefill_carries_the_tail_over_the_cut_then_decodes(model):
    """Two bucketed chunks of 32 (the second continues the pool's tail: its
    first two positions read the first chunk's last two products), then 16
    decode steps through cache and tails; a parked row's tail never moves."""
    cfg, params, toks, want = model
    cache = init_kv_cache(cfg, 2, 128, dtype=jnp.float32)
    state = cache["v"]["state"]
    assert set(state) == {"conv"} and state["conv"].shape == (8, 2, 256)
    cv = dict(cache["v"], state={"conv": state["conv"] - 3.0})  # a used slot's old tail: never read
    ck = cache["k"]
    slots, one = jnp.array([1]), toks[None, :].astype(np.int32)
    for start in (0, 32):
        logits, ck, cv = llama_prefill_chunk_batch(
            cfg, params, ck, cv, jnp.asarray(one[:, start : start + 32]), slots,
            jnp.array([start]), jnp.array([32]), skey=64, all_logits=True)
        assert np.max(np.abs(np.asarray(logits[0]) - want[start : start + 32])) < TOL, start
    before = np.asarray(cv["state"]["conv"][:, 0])
    got, ck, cv = _decode(cfg, params, ck, cv, toks, 64, 16, slot=1)
    assert np.max(np.abs(got - want[64:80])) < TOL
    assert np.array_equal(np.asarray(cv["state"]["conv"][:, 0]), before)  # a parked row never moves
    assert not np.array_equal(np.asarray(cv["state"]["conv"][:, 1]), before)


def test_a_ragged_chunk_leaves_the_tail_at_its_last_valid_position(model):
    cfg, params, toks, want = model
    cache = init_kv_cache(cfg, 2, 128, dtype=jnp.float32)
    one = toks[None, :32].astype(np.int32)
    _, ck, cv = llama_prefill_chunk_batch(
        cfg, params, cache["k"], cache["v"], jnp.asarray(one), jnp.array([0]), jnp.array([0]),
        jnp.array([21]), skey=32)
    got, _, _ = _decode(cfg, params, ck, cv, toks, 21, 4, slot=0)
    assert np.max(np.abs(got - want[21:25])) < TOL


def test_a_whole_prompt_then_decode_in_a_slot_reused_after_a_longer_sequence(model):
    """A long sequence leaves its tail and its KV rows in slot 1; a shorter
    prompt admitted there (`insert_state_row` writes the row outright) and then
    decoded must not see them: a stale tail would reach the first two steps."""
    cfg, params, toks, want = model
    cache = init_kv_cache(cfg, 2, 128, dtype=jnp.float32)
    old = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (1, 64), 3, 500), np.int32)
    _, ck, cv = llama_prefill_chunk_batch(
        cfg, params, cache["k"], cache["v"], jnp.asarray(old), jnp.array([1]), jnp.array([0]),
        jnp.array([64]), skey=64)
    stale = np.asarray(cv["state"]["conv"][:, 1])
    assert np.abs(stale).max() > 1e-3
    batch = toks[None, :32].astype(np.int32)
    _, ks, vs = llama_prefill(cfg, params, jnp.asarray(batch), jnp.array([20]))
    put = lambda c, rows: jax.lax.dynamic_update_slice(  # noqa: E731
        c, rows[:, :1].astype(c.dtype), (0, 1) + (0,) * (c.ndim - 2))
    ck = put(ck, jnp.pad(ks, ((0, 0),) * 3 + ((0, 96), (0, 0))))
    cv = dict(cv, v=put(cv["v"], jnp.pad(vs["v"], ((0, 0),) * 3 + ((0, 96), (0, 0)))),
              **hybrid.insert_state_row(cv, vs, 0, 1))
    assert not np.array_equal(np.asarray(cv["state"]["conv"][:, 1]), stale)
    got, _, _ = _decode(cfg, params, ck, cv, toks, 20, 4, slot=1)
    assert np.max(np.abs(got - want[20:24])) < TOL


def test_a_mixed_step_carries_a_prompt_beside_decode_rows(model, ref):
    """`hybrid_mixed_step`: two decode rows (one parked) and two fresh prompts
    packed from chunk boundaries through ONE pass over the weights. The decode
    row's logits and tail are the plain step's, each prompt's last logits are
    the reference's, its tail lands in its own pool row (and is what a prefill
    of it alone leaves), and no other row of the pool moves."""
    cfg, params, toks, want = model
    B, T, R = 4, 128, 4
    cache = init_kv_cache(cfg, B, 128, dtype=jnp.float32, quantized=True)
    # slot 0 decodes at position 40 after a chunked prefill of toks[:40]
    first = np.zeros((1, 64), np.int32)
    first[0, :40] = toks[:40]
    _, ck, cv = llama_prefill_chunk_batch(
        cfg, params, cache["k"], cache["v"], jnp.asarray(first), jnp.array([0]), jnp.array([0]),
        jnp.array([40]), skey=64)
    other = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (45,), 3, 500), np.int32)
    plens, pslots = [33, 45], [2, 3]
    p_tokens = np.zeros(T, np.int32)
    p_rowids, p_positions = np.full(T, R, np.int32), np.full(T, 128, np.int32)
    p_tokens[:33], p_tokens[64:109] = toks[:33], other
    p_rowids[:33], p_rowids[64:109] = 0, 1
    p_positions[:33], p_positions[64:109] = np.arange(33), np.arange(45)
    p_slots = np.array([2, 3, 2, 2], np.int32)
    p_last = np.array([32, 108, 0, 0], np.int32)
    tokens = np.array([toks[40], 0, 0, 0], np.int32)
    lengths = np.array([40, 128, 128, 128], np.int32)
    plain, _, cv_plain = jax.jit(lambda ck, cv: llama_decode_step(
        cfg, params, ck, cv, jnp.asarray(tokens), jnp.asarray(lengths)))(ck, cv)
    logits, ck2, cv2 = jax.jit(lambda ck, cv: hybrid.hybrid_mixed_step(
        cfg, params, ck, cv, jnp.asarray(tokens), jnp.asarray(lengths), jnp.asarray(p_tokens),
        jnp.asarray(p_rowids), jnp.asarray(p_positions), jnp.asarray(p_slots),
        jnp.asarray(p_last)))(ck, cv)
    assert logits.shape == (B + R, cfg.vocab_size)
    # the decode row reads its int8 KV rows: the plain step's logits (rounding), not float32's
    assert np.max(np.abs(np.asarray(logits[0]) - np.asarray(plain[0]))) < TOL
    assert np.max(np.abs(np.asarray(logits[B]) - want[32])) < TOL  # a prompt reads no cache
    want_other = ref.logits(cfg, params, np.pad(other, (0, 19)), np.array([44]), np.arange(cfg.vocab_size))
    assert np.max(np.abs(np.asarray(logits[B + 1]) - want_other[0])) < TOL
    tails, tails0 = np.asarray(cv2["state"]["conv"]), np.asarray(cv["state"]["conv"])
    assert np.allclose(tails[:, 0], np.asarray(cv_plain["state"]["conv"])[:, 0], atol=1e-5)
    assert np.array_equal(tails[:, 1], tails0[:, 1])  # a parked, unused row
    for prompt, slot in ((toks[:33], 2), (other, 3)):
        row = np.zeros((1, 64), np.int32)
        row[0, : len(prompt)] = prompt
        _, _, alone = llama_prefill(cfg, params, jnp.asarray(row), jnp.array([len(prompt)]))
        assert np.abs(tails[:, slot]).max() > 1e-3
        assert np.allclose(tails[:, slot], np.asarray(alone["state"]["conv"])[:, 0], atol=1e-4)
    moved = np.asarray(cv2["moe"]) - np.asarray(cv["moe"])  # [2, Le, 5]: decode | prefill
    assert (moved[0, :, 0] == 1).all() and (moved[1, :, 0] == 78).all() and (moved[:, :, 4] == 1).all()
    assert (moved[0, :, 1] == 4).all() and (moved[1, :, 1] == 4 * 78).all()  # every pair is held
    # the prompts decode on from what the mixed step left: KV rows and tail
    nxt = int(np.argmax(want[32]))
    seq = np.concatenate([toks[:33], [nxt]]).astype(np.int32)
    step, _, _ = jax.jit(lambda ck, cv: llama_decode_step(
        cfg, params, ck, cv, jnp.asarray([0, 0, nxt, 0], jnp.int32),
        jnp.asarray([128, 128, 33, 128], jnp.int32)))(ck2, cv2)
    after = ref.logits(cfg, params, np.pad(seq, (0, 30)), np.array([33]), np.arange(cfg.vocab_size))
    assert np.max(np.abs(np.asarray(step[2]) - after[0])) < 0.05  # through the int8 cache


def test_the_convolutions_three_forms_are_the_three_term_sum():
    """`conv_step` (a token on the pool's tails), `conv_chunk` (a chunk that
    continues a tail) and `conv_packed` (fresh prompts in one row) against
    z_t = sum_j w_j u_{t-2+j} over a zero-padded sequence, three taps; each
    form's tail is the last two inputs."""
    W, T, taps = 16, 12, 3
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    u = jax.random.normal(ks[0], (2, T, W))
    w = jax.random.normal(ks[1], (taps, W))
    padded = jnp.concatenate([jnp.zeros((2, taps - 1, W)), u], axis=1)
    want = sum(padded[:, j : j + T] * w[j] for j in range(taps))  # [2, T, W]
    # a chunk of 7 from a zero tail, then the rest from the tail it left; row 1 ragged
    mixed_a, tail_a = conv_chunk(jnp.zeros((2, taps - 1, W)), jnp.array([7, 5]), u[:, :7], w)
    assert np.allclose(mixed_a, want[:, :7], atol=1e-6)
    assert np.allclose(tail_a[0], u[0, 5:7]) and np.allclose(tail_a[1], u[1, 3:5])
    mixed_b, tail_b = conv_chunk(tail_a[:1], jnp.array([5]), u[:1, 7:], w)
    assert np.allclose(mixed_b[0], want[0, 7:], atol=1e-6) and np.allclose(tail_b[0], u[0, 10:])
    # token by token on a pool [Lk = 2, slots = 3, (taps-1) W], layer 1, row 0 -> slot 2
    pool = jnp.zeros((2, 3, (taps - 1) * W))
    for t in range(T):
        mixed, pool, ids = conv_step(pool, 1, jnp.array([2, 0]), jnp.array([True, False]),
                                     jnp.stack([u[0, t], u[1, t]]), w)
        assert np.allclose(mixed[0], want[0, t], atol=1e-6)
    assert np.allclose(pool[1, 2].reshape(taps - 1, W), u[0, T - 2 :]) and not np.asarray(pool[1, 0]).any()
    assert not np.asarray(pool[0]).any()
    # two prompts packed in one row (7 and 4 tokens, the second from position 8)
    row = jnp.zeros((16, W)).at[:7].set(u[0, :7]).at[8:12].set(u[1, :4])
    positions = jnp.asarray([*range(7), 99, *range(4), 99, 99, 99, 99])
    mixed_p, tails_p = conv_packed(row, positions, jnp.array([6, 11]), w)
    assert np.allclose(mixed_p[:7], want[0, :7], atol=1e-6)
    assert np.allclose(mixed_p[8:12], want[1, :4], atol=1e-6)  # reads nothing of its neighbour
    assert np.allclose(tails_p[0], u[0, 5:7]) and np.allclose(tails_p[1], u[1, 2:4])
    one_token = conv_packed(row, positions.at[8:12].set(jnp.asarray([0, 99, 99, 99])),
                            jnp.array([6, 8]), w)[1]
    assert not np.asarray(one_token[1, 0]).any() and np.allclose(one_token[1, 1], u[1, 0])


def test_the_layers_parts_compose_to_the_layer(model):
    """`project` / `operands` / `step_rows` / `scan_packed` / `output` (what a
    mixed step composes) give what `conv_prefill` gives; the recurrence is the
    identity and hands no state over."""
    cfg, params = model[:2]
    kp = jax.tree.map(lambda a: a[0], params["conv"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32, cfg.dim))
    S0, tail0 = shortconv.zero_state(cfg, 1, x.dtype)
    assert S0 is None and tail0.shape == (1, 2, cfg.dim)
    y, S, tail = shortconv.conv_prefill(cfg, kp, x, jnp.array([32]), S0, tail0)
    assert S is None
    bx, gate = shortconv.project(cfg, kp, x[0])
    mixed, tails = conv_packed(bx, jnp.arange(32), jnp.array([31]), kp["conv_w"])
    ops, gate = shortconv.operands(cfg, kp, mixed, gate)
    o, after = shortconv.scan_packed(ops[None], None, None, None)
    assert after is None and shortconv.step_rows(cfg, None, 0, None, None, ops) == (ops, None)
    assert np.allclose(shortconv.output(cfg, kp, o[0], gate, x.dtype), y[0], atol=1e-5)
    assert np.allclose(tails[0], tail[0], atol=1e-6)


def test_experts_held_whole_against_a_loop_over_every_expert(model):
    """`moe_share_ffn` with `Er == E` is the whole layer: every chosen pair is
    held, the bias chooses and does not weigh, against a plain loop over all
    the experts with gates from the unbiased scores."""
    cfg, params = model[:2]
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    assert lp["router"].shape == (cfg.dim, cfg.n_experts) and cfg.router_width == cfg.n_experts
    x = jax.random.normal(jax.random.PRNGKey(8), (24, cfg.dim))
    y, counts = moe.moe_share_ffn(cfg, lp, x)
    scores = jax.nn.sigmoid(jnp.matmul(x, lp["router"], precision="highest"))
    _, chosen = jax.lax.top_k(scores + lp["router_bias"], cfg.experts_per_tok)
    _, unbiased = jax.lax.top_k(scores, cfg.experts_per_tok)
    assert not np.array_equal(np.sort(chosen, -1), np.sort(unbiased, -1))  # the bias moved a choice
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    want = jnp.zeros_like(x)
    for e in range(cfg.n_experts):
        gate = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1, keepdims=True)
        g = jnp.matmul(x, lp["w1e"][e], precision="highest")
        want = want + gate * jnp.matmul(jax.nn.silu(g) * jnp.matmul(x, lp["w3e"][e], precision="highest"),
                                        lp["w2e"][e], precision="highest")
    assert np.max(np.abs(np.asarray(y) - np.asarray(want))) < 1e-5
    weighed = jnp.take_along_axis(scores + lp["router_bias"], chosen, axis=-1)
    assert np.max(np.abs(np.asarray(top) - np.asarray(weighed / weighed.sum(-1, keepdims=True)))) > 1e-2
    rows, pairs, touched, fullest, calls = (int(v) for v in counts)
    assert (rows, pairs, calls) == (24, 24 * cfg.experts_per_tok, 1)  # every pair is held here
    assert touched == cfg.n_experts and fullest >= pairs // cfg.n_experts
    assert moe.window_rows(64, 4, 32, 32) == 256  # a decode step at the published shape: two row tiles


@pytest.mark.parametrize("lower,least", [
    ("router_bf16", 3.0), ("fp8", 100.0), ("lost_tail", 100.0), ("no_gate", 100.0),
    ("bias_weighs", 100.0)])
def test_the_tolerance_refuses_a_lowered_forward(model, ref, lower, least):
    """The program's decode logits (a whole prompt, then 16 steps through cache
    and tails) lie within TOL of the reference and at least `least` x TOL from
    the reference computed with one thing lowered or left out."""
    cfg, params, toks, want = model
    batch = jnp.asarray(toks[None, :64].astype(np.int32))
    _, ks, vs = llama_prefill(cfg, params, batch, jnp.array([50]))
    cache = init_kv_cache(cfg, 2, 128, dtype=jnp.float32)
    put = lambda c, rows: jax.lax.dynamic_update_slice(  # noqa: E731
        c, rows[:, :1].astype(c.dtype), (0, 0) + (0,) * (c.ndim - 2))
    ck = put(cache["k"], ks)
    cv = dict(cache["v"], v=put(cache["v"]["v"], vs["v"]), **hybrid.insert_state_row(cache["v"], vs, 0, 0))
    got, _, _ = _decode(cfg, params, ck, cv, toks, 50, 16, slot=0)
    assert np.max(np.abs(got - want[50:66])) < TOL
    ref.LOWER = lower
    retrace(ref)
    try:
        lowered = ref.logits(cfg, params, toks, np.arange(49, 66), np.arange(cfg.vocab_size))[1:]
    finally:
        ref.LOWER = None
        retrace(ref)
    assert np.max(np.abs(got - lowered)) > least * TOL


@pytest.fixture(scope="module")
def engine():
    from llm_mcp_tpu.executor import GenerationEngine

    # the seeded tree with norms away from one and a selection bias of deviation
    # 0.2: at the seeded 0.01 the bias moves a gate by a hundredth, which sixteen
    # served tokens cannot tell from the program (`bias_weighs` then reads 0.000-
    # 0.003; the logits tell it at any size: test_the_tolerance_refuses_...)
    with jax.default_matmul_precision("highest"):
        params = _unlike_ones(init_llama_params(
            get_config("tiny-lfm2"), jax.random.PRNGKey(0), dtype=jnp.float32))
    eng = GenerationEngine("tiny-lfm2", max_slots=2, max_seq_len=256, dtype=jnp.float32,
                           prefill_chunk=32, prompt_cache_mb=64, kv_quant="int8",
                           params=params).start()
    yield eng
    eng.shutdown()


def _serve(eng, prompt, n=10):
    """(prompt ids, emitted ids) of one greedy request, tapped where the engine emits."""
    got = {}
    emit = eng._process_token

    def tap(slot, tok, pos):
        got.setdefault("ids", list(slot.req.prompt_ids))
        got.setdefault("out", []).append(int(tok))
        return emit(slot, tok, pos)

    eng._process_token = tap
    try:
        eng.generate(prompt, max_tokens=n, temperature=0.0)
    finally:
        del eng._process_token
    return got["ids"], got["out"]


def test_engine_serves_the_references_choice_whole_chunked_and_in_reused_slots(engine, ref):
    """Whole-prompt admission (under the engine's chunk of 32), a chunked
    prefill carried across ENGINE chunks (over it), and again in used slots (a
    short prompt after a long one: a stale tail must not leak), through the
    int8 cache; the pool's book says that the kind has no matrix state."""
    eng = engine
    allowed = np.flatnonzero(np.asarray(eng._allowed_mask))
    prompts = ["amber basil", "x" * 70 + " cedar dune ember", "y" * 45, "fjord grove " * 6, "kelp"]
    admitted = eng.perf_stats()["state_pool"]["admitted_total"]  # (the module's engine: what other cases of this worker were served)
    for prompt in prompts:
        ids, out = _serve(eng, prompt)
        seq = ids + out[:-1]
        rows = np.arange(len(ids) - 1, len(seq))
        seq = np.asarray(seq + [0] * (-len(seq) % 32), np.int32)
        want = ref.logits(eng.cfg, eng.params, seq, rows, allowed)
        for k, tok in enumerate(out):
            regret = float(np.max(want[k]) - want[k, np.flatnonzero(allowed == tok)[0]])
            assert regret < 2e-2, (prompt[:12], k, regret)  # the int8 cache's rounding at a near-tie
    assert {"chunk", "admit"} <= {r["phase"] for r in eng._ledger.table()}
    stats = eng.perf_stats()
    pool = stats["state_pool"]
    cfg = eng.cfg
    assert pool["layout"] == {"conv": [8, 2, 2 * cfg.dim]} and "S" not in pool["layout"]
    assert pool["bytes"] == 8 * 2 * 2 * cfg.dim * 4 == pool["bytes_per_slot"] * 2
    assert pool["admitted_total"] - admitted == len(prompts) > pool["slots"] and pool["live_slots"] == 0
    assert (eng.state_dtype, eng.weights_dtype, eng.expert_dtype) == ("float32",) * 3
    assert eng._layout.slot_member == "state" and eng._layout.name == "gqa_int8"
    assert not any(eng._runs(f) for f in ("prefix_cache", "offload", "migration", "speculation",
                                           "ragged_prefill"))
    assert eng._runs("mixed_round") and eng._ride_align == 32
    experts = stats["experts"]
    assert (experts["held"], experts["router"]) == (cfg.n_experts, cfg.n_experts)
    decode, prefill = np.asarray(experts["counts"])
    assert decode.shape == (8, 5) and (decode[:, 4] > 0).all() and (prefill[:, 4] > 0).all()
    assert (decode[:, 1] == cfg.experts_per_tok * decode[:, 0]).all()  # every pair held: Er == E
    zoo = {phase for phase, _ in eng.warmup_shape_zoo()}
    assert {"decode", "admit", "chunk"} <= zoo


def test_the_controls_fail_correct_on_the_twin(engine, ref):
    """`correctness.hold_to_reference` (run.py's comparison) on the twin: the
    program is correct, and a tail lost at admission, the gate C left out and
    float8 weights are each refused. The bias weighing the gates is NOT: even at
    this engine's deviation of 0.2 it moves a served token's logit by 0.02-0.14
    of the row's largest (0.14-0.30 at 0.5), inside the limit; sixteen greedy
    tokens cannot tell it from the program, the logits can
    (`test_the_tolerance_refuses_a_lowered_forward[bias_weighs]`)."""
    from benchmark import correctness

    ids, out = _serve(engine, "hold these sixteen tokens to the plain forward, " * 2, n=16)
    assert correctness.hold_to_reference(ref, engine, ids, out)["worst_regret_rel"] < 1e-2
    refused = {}
    try:
        for lower in ref.CONTROLS:
            ref.LOWER = lower
            retrace(ref)
            try:
                correctness.hold_to_reference(ref, engine, ids, out)
                refused[lower] = False
            except AssertionError as e:
                assert "under the reference's choice" in str(e)
                refused[lower] = True
    finally:
        ref.LOWER = None
        retrace(ref)
    assert refused == {"fp8": True, "lost_tail": True, "no_gate": True, "bias_weighs": False}
