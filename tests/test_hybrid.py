"""The hybrid decoder (KDA linear-attention layers with a per-slot recurrent
state, gated NoPE GQA layers, a dropless share of the routed experts) at the
tiny preset of the published shape, held to the plain reference
`benchmark/references/solar_open2.py` on seeded float32 weights: logits, not
tokens, through every path a sequence can take; and the engine's slot
life-cycle around the state pool."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family import reference_for, reference_source, retrace, stepwise
from llm_mcp_tpu.kernels.grouped import ROW_TILE, grouped_ffn, grouped_reference, tile_visits
from llm_mcp_tpu.kernels.kda import kda_decode_step, kda_decode_step_reference
from llm_mcp_tpu.models import moe
from llm_mcp_tpu.models.configs import get_config
from llm_mcp_tpu.models.kda import kda_chunk_scan
from llm_mcp_tpu.models.llama import (
    init_kv_cache,
    init_llama_params,
    llama_decode_step,
    llama_prefill,
    llama_prefill_chunk_batch,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# every model call of this file is ONE trace and ONE compile a (configuration, shape):
# called bare, a step dispatches its primitives one by one and lowers its kernels again
llama_decode_step, llama_prefill, llama_prefill_chunk_batch = map(
    stepwise, (llama_decode_step, llama_prefill, llama_prefill_chunk_batch))
TOL = 1e-4  # float32 against float32, of logits whose largest is about 4


@pytest.fixture(scope="module")
def ref():
    return reference_for("solar_open2")


@pytest.fixture(scope="module")
def model(ref):
    """(cfg, params, tokens [96], the reference's logits at every position)."""
    with jax.default_matmul_precision("highest"):
        cfg = get_config("tiny-solar")
        params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (96,), 3, 500))
        want = ref.logits(cfg, params, toks, np.arange(96), np.arange(cfg.vocab_size))
    return cfg, params, toks, want


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_reference_shares_no_code_with_the_program():
    assert "llm_mcp_tpu" not in reference_source("solar_open2")  # its docstring names the files


def test_full_prefill_of_rows_of_unlike_lengths(model):
    cfg, params, toks, want = model
    batch = np.zeros((4, 64), np.int32)
    lengths = [50, 30, 64, 1]
    for i, n in enumerate(lengths):
        batch[i, :n] = toks[:n]
    logits, ks, vs = llama_prefill(cfg, params, jnp.asarray(batch), jnp.asarray(lengths))
    for i, n in enumerate(lengths):
        assert np.max(np.abs(np.asarray(logits[i]) - want[n - 1])) < TOL, (i, n)
    assert ks.shape[0] == cfg.n_attn_layers == 1  # one GQA layer owns cache rows
    # three KDA layers, float32, in the pool's layout: the four heads of 32 values abreast
    assert vs["state"]["S"].shape == (3, 4, 1, 32, 128)
    # a row's state is its own prompt's, whatever the bucket holds behind it
    again, _, vs2 = llama_prefill(
        cfg, params, jnp.asarray(batch[:1, :]), jnp.asarray(lengths[:1]))
    assert np.allclose(vs2["state"]["S"][:, 0], vs["state"]["S"][:, 0], atol=1e-5)


def _used_cache(cfg, slots=4, seq=128):
    """A cache whose every slot was used: state and tails hold another
    sequence's leftovers, which a fresh admission must never read."""
    cache = init_kv_cache(cfg, slots, seq, dtype=jnp.float32)
    state = cache["v"]["state"]
    cache["v"]["state"] = dict(state, S=state["S"] + 7.0, conv=state["conv"] - 3.0)
    return cache["k"], cache["v"]


def test_two_chunks_then_decode_through_cache_and_state_in_a_reused_slot(model):
    cfg, params, toks, want = model
    ck, cv = _used_cache(cfg)
    slot, S = 2, 128
    for start, n in ((0, 32), (32, 18)):  # the second chunk ragged, padded to 32
        chunk = np.zeros((1, 32), np.int32)
        chunk[0, :n] = toks[start : start + n]
        logits, ck, cv = llama_prefill_chunk_batch(
            cfg, params, ck, cv, jnp.asarray(chunk), jnp.array([slot]), jnp.array([start]),
            jnp.array([n]), skey=32)
    assert np.max(np.abs(np.asarray(logits[0]) - want[49])) < TOL
    lens = np.full(4, S, np.int32)  # the other slots are parked
    lens[slot] = 50
    before = np.asarray(cv["state"]["S"][:, 0])
    for t in range(50, 60):  # the full batch, one live row
        tok = np.zeros(4, np.int32)
        tok[slot] = toks[t]
        logits, ck, cv = llama_decode_step(cfg, params, ck, cv, jnp.asarray(tok), jnp.asarray(lens))
        assert np.max(np.abs(np.asarray(logits[slot]) - want[t])) < TOL, t
        lens[slot] += 1
    for t in range(60, 70):  # a compact batch: row 0 serves the slot, row 1 is a pad
        logits, ck, cv = llama_decode_step(
            cfg, params, ck, cv, jnp.array([toks[t], 0]), jnp.array([lens[slot], S]),
            slot_ids=jnp.array([slot, 0]))
        assert np.max(np.abs(np.asarray(logits[0]) - want[t])) < TOL, t
        lens[slot] += 1
    assert np.array_equal(np.asarray(cv["state"]["S"][:, 0]), before)  # a parked row never moves
    counts = np.asarray(cv["moe"])
    assert counts[0, :, 0].tolist() == [20] * 4 and counts[0, :, 4].tolist() == [20] * 4
    assert counts[1, :, 0].tolist() == [50] * 4  # the chunks' valid rows, no padding


def test_chunk_rows_that_duplicate_row_0_write_what_row_0_writes(model):
    cfg, params, toks, want = model
    ck, cv = _used_cache(cfg)
    chunk = np.tile(toks[None, :32], (2, 1)).astype(np.int32)
    logits, ck, cv = llama_prefill_chunk_batch(
        cfg, params, ck, cv, jnp.asarray(chunk), jnp.array([1, 1]), jnp.array([0, 0]),
        jnp.array([32, 32]), skey=32)
    assert np.max(np.abs(np.asarray(logits) - want[31])) < TOL


def test_chunked_form_is_the_token_by_token_recurrence():
    A, T, H, d = 2, 64, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    q, k, v = (jax.random.normal(ks[i], (A, T, H, d)) for i in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    # decays from almost none to e**-12 a step: no quotient of exponentials survives that
    g = -jnp.exp(jax.random.uniform(ks[3], (A, T, H, d), minval=-7.0, maxval=2.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (A, T, H)))
    S = S0 = jax.random.normal(ks[5], (A, H, d, d))
    outs = []
    for t in range(T):
        S = S * jnp.exp(g[:, t])[..., None]
        u = beta[:, t][..., None] * (v[:, t] - jnp.einsum("ahk,ahkv->ahv", k[:, t], S))
        S = S + k[:, t][..., None] * u[..., None, :]
        outs.append(jnp.einsum("ahk,ahkv->ahv", q[:, t], S))
    o, S_end = jax.jit(kda_chunk_scan)(q, k, v, g, beta, S0)
    assert np.isfinite(np.asarray(o)).all()
    assert np.max(np.abs(np.asarray(o) - np.asarray(jnp.stack(outs, 1)))) < 1e-4
    assert np.max(np.abs(np.asarray(S_end) - np.asarray(S))) < 1e-4


@pytest.mark.parametrize("heads", [4, 32])  # one cell of heads, and two
def test_state_kernel_steps_live_rows_in_place_and_leaves_the_rest(heads):
    Lk, B, Ba, d = 3, 6, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    state = jax.random.normal(ks[0], (Lk, B, heads, d, d))
    q, k, v = (jax.random.normal(ks[i], (Ba, heads, d)) for i in (1, 2, 3))
    alpha = jax.nn.sigmoid(jax.random.normal(ks[4], (Ba, heads, d)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (Ba, heads)))
    ids = jnp.array([4, 1, 5, 5])  # two pads on one free row
    live = jnp.array([True, True, False, False])
    o_r, s_r = kda_decode_step_reference(state, jnp.int32(1), ids, live, q, k, v, alpha, beta)
    o_k, s_k = kda_decode_step(state, jnp.int32(1), ids, live, q, k, v, alpha, beta, interpret=True)
    assert np.allclose(np.asarray(o_r[:2]), np.asarray(o_k[:2]), rtol=1e-5, atol=1e-5)
    assert np.allclose(np.asarray(s_r), np.asarray(s_k), rtol=1e-5, atol=1e-5)
    untouched = np.asarray(s_k) == np.asarray(state)
    assert untouched[0].all() and untouched[2].all() and untouched[1, [0, 2, 3, 5]].all()
    assert not untouched[1, 1].all() and not untouched[1, 4].all()


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(ref):
    """Guide section 4: each member of the group routes over all 16 experts and
    adds its own 2; the eight parts and ONE shared expert are the whole layer."""
    cfg = dataclasses.replace(get_config("tiny-solar"), n_experts=2)
    whole = dataclasses.replace(cfg, n_experts=16, n_router_experts=0)
    lp = {k: v[0] for k, v in moe.init_moe_layer_params(whole, jax.random.PRNGKey(7), jnp.float32, 1).items()}
    lp["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (16,))
    x = jax.random.normal(jax.random.PRNGKey(9), (24, cfg.dim))
    want = ref._experts(whole, {k: v[None] for k, v in lp.items()}, jnp.int32(0), x)
    total = jnp.zeros_like(x)
    pairs = 0
    for rank in range(8):
        mine = slice(2 * rank, 2 * rank + 2)
        share = {
            # this member's experts first among the router's columns: the
            # program holds experts [0, E) of what its router scores
            "router": jnp.roll(lp["router"], -2 * rank, axis=1),
            "router_bias": jnp.roll(lp["router_bias"], -2 * rank),
            **{k: lp[k][mine] for k in ("w1e", "w3e", "w2e")},
        }
        if rank == 0:  # the shared expert is replicated over the group: counted once
            share.update({k: lp[k] for k in ("w1s", "w3s", "w2s")})
        part, counts = moe.moe_share_ffn(cfg, share, x)
        total, pairs = total + part, pairs + int(counts[1])
    assert pairs == 24 * cfg.experts_per_tok  # every pair landed on exactly one member
    assert np.max(np.abs(np.asarray(total) - np.asarray(want))) < 1e-5


@pytest.mark.parametrize("rows,padded", [(24, 0), (64, 13), (1, 0)])
def test_dropless_layer_is_the_capacity_layer_at_capacity_t(rows, padded):
    """DeepSeek-V2's softmax path keeps its meaning: the dropless layer with
    every expert held equals `moe_ffn` when nothing can be dropped."""
    cfg = get_config("tiny-v2")
    lp = {k: v[0] for k, v in moe.init_moe_layer_params(cfg, jax.random.PRNGKey(0), jnp.float32, 1).items()}
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.dim))
    valid = jnp.arange(rows) < rows - padded if padded else None
    old = moe.moe_ffn(cfg, lp, x, capacity=rows, valid=valid)
    new, counts = moe.moe_share_ffn(cfg, lp, x, valid=valid)
    keep = np.ones(rows, bool) if valid is None else np.asarray(valid)
    assert np.max(np.abs(np.asarray(old) - np.asarray(new))[keep]) < 1e-5
    assert counts.tolist()[:2] == [rows - padded, (rows - padded) * cfg.experts_per_tok]
    # and with the banks stacked over layers, as the layer scan hands them over
    banks = {k: jnp.stack([jnp.zeros_like(lp[k]), lp[k], jnp.ones_like(lp[k])]) for k in ("w1e", "w3e", "w2e")}
    stacked, _ = moe.moe_share_ffn(cfg, lp, x, valid=valid, banks=banks, layer=jnp.int32(1))
    assert np.max(np.abs(np.asarray(stacked) - np.asarray(new))[keep]) < 1e-5


@pytest.mark.parametrize("rows", [24, 900])  # one window of one row tile, and one of nine
def test_a_call_of_both_phases_counts_each_under_its_own(rows):
    """`moe_share_ffn(prompt=...)`, a mixed step's call: the same output as the
    call without it, and the counts of the decode rows and of the prompt tokens
    apart, each what a call of those rows alone counts (the output of a row
    does not depend on what shares the call)."""
    cfg = get_config("tiny-solar")
    lp = {k: v[0] for k, v in moe.init_moe_layer_params(cfg, jax.random.PRNGKey(0), jnp.float32, 1).items()}
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.dim))
    n = rows // 3
    valid = (jnp.arange(rows) % 7) != 3
    prompt = jnp.arange(rows) >= n
    plain, whole = moe.moe_share_ffn(cfg, lp, x, valid=valid)
    y, counts = moe.moe_share_ffn(cfg, lp, x, valid=valid, prompt=prompt)
    assert np.array_equal(np.asarray(y), np.asarray(plain)) and counts.shape == (2, 5)
    _, decode = moe.moe_share_ffn(cfg, lp, x[:n], valid=valid[:n])
    _, prefill = moe.moe_share_ffn(cfg, lp, x[n:], valid=valid[n:])
    assert counts.tolist() == [decode.tolist(), prefill.tolist()]
    assert (counts[0, :2] + counts[1, :2]).tolist() == whole[:2].tolist() and whole[1] > 0


PROMPT_ROWS = 1024  # a prompt's pass: eight row tiles


def _share_case(case: str):
    """(cfg, lp, x, valid, banks, layer, prompt) of one routing situation at
    `tiny-solar` (4 experts held of 16 scored, 4 a row, sigmoid router with a
    selection bias). A zero router leaves every score at 0.5, so the bias
    alone chooses, for every row alike. The cases from `a_prompts_rows` to
    `a_mixed_calls_rows` are prompt-sized (1,024 rows, a window of 1,280 pairs
    of the 4,096); those behind them are a decode step's and a mixed step's
    calls: few rows, padding among them, the decode rows in front and the
    packed prompt tokens behind (`hybrid_mixed_step`), one row tile a window
    up to 64 rows and four at 64 + 256."""
    cfg = get_config("tiny-solar")
    prompt_sized = case in (
        "a_prompts_rows", "one_expert_over_many_tiles", "no_pair_held_here", "every_pair_held_here",
        "the_whole_layer_held", "a_prompts_padded_rows", "a_prompt_on_stacked_banks",
        "a_mixed_calls_rows")
    decode_rows = {"one_row_of_two_phases": 1, "two_rows_of_two_phases": 1, "a_steps_rows_of_two_phases": 16,
                   "a_mixed_steps_rows": 64, "a_step_that_holds_no_pair": 64, "a_step_over_one_window": 64}
    rows = PROMPT_ROWS if prompt_sized else {
        "one_row": 1, "one_row_of_two_phases": 1, "two_rows_of_two_phases": 2, "sixteen_rows": 16,
        "a_mixed_steps_rows": 64 + 256}.get(case, 64)
    lp = {k: v[0] for k, v in moe.init_moe_layer_params(cfg, jax.random.PRNGKey(3), jnp.float32, 1).items()}
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(4), (cfg.router_width,))
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.dim))
    valid = banks = prompt = None
    layer = 0
    if case in ("padded_rows", "a_prompts_padded_rows"):
        valid = jnp.arange(rows) < rows - 13
    elif case == "an_expert_nobody_chose":
        bias = bias.at[2].set(-10.0)
    elif case in ("every_row_on_one_expert", "one_expert_over_many_tiles"):
        lp["router"] = jnp.zeros_like(lp["router"])
        bias = jnp.zeros_like(bias).at[jnp.asarray([0, 5, 6, 7])].set(1.0)
    elif case == "a_row_whose_choices_are_all_absent":
        x = x.at[0].set(0.0)  # its scores are all 0.5: the bias chooses 8..11
        bias = bias.at[8:12].add(0.02)
    elif case == "no_pair_held_here":
        bias = bias.at[8:12].add(10.0)  # every row chooses 8..11, none of them here
    elif case == "every_pair_held_here":
        bias = bias.at[:4].add(10.0)  # every row chooses 0..3: all T k pairs land here
    elif case == "the_whole_layer_held":
        cfg = dataclasses.replace(cfg, n_router_experts=0)  # scores its 4 experts, chooses all
        lp["router"], bias = lp["router"][:, :4], bias[:4]
    elif case in ("banks_stacked_over_layers", "a_prompt_on_stacked_banks"):
        banks = {k: jnp.stack([jnp.ones_like(lp[k]), lp[k]]) for k in ("w1e", "w3e", "w2e")}
        layer = jnp.int32(1)
    elif case == "a_mixed_calls_rows":
        valid = (jnp.arange(rows) % 7) != 3
        prompt = jnp.arange(rows) >= 64
    if case in decode_rows:
        valid = jnp.arange(rows) < 2 if rows <= 2 else (jnp.arange(rows) % 7) != 3
        prompt = jnp.arange(rows) >= decode_rows[case]
        if case == "a_step_that_holds_no_pair":
            bias = bias.at[8:12].add(10.0)
        elif case == "a_step_over_one_window":
            bias = bias.at[:4].add(10.0)  # all 220 pairs land here: a second turn of the window's loop
    lp["router_bias"] = bias
    return cfg, lp, x, valid, banks, layer, prompt


def _every_pair_by_itself(cfg, lp, x, valid, banks, layer):
    """The share's sum with no form at all: every row through every held
    expert, weighted by the row's gate for it (0 where it did not choose it),
    plus the shared expert. What both forms are held to."""
    hi = jax.lax.Precision.HIGHEST
    w1, w3, w2 = ((banks or lp)[n] for n in ("w1e", "w3e", "w2e"))
    if banks is not None:
        w1, w3, w2 = w1[layer], w3[layer], w2[layer]
    E = w1.shape[0]
    gates, experts = moe.route(cfg, jnp.dot(x, lp["router"], precision=hi), lp["router_bias"])
    if valid is not None:
        gates = jnp.where(valid[:, None], gates, 0.0)
    weight = jnp.sum(jnp.where(experts[..., None] == jnp.arange(E), gates[..., None], 0.0), axis=1)  # [T, E]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, w1, precision=hi)) * jnp.einsum(
        "td,edf->tef", x, w3, precision=hi)
    y = jnp.einsum("te,tef,efd->td", weight, h, w2, precision=hi)
    shared = jax.nn.silu(jnp.dot(x, lp["w1s"], precision=hi)) * jnp.dot(x, lp["w3s"], precision=hi)
    return np.asarray(y + jnp.dot(shared, lp["w2s"], precision=hi))


@pytest.mark.parametrize("case", [
    "one_row", "sixteen_rows", "sixty_four_rows", "padded_rows", "an_expert_nobody_chose",
    "every_row_on_one_expert", "a_row_whose_choices_are_all_absent", "banks_stacked_over_layers",
    "a_prompts_rows", "one_expert_over_many_tiles", "no_pair_held_here", "every_pair_held_here",
    "the_whole_layer_held", "a_prompts_padded_rows", "a_prompt_on_stacked_banks", "a_mixed_calls_rows",
    "one_row_of_two_phases", "two_rows_of_two_phases", "a_steps_rows_of_two_phases", "a_mixed_steps_rows",
    "a_step_that_holds_no_pair", "a_step_over_one_window",
])
def test_the_grouped_form_is_every_pair_by_itself(case):
    """One sum, in the one form it is served in (models/moe.py:_grouped: the
    pairs held here sorted in front and they alone gathered, multiplied by the
    grouped kernels and summed, a window at a time) and with no form at all
    (`_every_pair_by_itself`). Equal to float32 rounding in `y`, the counts
    exactly what the router's choices say, of the decode rows and of the prompt
    tokens apart where the call says which are which; whatever the router does
    (nothing here: no window runs; everything here: four windows of a prompt's
    rows, two of a decode step's; an expert over eight row tiles)."""
    cfg, lp, x, valid, banks, layer, prompt = _share_case(case)
    y, counts = jax.jit(lambda lp, x: moe.moe_share_ffn(
        cfg, lp, x, valid=valid, banks=banks, layer=layer, prompt=prompt))(lp, x)
    y_grouped, n_grouped = np.asarray(y), counts.tolist()
    keep = np.ones(x.shape[0], bool) if valid is None else np.asarray(valid)
    plain = _every_pair_by_itself(cfg, lp, x, valid, banks, layer)
    scale = max(1.0, np.max(np.abs(plain)))
    assert np.max(np.abs(y_grouped - plain)[keep]) < 1e-5 * scale
    k, E = cfg.experts_per_tok, cfg.n_experts
    window = moe.window_rows(x.shape[0], k, E, cfg.router_width)
    _, chosen = moe.route(cfg, x @ lp["router"], lp["router_bias"])

    def counted(phase):
        sizes = np.bincount(np.asarray(chosen)[phase].reshape(-1), minlength=cfg.router_width)[:E]
        return [int(phase.sum()), int(sizes.sum()), int((sizes > 0).sum()), int(sizes.max()), 1]

    if prompt is not None:  # the decode rows' counts and the prompt tokens', apart
        assert n_grouped == [counted(keep & ~np.asarray(prompt)), counted(keep & np.asarray(prompt))]
        held = n_grouped[0][1] + n_grouped[1][1]
        if case == "a_mixed_calls_rows":
            assert n_grouped[0][1] > 0 and n_grouped[1][1] > window // 2
        elif case == "a_step_that_holds_no_pair":
            assert held == 0
        elif case == "a_step_over_one_window":
            assert held == int(keep.sum()) * k > window == ROW_TILE
        else:
            assert 0 < held <= window == (4 * ROW_TILE if case == "a_mixed_steps_rows" else ROW_TILE)
        return
    assert n_grouped == counted(keep)
    rows, pairs, touched, fullest, calls = n_grouped
    if case == "an_expert_nobody_chose":
        assert touched < cfg.n_experts
    if case in ("every_row_on_one_expert", "one_expert_over_many_tiles"):
        assert (pairs, touched, fullest) == (rows, 1, rows)
    if case == "one_expert_over_many_tiles":
        assert fullest == 8 * ROW_TILE <= window
    if case == "a_row_whose_choices_are_all_absent":
        assert int(jnp.min(chosen[0])) >= cfg.n_experts > int(jnp.min(chosen[1]))
        # nothing is routed to it here, and the shared expert of a zero row is zero
        assert np.max(np.abs(y_grouped[0])) == 0.0 and np.max(np.abs(y_grouped[1])) > 0.0
    if case == "a_prompts_rows":
        assert 0 < pairs <= window == 1280  # one window holds them
    if case == "no_pair_held_here":
        assert (pairs, touched, fullest) == (0, 0, 0)  # no window at all: the shared expert alone
    if case == "every_pair_held_here":
        assert (pairs, touched, fullest) == (rows * k, 4, rows) and pairs > 3 * window  # four windows
    if case == "the_whole_layer_held":
        assert pairs == rows * k == window  # a share that is the whole layer: one window of all pairs


@pytest.mark.parametrize("preset", ["tiny-solar", "tiny-kexaone"])
@pytest.mark.parametrize("rows", [64, 320, 321, 768, 1024])
def test_the_form_follows_the_row_count_alone(preset, rows):
    """One form at every row count since PR 45, at both expert shares' presets:
    a decode round's 64 rows, a mixed round's 64 + 256, the first row count the
    old threshold sent to the kernels, and `kexaone_reason_closed`'s two admit
    programs all hold the grouped kernels inside one traced loop over the
    windows, and no product over all the pairs (PERF.md section 6, PR 45: the
    traced rounds of both expert cells are faster so, Solar's by 5-9%)."""
    cfg = get_config(preset)
    lp = {k: v[0] for k, v in moe.init_moe_layer_params(cfg, jax.random.PRNGKey(3), jnp.float32, 1).items()}
    x = jnp.zeros((rows, cfg.dim), jnp.float32)
    text = str(jax.make_jaxpr(lambda lp, x: moe.moe_share_ffn(cfg, lp, x))(lp, x))
    assert "grouped_swiglu" in text and "grouped_down" in text and "ragged_dot" not in text
    assert text.count("while[") == 1  # one traced body for all windows; no loop over the experts


@pytest.mark.parametrize("sizes,first", [
    ((100, 0, 300, 28, 0), 0),  # tiles shared by two groups, a group over three tiles, empty groups
    ((128, 128, 128, 0, 0), 5),  # groups that end at a tile's edge; banks at an offset in the stack
    ((0, 0, 0, 0, 0), 0),  # nothing held: no visit at all
    ((512, 0, 0, 0, 0), 3), ((1, 1, 1, 1, 1), 0), ((0, 0, 0, 0, 512), 0),
])
def test_the_grouped_kernels_are_the_ragged_products(sizes, first):
    """`kernels/grouped.py` against `jax.lax.ragged_dot` on a buffer of four
    row tiles: every row of a group equal to float32 rounding, whichever tiles
    the group lies in; what lies behind the last group is the caller's to mask.
    `tile_visits` lists each (group, tile) pair that holds a row, once."""
    E, C, K, F = len(sizes), 4 * ROW_TILE, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(sum(sizes) + first), 4)
    x = jax.random.normal(ks[0], (C, K))
    w1, w3 = (jax.random.normal(k, (first + E + 1, K, F)) * K**-0.5 for k in ks[1:3])
    w2 = jax.random.normal(ks[3], (first + E + 1, F, K)) * F**-0.5
    hi = jnp.cumsum(jnp.asarray(sizes, jnp.int32))
    lo = hi - jnp.asarray(sizes, jnp.int32)
    group, tile, count = tile_visits(lo, hi, C // ROW_TILE)
    want = {(e, t) for e, n in enumerate(sizes) for t in range(4)
            if n and int(lo[e]) < (t + 1) * ROW_TILE and int(hi[e]) > t * ROW_TILE}
    got = list(zip(group[:int(count)].tolist(), tile[:int(count)].tolist()))
    assert set(got) == want and len(got) == len(want) and got == sorted(got)
    ys = jax.jit(lambda *a: grouped_ffn(*a, interpret=True))(x, w1, w3, w2, lo, hi, jnp.int32(first))
    ref = grouped_reference(x, w1, w3, w2, lo, hi, first)
    n = sum(sizes)
    assert np.max(np.abs(np.asarray(ys - ref))[:n], initial=0.0) < 1e-5


@pytest.mark.parametrize("rows,k,held,router,window", [
    (1024, 8, 16, 128, 1280), (768, 8, 16, 128, 1024),  # K-EXAONE's share: the cell's two admit programs
    (2048, 8, 16, 128, 2560), (512, 8, 40, 320, 640), (1024, 8, 40, 320, 1280),  # a chunk; Solar's share
    (1024, 2, 8, 8, 2048), (321, 8, 1, 128, 128),  # a whole layer: every pair; a sliver: one tile
])
def test_a_window_is_the_expected_pairs_and_a_quarter_in_whole_tiles(rows, k, held, router, window):
    """`moe.window_rows` at the published shares: 1.25 times what the router
    lands here in expectation, never more than all the pairs, a multiple of the
    kernel's row tile. Not a capacity: `every_pair_held_here` above fills four."""
    assert moe.window_rows(rows, k, held, router) == window and window % ROW_TILE == 0


# -- the engine's slot life-cycle around the state pool --------------------------------


@pytest.fixture(scope="module")
def engine():
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-solar", max_slots=2, max_seq_len=128, dtype=jnp.float32,
                           prefill_chunk=32, prompt_cache_mb=64).start()
    yield eng
    eng.shutdown()


def _serve(eng, prompt, n=10):
    """(prompt ids, emitted ids, slot) of one greedy request, tapped where the engine emits."""
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


def test_engine_serves_the_references_choice_in_fresh_and_reused_slots(engine, ref):
    """Whole-prompt admission (under the chunk), chunked admission (over it),
    and again: with 2 slots the later requests land in used slots."""
    allowed = np.flatnonzero(np.asarray(engine._allowed_mask))
    prompts = ["amber basil", "x" * 70 + " cedar dune ember", "y" * 45, "fjord grove " * 6]
    admitted = engine.perf_stats()["state_pool"]["admitted_total"]  # (the module's engine: what other cases of this worker were served)
    for prompt in prompts:
        ids, out = _serve(engine, prompt)
        seq = ids + out[:-1]
        rows = np.arange(len(ids) - 1, len(seq))
        seq = np.asarray(seq + [0] * (-len(seq) % 32), np.int32)
        want = ref.logits(engine.cfg, engine.params, seq, rows, allowed)
        for k, tok in enumerate(out):
            regret = float(np.max(want[k]) - want[k, np.flatnonzero(allowed == tok)[0]])
            assert regret < 1e-3, (prompt[:12], k, regret)
    pool = engine.perf_stats()["state_pool"]
    assert pool["admitted_total"] - admitted == len(prompts) > pool["slots"]
    assert pool["live_slots"] == 0 and pool["bytes"] == pool["bytes_per_slot"] * 2
    decode, prefill = np.asarray(engine.perf_stats()["experts"]["counts"])
    assert (decode[:, 4] > 0).all() and (prefill[:, 4] > 0).all()
    assert (decode[:, 1] <= decode[:, 0] * engine.cfg.experts_per_tok).all()


def test_a_chunked_prefill_rides_decode_rounds_without_touching_their_state(engine, ref):
    """The fused step program: one slot decodes while two long prompts prefill
    chunk by chunk in the same dispatches, carrying their state from chunk to
    chunk; every stream's tokens stay the reference's own choice."""
    import threading
    import time

    served = {}
    emit = engine._process_token

    def tap(slot, tok, pos):
        served.setdefault(slot.req.request_id, (list(slot.req.prompt_ids), []))[1].append(int(tok))
        return emit(slot, tok, pos)

    engine._process_token = tap
    try:
        jobs = [threading.Thread(target=engine.generate, args=(p,),
                                 kwargs={"max_tokens": n, "temperature": 0.0})
                for p, n in (("short one", 100), ("z" * 90 + " long, in chunks", 12))]
        jobs[0].start()
        while not served or len(next(iter(served.values()))[1]) < 4:
            time.sleep(0.005)  # the short one is decoding when the long one arrives
        jobs[1].start()
        for j in jobs:
            j.join()
    finally:
        del engine._process_token
    assert "fused" in {r["phase"] for r in engine._ledger.table()}
    allowed = np.flatnonzero(np.asarray(engine._allowed_mask))
    assert len(served) == 2
    for ids, out in served.values():
        seq = ids + out[:-1]
        rows = np.arange(len(ids) - 1, len(seq))
        want = ref.logits(engine.cfg, engine.params,
                          np.asarray(seq + [0] * (-len(seq) % 32), np.int32), rows, allowed)
        for k, tok in enumerate(out):
            assert float(np.max(want[k]) - want[k, np.flatnonzero(allowed == tok)[0]]) < 1e-3


def test_a_recurrent_configuration_never_shares_drafts_or_offloads(engine, monkeypatch):
    """Prefix cache, speculation, offload and migration are off, decided from
    the configuration where the state pool is built, each with its counter."""
    shared = "the same long system prompt, word for word, " * 2
    before = dict(engine.perf_stats()["state_pool"]["off"])
    for tail in ("one", "two", "three", "four"):  # the third sharer would pin a prefix
        engine.generate(shared + tail, max_tokens=6, temperature=0.0)
    off = engine.perf_stats()["state_pool"]["off"]
    assert engine.prefix_cache_hits == 0 and not engine._prefix_cache and engine._prefix_budget == 0
    assert off["prefix_cache"] - before["prefix_cache"] == 4
    assert engine._verify_fn is None and engine.spec_drafted == 0
    assert off["speculation"] > before["speculation"]
    assert engine._pool is None and engine._phys is None and not engine.ragged_prefill
    assert engine.memory_stats().get("preempted_total", 0) == 0
    with pytest.raises(RuntimeError, match="recurrent state"):
        engine.migrate_import(b"")
    assert engine.migrate_export_one() is None
    assert engine.perf_stats()["state_pool"]["off"]["migration"] == before["migration"] + 2


def test_the_switches_that_turn_the_features_on_do_not_for_a_recurrent_configuration(monkeypatch):
    from llm_mcp_tpu.executor import GenerationEngine

    monkeypatch.setenv("TPU_KV_HOST_OFFLOAD", "1")
    monkeypatch.setenv("TPU_MIGRATE", "1")
    monkeypatch.setenv("TPU_SPEC", "1")
    eng = GenerationEngine("tiny-solar", max_slots=2, max_seq_len=64, dtype=jnp.float32)
    assert eng._pool is None and eng._migrate_in is None and eng._verify_fn is None
    assert GenerationEngine("tiny-llm", max_slots=2, max_seq_len=64,
                            dtype=jnp.float32)._state_pool is None


def test_a_mesh_is_refused_for_a_recurrent_configuration():
    from jax.sharding import Mesh

    from llm_mcp_tpu.executor import GenerationEngine

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("tp",))
    with pytest.raises(NotImplementedError, match="one chip"):
        GenerationEngine("tiny-solar", mesh=mesh, max_slots=2, max_seq_len=64)


# -- what the review of PR 32 asked to be held ------------------------------------------


def test_the_features_a_recurrent_configuration_runs_without_come_from_one_list(engine):
    """`memory.RECURRENT_OFF` names them, `engine._runs` answers from it, and
    the pool keeps a counter for each, and one more for what is NOT off: the
    admit programs such a configuration still takes of its own, under
    `mixed_round` (its admissions ride a decode round since PR 42); the file of
    the configuration holds the precisions a later change might lower
    (`program.expect`)."""
    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.executor.memory import POOL_COUNTS, RECURRENT_OFF

    assert POOL_COUNTS == ("mixed_round",) and "mixed_round" not in RECURRENT_OFF
    assert set(engine.perf_stats()["state_pool"]["off"]) == set(RECURRENT_OFF) | set(POOL_COUNTS)
    assert not any(engine._runs(f) for f in RECURRENT_OFF) and engine._runs("chunked_prefill")
    assert engine._runs("mixed_round")
    dense = GenerationEngine("tiny-llm", max_slots=2, max_seq_len=64, dtype=jnp.float32)
    assert all(dense._runs(f) for f in RECURRENT_OFF)
    assert (engine.state_dtype, dense.state_dtype, dense.expert_dtype) == ("float32", "", "")
    assert engine.expert_dtype == "float32"  # this engine's weights; bfloat16 as the cell boots it


def test_the_cells_warm_up_plan_holds_the_steps_it_held(monkeypatch):
    """The engine of `solar_decode_closed` as the cell sizes it (64 slots x
    1024, the entry point's defaults, the chip's kernels and so its ladder of
    prompt buckets, an admit program of at most 512 padded tokens): the 35
    shapes of PR 32 in the plan and, since PR 42, the mixed round at ONE rung,
    the largest (a configuration with recurrent layers: `engine._ride_rungs`)."""
    from llm_mcp_tpu.executor import GenerationEngine, warmup
    from llm_mcp_tpu.utils.config import Config

    monkeypatch.setenv("LLM_MCP_TPU_ATTN", "pallas")  # what `auto` resolves to on the chip
    cfg = Config()  # as benchmark/run.py:boot hands them over
    eng = GenerationEngine(
        "tiny-solar", max_slots=64, max_seq_len=1024, dtype=jnp.float32, kv_quant="int8",
        decode_compact=cfg.tpu_decode_compact, prefill_chunk=cfg.tpu_prefill_chunk,
        prefill_buckets=cfg.tpu_prefill_buckets)
    zoo = eng.warmup_shape_zoo()
    by_phase = {ph: sum(1 for p, _ in zoo if p == ph) for ph, _ in zoo}
    assert by_phase == {"admit": 13, "decode": 4, "chunk": 18, "mixed": 1} and len(zoo) == 36
    assert [key for ph, key in zoo if ph == "mixed"] == [(256, False)] and eng._ride_rungs == (256,)
    assert len(warmup.plan_steps(zoo)) == 36


@pytest.mark.parametrize("lengths,joins", [
    ([20], True),  # 2 rows x 32: the budget of 64, to the token
    ([20, 20], False),  # a third prompt pads the program to 4 rows x 32
    ([40], False),  # 2 rows x 48
    ([], True),  # alone: always
])
def test_an_admit_program_pads_to_no_more_than_a_chunk_of_prefill(lengths, joins):
    """What stands between two decode rounds is bounded by `prefill_chunk`
    tokens, for several whole prompts in one admit program as for a chunked
    prefill; the warm-up plan lists no admit shape beyond it."""
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-solar", max_slots=4, max_seq_len=128, dtype=jnp.float32,
                           prefill_chunk=64)
    batch = [(i, None, [1] * n) for i, n in enumerate(lengths)]
    assert eng._admit_tokens_max() == 64
    assert (not batch or not eng._over_admit_budget(batch, [1] * 20)) == joins
    assert not eng._over_admit_budget([(0, None, [1] * 60)], [1] * 100)  # chunked: joins no batch
    admits = [key for phase, key in eng.warmup_shape_zoo() if phase == "admit"]
    assert (1, 64) in admits and (2, 32) in admits
    assert all(rows * bucket <= 64 for rows, bucket in admits)
    unbounded = GenerationEngine("tiny-solar", max_slots=4, max_seq_len=128, dtype=jnp.float32,
                                 prefill_chunk=0)
    assert unbounded._admit_tokens_max() == 0
    assert not unbounded._over_admit_budget([(0, None, [1] * 100)] * 3, [1] * 100)


def test_prompts_over_the_admit_budget_wait_a_round_and_keep_their_order(ref):
    """Four prompts arrive at once where the budget holds two rows of 32: no
    admit program is dispatched beyond it, and each stream is still the
    reference's own choice, token for token."""
    import threading

    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-solar", max_slots=4, max_seq_len=128, dtype=jnp.float32,
                           prefill_chunk=64).start()
    served = {}
    emit = eng._process_token

    def tap(slot, tok, pos):
        served.setdefault(slot.req.request_id, (list(slot.req.prompt_ids), []))[1].append(int(tok))
        return emit(slot, tok, pos)

    eng._process_token = tap
    try:
        jobs = [threading.Thread(target=eng.generate, args=(f"prompt {k} " + "ab" * k,),
                                 kwargs={"max_tokens": 6, "temperature": 0.0}) for k in range(4)]
        for j in jobs:
            j.start()
        for j in jobs:
            j.join()
        # this engine's own first dispatches: the compile ledger is shared by
        # the process, and under xdist an earlier file's engine may have filed
        # an admit shape this engine's budget forbids (the driver's run on the
        # seed failed so; which files share a worker changes from run to run)
        shapes = [key for phase, key in eng._served_shapes if phase == "admit"]
    finally:
        del eng._process_token
        eng.shutdown()
    assert shapes and all(int(k.split(":")[0]) * int(k.split(":")[1]) <= 64 for k in shapes), shapes
    allowed = np.flatnonzero(np.asarray(eng._allowed_mask))
    assert len(served) == 4
    for ids, out in served.values():
        seq = ids + out[:-1]
        rows = np.arange(len(ids) - 1, len(seq))
        want = ref.logits(eng.cfg, eng.params,
                          np.asarray(seq + [0] * (-len(seq) % 32), np.int32), rows, allowed)
        for k, tok in enumerate(out):
            assert float(np.max(want[k]) - want[k, np.flatnonzero(allowed == tok)[0]]) < 1e-3


def test_the_harness_comparison_passes_the_program_and_refuses_a_float8_reference(ref):
    """scripts/solar_tolerance.py's two readings at the tiny size, through
    `correctness.hold_to_reference`: the served tokens are the reference's own
    choice; held to the reference computed in float8 they are not correct."""
    from benchmark import correctness
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-solar", max_slots=2, max_seq_len=256, dtype=jnp.float32).start()
    try:
        ids, out = _serve(eng, "hold these sixteen tokens to the plain forward, " * 2, n=16)[:2]
        assert correctness.hold_to_reference(ref, eng, ids, out)["worst_regret_rel"] < 1e-3
        for lower, refused in (("state_bf16", False), ("fp8", True)):
            ref.LOWER = lower
            retrace(ref)
            if refused:
                with pytest.raises(AssertionError, match="under the reference's choice"):
                    correctness.hold_to_reference(ref, eng, ids, out)
            else:
                assert correctness.hold_to_reference(ref, eng, ids, out)["worst_regret_rel"] < 0.1
    finally:
        ref.LOWER = None
        retrace(ref)
        eng.shutdown()


# -- Olmo-Hybrid (PR 35): Gated DeltaNet layers, a dense feed-forward, norms on the outputs --


@pytest.fixture(scope="module")
def olmo_ref():
    return reference_for("olmo_hybrid")


def _unlike_norms(params, key=11):
    """Norm weights away from one (the seeded tree has ones, under which a
    norm on the wrong leaf or over the wrong width would still agree)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(key), 16))

    def jitter(w):
        return w * (1.0 + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype))

    params = dict(params, final_norm=jitter(params["final_norm"]))
    params["layers"] = dict(params["layers"], attn_norm=jitter(params["layers"]["attn_norm"]),
                            ffn_norm=jitter(params["layers"]["ffn_norm"]))
    params["gqa"] = dict(params["gqa"], q_norm=jitter(params["gqa"]["q_norm"]),
                         k_norm=jitter(params["gqa"]["k_norm"]))
    params["kda"] = dict(params["kda"], o_norm=jitter(params["kda"]["o_norm"]))
    return params


@pytest.fixture(scope="module")
def olmo(olmo_ref):
    """(cfg, params, tokens [96], the reference's logits at every position) of
    `tiny-olmo-hybrid`: two periods of three linear layers and a full one, 6
    heads (no multiple of 8), keys of 24 and values of 48."""
    with jax.default_matmul_precision("highest"):
        cfg = get_config("tiny-olmo-hybrid")
        params = _unlike_norms(init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (96,), 3, 500))
        want = olmo_ref.logits(cfg, params, toks, np.arange(96), np.arange(cfg.vocab_size))
    return cfg, params, toks, want


def test_the_olmo_reference_shares_no_code_with_the_program():
    assert "llm_mcp_tpu" not in reference_source("olmo_hybrid")  # its docstring names the files


def test_olmo_full_prefill_of_rows_of_unlike_lengths(olmo):
    cfg, params, toks, want = olmo
    assert cfg.layer_period == ("kda", "kda", "kda", "gqa") and cfg.lin_dv == 2 * cfg.lin_head_dim
    batch = np.zeros((4, 64), np.int32)
    lengths = [50, 30, 64, 1]
    for i, n in enumerate(lengths):
        batch[i, :n] = toks[:n]
    logits, ks, vs = llama_prefill(cfg, params, jnp.asarray(batch), jnp.asarray(lengths))
    for i, n in enumerate(lengths):
        assert np.max(np.abs(np.asarray(logits[i]) - want[n - 1])) < TOL, (i, n)
    assert ks.shape[0] == cfg.n_attn_layers == 2
    assert vs["state"]["S"].shape == (6, 4, 6, 24, 48) and "moe" not in vs  # no experts, no counts


def test_olmo_two_chunks_then_decode_through_cache_and_pool_in_a_reused_slot(olmo):
    cfg, params, toks, want = olmo
    ck, cv = _used_cache(cfg)
    assert set(cv) == {"v", "state"}  # the expert counts' member is absent, not zero
    slot, S = 2, 128
    for start, n in ((0, 32), (32, 18)):  # the second chunk ragged, padded to 32
        chunk = np.zeros((1, 32), np.int32)
        chunk[0, :n] = toks[start : start + n]
        logits, ck, cv = llama_prefill_chunk_batch(
            cfg, params, ck, cv, jnp.asarray(chunk), jnp.array([slot]), jnp.array([start]),
            jnp.array([n]), skey=32)
    assert np.max(np.abs(np.asarray(logits[0]) - want[49])) < TOL
    lens = np.full(4, S, np.int32)  # the other slots are parked
    lens[slot] = 50
    before = np.asarray(cv["state"]["S"][:, 0]), np.asarray(cv["state"]["conv"][:, 0])
    for t in range(50, 58):  # the full batch, one live row
        tok = np.zeros(4, np.int32)
        tok[slot] = toks[t]
        logits, ck, cv = llama_decode_step(cfg, params, ck, cv, jnp.asarray(tok), jnp.asarray(lens))
        assert np.max(np.abs(np.asarray(logits[slot]) - want[t])) < TOL, t
        lens[slot] += 1
    for t in range(58, 66):  # a compact batch: row 0 serves the slot, row 1 is a pad
        logits, ck, cv = llama_decode_step(
            cfg, params, ck, cv, jnp.array([toks[t], 0]), jnp.array([lens[slot], S]),
            slot_ids=jnp.array([slot, 0]))
        assert np.max(np.abs(np.asarray(logits[0]) - want[t])) < TOL, t
        lens[slot] += 1
    assert np.array_equal(np.asarray(cv["state"]["S"][:, 0]), before[0])  # a parked row never moves
    assert np.array_equal(np.asarray(cv["state"]["conv"][:, 0]), before[1])
    assert set(cv) == {"v", "state"}


@pytest.mark.parametrize("placement", ["input", "output"])
def test_both_norm_placements_run_and_only_the_stated_one_is_the_reference(olmo, placement):
    """`ModelConfig.norm_placement` is one word in a preset: both values run
    through prefill and decode, the same weight leaves either way, and the two
    differ by far more than rounding."""
    cfg, params, toks, want = olmo
    cfg = dataclasses.replace(cfg, norm_placement=placement)
    batch = jnp.asarray(toks[None, :32].astype(np.int32))
    logits, _, _ = llama_prefill(cfg, params, batch, jnp.array([32]))
    ck, cv = _used_cache(cfg)
    chunked, ck, cv = llama_prefill_chunk_batch(
        cfg, params, ck, cv, batch, jnp.array([1]), jnp.array([0]), jnp.array([32]), skey=32)
    stepped, _, _ = llama_decode_step(
        cfg, params, ck, cv, jnp.array([toks[32]]), jnp.array([32]), slot_ids=jnp.array([1]))
    assert np.isfinite(np.asarray(stepped)).all()
    assert np.max(np.abs(np.asarray(logits[0]) - np.asarray(chunked[0]))) < TOL
    miss = np.max(np.abs(np.asarray(logits[0]) - want[31]))
    assert (miss < TOL) if placement == "output" else (miss > 0.1)


def test_the_one_decay_a_head_chunk_form_is_the_token_by_token_recurrence():
    """Keys of 24 and values of 48; the decay between two positions of a chunk
    is a [C, C] matrix a head, never a [C, C, dk] tensor."""
    A, T, H, dk, dv = 2, 64, 3, 24, 48
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    q, k = (jax.random.normal(ks[i], (A, T, H, dk)) for i in range(2))
    v = jax.random.normal(ks[2], (A, T, H, dv))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    # decays from almost none to e**-12 a step: no quotient of exponentials survives that
    g = -jnp.exp(jax.random.uniform(ks[3], (A, T, H), minval=-7.0, maxval=2.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (A, T, H)))
    S = S0 = jax.random.normal(ks[5], (A, H, dk, dv))
    outs = []
    for t in range(T):
        S = S * jnp.exp(g[:, t])[..., None, None]
        u = beta[:, t][..., None] * (v[:, t] - jnp.einsum("ahk,ahkv->ahv", k[:, t], S))
        S = S + k[:, t][..., None] * u[..., None, :]
        outs.append(jnp.einsum("ahk,ahkv->ahv", q[:, t], S))
    o, S_end = jax.jit(kda_chunk_scan)(q, k, v, g, beta, S0)
    assert np.isfinite(np.asarray(o)).all()
    assert np.max(np.abs(np.asarray(o) - np.asarray(jnp.stack(outs, 1)))) < 1e-4
    assert np.max(np.abs(np.asarray(S_end) - np.asarray(S))) < 1e-4
    text = str(jax.make_jaxpr(kda_chunk_scan)(q, k, v, g, beta, S0))
    assert f"32,32,{dk}]" not in text  # no decay a channel was built
    # and broadcast to every key channel it is the decay-a-channel form's own answer
    o_c, S_c = jax.jit(kda_chunk_scan)(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, S0)
    assert np.max(np.abs(np.asarray(o) - np.asarray(o_c))) < 1e-4
    assert np.max(np.abs(np.asarray(S_end) - np.asarray(S_c))) < 1e-4


@pytest.mark.parametrize("H,dk,dv,head_decay,abreast", [
    (30, 96, 192, True, 2),  # Olmo-Hybrid: two heads abreast, 384 = 3 x 128 lanes
    (30, 96, 192, False, 2),  # the same pool under a decay a channel
    (64, 128, 128, False, 1),  # Solar-Open2: the layout is [.., H, dk, dv] itself
    (6, 24, 48, True, 1),  # the tiny preset: no count of heads makes whole lanes
], ids=["olmo_30x96x192", "olmo_per_channel", "solar_64x128x128", "tiny_6x24x48"])
def test_state_kernel_in_the_pools_layout_is_the_reference_step(H, dk, dv, head_decay, abreast):
    from llm_mcp_tpu.kernels.kda import heads_abreast, pack_state, unpack_state

    Lk, B, Ba = 2, 6, 4
    assert heads_abreast(H, dv) == abreast
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    head_major = jax.random.normal(ks[0], (Lk, B, H, dk, dv))
    state = pack_state(head_major, abreast)
    assert state.shape == (Lk, B, H // abreast, dk, abreast * dv)
    assert np.array_equal(np.asarray(unpack_state(state, abreast)), np.asarray(head_major))
    q, k = (jax.random.normal(ks[i], (Ba, H, dk)) for i in (1, 2))
    v = jax.random.normal(ks[3], (Ba, H, dv))
    alpha = jax.nn.sigmoid(jax.random.normal(ks[4], (Ba, H) if head_decay else (Ba, H, dk)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (Ba, H)))
    ids = jnp.array([4, 1, 5, 5])  # two pads on one free row
    live = jnp.array([True, True, False, False])
    o_r, s_r = kda_decode_step_reference(state, jnp.int32(1), ids, live, q, k, v, alpha, beta)
    o_k, s_k = kda_decode_step(state, jnp.int32(1), ids, live, q, k, v, alpha, beta,
                               name="gdn_decode_step" if head_decay else "kda_decode_step",
                               interpret=True)
    # the step itself, head-major, by hand for row 0
    S = head_major[1, 4] * (alpha[0][:, None, None] if head_decay else alpha[0][..., None])
    u = beta[0][:, None] * (v[0] - jnp.einsum("hk,hkv->hv", k[0], S, precision="highest"))
    S = S + k[0][..., None] * u[:, None, :]
    assert np.allclose(np.asarray(unpack_state(s_r, abreast)[1, 4]), np.asarray(S), atol=1e-4)
    assert np.allclose(np.asarray(o_r[:2]), np.asarray(o_k[:2]), rtol=1e-4, atol=1e-3)
    assert np.allclose(np.asarray(s_r), np.asarray(s_k), rtol=1e-4, atol=1e-4)
    untouched = np.asarray(s_k) == np.asarray(state)
    assert untouched[0].all() and untouched[1, [0, 2, 3, 5]].all()  # a parked row unmoved
    assert not untouched[1, 1].all() and not untouched[1, 4].all()


@pytest.mark.parametrize("family", ["delta_rule", "state_space"])
def test_param_count_reckons_the_new_layer_kinds(family):
    """ISSUE 35's arithmetic: a linear layer 88.7 M + MLP 126.8 M, a full layer
    59.0 M + 126.8 M, embedding and head 770.7 M: 7.43 B whole, 4.93 B at the
    cut; Solar's count is what it was. ISSUE 41's: a state-space layer
    76,182,976 with its MLP and norms, an attention layer 60,821,504, the tied
    table once: 3,191,396,096, to the parameter."""
    if family == "state_space":
        cfg = get_config("granite-4.0-h-micro")
        mixer = 2048 * 8512 + (4 * 4352 + 4352) + 3 * 64 + 4096 + 4096 * 2048
        mlp, norms = 3 * 2048 * 8192, 2 * 2048
        assert mixer == 25_847_232 and mixer + mlp + norms == 76_182_976
        attention = 2 * 2048 * 2048 + 2 * 2048 * 512
        assert attention + mlp + norms == 60_821_504
        assert cfg.param_count() == 3_191_396_096 == (
            36 * 76_182_976 + 4 * 60_821_504 + 100_352 * 2048 + 2048)
        tiny = get_config("tiny-granite-hybrid")
        held = sum(x.size for x in jax.tree.leaves(init_llama_params(tiny, jax.random.PRNGKey(0))))
        assert tiny.param_count() == held  # every leaf, the tied table once
        return
    cut = get_config("olmo-hybrid-7b-d20")
    whole = dataclasses.replace(cut, n_layers=32, gqa_layers=tuple(range(3, 32, 4)))
    D, H, dk, dv, F, V = 3840, 30, 96, 192, 11_008, 100_352
    linear = D * (2 * H * dk + 2 * H * dv) + H * dv * D + 2 * D * H + 4 * H * (2 * dk + dv)
    full, mlp = 4 * D * D, 3 * D * F
    assert linear // 10**5 == 887 and mlp // 10**5 == 1268  # 88.7 M and 126.8 M
    for cfg, layers in ((cut, 20), (whole, 32)):
        reckoned = layers // 4 * (3 * linear + full) + layers * mlp + 2 * V * D
        assert abs(cfg.param_count() - reckoned) < 2e-4 * reckoned  # the norms' vectors
    assert round(cut.param_count() / 1e9, 2) == 4.93 and round(whole.param_count() / 1e9, 2) == 7.43
    tiny = get_config("tiny-olmo-hybrid")
    held = sum(x.size for x in jax.tree.leaves(init_llama_params(tiny, jax.random.PRNGKey(0))))
    assert abs(tiny.param_count() - held) < 1e-3 * held
    solar = get_config("solar-open2-250b-ep8")
    assert round(solar.param_count() / 1e9, 2) == 3.31


@pytest.fixture(scope="module")
def olmo_engine():
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-olmo-hybrid", max_slots=2, max_seq_len=128, dtype=jnp.float32,
                           prefill_chunk=32, prompt_cache_mb=64).start()
    yield eng
    eng.shutdown()


def test_olmo_engine_serves_the_references_choice_whole_and_chunked(olmo_engine, olmo_ref):
    """Whole-prompt admission (under the engine's chunk of 32), a chunked
    prefill carried across ENGINE chunks (over it), and again in used slots;
    the pool's book follows the layout and no expert block exists."""
    eng = olmo_engine
    allowed = np.flatnonzero(np.asarray(eng._allowed_mask))
    prompts = ["amber basil", "x" * 70 + " cedar dune ember", "y" * 45, "fjord grove " * 6]
    for prompt in prompts:
        ids, out = _serve(eng, prompt)
        seq = ids + out[:-1]
        rows = np.arange(len(ids) - 1, len(seq))
        seq = np.asarray(seq + [0] * (-len(seq) % 32), np.int32)
        want = olmo_ref.logits(eng.cfg, eng.params, seq, rows, allowed)
        for k, tok in enumerate(out):
            regret = float(np.max(want[k]) - want[k, np.flatnonzero(allowed == tok)[0]])
            assert regret < 1e-3, (prompt[:12], k, regret)
    assert "chunk" in {r["phase"] for r in eng._ledger.table()}
    stats = eng.perf_stats()
    pool = stats["state_pool"]
    assert "experts" not in stats and eng._experts is None and eng.expert_dtype == ""
    assert (eng.state_dtype, eng.weights_dtype) == ("float32", "float32")
    cfg = eng.cfg
    logical = 6 * 2 * (cfg.lin_heads * cfg.lin_head_dim * cfg.lin_dv * 4
                       + 3 * cfg.lin_heads * (2 * cfg.lin_head_dim + cfg.lin_dv) * 4)
    assert pool["bytes"] == logical == pool["bytes_per_slot"] * 2
    assert pool["layout"] == {"S": [6, 2, 6, 24, 48], "conv": [6, 2, 3 * 6 * 96]}
    assert pool["admitted_total"] == len(prompts) and pool["live_slots"] == 0
    assert not any(eng._runs(f) for f in ("prefix_cache", "offload", "migration", "speculation",
                                           "ragged_prefill"))


def test_the_harness_comparison_passes_the_olmo_program_and_refuses_its_controls(olmo_ref):
    """scripts/solar_tolerance.py's readings at the tiny size, through
    `correctness.hold_to_reference`: the served tokens are the reference's own
    choice (float32 against float32: the tolerance on the chip, 0.2 of a row's
    max |logit|, is for bfloat16 weights); held to the reference computed in
    float8, or with the first linear layer's state lost, they are not correct."""
    from benchmark import correctness
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-olmo-hybrid", max_slots=2, max_seq_len=256, dtype=jnp.float32).start()
    try:
        ids, out = _serve(eng, "hold these sixteen tokens to the plain forward, " * 2, n=16)[:2]
        assert correctness.hold_to_reference(olmo_ref, eng, ids, out)["worst_regret_rel"] < 1e-3
        for lower in olmo_ref.CONTROLS:
            olmo_ref.LOWER = lower
            retrace(olmo_ref)
            if lower in ("fp8", "lost_state"):
                with pytest.raises(AssertionError, match="under the reference's choice"):
                    correctness.hold_to_reference(olmo_ref, eng, ids, out)
            else:
                assert correctness.hold_to_reference(olmo_ref, eng, ids, out)["worst_regret_rel"] < 0.2
    finally:
        olmo_ref.LOWER = None
        retrace(olmo_ref)
        eng.shutdown()


# -- Granite-4.0-H: Mamba-2 state-space layers, four multipliers, heads of 64 ----------


@pytest.fixture(scope="module")
def granite_ref():
    return reference_for("granite_hybrid")


def _unlike_ones(params, key=13):
    """Norm weights and the state-space layers' skip away from one (the seeded
    tree has ones, under which a norm over the wrong width, or a skip on the
    wrong head, would still agree)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(key), 16))

    def jitter(w):
        return w * (1.0 + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype))

    params = dict(params, final_norm=jitter(params["final_norm"]))
    params["layers"] = dict(params["layers"], attn_norm=jitter(params["layers"]["attn_norm"]),
                            ffn_norm=jitter(params["layers"]["ffn_norm"]))
    params["ssm"] = dict(params["ssm"], norm=jitter(params["ssm"]["norm"]),
                         D=jitter(params["ssm"]["D"]))
    return params


@pytest.fixture(scope="module")
def granite(granite_ref):
    """(cfg, params, tokens [96], the reference's logits at every position) of
    `tiny-granite-hybrid`: two periods of four state-space layers (a run the
    program scans), an attention layer and one more state-space layer; 6 heads
    (no multiple of 8) of 64 values, two abreast in the pool; one group."""
    with jax.default_matmul_precision("highest"):
        cfg = get_config("tiny-granite-hybrid")
        params = _unlike_ones(init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (96,), 3, 500))
        want = granite_ref.logits(cfg, params, toks, np.arange(96), np.arange(cfg.vocab_size))
    return cfg, params, toks, want


# float32 against float32, of logits whose largest is about 0.05 (a table drawn
# 12 times smaller, tied, and logits divided by 8): 1e-4 of that. The chunk form's
# products and the reference's token-by-token sums differ by rounding alone.
GRANITE_TOL = 5e-6


def test_the_granite_reference_shares_no_code_with_the_program():
    assert "llm_mcp_tpu" not in reference_source("granite_hybrid")  # its docstring names the files


def test_granite_full_prefill_of_rows_of_unlike_lengths(granite):
    cfg, params, toks, want = granite
    assert cfg.layer_period == ("ssm", "ssm", "ssm", "ssm", "gqa", "ssm")
    # the period's attention layer is not at an end
    assert 0.03 < np.max(np.abs(want)) < 0.08
    batch = np.zeros((4, 64), np.int32)
    lengths = [50, 30, 64, 1]
    for i, n in enumerate(lengths):
        batch[i, :n] = toks[:n]
    logits, ks, vs = llama_prefill(cfg, params, jnp.asarray(batch), jnp.asarray(lengths))
    for i, n in enumerate(lengths):
        assert np.max(np.abs(np.asarray(logits[i]) - want[n - 1])) < GRANITE_TOL, (i, n)
    assert ks.shape[0] == cfg.n_attn_layers == 2
    # ten layers' states, 6 heads of [32 keys, 64 values] two abreast, and no expert counts
    assert vs["state"]["S"].shape == (10, 4, 3, 32, 128) and "moe" not in vs
    assert vs["state"]["conv"].shape == (10, 4, 3 * (6 * 64 + 2 * 32))


@pytest.mark.parametrize("quantized,tol", [
    (False, GRANITE_TOL),
    # the attention layers' keys and values rounded to 8 bits with one scale a
    # head and token: 1/254 of a head's largest value a number, which two
    # attention layers of twelve bring to the logits as 2e-5 to 3e-5, 6e-4 of
    # the largest (read here)
    (True, 1e-4),
], ids=["float32_cache", "int8_cache"])
def test_granite_two_chunks_then_decode_through_cache_and_pool_in_a_reused_slot(
        granite, granite_ref, quantized, tol):
    cfg, params, toks, want = granite
    cache = init_kv_cache(cfg, 4, 128, dtype=jnp.float32, quantized=quantized)
    state = cache["v"]["state"]  # every slot was used: another sequence's leftovers
    ck, cv = cache["k"], dict(cache["v"], state=dict(state, S=state["S"] + 7.0, conv=state["conv"] - 3.0))
    assert set(cv) == {"v", "state"}
    slot, S = 2, 128
    for start, n in ((0, 32), (32, 18)):  # across a chunk's edge; the second ragged, padded to 32
        chunk = np.zeros((1, 32), np.int32)
        chunk[0, :n] = toks[start : start + n]
        logits, ck, cv = llama_prefill_chunk_batch(
            cfg, params, ck, cv, jnp.asarray(chunk), jnp.array([slot]), jnp.array([start]),
            jnp.array([n]), skey=32)
    assert np.max(np.abs(np.asarray(logits[0]) - want[49])) < tol
    lens = np.full(4, S, np.int32)  # the other slots are parked
    lens[slot] = 50
    before = np.asarray(cv["state"]["S"][:, 0]), np.asarray(cv["state"]["conv"][:, 0])
    got = []
    for t in range(50, 58):  # the full batch, one live row
        tok = np.zeros(4, np.int32)
        tok[slot] = toks[t]
        logits, ck, cv = llama_decode_step(cfg, params, ck, cv, jnp.asarray(tok), jnp.asarray(lens))
        got.append(np.asarray(logits[slot]))
        lens[slot] += 1
    for t in range(58, 66):  # a compact batch: row 0 serves the slot, row 1 is a pad
        logits, ck, cv = llama_decode_step(
            cfg, params, ck, cv, jnp.array([toks[t], 0]), jnp.array([lens[slot], S]),
            slot_ids=jnp.array([slot, 0]))
        got.append(np.asarray(logits[0]))
        lens[slot] += 1
    miss = np.max(np.abs(np.stack(got) - want[50:66]), axis=-1)
    assert miss.max() < tol, miss
    assert np.array_equal(np.asarray(cv["state"]["S"][:, 0]), before[0])  # a parked row never moves
    assert np.array_equal(np.asarray(cv["state"]["conv"][:, 0]), before[1])
    if not quantized:
        # tight enough that a state rounded to bfloat16 after every token fails it
        granite_ref.LOWER = "state_bf16"
        retrace(granite_ref)
        try:
            lower = granite_ref.logits(cfg, params, toks, np.arange(50, 66), np.arange(cfg.vocab_size))
        finally:
            granite_ref.LOWER = None
            retrace(granite_ref)
        assert np.max(np.abs(np.stack(got) - lower)) > 4 * tol


@pytest.mark.parametrize("field,neutral", [
    ("embed_multiplier", 1.0), ("residual_multiplier", 1.0), ("logits_divisor", 1.0),
    ("attn_multiplier", 0.0),  # 0: the scores are scaled by head_dim**-0.5, as elsewhere
])
def test_each_of_the_four_multipliers_matters(granite, field, neutral):
    """With one multiplier at its neutral value, prefill and a decode step
    through cache and pool still agree with each other and miss the reference
    by far more than rounding."""
    cfg, params, toks, want = granite
    assert getattr(cfg, field) != neutral
    cfg = dataclasses.replace(cfg, **{field: neutral})
    batch = jnp.asarray(toks[None, :33].astype(np.int32))
    logits, _, _ = llama_prefill(cfg, params, batch, jnp.array([33]))
    cache = init_kv_cache(cfg, 2, 128, dtype=jnp.float32)
    _, ck, cv = llama_prefill_chunk_batch(
        cfg, params, cache["k"], cache["v"], batch[:, :32], jnp.array([1]), jnp.array([0]),
        jnp.array([32]), skey=32)
    stepped, _, _ = llama_decode_step(
        cfg, params, ck, cv, jnp.array([toks[32]]), jnp.array([32]), slot_ids=jnp.array([1]))
    assert np.max(np.abs(np.asarray(logits[0]) - np.asarray(stepped[0]))) < 10 * GRANITE_TOL
    assert np.max(np.abs(np.asarray(stepped[0]) - want[32])) > 100 * GRANITE_TOL


def test_the_chunk_form_without_the_delta_rule_is_the_token_by_token_recurrence():
    """Mamba-2's chunk form: `kda_chunk_scan` with no beta (U = V, no system
    solved) and keys and queries ONE group for every head, across a chunk's edge
    (64 positions in chunks of 32) and with padding behind row 1's 41 positions."""
    A, T, H, N, P = 2, 64, 3, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    C, B = (jax.random.normal(ks[i], (A, T, N)) for i in range(2))
    nvalid = jnp.array([64, 41])
    valid = jnp.arange(T)[None, :] < nvalid[:, None]
    v = jnp.where(valid[..., None, None], jax.random.normal(ks[2], (A, T, H, P)), 0.0)
    # decays from almost none to e**-12 a step, none at a padding position
    g = jnp.where(valid[..., None], -jnp.exp(jax.random.uniform(ks[3], (A, T, H), minval=-7.0, maxval=2.5)), 0.0)
    S = S0 = jax.random.normal(ks[4], (A, H, N, P))
    outs, ends = [], {}
    for t in range(T):
        S = S * jnp.exp(g[:, t])[..., None, None] + B[:, t][:, None, :, None] * v[:, t][:, :, None, :]
        outs.append(jnp.einsum("ak,ahkv->ahv", C[:, t], S))
        ends[t + 1] = S
    scan = jax.jit(lambda C, B, v, g, S0: kda_chunk_scan(C[:, :, None], B[:, :, None], v, g, None, S0))
    o, S_end = scan(C, B, v, g, S0)
    want = np.asarray(jnp.stack(outs, 1))
    assert np.isfinite(np.asarray(o)).all()
    assert np.max(np.abs(np.where(valid[..., None, None], np.asarray(o) - want, 0.0))) < 1e-4
    assert np.max(np.abs(np.asarray(S_end[0]) - np.asarray(ends[64][0]))) < 1e-4
    assert np.max(np.abs(np.asarray(S_end[1]) - np.asarray(ends[41][1]))) < 1e-4  # padding moved nothing
    text = str(jax.make_jaxpr(scan)(C, B, v, g, S0))
    assert "triangular_solve" not in text and f"{A},{T},{H},{N}]" not in text  # no solve, no copy a head


@pytest.mark.parametrize("H,N,P,abreast", [
    (64, 128, 64, 2),  # Granite-4.0-H: the pool's tile [.., 32, 128, 128]
    (6, 32, 64, 2),  # the tiny preset: heads off 8, three tiles a row
    (5, 16, 24, 1),  # no count of heads makes whole lanes: one head a tile
], ids=["granite_64x128x64", "tiny_6x32x64", "odd_5x16x24"])
def test_state_kernel_without_the_delta_rule_is_the_plain_step(H, N, P, abreast):
    """`ssd_decode_step`: the pool's kernel body with the correction left out
    and B, C one row a batch row, in interpret mode against the step by hand;
    live rows stepped in place, parked and padding rows untouched."""
    from llm_mcp_tpu.kernels.kda import heads_abreast, pack_state, unpack_state

    Lk, slots, Ba = 2, 6, 4
    assert heads_abreast(H, P) == abreast
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    head_major = jax.random.normal(ks[0], (Lk, slots, H, N, P))
    state = pack_state(head_major, abreast)
    assert state.shape == (Lk, slots, H // abreast, N, abreast * P)
    C, B = (jax.random.normal(ks[i], (Ba, N)) for i in (1, 2))
    v = jax.random.normal(ks[3], (Ba, H, P))
    alpha = jax.nn.sigmoid(jax.random.normal(ks[4], (Ba, H)))
    ids = jnp.array([4, 1, 5, 5])  # two pads on one free row
    live = jnp.array([True, True, False, False])
    o_k, s_k = kda_decode_step(state, jnp.int32(1), ids, live, C, B, v, alpha,
                               name="ssd_decode_step", interpret=True)
    o_r, s_r = kda_decode_step_reference(state, jnp.int32(1), ids, live, C, B, v, alpha)
    for row in (0, 1):  # the step itself, head-major, by hand
        S = (head_major[1, ids[row]] * alpha[row][:, None, None]
             + B[row][None, :, None] * v[row][:, None, :])
        assert np.allclose(np.asarray(unpack_state(s_k, abreast)[1, ids[row]]), np.asarray(S), atol=1e-5)
        o = jnp.einsum("k,hkv->hv", C[row], S, precision="highest")
        assert np.allclose(np.asarray(o_k[row]), np.asarray(o), rtol=1e-4, atol=1e-4)
    assert np.allclose(np.asarray(o_r[:2]), np.asarray(o_k[:2]), rtol=1e-4, atol=1e-4)
    assert np.allclose(np.asarray(s_r), np.asarray(s_k), rtol=1e-5, atol=1e-5)
    untouched = np.asarray(s_k) == np.asarray(state)
    assert untouched[0].all() and untouched[1, [0, 2, 3, 5]].all()  # a parked row unmoved
    assert not untouched[1, 1].all() and not untouched[1, 4].all()


@pytest.mark.parametrize("quantized", [True, False], ids=["int8", "bf16"])
def test_the_append_kernel_takes_heads_of_64_with_no_fall(quantized):
    """Head size 64 (Granite-4.0-H's attention): the append is the tile-rewrite
    kernel, its step rows repeated down the tile, and not the scatter it fell
    to; parked rows write nothing. (Mosaic's acceptance of the shape is
    tests/test_tpu_compile.py -k granite.)"""
    from llm_mcp_tpu.kernels import attention as A

    L, slots, Hkv, S, hd, Ba = 2, 5, 2, 128, 64, 3
    cfg = dataclasses.replace(get_config("tiny-llm"), n_layers=L, n_kv_heads=Hkv, head_dim=hd)
    cache = init_kv_cache(cfg, slots, S, dtype=jnp.float32, quantized=quantized)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    new_k, new_v = (jax.random.normal(k, (L, Ba, Hkv, hd)) for k in ks)
    lengths, ids = jnp.array([5, 127, S]), jnp.array([3, 0, 1])  # the third row is parked
    append = A.append_kv_q8 if quantized else A.append_kv_bf16
    reference = A.append_kv_q8_reference if quantized else A.append_kv_bf16_reference

    def call(ck, cv):
        return append(ck, cv, new_k, new_v, lengths, slot_ids=ids, interpret=True)

    assert "pallas_call" in str(jax.make_jaxpr(call)(cache["k"], cache["v"]))
    got = call(cache["k"], cache["v"])
    want = reference(cache["k"], cache["v"], new_k, new_v, lengths, slot_ids=ids)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    first = jax.tree.leaves(got)[0]
    assert np.asarray(first[:, 3, :, 5]).any() and not np.asarray(first[:, 1]).any()


@pytest.fixture(scope="module")
def granite_engine():
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-granite-hybrid", max_slots=2, max_seq_len=128, dtype=jnp.float32,
                           prefill_chunk=32, prompt_cache_mb=64, kv_quant="int8").start()
    yield eng
    eng.shutdown()


def test_granite_engine_serves_the_references_choice_whole_and_chunked(granite_engine, granite_ref):
    """Whole-prompt admission (under the engine's chunk of 32), a chunked
    prefill carried across ENGINE chunks (over it), and again in used slots,
    through the int8 cache; the pool's book follows the new state's layout."""
    eng = granite_engine
    allowed = np.flatnonzero(np.asarray(eng._allowed_mask))
    prompts = ["amber basil", "x" * 70 + " cedar dune ember", "y" * 45, "fjord grove " * 6]
    for prompt in prompts:
        ids, out = _serve(eng, prompt)
        seq = ids + out[:-1]
        rows = np.arange(len(ids) - 1, len(seq))
        seq = np.asarray(seq + [0] * (-len(seq) % 32), np.int32)
        want = granite_ref.logits(eng.cfg, eng.params, seq, rows, allowed)
        for k, tok in enumerate(out):
            regret = float(np.max(want[k]) - want[k, np.flatnonzero(allowed == tok)[0]])
            assert regret < 1e-3, (prompt[:12], k, regret)
    assert "chunk" in {r["phase"] for r in eng._ledger.table()}
    stats = eng.perf_stats()
    pool = stats["state_pool"]
    assert "experts" not in stats and eng._experts is None
    assert (eng.state_dtype, eng.weights_dtype) == ("float32", "float32")
    cfg = eng.cfg
    width = cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_state
    logical = 10 * 2 * (cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim * 4 + 3 * width * 4)
    assert pool["bytes"] == logical == pool["bytes_per_slot"] * 2
    assert pool["layout"] == {"S": [10, 2, 3, 32, 128], "conv": [10, 2, 3 * width]}
    assert pool["admitted_total"] == len(prompts) and pool["live_slots"] == 0
    assert not any(eng._runs(f) for f in ("prefix_cache", "offload", "migration", "speculation",
                                           "ragged_prefill"))
    # a recurrent configuration's admissions may ride a decode round (not on this
    # engine: the XLA decode path, `other`); the pool counts the admit programs of
    # whole prompts it still takes of its own, here every one
    assert eng._runs("mixed_round") and eng._ride_off() == "other" and eng._ride_align == 32
    assert pool["off"]["mixed_round"] == sum(stats["admit"]["own"].values()) > 0


def test_the_harness_comparison_passes_the_granite_program_and_refuses_its_controls(granite_ref):
    """scripts/solar_tolerance.py's readings at the tiny size, through
    `correctness.hold_to_reference`: the served tokens are the reference's own
    choice; held to the reference computed in float8, or with the first
    state-space layer's state lost, they are not correct."""
    from benchmark import correctness
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-granite-hybrid", max_slots=2, max_seq_len=256, dtype=jnp.float32).start()
    try:
        ids, out = _serve(eng, "hold these sixteen tokens to the plain forward, " * 2, n=16)[:2]
        assert correctness.hold_to_reference(granite_ref, eng, ids, out)["worst_regret_rel"] < 1e-3
        for lower in granite_ref.CONTROLS:
            granite_ref.LOWER = lower
            retrace(granite_ref)
            if lower in ("fp8", "lost_state"):
                with pytest.raises(AssertionError, match="under the reference's choice"):
                    correctness.hold_to_reference(granite_ref, eng, ids, out)
            else:
                assert correctness.hold_to_reference(granite_ref, eng, ids, out)["worst_regret_rel"] < 0.2
    finally:
        granite_ref.LOWER = None
        retrace(granite_ref)
        eng.shutdown()


# -- the parts a mixed step composes a recurrent layer from (PR 42) -------------------


@pytest.mark.parametrize("form", ["a decay a channel", "a decay a head", "no delta rule"])
def test_a_packed_chunk_scan_starts_from_zero_at_a_fresh_chunk(form):
    """`kda_packed_scan` over two sequences packed in ONE row, each from a chunk
    boundary on, with `fresh` marking their first chunks: every position's
    output and each sequence's state after its last chunk are what
    `kda_chunk_scan` gives the sequence alone from zero state, in all three
    forms of the recurrence; padding inside a sequence's last chunk and a whole
    chunk of it leave the state alone, and a chunk past the `staged` ones is
    not run at all: its outputs read 0. The states come by chunk in the pool's
    layout, a sequence's own at its last chunk RUN (the kernel form, one decay
    a head, writes no other)."""
    from llm_mcp_tpu.models.kda import CHUNK, kda_chunk_scan, kda_packed_scan

    H, dk, dv = 3, 8, 16
    rng = np.random.default_rng(0)
    lens, T = (40, 32), 4 * CHUNK  # 40 pads to 64, 32 is a chunk to the token, a chunk of padding

    def draw(n):
        valid = (np.arange(-(-n // CHUNK) * CHUNK) < n)[None, :, None]
        t = valid.shape[1]
        q, k = (rng.normal(size=(1, t, H, dk)).astype(np.float32) * 0.3 for _ in range(2))
        v = rng.normal(size=(1, t, H, dv)).astype(np.float32) * valid[..., None]
        g = -rng.uniform(0.01, 0.5, size=(1, t, H, dk) if form == "a decay a channel" else (1, t, H))
        g = (g * (valid[..., None] if g.ndim == 4 else valid)).astype(np.float32)
        beta = None if form == "no delta rule" else (
            rng.uniform(0.1, 1.9, size=(1, t, H)) * valid).astype(np.float32)
        return q, k, v, g, beta

    S0 = jnp.zeros((1, H, dk, dv), jnp.float32)
    alone = [draw(n) for n in lens]
    pad = [np.zeros((1, CHUNK, *a.shape[2:]), np.float32) if a is not None else None
           for a in alone[0]]
    packed = [None if parts[0] is None else np.concatenate(parts, axis=1)
              for parts in zip(*alone, pad)]
    assert packed[0].shape[1] == T
    fresh = jnp.asarray([True, False, True, False])
    o, after = kda_packed_scan(*packed, fresh, 4)
    assert after.shape == (4, 1, H, dk, dv)  # no count of 3 heads of 16 makes whole lanes
    o3, after3 = jax.jit(kda_packed_scan)(*packed, fresh, jnp.int32(3))  # the chunks that hold tokens
    at = 0
    for ops, n, last, last3 in zip(alone, lens, (1, 3), (1, 2)):
        want_o, want_S = kda_chunk_scan(*ops, S0)
        np.testing.assert_allclose(o[:, at : at + n], want_o[:, :n], rtol=1e-6, atol=1e-7)
        # a chunk of padding moves nothing: the second sequence's state is the same behind it
        np.testing.assert_allclose(after[last], want_S, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(after3[last3], want_S, rtol=1e-6, atol=1e-7)
        assert float(jnp.abs(want_S).max()) > 1e-2
        at += want_o.shape[1]
    assert np.array_equal(o3[:, : 3 * CHUNK], o[:, : 3 * CHUNK]) and not o3[:, 3 * CHUNK :].any()
    if form == "a decay a channel":  # the loop form holds the state after EVERY chunk run
        assert np.array_equal(after[3], after[2])
        assert np.array_equal(after3[:3], after[:3]) and not after3[3].any()


CHUNK_KERNEL_LAYOUTS = {  # H, dk, dv: the pool's tile [dk, P dv] of P heads abreast
    "olmo_two_abreast": (4, 24, 192),  # dk 96 / dv 192 in miniature: rows of 384 = 3 x 128 lanes
    "granite_two_abreast": (4, 32, 64),  # dk 128 / dv 64 in miniature: square tiles of 128
    "one_head_a_tile": (3, 16, 128),
    "no_whole_lanes": (3, 8, 16),  # the tiny presets' kind: interpreted here, the loop form on the chip
}


def _chunk_kernel_operands(rng, layout, group, arm, lens):
    """Sequences of `lens` tokens, each padded to whole chunks: the recurrence's
    operands one decay a head, as the layer masks them (no decay, no beta and,
    without the delta rule, no input at a padding position)."""
    from llm_mcp_tpu.models.kda import CHUNK

    H, dk, dv = CHUNK_KERNEL_LAYOUTS[layout]
    Hq = 1 if group == "one_group" else H
    seqs = []
    for n in lens:
        t = -(-n // CHUNK) * CHUNK
        valid = (np.arange(t) < n)[None, :, None]
        q = rng.normal(size=(1, t, Hq, dk)).astype(np.float32)
        k = rng.normal(size=(1, t, Hq, dk)).astype(np.float32)
        q, k = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5, k / np.linalg.norm(k, axis=-1, keepdims=True)
        v = rng.normal(size=(1, t, H, dv)).astype(np.float32)
        g = (-np.exp(rng.uniform(-6.0, 0.5, size=(1, t, H))) * valid).astype(np.float32)
        beta = (rng.uniform(0.1, 1.9, size=(1, t, H)) * valid).astype(np.float32)
        seqs.append((q, k, v * valid[..., None] if arm == "no_delta" else v, g,
                     None if arm == "no_delta" else beta))
    return seqs


def _by_token(q, k, v, g, beta, S, n):
    """The recurrence token by token over the first n positions of ONE row, float64."""
    q, k, v, g, S = (np.asarray(x, np.float64) for x in (q, k, v, g, S))
    H = v.shape[2]
    outs = []
    for t in range(n):
        kt, qt = np.broadcast_to(k[0, t], (H, k.shape[-1])), np.broadcast_to(q[0, t], (H, q.shape[-1]))
        S = S * np.exp(g[0, t])[:, None, None]
        u = v[0, t] if beta is None else np.asarray(beta, np.float64)[0, t][:, None] * (
            v[0, t] - np.einsum("hk,hkv->hv", kt, S))
        S = S + kt[:, :, None] * u[:, None, :]
        outs.append(np.einsum("hk,hkv->hv", qt, S))
    return np.stack(outs), S


@pytest.mark.parametrize("arm", ["delta", "no_delta"])
@pytest.mark.parametrize("group", ["one_group", "a_head_each"])
@pytest.mark.parametrize("layout", list(CHUNK_KERNEL_LAYOUTS))
def test_the_chunk_kernel_is_the_loop_form_from_a_carried_state(layout, group, arm):
    """`kda_chunk_scan` with one decay a head is the Pallas kernel
    (`kernels/kda.py:chunk_scan`, interpreted here): against the `lax.scan` of
    `_chunk_step` it replaced and against the recurrence token by token, from a
    NONZERO state, two rows of three chunks, the second with padding inside its
    last chunk (70 of 96 positions), which leaves the state where the 70th
    token put it; both arms, Q and K one group and a head each, the pool's
    layouts of one head a tile and of two abreast."""
    from llm_mcp_tpu.kernels.kda import heads_abreast, pack_state, unpack_state
    from llm_mcp_tpu.models import kda

    rng = np.random.default_rng(50)
    lens = (96, 70)
    rows = _chunk_kernel_operands(rng, layout, group, arm, lens)
    ops = [None if rows[0][i] is None else jnp.asarray(np.concatenate([r[i] for r in rows]))
           for i in range(5)]
    H, dk, dv = CHUNK_KERNEL_LAYOUTS[layout]
    P = heads_abreast(H, dv)
    head_major = jnp.asarray(rng.normal(size=(2, H, dk, dv)), jnp.float32)
    S0 = pack_state(head_major, P)  # both forms take and leave the pool's layout
    assert kda._kernel_name(ops[3], ops[1], ops[2], ops[4], kda.CHUNK) == (
        "ssd_chunk_scan" if arm == "no_delta" else "gdn_chunk_scan")
    text = str(jax.make_jaxpr(kda.kda_chunk_scan)(*ops, S0))
    assert "pallas_call" in text and "while" not in text and "bf16" not in text  # one call, float32
    o, S = jax.jit(kda.kda_chunk_scan)(*ops, S0)
    o_loop, S_loop = jax.jit(kda._loop_chunk_scan)(*ops, S0)
    assert S.shape == S_loop.shape == (2, H // P, dk, P * dv)
    np.testing.assert_allclose(o, o_loop, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(S, S_loop, rtol=2e-5, atol=2e-6)
    for a, n in enumerate(lens):
        want_o, want_S = _by_token(*rows[a], head_major[a], n)
        np.testing.assert_allclose(o[a, :n], want_o, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(unpack_state(S, P)[a], want_S, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("arm", ["delta", "no_delta"])
@pytest.mark.parametrize("group", ["one_group", "a_head_each"])
@pytest.mark.parametrize("layout", list(CHUNK_KERNEL_LAYOUTS))
def test_the_packed_chunk_kernel_is_each_prompt_alone_from_zero(layout, group, arm):
    """`kda_packed_scan` with one decay a head is the same kernel over SEVERAL
    fresh prompts in one row of eight chunks: one of exactly a chunk, one a
    token over it, one of 70 tokens (padding inside its last chunk) and two
    chunks nothing was staged in. Every prompt's outputs and its state after
    its last chunk run are the loop form's of the prompt ALONE from zero state;
    the outputs behind the staged chunks read 0; with `staged` below the row's
    chunks (4 of 6) the cut prompt's state is the one after its first chunk,
    where the 32nd token put it. The states come in the pool's layout."""
    from llm_mcp_tpu.kernels.kda import heads_abreast, unpack_state
    from llm_mcp_tpu.models import kda

    C = kda.CHUNK
    rng = np.random.default_rng(51)
    lens = (32, 33, 70)
    alone = _chunk_kernel_operands(rng, layout, group, arm, lens)
    T = 8 * C
    packed = []
    for i in range(5):
        if alone[0][i] is None:
            packed.append(None)
            continue
        parts = [a[i] for a in alone]
        parts.append(np.zeros((1, T - sum(p.shape[1] for p in parts), *parts[0].shape[2:]), np.float32))
        packed.append(jnp.asarray(np.concatenate(parts, axis=1)))
    H, dk, dv = CHUNK_KERNEL_LAYOUTS[layout]
    P = heads_abreast(H, dv)
    fresh = jnp.asarray([True, True, False, True, False, False, False, False])
    scan = jax.jit(kda.kda_packed_scan)
    o, after = scan(*packed, fresh, jnp.int32(6))
    assert after.shape == (8, 1, H // P, dk, P * dv)
    S0 = jnp.zeros((1, H // P, dk, P * dv), jnp.float32)
    at = 0
    for ops, n, last in zip(alone, lens, (0, 2, 5)):
        want_o, want_S = kda._loop_chunk_scan(*(None if x is None else jnp.asarray(x) for x in ops), S0)
        np.testing.assert_allclose(o[:, at : at + n], want_o[:, :n], rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(after[last], want_S, rtol=2e-5, atol=2e-6)
        assert float(jnp.abs(want_S).max()) > 1e-2
        at += want_o.shape[1]
    assert not np.asarray(o[:, 6 * C :]).any()  # nothing was run there
    o4, after4 = scan(*packed, fresh, jnp.int32(4))  # the third prompt cut behind its first chunk
    assert np.array_equal(o4[:, : 4 * C], o[:, : 4 * C]) and not np.asarray(o4[:, 4 * C :]).any()
    assert np.array_equal(after4[0], after[0]) and np.array_equal(after4[2], after[2])
    _, want_S = _by_token(*alone[2], np.zeros((H, dk, dv)), C)
    np.testing.assert_allclose(unpack_state(after4[3], P)[0], want_S, rtol=1e-4, atol=1e-5)
    # the loop form under the same contract: the same states in the same layout
    o_loop, after_loop = jax.jit(kda._loop_packed_scan)(*packed, fresh, jnp.int32(6))
    assert after_loop.shape == after.shape
    np.testing.assert_allclose(o, o_loop, rtol=2e-5, atol=2e-6)
    for last in (0, 2, 5):
        np.testing.assert_allclose(after[last], after_loop[last], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("arm", ["delta", "no_delta"])
@pytest.mark.parametrize("group", ["one_group", "a_head_each"])
@pytest.mark.parametrize("layout", ["granite_two_abreast", "no_whole_lanes"])
def test_a_packed_chunk_kernel_with_nothing_staged_runs_nothing(layout, group, arm):
    """`staged` 0: a mixed step computes the count from its row ids and nothing
    in the program rules a row with no token out. No chunk is run, every output
    reads 0, and the row of the states that the cells name (chunk 0's, never a
    row before the buffer) reads 0 as the loop form's does."""
    from llm_mcp_tpu.models import kda

    rng = np.random.default_rng(52)
    (ops,) = _chunk_kernel_operands(rng, layout, group, arm, (4 * kda.CHUNK,))
    ops = [None if x is None else jnp.asarray(x) for x in ops]
    fresh = jnp.asarray([True, False, True, False])
    o, after = jax.jit(kda.kda_packed_scan)(*ops, fresh, jnp.int32(0))
    o_loop, after_loop = jax.jit(kda._loop_packed_scan)(*ops, fresh, jnp.int32(0))
    assert after.shape == after_loop.shape and not np.asarray(after_loop).any()
    assert not np.asarray(o).any() and not np.asarray(o_loop).any()
    assert not np.asarray(after[0]).any()


def test_a_chunk_scan_no_tile_fits_falls_to_the_loop_form_and_is_counted(monkeypatch, tmp_path):
    """On the chip a shape Mosaic cannot tile (a state row that is no whole
    number of lanes: the tiny presets') takes `_chunk_step` and counts in
    `kernels.attention.reference_falls` under the chunk kernel's name; a decay
    a key channel is another algorithm and no fall; interpret mode takes the
    kernel at any shape. The fall lands in a recorder of the test's own
    (tests/test_tpu_compile.py says why)."""
    from llm_mcp_tpu.kernels import attention as A
    from llm_mcp_tpu.models import kda
    from llm_mcp_tpu.telemetry import recorder as flight
    from llm_mcp_tpu.utils import platform

    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    H, dk, dv = CHUNK_KERNEL_LAYOUTS["no_whole_lanes"]
    ops = (sd(1, 64, H, dk), sd(1, 64, H, dk), sd(1, 64, H, dv), sd(1, 64, H), sd(1, 64, H), sd(1, H, dk, dv))
    falls = dict(A.reference_falls)
    assert "pallas_call" in str(jax.make_jaxpr(kda.kda_chunk_scan)(*ops))
    monkeypatch.setattr(platform, "device_platform", lambda: "tpu")
    jax.clear_caches()
    own = flight.FlightRecorder(capacity=64, dump_dir=str(tmp_path))
    flight.get_recorder()  # (makes the process's ring, if none was)
    prev = flight.set_recorder(own)
    try:
        assert "pallas_call" not in str(jax.make_jaxpr(kda.kda_chunk_scan)(*ops))
        assert A.reference_falls == {**falls, "gdn_chunk_scan": falls.get("gdn_chunk_scan", 0) + 1}
        assert [e["fields"]["kernel"] for e in own.snapshot(etype="kernel_fall")] == ["gdn_chunk_scan"]
        a_channel = (*ops[:3], sd(1, 64, H, dk), *ops[4:])
        assert "pallas_call" not in str(jax.make_jaxpr(kda.kda_chunk_scan)(*a_channel))
        assert A.reference_falls == {**falls, "gdn_chunk_scan": falls.get("gdn_chunk_scan", 0) + 1}
    finally:  # table and ring are the process's: leave them as the other tests expect them
        flight.set_recorder(prev)
        A.reference_falls.clear()
        A.reference_falls.update(falls)
        monkeypatch.undo()
        jax.clear_caches()


def test_a_packed_convolution_reads_nothing_before_a_prompts_first_token():
    """`conv_packed` against `conv_chunk` of each prompt alone from a zero tail:
    outputs and tails, a prompt shorter than the taps' reach among them."""
    from llm_mcp_tpu.models.kda import conv_chunk, conv_packed

    taps, W = 4, 6
    rng = np.random.default_rng(1)
    conv_w = jnp.asarray(rng.normal(size=(taps, W)), jnp.float32)
    lens, starts, T = (5, 2, 7), (0, 8, 16), 24
    proj = jnp.asarray(rng.normal(size=(T, W)), jnp.float32)
    positions = np.full(T, 99, np.int32)
    for n, at in zip(lens, starts):
        positions[at : at + n] = np.arange(n)
    last = jnp.asarray([at + n - 1 for n, at in zip(lens, starts)], jnp.int32)
    mixed, tails = conv_packed(proj, jnp.asarray(positions), last, conv_w)
    for r, (n, at) in enumerate(zip(lens, starts)):
        want, tail = conv_chunk(jnp.zeros((1, taps - 1, W)), jnp.asarray([n]), proj[None, at : at + 8], conv_w)
        assert np.array_equal(mixed[at : at + n], want[0, :n])
        assert np.array_equal(tails[r], tail[0])
