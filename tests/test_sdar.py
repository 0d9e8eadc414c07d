"""Generation by diffusion over blocks (`tiny-sdar`, the toy twin of
`sdar-30b-a3b-ep8`), on the CPU with seeded weights, held to the plain float32
reference `benchmark/references/sdar_moe.py`: the block mask of the whole-prompt
prefill (both arms), the bucketed chunk and the packed prompt; every denoising
pass's logits and the committed KV of a multi-block reply with the order of
unmasking followed pass by pass; the engine's block round against the same
passes driven by hand; the prompt's remainder in a first block, EOS inside a
block, `max_tokens` that is no multiple of the block, the last block at
`max_seq_len`; the shares of the expert layer adding up to the uncut layer; what
such a configuration runs without, counted; the next round dispatched before the
last is fetched; and with `block_len` 0 the programs of the causal presets as
they were."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell_programs
from family import reference_for, reference_source, retrace, stepwise
import llm_mcp_tpu.kernels.attention as A
from llm_mcp_tpu.models import llama, moe
from llm_mcp_tpu.models.configs import get_config
from llm_mcp_tpu.ops.sampling import sample_tokens, sample_tokens_p

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 against float32, of logits whose largest is about 4: the program's
# blocked and grouped products and the reference's whole-sequence ones differ by
# rounding alone (2e-6 measured); the causal mask in a block's place moves a
# logit by 0.1 and more (`test_the_controls_...`)
TOL = 1e-4
L = 4
# a reply driven by hand calls a pass after a pass at one shape: one trace and one compile
block_pass, block_denoise = stepwise(llama.block_pass), stepwise(llama.block_denoise)


@pytest.fixture(scope="module")
def ref():
    return reference_for("sdar_moe")


def _unlike_ones(params, key=13):
    """Norm weights away from one: under ones a norm left out would still agree."""
    keys = iter(jax.random.split(jax.random.PRNGKey(key), 8))

    def jitter(w):
        return w * (1.0 + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype))

    layers = params["layers"]
    return dict(params, final_norm=jitter(params["final_norm"]), layers=dict(
        layers, **{n: jitter(layers[n]) for n in ("attn_norm", "ffn_norm", "q_norm", "k_norm")}))


@pytest.fixture(scope="module")
def model(ref):
    """(cfg, params, tokens [96], the reference's logits at every position)."""
    with jax.default_matmul_precision("highest"):
        cfg = get_config("tiny-sdar")
        params = _unlike_ones(llama.init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (96,), 3, 500), np.int32)
        want = ref.forward(cfg, params, toks)
    return cfg, params, toks, want


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


# through the int8 cache (the kernel's arm reads no other) a pass reads its past
# quantized, and against the float32 reference a position's largest difference
# over its largest |logit| is taken by the MEDIAN over a block's positions (a
# maximum catches the position whose router choice the rounding moved:
# tests/test_joyai.py): 0.005-0.041 read here, keys and values 0.01 and less. The two ARMS on the same int8 cache are held to each
# other at `TOL`, every pass and the commit.
TOL_Q8 = 0.08


def _far(got, want, q8):
    """How far a block's logits [L, V] (or a row's keys [.., n, hd]) lie from
    the reference's: the largest difference through the float cache, the median
    position's relative one through the int8 cache."""
    diff = np.max(np.abs(got - want), axis=-1)
    return float(np.median(diff / np.max(np.abs(want), axis=-1))) if q8 else float(np.max(diff))


def _kv_of(cfg, ck, cv):
    """(K, V) [Lyr, B, Hkv, S, hd] float32 of either cache: the float pair as it
    is, the fused int8 payload times its plain scales."""
    if not isinstance(ck, dict):
        return np.asarray(ck), np.asarray(cv["v"])
    Hkv, _, P = A.fused_q8_heads(ck)
    k, v = A.fused_kv(ck["q"], Hkv, P)
    sc = np.asarray(ck["s"], np.float32)[..., None]
    return np.asarray(k, np.float32) * sc[:, :, :Hkv], np.asarray(v, np.float32) * sc[:, :, Hkv:]


def _filled(cfg, params, toks, n, slots=2, seq=128, quantized=False):
    """A cache whose row 1 holds the first `n` tokens' KV, by the whole-prompt prefill."""
    cache = llama.init_kv_cache(cfg, slots, seq, dtype=jnp.float32, quantized=quantized)
    bucket = -(-n // 32) * 32
    tk = np.zeros((1, bucket), np.int32)
    tk[0, :n] = toks[:n]
    _, ks, vs = llama.llama_prefill(cfg, params, jnp.asarray(tk), jnp.asarray([n]), quant_kv=quantized)
    put = lambda c, r: c.at[:, 1:2, :, :bucket].set(r)  # noqa: E731
    ck = jax.tree.map(put, cache["k"], ks)
    cv = dict(cache["v"], v=jax.tree.map(put, cache["v"]["v"], vs["v"]))
    return ck, cv


def test_the_reference_shares_no_code_with_the_program():
    assert "llm_mcp_tpu" not in reference_source("sdar_moe")  # its docstring names the files


def test_the_presets_are_the_published_structure(model):
    cfg, full = model[0], get_config("sdar-30b-a3b-ep8")
    for c in (cfg, full):
        assert c.block_len == 4 and c.denoise_steps == 4 and c.unmask_threshold == 0.9
        assert c.unmask_rule == "low_confidence_dynamic" and c.qk_norm and not c.qk_norm_whole
        assert c.router_score == "softmax" and c.norm_topk_prob and c.routed_scaling_factor == 1.0
        assert not c.n_shared_experts and not c.first_dense_layers and not c.tie_embeddings
        assert moe.share_form(c) and c.n_experts * (8 if c is full else 4) == c.router_width
        assert not llama.mixed_step_supported(c)
    assert (full.dim, full.n_layers, full.n_heads, full.n_kv_heads, full.vocab_size) == (
        2048, 48, 32, 4, 151_936)
    assert (full.n_experts, full.router_width, full.experts_per_tok, full.moe_ffn_hidden,
            full.ffn_hidden, full.resolved_head_dim) == (16, 128, 8, 768, 6144, 128)
    assert full.rope_theta == 1e6 and full.norm_eps == 1e-6 and full.mask_token_id == 151_669
    # every preset there was yields one token a step
    assert not get_config("tiny-qwen3").block_len and not get_config("tiny-moe").block_len


@pytest.mark.parametrize("held,want", [
    (16, 5_164_972_032),  # this chip's share: ISSUE 59's 10.33 GB at 2 bytes
    (128, 30_532_122_624),  # the uncut model: "30B"
], ids=["ep8_share", "uncut_128"])
def test_param_count_is_exact(held, want):
    cfg = dataclasses.replace(get_config("sdar-30b-a3b-ep8"), n_experts=held)
    D = 2048
    rest = D * 4096 + 2 * D * 512 + 4096 * D + 2 * 128 + 2 * D + D * 128
    assert rest == 19_140_864 and 3 * D * 768 == 4_718_592
    assert cfg.param_count() == 48 * (rest + held * 4_718_592) + 2 * 151_936 * D + D == want


def test_param_count_is_the_trees_size(model):
    cfg, params = model[:2]
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.param_count()


# -- (a) the block mask of the three prompt paths ------------------------------------


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_whole_prompts_of_unlike_lengths_under_the_block_mask(model, ref, attn_impl):
    """Rows of 40, 36 and 8 tokens through one prefill: each row's last logits
    are the reference's at its own last position (UNSHIFTED: that position's own
    token), on the XLA mask and through the prompt kernel's edge blocks."""
    cfg, params, toks, want = model
    lens = [40, 36, 8]
    tk = np.zeros((3, 64), np.int32)
    for i, n in enumerate(lens):
        tk[i, :n] = toks[:n]
    lg, ks, vs = llama.llama_prefill(cfg, params, jnp.asarray(tk), jnp.asarray(lens), attn_impl=attn_impl)
    for i, n in enumerate(lens):
        w = ref.forward(cfg, params, toks[:n], [n - 1])[0]
        assert np.max(np.abs(np.asarray(lg[i]) - w)) < TOL, (attn_impl, n)
    counts = np.asarray(vs["moe"])  # the padding routes nothing
    assert counts.shape == (3, 5) and (counts[:, 0] == sum(lens)).all()


def test_the_causal_mask_is_another_program(model, ref):
    """Inside a block a query sees LATER keys: under the causal mask the same
    rows read otherwise, by far more than rounding."""
    cfg, params, toks, want = model
    ref.LOWER = "causal"
    retrace(ref)
    try:
        causal = ref.forward(cfg, params, toks[:40])
    finally:
        ref.LOWER = None
        retrace(ref)
    assert np.max(np.abs(causal - want[:40])) > 0.05


def test_bucketed_chunks_under_the_block_mask(model):
    """A prompt in chunks of 32, a ragged last one: logits at every position and
    the cache the chunks leave are the reference's (the past whole, the chunk's
    own segment by block, a padding key hidden from its block's queries)."""
    cfg, params, toks, want = model
    cache = llama.init_kv_cache(cfg, 2, 128, dtype=jnp.float32)
    ck, cv = cache["k"], cache["v"]
    for start, n in ((0, 32), (32, 32), (64, 20)):
        tk = np.zeros((1, 32), np.int32)
        tk[0, :n] = toks[start : start + n]
        lg, ck, cv = llama.llama_prefill_chunk_batch(
            cfg, params, ck, cv, jnp.asarray(tk), jnp.asarray([1]), jnp.asarray([start]),
            jnp.asarray([n]), all_logits=True)
        assert np.max(np.abs(np.asarray(lg[0, :n]) - want[start : start + n])) < TOL, start
    assert (np.asarray(cv["moe"])[1, :, 0] == 84).all() and (np.asarray(cv["moe"])[1, :, 4] == 3).all()
    whole_k, whole_v = _filled(cfg, params, toks, 84)
    assert np.max(np.abs(np.asarray(ck[:, 1, :, :84] - whole_k[:, 1, :, :84]))) < TOL
    assert np.max(np.abs(np.asarray(cv["v"][:, 1, :, :84] - whole_v["v"][:, 1, :, :84]))) < TOL


def test_packed_prompts_under_the_block_mask(model, ref):
    """`packed_prompt_attn` (the mixed step's prompt half) over two prompts
    packed back to back: each token sees its own prompt's tokens of its own and
    earlier blocks, by the reference's mask on the same q, k, v."""
    cfg, _, _, _ = model
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    lens, T = (10, 7), 24
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (T, H, hd), jnp.float32)
    k = jax.random.normal(keys[1], (T, Hkv, hd), jnp.float32)
    v = jax.random.normal(keys[2], (T, Hkv, hd), jnp.float32)
    rowids = np.full(T, 2, np.int32)
    positions = np.full(T, 128, np.int32)
    at = 0
    for r, n in enumerate(lens):
        rowids[at : at + n], positions[at : at + n] = r, np.arange(n)
        at += n
    got = np.asarray(llama.packed_prompt_attn(
        cfg, q, k, v, jnp.asarray(rowids), jnp.asarray(positions))).reshape(T, H, hd)
    at = 0
    for n in lens:
        seen = np.asarray(ref.seen(cfg, n))
        for h in range(H):
            s = np.asarray(q[at : at + n, h] @ k[at : at + n, h // (H // Hkv)].T) * hd**-0.5
            p = np.exp(np.where(seen, s, -np.inf) - np.max(np.where(seen, s, -np.inf), -1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ np.asarray(v[at : at + n, h // (H // Hkv)])
            assert np.max(np.abs(got[at : at + n, h] - want)) < TOL
        at += n


# -- (b) every pass of a multi-block reply ----------------------------------------------


def _by_hand(cfg, params, ck, cv, first, start, key, temp, n_blocks, allowed=None, threshold=None,
             attn_impl="xla"):
    """A reply of `n_blocks` blocks through the program's own passes, one call a
    pass as `engine.block_round_fn` makes them (row 1 of a batch of 2 in order,
    row 0 parked): ([per block: [per pass: (block before, logits, block after)]],
    the final blocks, ck, cv). The round's key splits once a pass. On the
    kernel's arm every pass is also held to the XLA arm's logits on the same
    cache and block."""
    if threshold is not None:
        cfg = dataclasses.replace(cfg, unmask_threshold=threshold)
    S = ck.shape[3] if not isinstance(ck, dict) else ck["q"].shape[3]
    live = jnp.asarray([False, True])
    t, k, p = (jnp.asarray([0.0, temp], jnp.float32), jnp.zeros((2,), jnp.int32),
               jnp.ones((2,), jnp.float32))
    blocks, trail = [], []
    block = np.asarray(first, np.int32)
    for b in range(n_blocks):
        starts = jnp.asarray([S, start + b * L])
        rng = jax.random.fold_in(key, b)
        passes = []
        while (block == cfg.mask_token_id).any():
            rng, sub = jax.random.split(rng)
            both = jnp.asarray(np.stack([block, block]))
            if attn_impl == "pallas":
                other, _, _ = block_pass(
                    cfg, params, ck, cv, both, None, starts, live, commit=False, attn_impl="xla")
            new, cv, lg = block_denoise(
                cfg, params, ck, cv, both, None, starts, live, sub, t, k, p, allowed=allowed,
                attn_impl=attn_impl)
            if attn_impl == "pallas":
                assert np.max(np.abs(np.asarray(lg[1] - other[1]))) < TOL
            passes.append((block, np.asarray(lg[1]), np.asarray(new[1])))
            block = np.asarray(new[1])
        final = jnp.asarray(np.stack([block, block]))
        # (the commits dispatched bare, three a reply: compiled whole, an int8 entry on a
        # rounding edge falls the other way on one arm and not on the other)
        if attn_impl == "pallas":  # the commit's reads and its writes, arm against arm
            _, ck_x, _ = llama.block_pass(
                cfg, params, ck, cv, final, None, starts, live, commit=True, attn_impl="xla")
        _, ck, cv = llama.block_pass(
            cfg, params, ck, cv, final, None, starts, live, commit=True, attn_impl=attn_impl)
        if attn_impl == "pallas":
            assert all(np.max(np.abs(a - b)) < TOL for a, b in zip(
                _kv_of(cfg, ck, cv), _kv_of(cfg, ck_x, cv)))
        trail.append(passes)
        blocks.append(block)
        block = np.full(L, cfg.mask_token_id, np.int32)
    return trail, blocks, ck, cv


@pytest.mark.parametrize("temp,threshold,n_passes", [
    (0.7, None, "masks"),  # seeded weights: no probability passes 0.9, one position a pass
    (0.0, None, "one"),  # greedy is top-1 at probability 1: the whole block in its first pass
    (0.7, 0.005, "between"),  # a threshold between: some passes fill several positions
], ids=["sampled_four_passes", "greedy_one_pass", "threshold_between"])
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_every_pass_and_the_committed_cache_of_a_reply(model, ref, temp, threshold, n_passes, attn_impl):
    """A prompt of 34 tokens (8 whole blocks prefilled, 2 tokens fixed at the
    first block's front), then three blocks. Every denoising pass's logits are
    the reference's full forward of [everything committed ; the block as the
    pass saw it] at the block's positions; the order of unmasking is the
    published rule's on the pass's own samples; the committed keys and values
    are the whole-prompt prefill's of the final sequence. On the XLA arm through
    the float cache; on the kernel's (`block_attend_q8`, interpreted) through
    the fused int8 cache of 96 positions, three blocks of 32 a row, so that the
    second and third blocks of the reply read two blocks of their past."""
    cfg, params, toks, _ = model
    P, P0 = 34, 32
    q8 = attn_impl == "pallas"
    tol = TOL_Q8 if q8 else TOL
    ck, cv = _filled(cfg, params, toks, P0, seq=96 if q8 else 128, quantized=q8)
    assert llama.block_attn_arm(cfg, ck, attn_impl)[0] == attn_impl
    first = np.full(L, cfg.mask_token_id, np.int32)
    first[: P - P0] = toks[P0:P]
    trail, blocks, ck, cv = _by_hand(
        cfg, params, ck, cv, first, P0, jax.random.PRNGKey(7), temp, 3, threshold=threshold,
        attn_impl=attn_impl)
    seq = list(toks[:P0])
    took = []  # (masks a block started with, passes it took)
    for passes, final in zip(trail, blocks):
        took.append((int((passes[0][0] == cfg.mask_token_id).sum()), len(passes)))
        for before, lg, after in passes:
            want = ref.forward(cfg, params, np.asarray(seq + list(before), np.int32),
                               np.arange(len(seq), len(seq) + L))
            assert _far(lg, want, q8) < tol
            # the rule, on the samples the pass must have drawn: what it filled
            # it filled with a token, and only where the mask stood
            filled = before != after
            assert (before[filled] == cfg.mask_token_id).all() and filled.any()
            if temp == 0.0:  # (through the int8 cache a near-tie may fall the other way)
                assert (after == np.where(
                    before == cfg.mask_token_id, np.argmax(lg if q8 else want, -1), before)).all()
        assert not (final == cfg.mask_token_id).any()
        seq += list(final)
    assert took[0][0] == 2 and took[1][0] == took[2][0] == 4
    if n_passes == "masks":
        assert all(n == m for m, n in took), took  # 2, 4, 4
    elif n_passes == "one":
        assert all(n == 1 for _, n in took), took
    else:
        assert all(n <= m for m, n in took) and any(1 < n < m for m, n in took), took
    assert list(blocks[0][: P - P0]) == list(toks[P0:P])  # the prompt's remainder stood fixed
    # the committed cache is the prefill's of the final sequence, under the block mask
    n = len(seq)
    want_k, want_v = _filled(cfg, params, np.asarray(seq, np.int32), n)
    got_k, got_v = _kv_of(cfg, ck, cv)
    assert _far(got_k[:, 1, :, :n], np.asarray(want_k[:, 1, :, :n]), q8) < tol
    assert _far(got_v[:, 1, :, :n], np.asarray(want_v["v"][:, 1, :, :n]), q8) < tol
    assert np.max(np.abs(got_k[:, 0])) == 0.0  # the parked row was not written
    # the expert counts of every pass, denoising and commit, under the decode phase
    calls = sum(len(p) for p in trail) + 3
    assert (np.asarray(cv["moe"])[0, :, 4] == calls).all() and (np.asarray(cv["moe"])[0, :, 0] == L * calls).all()


def test_the_reference_sampler_fills_a_block_as_the_program_does(model, ref):
    """`generate_block` under the program's own draws (tapped: the program's
    sampler on the reference's logits with the pass's key) leaves the block the
    program leaves, pass by pass."""
    cfg, params, toks, _ = model
    ck, cv = _filled(cfg, params, toks, 32)
    first = np.full(L, cfg.mask_token_id, np.int32)
    first[:1] = toks[32:33]
    key = jax.random.PRNGKey(11)
    trail, blocks, _, _ = _by_hand(cfg, params, ck, cv, first, 32, key, 0.7, 1)
    subs, rng = [], jax.random.fold_in(key, 0)
    for _ in trail[0]:
        rng, sub = jax.random.split(rng)
        subs.append(sub)

    def draws(i, lg):  # the round's batch: a parked row in front, this row behind it
        both = jnp.concatenate([jnp.asarray(lg), jnp.asarray(lg)])
        x0, p = sample_tokens_p(
            both, subs[i], jnp.repeat(jnp.asarray([0.0, 0.7]), L), jnp.zeros((2 * L,), jnp.int32),
            jnp.ones((2 * L,)), active=jnp.repeat(jnp.asarray([False, True]), L))
        return np.asarray(x0[L:]), np.asarray(p[L:])

    got = ref.generate_block(cfg, params, toks[:32], first, draws)
    assert len(got) == len(trail[0]) == 3  # one position was the prompt's
    for mine, (before, _, after) in zip(got, trail[0]):
        assert (mine["block"] == before).all() and (mine["after"] == after).all()


def test_the_sampler_says_how_sure_it_was():
    lg = jax.random.normal(jax.random.PRNGKey(0), (6, 300)) * 3
    key = jax.random.PRNGKey(1)
    for t, tk, tp in ((0.0, 0, 1.0), (0.7, 0, 1.0), (0.7, 5, 0.9)):
        T, K, Pp = jnp.full((6,), t), jnp.full((6,), tk, jnp.int32), jnp.full((6,), tp)
        tok, p = sample_tokens_p(lg, key, T, K, Pp)
        assert (tok == sample_tokens(lg, key, T, K, Pp)).all()  # the same draws
        if t == 0.0:
            assert (p == 1.0).all()
        elif not tk:
            soft = jax.nn.softmax(lg / t, axis=-1)
            assert np.allclose(p, soft[jnp.arange(6), tok], atol=1e-6)
        else:  # after the filters: at least the unfiltered probability
            soft = jax.nn.softmax(lg / t, axis=-1)
            assert (p >= soft[jnp.arange(6), tok] - 1e-6).all() and (p <= 1.0).all()


@pytest.mark.parametrize("rule", ["low_confidence_dynamic", "low_confidence_static"])
def test_the_unmask_rule_against_the_reference(model, ref, rule):
    cfg = dataclasses.replace(model[0], unmask_rule=rule, unmask_threshold=0.5)
    M = cfg.mask_token_id
    rng = np.random.default_rng(0)
    for _ in range(40):
        block = np.where(rng.random(L) < 0.6, M, rng.integers(3, 500, L)).astype(np.int32)
        x0, p = rng.integers(3, 500, L).astype(np.int32), rng.random(L).astype(np.float32)
        got = np.asarray(llama.block_unmask(cfg, jnp.asarray(block[None]), jnp.asarray(x0[None]),
                                            jnp.asarray(p[None])))[0]
        assert (got == ref.unmask(cfg, block, x0, p)).all(), (block, x0, p)


# -- (d) the shares of the expert layer ----------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(model, ref):
    """Four shares of four experts (the published eight of sixteen at toy size):
    each member's part by the program's `moe_share_ffn` (the router's columns
    rolled so that its experts are the held ones) against the reference's share,
    and their sum against the reference's uncut layer over all 16 experts."""
    cfg = model[0]
    uncut = dataclasses.replace(cfg, n_experts=16, n_router_experts=0)
    whole = moe.init_moe_layer_params(uncut, jax.random.PRNGKey(3), jnp.float32, 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, cfg.dim), jnp.float32)
    want = ref._experts(uncut, whole, jnp.int32(0), x)
    lp = {n: v[0] for n, v in whole.items()}
    routed, pairs = jnp.zeros_like(x), 0
    for member in range(4):
        order = np.roll(np.arange(16), -4 * member)
        mine = dict(lp, router=lp["router"][:, order],
                    **{n: lp[n][4 * member : 4 * member + 4] for n in ("w1e", "w3e", "w2e")})
        y, counts = moe.moe_share_ffn(cfg, mine, x)
        part = ref._experts(cfg, {n: v[None] for n, v in mine.items()}, jnp.int32(0), x)
        assert np.max(np.abs(np.asarray(y - part))) < TOL, member
        routed, pairs = routed + y, pairs + int(counts[1])
    assert pairs == 40 * cfg.experts_per_tok  # every pair landed on exactly one member
    assert np.max(np.abs(np.asarray(routed - want))) < TOL


def test_check_covers_this_family_alone(ref):
    ref.check(get_config("tiny-sdar"))
    ref.check(get_config("sdar-30b-a3b-ep8"))
    for name in ("tiny-qwen3", "tiny-moe", "tiny-joyai", "tiny-kexaone"):
        with pytest.raises(NotImplementedError):
            ref.check(get_config(name))
    for field, value in (("block_len", 0), ("router_score", "sigmoid"), ("n_shared_experts", 1),
                         ("sliding_window", 128), ("qk_norm", False), ("tie_embeddings", True)):
        with pytest.raises(NotImplementedError):
            ref.check(dataclasses.replace(get_config("tiny-sdar"), **{field: value}))


def test_the_controls_move_what_the_request_is_held_to(model, ref):
    """`logits` as `correctness.hold_to_reference` calls it, on a reply of four
    blocks behind a prompt of 34: the reference against itself is exact, and a
    cache that was never committed or the causal mask moves rows by far more
    than rounding (float8 too)."""
    cfg, params, toks, _ = model
    P, n = 34, 14
    seq = np.zeros(128, np.int32)
    seq[: P + n - 1] = toks[: P + n - 1]
    rows, cols = np.arange(P - 1, P - 1 + n), np.arange(3, 259)
    base = ref.logits(cfg, params, seq, rows, cols)
    assert base.shape == (n, 256) and np.isfinite(base).all()
    # row k is the forward of [committed ; remainder ; masks] at its own position
    first = np.concatenate([toks[:P], np.full(2, cfg.mask_token_id, np.int32)])
    assert np.max(np.abs(base[:2] - ref.forward(cfg, params, first, [34, 35], cols))) < TOL
    for control in ("no_commit", "causal", "fp8"):
        ref.LOWER = control
        retrace(ref)
        try:
            moved = ref.logits(cfg, params, seq, rows, cols)
        finally:
            ref.LOWER = None
            retrace(ref)
        far = np.max(np.abs(moved - base), axis=-1) / np.max(np.abs(base), axis=-1)
        assert np.median(far[2:]) > 0.02, (control, far)  # the first block sees the prompt alone


@pytest.mark.parametrize("seed", [3200006000, 3200006001])
def test_the_harness_comparison_sees_a_lost_commit_and_a_causal_mask(ref, seed):
    """The configuration's own reference request (a short prompt, a long reply:
    13 tokens as the chat endpoint renders them, 64 served) through
    `correctness.hold_to_reference`, as run.py makes the comparison that decides
    `correct`, on the tiny preset: the engine's reply is within the module's
    tolerance, and the SAME reply held to the reference with the commit pass left
    out, or with the causal mask inside a block, is refused. Behind the first
    request of this configuration (200 bytes, 16 tokens) both passed."""
    import json

    from benchmark import correctness, trafficgen
    from llm_mcp_tpu.utils.tokens import messages_to_prompt

    config = json.load(open(os.path.join(ROOT, "benchmark", "configs", "sdar-30b-a3b-ep8-bf16.json")))
    n_bytes, n_tokens = correctness.reference_request(config, 1024)
    assert (n_bytes, n_tokens) == (6, 64)
    eng = _engine(max_seq_len=256).start()
    eng.tokenizer.eos_id = eng.cfg.mask_token_id  # an id the sampler cannot emit: all 64 are served
    try:
        prompt = messages_to_prompt([{"role": "user", "content": trafficgen.text(n_bytes, seed, "ref")}])
        _, toks, _, ids = _tapped(eng, lambda: eng.generate(prompt, max_tokens=n_tokens, temperature=0.0))
        assert len(ids) == 13 and len(toks) == 64
        notes = correctness.hold_to_reference(ref, eng, ids, toks)
        assert notes["worst_regret_rel"] < ref.SERVED_TOL_REL / 2 and notes["tolerance"] == ref.SERVED_TOL_REL
        for control in ("no_commit", "causal"):
            ref.LOWER = control
            retrace(ref)
            try:
                with pytest.raises(AssertionError, match="under the reference's choice"):
                    correctness.hold_to_reference(ref, eng, ids, toks)
            finally:
                ref.LOWER = None
                retrace(ref)
    finally:
        eng.shutdown()


# -- the engine: a round is a block ---------------------------------------------------------


def _engine(**kw):
    from llm_mcp_tpu.executor import GenerationEngine

    kw = dict(dict(max_slots=2, max_seq_len=128, dtype=jnp.float32, prefill_chunk=32,
                   kv_quant="int8"), **kw)
    return GenerationEngine("tiny-sdar", **kw)


@pytest.fixture(scope="module")
def engine():
    eng = _engine().start()
    yield eng
    eng.shutdown()


@pytest.fixture()
def no_eos(engine, monkeypatch):
    """Seeded weights sample the EOS now and then: a test that counts tokens
    takes an id the sampler cannot emit for it."""
    monkeypatch.setattr(engine.tokenizer, "eos_id", engine.cfg.mask_token_id)


def _tapped(eng, fn):
    """(what `fn()` returns, the tokens the engine emitted for it, its prompt ids)."""
    seen = []
    emit = eng._process_token

    def tap(slot, tok, pos):
        seen.append((list(slot.req.prompt_ids), int(tok), int(pos)))
        return emit(slot, tok, pos)

    eng._process_token = tap
    try:
        out = fn()
    finally:
        del eng._process_token
    return out, [t for _, t, _ in seen], [p for _, _, p in seen], (seen[0][0] if seen else [])


def test_the_layout_and_what_a_block_configuration_runs_without(engine):
    from llm_mcp_tpu.executor.memory import BLOCK_OFF, COUNTED_OFF

    lay = engine._layout
    assert not lay.latent and lay.counted and lay.wrapped and lay.fused and not lay.slot_member
    assert dict(lay.without) == BLOCK_OFF and set(COUNTED_OFF) < set(BLOCK_OFF)
    assert set(engine._cv) == {"v", "moe"} and engine._cv["v"] == {}
    assert engine._block == engine.decode_chunk == 4 and engine._d_last_tok.shape == (2, 4)
    assert not engine.spec_enabled and engine._verify_fn is None and not engine.ragged_prefill
    assert not engine.constrain_enabled and engine._constrain is None
    assert engine._prefix_budget == 0 and engine._pool is None and engine._migrate_in is None
    assert engine._ride_off() == "other" and engine._attn_stream is None
    assert engine._round_prog(None, None) == "block"
    with pytest.raises(ValueError, match="decode_chunk"):
        _engine(decode_chunk=8)


@pytest.mark.parametrize("rem", [0, 1, 2, 3])
def test_the_prompts_remainder_stands_at_the_front_of_the_first_block(engine, ref, no_eos, rem):
    """Prompts of P mod 4 = 0..3 (the byte tokenizer: a BOS and a byte a token),
    greedy, 9 tokens: the first round delivers 4 - P mod 4 tokens, every served
    token is the reference's own choice among the ids the engine may emit at its
    OWN position of [everything before the block ; the remainder ; masks] (one
    denoising pass a greedy block), and `usage` counts what was delivered."""
    text = "x" * (11 + rem)  # 12 + rem tokens with the BOS
    before = engine.perf_stats()["blocks"]
    out, toks, pos, ids = _tapped(engine, lambda: engine.generate(text, max_tokens=9, temperature=0.0))
    P = len(ids)
    assert P % L == rem and out["usage"] == {
        "prompt_tokens": P, "completion_tokens": 9, "total_tokens": P + 9}
    assert out["finish_reason"] == "length" and len(toks) == 9 and pos == list(range(P, P + 9))
    after = engine.perf_stats()["blocks"]
    rounds = -(-(rem + 9) // L)
    assert after["rounds"] - before["rounds"] == rounds
    assert after["remainder_tokens"] - before["remainder_tokens"] == rem
    assert after["unmasked"] - before["unmasked"] == rounds * L - rem
    assert after["delivered"] - before["delivered"] == 9
    assert after["passes"] - before["passes"] == after["commits"] - before["commits"] == rounds
    allowed = np.flatnonzero(np.asarray(engine._allowed_mask))
    seq = np.asarray((ids + toks[:-1] + [0] * 128)[:128], np.int32)
    want = ref.logits(engine.cfg, engine.params, seq, np.arange(P - 1, P - 1 + 9), allowed)
    for k, tok in enumerate(toks):  # through the int8 cache: the choice, or a near tie
        col = int(np.flatnonzero(allowed == tok)[0])
        assert np.max(want[k]) - want[k, col] < 0.05 * np.max(np.abs(want[k])), (k, tok)


@pytest.mark.parametrize("max_tokens", [5, 7])
def test_max_tokens_that_is_no_multiple_of_the_block(engine, no_eos, max_tokens):
    out, toks, _, ids = _tapped(
        engine, lambda: engine.generate("y" * 15, max_tokens=max_tokens, temperature=0.7))
    assert len(ids) % L == 0 and len(toks) == max_tokens
    assert out["usage"]["completion_tokens"] == max_tokens and out["finish_reason"] == "length"


def test_a_reply_ends_at_the_first_eos_of_a_committed_block(engine, monkeypatch):
    """A token inside a block is made the EOS: the tokens before it are
    delivered, what follows it in the block is dropped, and the reply ends `stop`."""
    prompt = "the quick brown fox"
    _, toks0, _, ids = _tapped(engine, lambda: engine.generate(prompt, max_tokens=12, temperature=0.0))
    # the first served token, not at a block's end, that no earlier one equals
    j = next(k for k in range(1, 12) if toks0[k] not in toks0[:k] and (len(ids) + k + 1) % L)
    eos = toks0[j]
    monkeypatch.setattr(engine.tokenizer, "eos_id", eos)
    try:
        out, toks, _, _ = _tapped(engine, lambda: engine.generate(prompt, max_tokens=12, temperature=0.0))
    finally:
        monkeypatch.undo()
    assert toks == toks0[: j + 1] and out["finish_reason"] == "stop"
    assert out["usage"]["completion_tokens"] == j  # the EOS itself is no completion token


def test_the_last_block_at_max_seq_len():
    """A sequence whose next block would pass the cache's end finishes `length`
    with its last whole block delivered: 64 positions, a prompt of 52."""
    eng = _engine(max_seq_len=64).start()
    eng.tokenizer.eos_id = eng.cfg.mask_token_id  # an id the sampler cannot emit
    try:
        out, toks, pos, ids = _tapped(
            eng, lambda: eng.generate("w" * 51, max_tokens=40, temperature=0.0))
        assert len(ids) == 52 and out["finish_reason"] == "length"
        assert pos[-1] == 63 and len(toks) == 12  # blocks at 52, 56, 60: the cache is full
        # and a prompt too long for a block behind it is cut to leave room for one
        out, toks, pos, ids2 = _tapped(
            eng, lambda: eng.generate("w" * 80, max_tokens=40, temperature=0.0))
        assert out["usage"]["prompt_tokens"] == 60 and len(toks) == 4 and pos[-1] == 63
    finally:
        eng.shutdown()


def test_a_long_prompt_takes_chunks_between_block_rounds(engine, no_eos):
    """A prompt over `prefill_chunk` prefills its whole blocks chunk by chunk,
    its remainder starts its first block, and a chunk group beside decoding rows
    runs as a program of its own (no round carries it: counted)."""
    before = engine.perf_stats()["blocks"]
    out, toks, pos, ids = _tapped(
        engine, lambda: engine.generate("v" * 69, max_tokens=6, temperature=0.0))
    P = len(ids)
    assert P == 70 and pos == list(range(P, P + 6)) and out["usage"]["completion_tokens"] == 6
    after = engine.perf_stats()["blocks"]
    assert after["remainder_tokens"] - before["remainder_tokens"] == 2
    assert after["off"]["ragged_prefill"] > before["off"]["ragged_prefill"]


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_engines_round_is_the_passes_driven_by_hand(ref, monkeypatch, attn_impl):
    """ONE dispatch of `block_round_fn` against `block_denoise` / `block_pass`
    called pass by pass with the round's own keys: the same tokens, the same
    passes a row, the same cache; and the round's successor is dispatched on the
    device's own start buffer before this one is fetched. On the XLA arm with
    float caches (what it serves is the reference's own choice), on the
    kernel's with the fused int8 cache, which the engine's round reads through
    `block_attend_q8` as the passes by hand do."""
    monkeypatch.setenv("LLM_MCP_TPU_ATTN", attn_impl)
    q8 = attn_impl == "pallas"
    eng = _engine(kv_quant="int8" if q8 else "")
    try:
        cfg, params = eng.cfg, eng.params
        assert eng.decode_impl == attn_impl == eng.perf_stats()["blocks"]["attn"]["arm"]
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (40,), 3, 250), np.int32)
        ck, cv = _filled(cfg, params, toks, 32, slots=2, seq=128, quantized=q8)
        eng._ck, eng._cv = ck, cv
        first = np.full((2, L), cfg.mask_token_id, np.int32)
        first[1, :2] = toks[32:34]
        eng._d_last_tok = jnp.asarray(first)
        eng._d_temp = jnp.asarray([0.0, 0.7], jnp.float32)
        packed = np.asarray([128, 32, 5], np.int32)  # row 0 parked, row 1 at 32, counter 5
        key = jax.random.fold_in(eng._base_key, 5)
        out, ck1, cv1, d_last = eng._decode_fn(
            params, ck, cv, packed, eng._d_temp, eng._d_topk, eng._d_topp, eng._d_last_tok,
            compact=False)
        assert not isinstance(d_last, np.ndarray) and (np.asarray(d_last)[1] == cfg.mask_token_id).all()
        assert (np.asarray(d_last)[0] == first[0]).all()  # a parked row's start stands
        out = np.asarray(out)
        ck0, cv0 = _filled(cfg, params, toks, 32, slots=2, seq=128, quantized=q8)

        def by_hand():
            rng, block, n = key, first[1].copy(), 0
            cvh = cv0
            live, starts = jnp.asarray([False, True]), jnp.asarray([128, 32])
            while (block == cfg.mask_token_id).any():
                rng, sub = jax.random.split(rng)
                both = jnp.asarray(np.stack([first[0], block]))
                new, cvh, _ = block_denoise(
                    cfg, params, ck0, cvh, both, None, starts, live, sub,
                    jnp.asarray([0.0, 0.7]), jnp.zeros((2,), jnp.int32), jnp.ones((2,)),
                    allowed=eng._allowed_mask, attn_impl=attn_impl)
                block, n = np.asarray(new[1]), n + 1
            _, ckh, cvh = block_pass(
                cfg, params, ck0, cvh, jnp.asarray(np.stack([first[0], block])), None, starts, live,
                commit=True, attn_impl=attn_impl)
            return block, n, ckh, cvh

        block, n, ckh, cvh = by_hand()
        assert list(out[:L, 1]) == list(block) and out[L, 1] == n == 2 and out[L, 0] == 0
        assert all(np.max(np.abs(a - b)) < TOL for a, b in zip(_kv_of(cfg, ck1, cv1), _kv_of(cfg, ckh, cvh)))
        assert (np.asarray(cv1["moe"]) == np.asarray(cvh["moe"])).all()
        assert (out[L + 1 :].reshape(-1)[:30].reshape(2, 3, 5) == np.asarray(cv1["moe"])).all()
    finally:
        eng.shutdown()


def test_a_block_pass_on_a_float_cache_takes_the_xla_arm_and_says_so_once(model, monkeypatch):
    """The kernel reads the fused int8 cache alone: asked for on a float cache
    (and on a compiled path: interpreted runs take exact math by design and
    note nothing), a pass takes the bucketed chunk's XLA attention, gives the
    logits it gives unasked, and notes ONE fall with the reason."""
    cfg, params, toks, _ = model
    ck, cv = _filled(cfg, params, toks, 32)
    assert llama.block_attn_arm(cfg, ck, "pallas") == ("xla", "no int8 cache")
    assert llama.block_attn_arm(cfg, ck, "xla") == ("xla", "attn_impl=xla")
    q8 = _filled(cfg, params, toks, 32, quantized=True)[0]
    assert llama.block_attn_arm(cfg, q8, "pallas") == ("pallas", "")
    assert llama.block_attn_arm(dataclasses.replace(cfg, attn_softcap=30.0), q8, "pallas")[0] == "xla"
    block = jnp.full((2, L), cfg.mask_token_id, jnp.int32)
    args = (cfg, params, ck, cv, block, None, jnp.asarray([128, 32]), jnp.asarray([False, True]))
    want, _, _ = llama.block_pass(*args, commit=False)
    monkeypatch.setattr(llama, "_interpret", lambda: False)
    monkeypatch.setattr(A, "reference_falls", {})
    got, _, _ = llama.block_pass(*args, commit=False, attn_impl="pallas")
    assert A.reference_falls == {"block_attn_q8": 1}
    assert np.max(np.abs(np.asarray(got[1] - want[1]))) == 0.0


def test_the_blocks_book_names_the_arm_and_what_it_streams(engine, no_eos):
    """`perf_stats()["blocks"]["attn"]`: the arm in force, and over a reply's
    rounds the positions the passes' attention fetched against the positions
    live, both growing every round, live never the larger."""
    arm = llama.block_attn_arm(engine.cfg, engine._ck, engine.decode_impl)[0]
    seen = [engine.perf_stats()["blocks"]]
    for _ in range(2):
        engine.generate("a prompt of some length, for a past", max_tokens=8, temperature=0.0)
        seen.append(engine.perf_stats()["blocks"])
    for before, after in zip(seen, seen[1:]):
        a, b = before["attn"], after["attn"]
        assert b["arm"] == arm and b["block_tokens"] == (128 if arm == "pallas" else 0)
        assert b["passes"] - a["passes"] == (after["passes"] - before["passes"]) + (
            after["commits"] - before["commits"]) > 0
        assert b["tokens_live"] > a["tokens_live"] and b["tokens_streamed"] > a["tokens_streamed"]
        assert b["tokens_live"] <= b["tokens_streamed"]
        assert b["live_over_streamed"] == round(b["tokens_live"] / b["tokens_streamed"], 4)


def _covering_tokenizer(cfg):
    """A tokenizer whose ids cover the whole table, the mask's among them."""
    from llm_mcp_tpu.executor.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    tok.vocab_size = cfg.vocab_size
    return tok


@pytest.mark.parametrize("covering", [False, True])
def test_the_sampler_cannot_emit_the_mask_whatever_the_tokenizer_covers(covering):
    """A block is done when no position holds the mask, so the mask's id is out
    of the sampler's ids from the configuration, not by the accident that the
    byte tokenizer ends below it; and a head that prefers the mask at every
    position still fills its blocks, with the sampler's next choice."""
    cfg = get_config("tiny-sdar")
    eng = _engine(kv_quant="", **({"tokenizer": _covering_tokenizer(cfg)} if covering else {}))
    try:
        allowed = np.asarray(eng._allowed_mask)
        assert not allowed[cfg.mask_token_id] and allowed[3:259].all()
        assert allowed[259 : cfg.mask_token_id].all() == covering
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (36,), 3, 250), np.int32)
        ck, cv = _filled(cfg, eng.params, toks, 32, slots=2, seq=128)
        first = jnp.full((2, L), cfg.mask_token_id, jnp.int32)

        def a_pass():
            return np.asarray(llama.block_pass(
                cfg, eng.params, ck, cv, first, None, jnp.asarray([32, 128]),
                jnp.asarray([True, False]), commit=False)[0][0], np.float64)  # row 0: [L, V]

        # the mask's column of the head along what the head reads at the block's
        # positions (recovered from the logits: head.T n = logits), 50 times over
        head = np.asarray(eng.params["lm_head"], np.float64)
        reads = np.linalg.lstsq(head.T, a_pass().T, rcond=None)[0].mean(axis=1)
        eng.params["lm_head"] = eng.params["lm_head"].at[:, cfg.mask_token_id].set(
            jnp.asarray(50.0 * reads / np.linalg.norm(reads), eng.params["lm_head"].dtype))
        logits = a_pass()
        assert (np.argmax(logits, axis=-1) == cfg.mask_token_id).all()
        out, *_ = eng._decode_fn(
            eng.params, ck, cv, np.asarray([32, 128, 9], np.int32), eng._d_temp, eng._d_topk,
            eng._d_topp, first, compact=False)
        out = np.asarray(out)
        assert (out[:L, 0] != cfg.mask_token_id).all() and out[L, 0] == 1  # greedy: one pass
        assert allowed[out[:L, 0]].all()
    finally:
        eng.shutdown()


def test_a_block_that_keeps_its_masks_ends_the_round_at_the_steps(monkeypatch):
    """The device's loop over denoising passes is bounded by `denoise_steps`: a
    pass that fills nothing (a fault: the unmask rule fills a position a pass at
    the least) ends the round after that many and does not spin on the chip."""
    from llm_mcp_tpu.executor import engine as engine_mod

    calls = []

    def fills_nothing(cfg, params, ck, cv, tokens, *a, **k):
        calls.append(1)
        return tokens, cv, None

    monkeypatch.setattr(engine_mod, "block_denoise", fills_nothing)
    eng = _engine(kv_quant="")
    try:
        cfg = eng.cfg
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (36,), 3, 250), np.int32)
        ck, cv = _filled(cfg, eng.params, toks, 32, slots=2, seq=128)
        first = jnp.full((2, L), cfg.mask_token_id, jnp.int32)
        out, *_ = eng._decode_fn(
            eng.params, ck, cv, np.asarray([32, 128, 1], np.int32), eng._d_temp, eng._d_topk,
            eng._d_topp, first, compact=False)
        out = np.asarray(out)
        assert len(calls) == 1  # traced once, as the loop's body
        assert out[L, 0] == cfg.denoise_steps == 4 and out[L, 1] == 0  # the live row's passes; a parked row's
        assert (out[:L, 0] == cfg.mask_token_id).all()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("field,value", [
    ("denoise_steps", 0), ("denoise_steps", 3), ("mask_token_id", 512), ("mask_token_id", -1),
    ("unmask_rule", "sequential")])
def test_a_block_configuration_the_round_cannot_run_is_refused_at_boot(monkeypatch, field, value):
    from llm_mcp_tpu.executor import engine as engine_mod

    cfg = dataclasses.replace(get_config("tiny-sdar"), **{field: value})
    monkeypatch.setattr(engine_mod, "resolve_config", lambda *a, **k: cfg)
    with pytest.raises(ValueError, match="unmask_rule"):
        _engine()


def test_the_next_round_is_dispatched_before_the_last_is_fetched(monkeypatch):
    """At pipeline depth 2 a second block round goes out while the first is
    unfetched: its blocks come from the device's own start buffer."""
    monkeypatch.setenv("TPU_PIPELINE_DEPTH", "2")
    eng = _engine().start()
    eng.tokenizer.eos_id = eng.cfg.mask_token_id  # an id the sampler cannot emit
    try:
        fetched, order = [], []
        real_fetch, real_disp = eng._complete_round, eng._dispatch_decode

        def disp(active, *a):
            d = real_disp(active, *a)
            order.append(("dispatch", d.rid, eng._rid_fetched))
            return d

        def fetch(d):
            order.append(("fetch", d.rid, eng._rid_dispatched))
            return real_fetch(d)

        eng._dispatch_decode, eng._complete_round = disp, fetch
        out = eng.generate("u" * 15, max_tokens=24, temperature=0.7)
        assert out["usage"]["completion_tokens"] == 24
        ahead = [rid - got for kind, rid, got in order if kind == "dispatch"]
        assert max(ahead) == 2, order  # round n + 1 dispatched with round n unfetched
        assert eng.perf_stats()["rounds"]["by_program"]["block"]["rounds"] >= 6
        del fetched
    finally:
        eng.shutdown()


def test_each_feature_that_is_off_counts_and_is_not_run(engine, no_eos, monkeypatch):
    from llm_mcp_tpu.executor.engine import GenRequest

    off0 = dict(engine.perf_stats()["blocks"]["off"])
    for name in ("_spec_round", "_stage_ride", "_cn_step_round", "_cn_round", "_start_cached"):
        monkeypatch.setattr(engine, name, lambda *a, _n=name, **k: pytest.fail(f"{_n} ran"))
    assert engine._fused_fn is not None  # built like any engine's, never dispatched:
    monkeypatch.setattr(engine, "_fused_fn", lambda *a, **k: pytest.fail("a fused round ran"))
    engine.generate("t" * 20, max_tokens=8, temperature=0.7)
    events = list(engine.generate_stream(
        "t" * 20, max_tokens=8, constraint={"type": "choice", "choices": ["a", "b"]}))
    assert events[-1]["type"] == "error" and "constrained decoding is off" in events[-1]["error"]
    with pytest.raises(RuntimeError, match="migration is off"):
        engine.migrate_import(b"")
    assert engine.migrate_export_one() is None
    off = engine.perf_stats()["blocks"]["off"]
    for feature in ("speculation", "mixed_round", "prefix_cache", "constrain", "migration"):
        assert off[feature] > off0[feature], feature
    # a chunk group beside decoding rows: a program of its own, between two rounds
    a = engine.submit(GenRequest(prompt_ids=[1] + [70] * 15, max_tokens=40, temperature=0.7))
    b = engine.submit(GenRequest(prompt_ids=[1] + [71] * 69, max_tokens=4, temperature=0.7))
    for req in (a, b):
        while True:
            ev = req.out.get(timeout=120)
            if not isinstance(ev, dict):
                break
    off = engine.perf_stats()["blocks"]["off"]
    assert off["ragged_prefill"] > off0["ragged_prefill"]
    assert set(off) == set(engine._layout.without)


def test_the_ring_event_and_the_account_of_rounds(engine, no_eos):
    engine.generate("s" * 15, max_tokens=8, temperature=0.7)
    st = engine.perf_stats()
    blk, row = st["blocks"], st["rounds"]["by_program"]
    assert set(row) == {"block"} and row["block"]["rounds"] == blk["rounds"]
    assert row["block"]["rows"] == blk["rows"] and row["block"]["row_steps"] == L * blk["rows"]
    assert row["block"]["delivered"] == blk["delivered"]
    assert sum(int(k) * v for k, v in blk["by_passes"].items()) >= blk["passes"]
    assert sum(blk["by_passes"].values()) == blk["rows"]
    ring = engine._flight.snapshot(etype="block")
    assert ring and {"rid", "rows", "t"} <= set(ring[-1]["fields"])
    counts = np.asarray(st["experts"]["counts"])
    assert (counts[0, :, 4] == blk["passes"] + blk["commits"]).all()  # a call a pass


# -- (g) with block_len 0 the causal presets' programs are what they were --------------------


# The step programs of two presets that yield one token a step, lowered for the
# CPU (kernels interpreted, so their bodies are in the text), canonicalised and
# hashed as scripts/hybrid_hlo_digest.py does it: the digests of the parent
# commit 2c21b86, read there by the same lines (PR 59). Everything this family
# added to the shared code (the block mask, the expert share, the chunk's `in
# order` read and its kept rows) stands behind `cfg.block_len` or
# `moe.share_form(cfg)`, so these do not move. A later PR that MEANS to change
# one of these programs reads the new digest off the assertion and pins it.
PARENTS = {
    "tiny-qwen3": {"decode": "2af47095735c", "chunk": "81ad2a4a46e3", "prefill": "d8a22d080ce9"},
    "tiny-moe": {"decode": "ef3bf8bfc95f", "chunk": "3a90e24a6de9", "prefill": "7a8f9cb7da81"},
}


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_with_block_len_0_the_programs_are_the_parents(name):
    import hashlib
    from functools import partial

    from jax._src.lib.mlir import passmanager

    cfg = get_config(name)
    assert not cfg.block_len and not moe.share_form(cfg)

    params = jax.eval_shape(partial(llama.init_llama_params, cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    cache = jax.eval_shape(partial(llama.init_kv_cache, cfg, 4, 128, dtype=jnp.float32, quantized=True))
    assert cache["v"] == {}  # no counts ride a pair that states no share
    programs = {tag: p for tag, p in cell_programs.preset_steps(cfg, 4, 128, 4).items()
                if tag in PARENTS[name]}
    got = {}
    with jax.default_matmul_precision("default"):  # as the parent's were lowered
        for tag, (fn, operands) in programs.items():
            module = jax.jit(fn).trace(params, cache["k"], cache["v"], *operands).lower(
                lowering_platforms=("cpu",)).compiler_ir("stablehlo")
            with module.context:
                passmanager.PassManager.parse("builtin.module(cse,canonicalize,cse)").run(module.operation)
            got[tag] = hashlib.sha1(str(module).encode()).hexdigest()[:12]
    assert got == PARENTS[name]
