"""Model correctness: prefill/decode consistency, masking, embedder, sampling."""

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
    init_embedder_params,
    embed_forward,
)
from llm_mcp_tpu.ops.sampling import sample_tokens

from family import stepwise  # noqa: E402

# every model call of this file is ONE trace and ONE compile a (configuration, shape):
# called bare, a step dispatches its primitives one by one and lowers its kernels again
llama_decode_step, llama_prefill = map(stepwise, (llama_decode_step, llama_prefill))

CFG = get_config("tiny-llm")


@pytest.fixture(scope="module")
def params():
    return init_llama_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def test_decode_matches_prefill(params):
    """Logits from incremental decode == logits from one-shot prefill."""
    key = jax.random.PRNGKey(1)
    prompt = jax.random.randint(key, (1, 7), 3, CFG.vocab_size)
    lengths = jnp.array([7], dtype=jnp.int32)

    # One-shot: prefill the 7-token prompt, take last logits.
    full_logits, ks, vs = llama_prefill(CFG, params, prompt, lengths)

    # Incremental: prefill first 6 tokens, then decode token 7.
    l6 = jnp.array([6], dtype=jnp.int32)
    _, ks6, vs6 = llama_prefill(CFG, params, prompt[:, :6], l6)
    cache = init_kv_cache(CFG, batch=2, max_seq=16, dtype=jnp.float32)
    # insert prompt KV into slot 1
    ck = cache["k"].at[:, 1:2, :, :6].set(ks6)
    cv = cache["v"].at[:, 1:2, :, :6].set(vs6)
    tok = jnp.array([0, int(prompt[0, 6])], dtype=jnp.int32)
    lens = jnp.array([0, 6], dtype=jnp.int32)
    step_logits, _, _ = llama_decode_step(CFG, params, ck, cv, tok, lens)

    np.testing.assert_allclose(
        np.asarray(step_logits[1]), np.asarray(full_logits[0]), rtol=2e-4, atol=2e-4
    )


def test_prefill_padding_invariance(params):
    """Right-padding must not change the real tokens' logits."""
    key = jax.random.PRNGKey(2)
    prompt = jax.random.randint(key, (1, 5), 3, CFG.vocab_size)
    lengths = jnp.array([5], dtype=jnp.int32)
    logits_a, _, _ = llama_prefill(CFG, params, prompt, lengths)
    padded = jnp.concatenate([prompt, jnp.zeros((1, 3), dtype=prompt.dtype)], axis=1)
    logits_b, _, _ = llama_prefill(CFG, params, padded, lengths)
    np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b), rtol=2e-4, atol=2e-4)


def test_decode_step_is_batch_independent(params):
    """One slot's output must not depend on other slots' contents."""
    cache = init_kv_cache(CFG, batch=2, max_seq=8, dtype=jnp.float32)
    tok = jnp.array([5, 9], dtype=jnp.int32)
    lens = jnp.array([0, 0], dtype=jnp.int32)
    logits, _, _ = llama_decode_step(CFG, params, cache["k"], cache["v"], tok, lens)
    tok2 = jnp.array([5, 123], dtype=jnp.int32)
    logits2, _, _ = llama_decode_step(CFG, params, cache["k"], cache["v"], tok2, lens)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(logits2[0]), rtol=1e-5)


def test_embedder_normalized_and_pad_invariant():
    cfg = get_config("tiny-embed")
    p = init_embedder_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 3, cfg.vocab_size)
    lens = jnp.array([6, 4], dtype=jnp.int32)
    out = embed_forward(cfg, p, toks, lens)
    assert out.shape == (2, cfg.dim)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(out), axis=-1), 1.0, rtol=1e-5)
    # row 1 with junk in its padded tail must be unchanged
    toks2 = toks.at[1, 4:].set(7)
    out2 = embed_forward(cfg, p, toks2, lens)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(out2[1]), rtol=1e-4, atol=1e-5)


def test_sampling_greedy_and_topk():
    logits = jnp.array([[0.0, 5.0, 1.0, 2.0], [9.0, 0.0, 0.0, 0.0]], dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    greedy = sample_tokens(
        logits, rng,
        temperature=jnp.array([0.0, 0.0]),
        top_k=jnp.array([0, 0], dtype=jnp.int32),
        top_p=jnp.array([1.0, 1.0]),
    )
    assert list(np.asarray(greedy)) == [1, 0]
    # top_k=1 is greedy regardless of temperature
    tk1 = sample_tokens(
        logits, rng,
        temperature=jnp.array([1.5, 1.5]),
        top_k=jnp.array([1, 1], dtype=jnp.int32),
        top_p=jnp.array([1.0, 1.0]),
    )
    assert list(np.asarray(tk1)) == [1, 0]


def test_sampling_distribution_respects_temperature():
    # Gumbel noise is iid per row, so one 200-row batch over identical
    # logits yields 200 independent samples — same statistics as 200
    # sequential single-row calls, without 200 dispatches.
    N = 200
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0]], dtype=jnp.float32).repeat(N, axis=0)
    t = sample_tokens(
        logits, jax.random.PRNGKey(0),
        temperature=jnp.ones((N,)),
        top_k=jnp.zeros((N,), dtype=jnp.int32),
        top_p=jnp.ones((N,)),
    )
    counts = np.bincount(np.asarray(t), minlength=4)
    assert counts[0] > counts[2] > 0  # roughly monotone in logit


def test_param_count_llama8b():
    cfg = get_config("llama-3.1-8b")
    n = cfg.param_count()
    assert 7.5e9 < n < 8.5e9


def test_prefill_chunk_matches_full(params):
    """Chunked prefill (llama_prefill_chunk) must reproduce one-shot prefill:
    same cache contents, same final logits — including a ragged last chunk."""
    from llm_mcp_tpu.models.llama import llama_prefill_chunk

    key = jax.random.PRNGKey(3)
    P = 11  # 4 + 4 + ragged 3
    prompt = jax.random.randint(key, (1, 16), 3, CFG.vocab_size)
    lengths = jnp.array([P], dtype=jnp.int32)
    full_logits, ks, vs = llama_prefill(CFG, params, prompt, lengths)

    cache = init_kv_cache(CFG, batch=2, max_seq=16, dtype=jnp.float32)
    ck, cv = cache["k"], cache["v"]
    slot = jnp.int32(1)
    logits = None
    for start, n in ((0, 4), (4, 4), (8, 3)):
        chunk = jnp.zeros((4,), dtype=jnp.int32).at[:n].set(prompt[0, start : start + n])
        logits, ck, cv = llama_prefill_chunk(
            CFG, params, ck, cv, chunk, slot, jnp.int32(start), jnp.int32(n)
        )
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(full_logits[0]), rtol=2e-4, atol=2e-4
    )
    # cache rows match the one-shot prompt KV (untouched slot 0 stays zero)
    np.testing.assert_allclose(
        np.asarray(ck[:, 1, :, :P]), np.asarray(ks[:, 0, :, :P]), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(cv[:, 1, :, :P]), np.asarray(vs[:, 0, :, :P]), rtol=2e-4, atol=2e-4
    )
    assert not np.asarray(ck[:, 0]).any()


def test_prefill_chunk_int8_cache(params):
    """Chunked prefill into an int8 cache stays close to the f32 path (the
    chunk attends its own quantized K/V — bounded error, not divergence)."""
    from llm_mcp_tpu.models.llama import llama_prefill_chunk

    key = jax.random.PRNGKey(4)
    P = 8
    prompt = jax.random.randint(key, (1, 8), 3, CFG.vocab_size)
    full_logits, _, _ = llama_prefill(CFG, params, prompt, jnp.array([P], dtype=jnp.int32))

    cache = init_kv_cache(CFG, batch=1, max_seq=16, dtype=jnp.float32, quantized=True)
    ck, cv = cache["k"], cache["v"]
    logits = None
    for start in (0, 4):
        logits, ck, cv = llama_prefill_chunk(
            CFG, params, ck, cv, prompt[0, start : start + 4],
            jnp.int32(0), jnp.int32(start), jnp.int32(4),
        )
    a, b = np.asarray(logits[0]), np.asarray(full_logits[0])
    assert np.argmax(a) == np.argmax(b)  # greedy token survives quantization
    np.testing.assert_allclose(a, b, rtol=0.1, atol=0.35)


def test_llama_encode_decoder_embedding():
    """The causal decoder as a text encoder (Qwen3-Embedding style): unit
    vectors, padding-invariant, last-token sensitive."""
    from llm_mcp_tpu.models.llama import llama_encode

    cfg = get_config("tiny-qwen3")
    p = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 3, cfg.vocab_size)
    lens = jnp.array([8, 5], dtype=jnp.int32)
    out = llama_encode(cfg, p, toks, lens)
    assert out.shape == (2, cfg.dim)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1), 1.0, rtol=1e-5
    )
    # junk in the padded tail must not move row 1's vector
    out2 = llama_encode(cfg, p, toks.at[1, 5:].set(9), lens)
    np.testing.assert_allclose(
        np.asarray(out[1]), np.asarray(out2[1]), rtol=1e-4, atol=1e-5
    )
    # changing the LAST valid token must move it (last-token pooling)
    out3 = llama_encode(
        cfg, p, toks.at[1, 4].set((int(toks[1, 4]) + 1) % cfg.vocab_size), lens
    )
    assert float(np.abs(np.asarray(out3[1]) - np.asarray(out[1])).max()) > 1e-4


def _packed_rows(cfg, lens_by_row, S, K, seed=5):
    """Rows of random texts back to back: (tokens [R, S], seg_lens [R, K],
    the texts as lists of ids, row by row)."""
    key = jax.random.PRNGKey(seed)
    tokens = np.zeros((len(lens_by_row), S), np.int32)
    seg = np.zeros((len(lens_by_row), K), np.int32)
    texts = []
    for r, lens in enumerate(lens_by_row):
        at, row = 0, []
        for k, n in enumerate(lens):
            key, sub = jax.random.split(key)
            ids = np.asarray(jax.random.randint(sub, (n,), 3, cfg.vocab_size))
            tokens[r, at : at + n] = ids
            seg[r, k] = n
            at += n
            row.append(ids)
        texts.append(row)
    return tokens, seg, texts


def _alone(cfg, p, ids, S):
    from llm_mcp_tpu.models.llama import llama_encode

    row = np.zeros((1, S), np.int32)
    row[0, : len(ids)] = ids
    return np.asarray(llama_encode(cfg, p, jnp.asarray(row), jnp.array([len(ids)], jnp.int32))[0])


@pytest.mark.parametrize(
    "model,lens_by_row",
    [
        ("tiny-qwen3", [[40, 17, 30], [96], [5, 5, 5, 5]]),
        # window 64: the 80-token text is longer than the window, and its
        # neighbours sit within a window's reach of it in the row
        ("tiny-mistral", [[80, 30, 18], [20, 100], [64, 64]]),
    ],
)
def test_llama_encode_packed_equals_each_text_alone(model, lens_by_row):
    """Sequence packing (PR 31): a text's vector from a row it shares equals
    its vector from `llama_encode` alone in a padded row; a neighbour's
    tokens do not move it, its own last token does; unused places and a
    padding row give zeros."""
    from llm_mcp_tpu.models.llama import llama_encode_packed

    cfg = get_config(model)
    p = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    S, K = 128, 4
    tokens, seg, texts = _packed_rows(cfg, lens_by_row + [[1]], S, K)
    out = np.asarray(llama_encode_packed(cfg, p, jnp.asarray(tokens), jnp.asarray(seg)))
    assert out.shape == (len(lens_by_row) + 1, K, cfg.dim)
    for r, row in enumerate(texts[:-1]):
        for k, ids in enumerate(row):
            np.testing.assert_allclose(out[r, k], _alone(cfg, p, ids, S), rtol=1e-4, atol=1e-5)
        assert not out[r, len(row) :].any()  # unused places
    np.testing.assert_allclose(np.linalg.norm(out[0, :3], axis=-1), 1.0, rtol=1e-5)
    assert not out[-1, 1:].any()  # the padding row: one place of 1, nothing else
    # a NEIGHBOUR changes (the text before and the text after the middle one,
    # and the row's padding): the middle text's vector stays
    a, b = lens_by_row[0][0], lens_by_row[0][0] + lens_by_row[0][1]
    other = tokens.copy()
    other[0, :a] = (other[0, :a] + 7) % cfg.vocab_size
    other[0, b:] = (other[0, b:] + 11) % cfg.vocab_size
    out2 = np.asarray(llama_encode_packed(cfg, p, jnp.asarray(other), jnp.asarray(seg)))
    np.testing.assert_allclose(out2[0, 1], out[0, 1], rtol=1e-4, atol=1e-5)
    assert np.abs(out2[0, 0] - out[0, 0]).max() > 1e-4
    # its OWN last token changes: it moves, its neighbours do not
    own = tokens.copy()
    own[0, b - 1] = (own[0, b - 1] + 1) % cfg.vocab_size
    out3 = np.asarray(llama_encode_packed(cfg, p, jnp.asarray(own), jnp.asarray(seg)))
    assert np.abs(out3[0, 1] - out[0, 1]).max() > 1e-4
    np.testing.assert_allclose(out3[0, 0], out[0, 0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out3[0, 2], out[0, 2], rtol=1e-4, atol=1e-5)


def test_llama_encode_packed_int8_tracks_unpacked():
    """w8a8: activation scales are per token, so packed against unpacked on
    the same quantized tree differ by rounding noise only."""
    from llm_mcp_tpu.models.llama import llama_encode_packed
    from llm_mcp_tpu.models.quant import quantize_params

    cfg = get_config("tiny-qwen3")
    q = quantize_params(init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    tokens, seg, texts = _packed_rows(cfg, [[33, 50, 12], [70, 9]], 128, 4)
    out = np.asarray(llama_encode_packed(cfg, q, jnp.asarray(tokens), jnp.asarray(seg)))
    for r, row in enumerate(texts):
        for k, ids in enumerate(row):
            cos = float(np.dot(out[r, k], _alone(cfg, q, ids, 128)))
            assert cos > 0.9999, (r, k, cos)


def test_embedding_engine_decoder_arch():
    """EmbeddingEngine serves decoder configs through llama_encode (incl.
    int8), with Matryoshka truncation renormalized."""
    from llm_mcp_tpu.executor import EmbeddingEngine

    eng = EmbeddingEngine(
        "tiny-qwen3", max_batch=4, max_seq_len=64, dtype=jnp.float32
    )
    assert eng.decoder_arch
    vecs, ntok = eng.embed(["decoder embedding one", "two"], dimensions=32)
    assert len(vecs) == 2 and len(vecs[0]) == 32 and ntok > 0
    np.testing.assert_allclose(np.linalg.norm(vecs, axis=-1), 1.0, rtol=1e-5)
    # quantize the SAME weights (a fresh int8 init would be a different
    # random model): int8 must track the f32 vector closely (w8a8 bound)
    q = EmbeddingEngine(
        "tiny-qwen3", max_batch=2, max_seq_len=64, dtype=jnp.float32,
        quant="int8", params=eng.params,
    )
    vq, _ = q.embed(["decoder embedding one"])
    assert len(vq[0]) == eng.cfg.dim
    vf, _ = eng.embed(["decoder embedding one"])
    cos = float(np.dot(vq[0], vf[0]))
    assert cos > 0.98, cos
