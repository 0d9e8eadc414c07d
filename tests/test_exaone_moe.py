"""The decoder of window and global attention layers (a per-layer list of
windows, a KV cache of two kinds: full-length rows for the global layers, a
ring of the last positions for the window layers), a leading dense layer
before the expert layers, gates renormalised and then scaled, and the
multi-token-prediction module, at the tiny preset of the published shape, held
to the plain reference `benchmark/references/exaone_moe.py` on seeded float32
weights: logits, not tokens, through every path a sequence can take, and the
engine's book of the two kinds."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.kernels.attention import AttnStream, decode_attend_q8
from llm_mcp_tpu.models import hybrid, moe
from llm_mcp_tpu.models.configs import get_config, periodic_windows
from llm_mcp_tpu.models.llama import (
    init_kv_cache,
    init_llama_params,
    layer_windows,
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
TOL = 1e-4  # float32 against float32, of logits whose largest is about 4
# Through the int8 cache and rings, on the MEDIAN over positions of a row's
# largest difference: rounding keys and values to 8 bits moves a logit by about
# 0.06 (the reading here: 0.059), and now and then it moves a router's fourth
# choice, which moves the row by a whole gated expert (1.2 here): the median does
# not see single rows. The weakest control, one key lost of a window's 32, reads
# 0.13 by the same statistic; the others 0.55 and more.
TOL_Q8 = 0.09
T = 256  # eight windows of 32; the ring of 128 wraps at half of it


@pytest.fixture(scope="module")
def ref():
    return reference_for("exaone_moe")


@pytest.fixture(scope="module")
def model(ref):
    """(cfg, params, tokens [T], the reference's logits at every position)."""
    with jax.default_matmul_precision("highest"):
        cfg = get_config("tiny-kexaone")
        params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (T,), 3, 500))
        want = ref.logits(cfg, params, toks, np.arange(T), np.arange(cfg.vocab_size))
    return cfg, params, toks, want


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _through_the_cache(cfg, params, toks, quantized, chunks, upto, slot=2):
    """Chunks of 64 into a used slot, then decode steps of the full batch with
    one live row: the logits after the chunks and after every step. The step
    programs are jitted: run op by op, every step's scan is a new executable."""
    chunk_fn = jax.jit(lambda *a: llama_prefill_chunk_batch(cfg, params, *a, skey=256))
    step_fn = jax.jit(lambda *a: llama_decode_step(cfg, params, *a))
    cache = init_kv_cache(cfg, 4, 512, dtype=jnp.float32, quantized=quantized)
    # every slot was used: the rings hold another sequence's leftovers
    ck, cv = cache["k"], dict(cache["v"], win=jax.tree.map(
        lambda a: a + jnp.asarray(3, a.dtype), cache["v"]["win"]))
    start = 0
    for n in chunks:
        chunk = np.zeros((1, 64), np.int32)
        chunk[0, :n] = toks[start : start + n]
        logits, ck, cv = chunk_fn(
            ck, cv, jnp.asarray(chunk), jnp.array([slot]), jnp.array([start]), jnp.array([n]))
        start += n
    out = [np.asarray(logits[0])]
    lens = np.full(4, 512, np.int32)  # the other slots are parked
    lens[slot] = start
    parked = jax.tree.map(lambda a: np.asarray(a[:, 0]), cv["win"])
    for t in range(start, upto):
        tok = np.zeros(4, np.int32)
        tok[slot] = toks[t]
        logits, ck, cv = step_fn(ck, cv, jnp.asarray(tok), jnp.asarray(lens))
        out.append(np.asarray(logits[slot]))
        lens[slot] += 1
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a[:, 0]), b),
                 cv["win"], parked)  # a parked row's ring never moves
    return start, np.stack(out), cv


def test_the_reference_shares_no_code_with_the_program():
    assert "llm_mcp_tpu" not in reference_source("exaone_moe")  # its docstring names the files


def test_the_windows_are_the_published_list_and_a_period_is_written_out():
    cfg = get_config("tiny-kexaone")
    assert np.asarray(layer_windows(cfg)).tolist() == [32, 32, 32, 0, 32]
    assert cfg.gqa_layers == (3,) and cfg.recurrent_kind == "win" and cfg.recurrent
    assert cfg.layer_period == ("win", "win", "gqa", "win") and cfg.ring_len == 128
    big = get_config("k-exaone-236b-ep8")
    assert big.sliding_windows == (128, 128, 128, 0, 128) and big.ring_len == 128
    assert periodic_windows(64, 2, 4) == (64, 0, 64, 0) and periodic_windows(0, 1, 4) == ()
    assert get_config("mistral-7b").sliding_windows == (4096,) * 32
    assert get_config("gemma2-9b").sliding_windows == (4096, 0) * 21
    assert not get_config("tiny-mistral").recurrent  # a mask over full-length caches there


def test_whole_prompts_of_unlike_lengths_and_the_rings_they_leave(model):
    cfg, params, toks, want = model
    batch = np.zeros((3, 256), np.int32)
    lengths = [200, 37, 129]  # wrapped, inside the first turn, one past it
    for i, n in enumerate(lengths):
        batch[i, :n] = toks[:n]
    logits, ks, vs = llama_prefill(cfg, params, jnp.asarray(batch), jnp.asarray(lengths))
    for i, n in enumerate(lengths):
        assert np.max(np.abs(np.asarray(logits[i]) - want[n - 1])) < TOL, (i, n)
    assert ks.shape == (1, 3, 2, 256, 32)  # one global layer owns full-length rows
    assert vs["win"]["k"].shape == vs["win"]["v"].shape == (4, 3, 2, 128, 32)  # four rings
    # index j holds the prompt's last position that is j modulo 128: of 200, 128..199 then 72..127
    again, ks1, _ = llama_prefill(cfg, params, jnp.asarray(batch[:1]), jnp.asarray(lengths[:1]))
    assert ks1.shape[1] == 1 and np.max(np.abs(np.asarray(again[0]) - want[199])) < TOL
    counts = np.asarray(vs["moe"])  # the four expert layers': the dense one routes nothing
    assert counts.shape == (4, 5) and counts[:, 0].tolist() == [sum(lengths)] * 4


@pytest.mark.parametrize("chunks", [(64, 64, 42), (64, 64, 64, 7)],
                         ids=["ragged_across_the_wrap", "cut_after_the_wrap"])
def test_chunks_then_decode_through_both_kinds_in_a_reused_slot(model, chunks):
    """Chunk cuts inside a window (64 of 32s is on an edge; 42 and 7 are not)
    and across the ring's wrap at 128, a ragged last chunk whose padding must
    not reach the ring, then decode steps to the sixth window and beyond."""
    cfg, params, toks, want = model
    start, got, _ = _through_the_cache(cfg, params, toks, False, chunks, T)
    assert np.max(np.abs(got - want[start - 1 : T])) < TOL


def test_the_int8_cache_and_rings_stay_within_a_stated_tolerance(model):
    cfg, params, toks, want = model
    start, got, cv = _through_the_cache(cfg, params, toks, True, (64, 64, 42), T)
    assert cv["win"]["k"]["q"].dtype == jnp.int8 and cv["win"]["v"] == {}
    assert np.median(np.max(np.abs(got - want[start - 1 : T]), axis=1)) < TOL_Q8


@pytest.mark.parametrize("control", ["fp8", "no_window", "rope_global", "no_scale", "lost_ring"])
def test_each_control_reads_outside_the_tolerance(model, ref, control):
    """What the program computes, through the int8 cache, lies inside TOL_Q8 of
    the reference (above); the reference with one stated thing left out lies
    outside it, so the comparison can tell each from the program."""
    cfg, params, toks, want = model
    ref.LOWER = control
    retrace(ref)
    try:
        other = ref.logits(cfg, params, toks, np.arange(T), np.arange(cfg.vocab_size))
    finally:
        ref.LOWER = None
        retrace(ref)
    # readings: lost_ring 0.130, rope_global 0.55, no_scale 0.74, fp8 1.85, no_window 3.9
    assert np.median(np.max(np.abs(other[170:] - want[170:]), axis=1)) > TOL_Q8, control


def test_the_logit_hold_of_the_chip_runs_at_the_tiny_preset(ref):
    """scripts/logit_hold.py, the comparison that holds the ring, the rotation by
    kind and the gates' factor at the published widths on the chip, end to end
    here: the engine's admit program into a used slot, decode steps across the
    ring's wrap with the kernels interpreted, the program inside
    `LOGIT_TOL_REL` and every control outside it (exit 0). A process of its
    own: the script sets the configuration's environment."""
    import subprocess
    import sys

    assert 0.0233 < ref.LOGIT_TOL_REL < 0.128  # between the chip's two readings
    env = dict(os.environ, LLM_MCP_TPU_ATTN="pallas", JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "logit_hold.py"), "--model", "tiny-kexaone",
         "--prompt-tokens", "180", "--steps", "140", "--seeds", "1"],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert got.returncode == 0, got.stdout[-2000:] + got.stderr[-2000:]
    summary = json.loads(got.stdout.split("SUMMARY ", 1)[1].splitlines()[0])
    assert summary["held"] and summary["shapes"]["admit"] == "1:256"
    assert summary["program"]["max"] < ref.LOGIT_TOL_REL < summary["lost_ring"]["min"]


def test_the_window_arm_reads_a_ring_as_plain_attention_reads_its_window():
    """The decode kernel's window arm over an int8 ring, against float32
    softmax over the dequantised positions the window holds, at fills before
    and after the ring wraps; a parked row is finite and nobody's."""
    from llm_mcp_tpu.models.llama import fuse_prompt_kv

    B, Hkv, G, hd, R, W = 4, 2, 2, 32, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    k_all = jax.random.normal(ks[0], (B, Hkv, 400, hd))
    v_all = jax.random.normal(ks[1], (B, Hkv, 400, hd))
    q = jax.random.normal(ks[2], (B, Hkv, G, hd))
    lengths = np.array([5, 127, 300, 4096], np.int32)  # the last one parked
    ring = {"q": np.zeros((1, B, 2 * Hkv + 1, R, hd), np.int8), "s": np.zeros((1, B, 2 * Hkv, R), np.float32)}
    fused = fuse_prompt_kv(k_all, v_all, scale_dtype=jnp.float32)
    for b, w in enumerate(lengths[:3]):
        for p in range(max(0, w - R), w):  # what a sequence at w has written
            ring["q"][0, b, :, p % R] = np.asarray(fused["q"][b, :, p])
            ring["s"][0, b, :, p % R] = np.asarray(fused["s"][b, :, p])
    ring = jax.tree.map(jnp.asarray, ring)
    nk, nv = k_all[:, :, 399], v_all[:, :, 399]
    got = np.asarray(decode_attend_q8(
        q, nk, nv, ring, {}, jnp.int32(0), jnp.asarray(lengths), window=W))
    assert np.isfinite(got).all()
    kq = fused["q"][:, :Hkv].astype(jnp.float32) * fused["s"][:, :Hkv, :, None]
    vq = fused["q"][:, Hkv : 2 * Hkv].astype(jnp.float32) * fused["s"][:, Hkv:, :, None]
    for b, w in enumerate(lengths[:3]):
        lo = max(0, w - W + 1)
        keys = jnp.concatenate([kq[b, :, lo:w], nk[b][:, None]], axis=1)  # [Hkv, n, hd]
        vals = jnp.concatenate([vq[b, :, lo:w], nv[b][:, None]], axis=1)
        p = jax.nn.softmax(jnp.einsum("hgd,hnd->hgn", q[b], keys) * hd**-0.5, axis=-1)
        want = np.asarray(jnp.einsum("hgn,hnd->hgd", p, vals))
        assert np.max(np.abs(got[b] - want)) < 0.02, (b, w)  # the kernel's 8-bit q and probs


def test_eight_shares_of_the_expert_layer_and_the_shared_expert_once_are_the_uncut_layer(ref):
    """Eight members that each hold 2 of 16 experts add their parts of the
    routed sum; with the shared expert counted once that is the reference's
    layer over all 16, the gates renormalised and then times 2.5."""
    cfg = dataclasses.replace(get_config("tiny-kexaone"), n_experts=2)
    whole = dataclasses.replace(cfg, n_experts=16, n_router_experts=0)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    full = moe.init_moe_layer_params(whole, ks[0], jnp.float32, 1)
    full["router_bias"] = 0.01 * jax.random.normal(ks[1], (1, 16), jnp.float32)
    x = jax.random.normal(ks[2], (24, cfg.dim), jnp.float32)
    want = np.asarray(ref._experts(whole, full, jnp.int32(0), x))
    lp = {n: v[0] for n, v in full.items()}
    total = np.zeros_like(want)
    for share in range(8):
        turn = lambda a: jnp.roll(a, -2 * share, axis=-1)  # this member's experts first
        mine = {"router": turn(lp["router"]), "router_bias": turn(lp["router_bias"]),
                **{n: lp[n][2 * share : 2 * share + 2] for n in ("w1e", "w3e", "w2e")}}
        y, counts = moe.moe_share_ffn(cfg, mine, x)
        total += np.asarray(y)
        assert int(counts[0]) == 24
    shared, _ = moe.moe_share_ffn(
        cfg, {**{n: lp[n] for n in ("w1s", "w3s", "w2s")}, "router": lp["router"],
              "router_bias": lp["router_bias"] + 100.0 * (jnp.arange(16) >= 2),
              **{n: lp[n][:2] * 0 for n in ("w1e", "w3e", "w2e")}}, x)
    assert np.max(np.abs(total + np.asarray(shared) - want)) < TOL
    # and the 2.5 is in: without it the routed part is that much smaller
    gates, _ = moe.route(whole, jnp.zeros((3, 16)))
    assert np.allclose(np.asarray(gates).sum(-1), 2.5)
    solar = get_config("tiny-solar")
    assert np.allclose(np.asarray(moe.route(solar, jnp.zeros((3, 16)))[0]).sum(-1), 1.0)
    raw = dataclasses.replace(whole, norm_topk_prob=False, router_score="softmax")
    assert np.allclose(np.asarray(moe.route(raw, jnp.zeros((3, 16)))[0]), 2.5 / 16)


def test_the_prediction_module_agrees_with_the_references(model, ref):
    cfg, params, toks, _ = model
    mtp = hybrid.init_mtp_params(cfg, jax.random.PRNGKey(11), dtype=jnp.float32)
    assert mtp["eh_proj"].shape == (2 * cfg.dim, cfg.dim) and mtp["layers"]["w1e"].shape[:2] == (1, 4)
    n = 192
    rows = np.arange(n - 1)  # the last position has no next token
    want = ref.mtp_logits(cfg, params, mtp, toks[:n], rows, np.arange(cfg.vocab_size))
    h, _, _ = hybrid.hybrid_prefill(
        cfg, params, jnp.asarray(toks[None, :n]), jnp.asarray([n]), hidden=True)
    nxt = np.append(toks[1:n], 0)[None]
    got = hybrid.mtp_logits(cfg, params, mtp, h, jnp.asarray(nxt), jnp.asarray([n]))
    assert got.shape == (1, n, cfg.vocab_size)
    assert np.max(np.abs(np.asarray(got[0, : n - 1]) - want)) < TOL
    main = ref.logits(cfg, params, toks[:n], rows, np.arange(cfg.vocab_size))
    assert np.max(np.abs(want - main)) > 0.1  # another distribution: the token after next


def test_param_count_reckons_the_cut_and_the_published_row():
    cut = get_config("k-exaone-236b-ep8")
    assert abs(cut.param_count() - 3_712e6) < 1e6  # ISSUE 43's arithmetic
    whole = dataclasses.replace(
        cut, n_layers=48, n_experts=128, vocab_size=153_600,
        gqa_layers=tuple(range(3, 48, 4)), sliding_windows=(128, 128, 128, 0) * 12)
    assert round(whole.param_count() / 1e9) == 237  # "236B" and the untied head
    assert len(whole.layer_period) == 47  # 47 expert layers are no whole number of periods
    tiny = get_config("tiny-kexaone")
    params = jax.eval_shape(
        lambda: init_llama_params(tiny, jax.random.PRNGKey(0), dtype=jnp.float32))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert len(params["first"]) == 1 and params["first"][0]["w1"].shape == (128, 256)
    assert params["win"]["wq"].shape[0] == 3 and params["layers"]["attn_norm"].shape == (4, 128)
    # the count leaves out the q/k norms and the selection bias
    assert leaves - tiny.param_count() == 5 * 2 * 32 + 4 * 16


def test_the_streams_book_counts_a_ring_in_full_and_a_window_as_live():
    book = AttnStream((4, 8, 17, 128, 128), window=128, max_seq_len=4096)
    book.dispatched(np.array([5, 700, 4096], np.int32), 4)
    stats = book.stats()
    assert stats["tokens_streamed"] == 3 * 4 * 128 and stats["ring_tokens"] == 128
    assert stats["tokens_live"] == (6 + 7 + 8 + 9) + 4 * 128 and stats["block_tokens"] == 0


# -- the engine: the normal path, the pool's book of the second kind -----------------


@pytest.fixture(scope="module")
def engine():
    from llm_mcp_tpu.executor import GenerationEngine

    os.environ["LLM_MCP_TPU_ATTN"] = "pallas"  # the kernels' arms, interpreted
    try:
        eng = GenerationEngine("tiny-kexaone", max_slots=2, max_seq_len=512, dtype=jnp.float32,
                               prefill_chunk=128, kv_quant="int8").start()
    finally:
        del os.environ["LLM_MCP_TPU_ATTN"]
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def plain_engine():
    """The same engine over float32 caches of both kinds: what it serves is the
    reference's own choice, with no rounding to argue about."""
    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine("tiny-kexaone", max_slots=2, max_seq_len=512, dtype=jnp.float32,
                           prefill_chunk=128).start()
    yield eng
    eng.shutdown()


def _served(engine, prompt, n):
    got: dict = {}
    emit = engine._process_token

    def tap(slot, tok, pos):
        got.setdefault("ids", list(slot.req.prompt_ids))
        got.setdefault("out", []).append(int(tok))
        return emit(slot, tok, pos)

    engine._process_token = tap
    try:
        engine.generate(prompt, max_tokens=n, temperature=0.0)
    finally:
        del engine._process_token
    return got["ids"], got["out"]


@pytest.mark.parametrize("n_bytes", [90, 300], ids=["an_admit_program", "three_chunks"])
def test_engine_serves_the_references_choice_whole_and_chunked(plain_engine, ref, n_bytes):
    """Through admission, the cache pair of two kinds and decode rounds past
    the ring's wrap, in a fresh slot and a reused one: every served token is the
    reference's choice."""
    from benchmark import trafficgen

    allowed = np.flatnonzero(np.asarray(plain_engine._allowed_mask))
    for seed in (1, 2, 3):
        ids, out = _served(plain_engine, trafficgen.text(n_bytes, seed, "ref"), 60)
        seq = ids + out[:-1]
        rows = np.arange(len(ids) - 1, len(seq))
        want = ref.logits(plain_engine.cfg, plain_engine.params,
                          np.asarray(seq + [0] * (-len(seq) % 128), np.int32), rows, allowed)
        assert len(out) == 60 and len(seq) > (128 if n_bytes > 128 else 0)
        for k, tok in enumerate(out):
            assert float(np.max(want[k]) - want[k, np.flatnonzero(allowed == tok)[0]]) < 1e-3, k
    phases = {r["phase"] for r in plain_engine._ledger.table()}
    assert ("admit" if n_bytes < 128 else "chunk") in phases, phases


def test_the_int8_engine_is_held_as_the_harness_holds_it(engine, ref):
    """`correctness.hold_to_reference` on what the int8 engine serves, with the
    module's own tolerance: rounding keys to 8 bits now and then moves a router's
    choice, and the row by a gated expert (a tenth of its largest logit here)."""
    from benchmark import correctness, trafficgen

    for n_bytes in (90, 300):
        ids, out = _served(engine, trafficgen.text(n_bytes, 7, "ref"), 40)
        notes = correctness.hold_to_reference(ref, engine, ids, out)
        assert notes["served_tokens"] == 40 and notes["tolerance"] == ref.SERVED_TOL_REL


def test_window_layers_hold_a_ring_a_slot_and_not_the_whole_length(engine):
    # (a reply of its own: under `--dist load` this case may be the first of its
    # worker to see the engine, and the window book below counts decode steps)
    engine.generate("a ring a slot", max_tokens=3, temperature=0.0)
    kinds = engine.perf_stats()["kv_kinds"]
    assert kinds["full"]["layers"] == 1 and kinds["window"]["layers"] == 4
    assert kinds["full"]["positions"] == 2 * 512 and kinds["window"]["positions"] == 2 * 128
    per_position = kinds["full"]["bytes"] / kinds["full"]["positions"]
    assert kinds["window"]["bytes"] == 4 * per_position * kinds["window"]["positions"]
    assert engine._cv["win"]["k"]["q"].shape == (4, 2, 5, 128, 32)
    pool = engine.perf_stats()["state_pool"]
    assert pool["bytes"] == kinds["window"]["bytes"]
    assert pool["layout"] == {"k.q": [4, 2, 5, 128, 32], "k.s": [4, 2, 4, 128]}
    attn = engine.perf_stats()["decode_attn"]
    assert attn["window"]["ring_tokens"] == 128 and attn["window"]["window"] == 32
    assert attn["window"]["steps"] == attn["steps"] > 0
    dense = engine.__class__("tiny-llm", max_slots=2, max_seq_len=64, dtype=jnp.float32)
    assert set(dense.perf_stats()["kv_kinds"]) == {"full"}


def test_a_ring_configuration_never_shares_drafts_or_offloads(engine, monkeypatch):
    """Prefix cache, speculation, offload, migration and ragged prefill are off
    for a ring as for a recurrent state, decided where the pool is built, each
    with its counter and its reason; admissions do not ride a decode round."""
    from llm_mcp_tpu.executor.memory import POOL_COUNTS, RECURRENT_OFF
    from llm_mcp_tpu.models.llama import mixed_step_supported

    assert set(engine.perf_stats()["state_pool"]["off"]) == set(RECURRENT_OFF) | set(POOL_COUNTS)
    assert all("ring" in why for why in RECURRENT_OFF.values())  # the one table says why for a ring too
    shared = "the same long system prompt, word for word, " * 2
    before = dict(engine.perf_stats()["state_pool"]["off"])
    for tail in ("one", "two", "three", "four"):  # the third sharer would pin a prefix
        engine.generate(shared + tail, max_tokens=6, temperature=0.0)
    off = engine.perf_stats()["state_pool"]["off"]
    assert engine.prefix_cache_hits == 0 and not engine._prefix_cache and engine._prefix_budget == 0
    assert off["prefix_cache"] - before["prefix_cache"] == 4
    assert engine._verify_fn is None and off["speculation"] > before["speculation"]
    assert engine._pool is None and engine._phys is None and not engine.ragged_prefill
    assert engine.migrate_export_one() is None
    assert not mixed_step_supported(engine.cfg) and engine._ride_off() == "other"
    assert (engine.state_dtype, engine.expert_dtype, engine.weights_dtype) == ("", "float32", "float32")
