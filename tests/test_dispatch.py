"""Unified dispatch plane (executor/dispatch.py + the engine's _dx funnel).

Four layers, cheapest first:

1. Channel protocol units — CmdLeader/CmdFollower framing, in-order step
   replay through `GSPMDBackend.run_follower`, ping liveness frames, and
   the unknown-frame protocol error. No engine, no model.
2. pp×tp boot parity — an engine on a `pp=2,tp=2` virtual mesh with the
   GPipe stage-scan prefill (TPU_PP_PREFILL=1) emits greedy tokens
   identical to the single-stage scan (TPU_PP_PREFILL=0) AND to a
   mesh-less engine. The acceptance bar for layer-sharded serving.
3. Leader/follower step-program parity, in-process — a REAL leader engine
   (GSPMDBackend, forced to expect one follower) and a REAL follower
   engine replaying over an actual TCP command channel, both in this
   process on the same virtual mesh. Traffic exercises admission, ragged
   chunked prefill, a prefix-cache hit, speculative verify rounds, and the
   paged prefix pin — and every one of them must cross the wire as plain
   DISPATCH_OPS steps (zero per-feature mirror code; the dispatch-surface
   lint pass enforces the same statically). Greedy tokens must match a
   LocalArraysBackend reference, and the follower's device arrays must
   finish bit-identical to the leader's.
4. True 2-process GSPMD boot — the `python -m llm_mcp_tpu.executor.dispatch`
   demo across two OS processes. jax's CPU backend cannot run multiprocess
   computations at all (XLA raises "Multiprocess computations aren't
   implemented on the CPU backend"), so off-TPU this leg skips; on real
   multi-host metal it runs the whole boot.
"""

from __future__ import annotations

import os
import re
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# A prompt whose greedy continuation re-treads its own n-grams, so the
# self-speculative drafter engages and verify rounds actually run (the same
# trigger test_spec.py uses for its identity check).
REPETITIVE_PROMPT = (
    "repeat this exact list again and again: alpha beta gamma delta "
    "alpha beta gamma delta alpha beta gamma delta"
)
SHORT_PROMPT = "admission check"


# ------------------------------------------------------ channel protocol --


def test_follower_replays_steps_in_order():
    from llm_mcp_tpu.executor.dispatch import GSPMDBackend

    addr = f"127.0.0.1:{_free_port()}"
    backend = GSPMDBackend(addr, connect_timeout_s=30.0)
    backend._n_followers = 1  # single-process: force a real channel
    executed: list[tuple] = []
    table = {
        "alpha": lambda *a: executed.append(("alpha", a)),
        "beta": lambda *a: executed.append(("beta", a)),
    }
    fol = threading.Thread(target=backend.run_follower, args=(table,), daemon=True)
    fol.start()
    backend.start()  # blocking accept of the one follower
    try:
        payload = np.arange(6, dtype=np.int32).reshape(2, 3)
        backend.emit("alpha", (1, "x"))
        backend.emit("beta", (payload,))
        backend.emit("alpha", (2.5,))
        backend.idle()  # ping frames must be transparent to replay
        backend.stop()
        fol.join(timeout=30)
        assert not fol.is_alive(), "follower did not exit on stop"
    finally:
        backend.close()
    assert [(op, a[1:] if op == "beta" else a) for op, a in executed] == [
        ("alpha", (1, "x")), ("beta", ()), ("alpha", (2.5,))
    ]
    np.testing.assert_array_equal(executed[1][1][0], payload)


def test_follower_rejects_unknown_frame():
    from llm_mcp_tpu.executor.dispatch import CmdLeader, GSPMDBackend

    addr = f"127.0.0.1:{_free_port()}"
    backend = GSPMDBackend(addr, connect_timeout_s=30.0)
    errs: list[str] = []

    def run():
        try:
            backend.run_follower({})
        except ValueError as e:
            errs.append(str(e))

    fol = threading.Thread(target=run, daemon=True)
    fol.start()
    leader = CmdLeader(addr, 1, timeout_s=30.0)
    try:
        leader.send(("ping",))  # liveness beacon: follower keeps waiting
        leader.send(("frobnicate", 7))  # not part of the protocol
        fol.join(timeout=30)
        assert not fol.is_alive()
    finally:
        leader.close()
    assert errs and "frobnicate" in errs[0]


def test_dispatch_ops_is_a_closed_string_vocabulary():
    """The published step vocabulary stays a plain string tuple — the
    follower's exec_table keys and the lint census both key off it."""
    from llm_mcp_tpu.executor.dispatch import DISPATCH_OPS

    assert isinstance(DISPATCH_OPS, tuple)
    assert all(isinstance(op, str) and op for op in DISPATCH_OPS)
    assert len(set(DISPATCH_OPS)) == len(DISPATCH_OPS)


# ------------------------------------------------------- pp×tp boot parity --


def _mk(model="tiny-llm", start=True, **kw):
    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("seed", 3)
    eng = GenerationEngine(model, **kw)
    return eng.start() if start else eng


def test_pp_tp_boot_greedy_identity(monkeypatch):
    """pp=2,tp=2 boot with the GPipe stage-scan prefill is token-identical
    to the single-stage layer scan on the same mesh AND to a mesh-less
    engine: layer-on-pp sharding plus the pipeline schedule change WHERE
    the math runs, never WHAT it computes."""
    import jax

    from llm_mcp_tpu.parallel.mesh import make_mesh

    prompt = "stage scan parity probe for the pipeline axis"
    monkeypatch.delenv("TPU_PP_PREFILL", raising=False)
    mesh = make_mesh("pp=2,tp=2", devices=jax.devices()[:4])
    pp = _mk(mesh=mesh)
    try:
        assert pp.pp_prefill == 2, "stage-scan prefill did not engage"
        got = pp.generate(prompt, max_tokens=12, temperature=0.0)
    finally:
        pp.shutdown()

    monkeypatch.setenv("TPU_PP_PREFILL", "0")
    flat = _mk(mesh=make_mesh("pp=2,tp=2", devices=jax.devices()[:4]))
    try:
        assert flat.pp_prefill == 1
        want = flat.generate(prompt, max_tokens=12, temperature=0.0)
    finally:
        flat.shutdown()
    monkeypatch.delenv("TPU_PP_PREFILL", raising=False)

    local = _mk(mesh=None)
    try:
        base = local.generate(prompt, max_tokens=12, temperature=0.0)
    finally:
        local.shutdown()

    assert got["text"] == want["text"] == base["text"]
    assert got["usage"] == want["usage"] == base["usage"]


# ---------------------------------------- leader/follower parity, in-proc --


def test_leader_follower_step_program_parity(monkeypatch):
    """The whole dispatch plane end to end, in one process: a leader engine
    broadcasting over a real TCP command channel, a follower engine
    replaying the step-program, and a LocalArraysBackend reference — all on
    the same pp=2,tp=2 virtual mesh with the same seed. Admission, ragged
    chunked prefill, a prefix-cache hit, speculative verify rounds, and the
    paged prefix pin all cross the wire as plain DISPATCH_OPS steps, greedy
    output matches the local backend token-for-token, and the follower's
    device arrays end bit-identical to the leader's."""
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.executor.dispatch import DISPATCH_OPS, GSPMDBackend
    from llm_mcp_tpu.models.configs import MODEL_CONFIGS
    from llm_mcp_tpu.models.llama import init_llama_params
    from llm_mcp_tpu.parallel.mesh import make_mesh
    from llm_mcp_tpu.parallel.sharding import llama_param_specs, shard_pytree

    for knob in ("TPU_SPEC", "TPU_RAGGED_PREFILL", "TPU_PAGED_PHYSICAL",
                 "TPU_PP_PREFILL", "TPU_KV_BLOCK_TOKENS"):
        monkeypatch.delenv(knob, raising=False)

    addr = f"127.0.0.1:{_free_port()}"
    kw = dict(max_slots=2, max_seq_len=256, decode_chunk=4,
              prefill_chunk=32, prompt_cache_mb=64, seed=3)

    # ONE param tree for all three engines (what a shared checkpoint gives a
    # real boot). Letting each engine self-init would compare a jitted
    # born-sharded init against an eager one — bitwise-different by an ULP,
    # which a random toy model amplifies into different argmax tokens.
    mesh = make_mesh("pp=2,tp=2", devices=jax.devices()[:4])
    cfg = MODEL_CONFIGS["tiny-llm"]
    params = shard_pytree(
        init_llama_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32),
        llama_param_specs(cfg), mesh)

    lead_backend = GSPMDBackend(addr, connect_timeout_s=120.0)
    lead_backend._n_followers = 1  # the follower lives in this process
    emitted: list[str] = []
    orig_emit = lead_backend.emit
    lead_backend.emit = lambda op, args: (emitted.append(op), orig_emit(op, args))[1]

    # NOT start()ed: a follower has no scheduling loop (and no channel to
    # bind) — it only replays the leader's step-program
    follower = _mk(mesh=mesh, params=params, start=False,
                   backend=GSPMDBackend(addr, connect_timeout_s=120.0), **kw)
    fol_thread = threading.Thread(target=follower.run_follower, daemon=True)

    leader = None
    reference = None
    try:
        fol_thread.start()
        leader = _mk(mesh=mesh, params=params, backend=lead_backend, **kw)
        assert leader._spmd
        assert leader.pp_prefill == 2, "stage-scan prefill off under dispatch"
        assert leader._phys is not None, "physical pool off under dispatch"
        reference = _mk(mesh=mesh, params=params, **kw)

        # ~57 tokens: its stored prefix pow2-floors to 32, which is NOT
        # block-aligned (block_tokens=64) — the third occurrence's hit must
        # COW the boundary block over the wire. The ~110-token repetitive
        # prompt floors to an aligned 64 — its hit is a pure pin (no device
        # op at all: the paged win the dispatch stream must preserve).
        mid = "pin this shared preamble across the process boundary now "
        traffic = [
            (SHORT_PROMPT, 8),            # fused whole-prompt admission
            (REPETITIVE_PROMPT, 48),      # ragged chunked prefill + verify
            (REPETITIVE_PROMPT, 48),      # 2nd sight: prefix store → pool
            (REPETITIVE_PROMPT, 16),      # 3rd sight: aligned hit, pin-only
            (mid, 8),
            (mid, 8),                     # store (32 tokens, unaligned)
            (mid, 8),                     # hit → boundary-block COW
        ]
        for prompt, n in traffic:
            got = leader.generate(prompt, max_tokens=n, temperature=0.0)
            want = reference.generate(prompt, max_tokens=n, temperature=0.0)
            assert got["text"] == want["text"], prompt
            assert got["usage"] == want["usage"], prompt

        assert not leader.dead
        assert leader.prefix_cache_hits >= 2, "prefix cache never hit"
        assert leader.speculation_stats()["verify_calls"] > 0, \
            "drafter never engaged"

        seen = set(emitted)
        assert seen <= set(DISPATCH_OPS), seen - set(DISPATCH_OPS)
        for op, feature in [
            ("admit", "fused whole-prompt admission"),
            ("ragged", "ragged chunked prefill"),
            ("bsample", "chunk-boundary sample"),
            ("verify", "speculative verify round"),
            ("pput", "paged prefix pin (pool store)"),
            ("cow", "boundary-block copy-on-write"),
        ]:
            assert op in seen, f"{feature} never crossed the wire as {op!r}"
    finally:
        if leader is not None:
            leader.shutdown()  # sends stop — releases the follower loop
        fol_thread.join(timeout=120)
        if reference is not None:
            reference.shutdown()
    assert not fol_thread.is_alive(), "follower never saw stop"

    # Replay left the follower's device plane bit-identical to the leader's:
    # KV cache, physical pool, and per-slot sampling rows.
    np.testing.assert_array_equal(np.asarray(leader._ck), np.asarray(follower._ck))
    np.testing.assert_array_equal(np.asarray(leader._cv), np.asarray(follower._cv))
    assert (follower._pool_k is None) == (leader._pool_k is None)
    if leader._pool_k is not None:
        np.testing.assert_array_equal(
            np.asarray(leader._pool_k), np.asarray(follower._pool_k))
        np.testing.assert_array_equal(
            np.asarray(leader._pool_v), np.asarray(follower._pool_v))
    np.testing.assert_array_equal(
        np.asarray(leader._d_last_tok), np.asarray(follower._d_last_tok))


# --------------------------------------------------- true 2-process boot --

_HOST_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")


def test_two_process_dispatch_demo_boots():
    """Spawn the dispatch demo across two real OS processes (leader +
    follower, jax.distributed, global pp=2,tp=2 mesh). Skips wherever the
    platform cannot run multiprocess GSPMD (jax's CPU backend raises
    "Multiprocess computations aren't implemented"); on multi-host TPU this
    is the full boot."""
    coord_port, cmd_port = _free_port(), _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        # children size their own 2-device CPU platform
        env["XLA_FLAGS"] = _HOST_COUNT_RE.sub("", env.get("XLA_FLAGS", "")).strip()
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{coord_port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(pid)
        env["JAX_PLATFORMS"] = "cpu"
        env["SLICE_CMD_ADDR"] = f"127.0.0.1:{cmd_port}"
        env["SLICE_LOCAL_DEVICES"] = "2"
        env["SLICE_MESH"] = "pp=2,tp=2"
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "llm_mcp_tpu.executor.dispatch"],
                env=env,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out or "")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    while len(outs) < 2:
        outs.append("")
    if "Multiprocess computations aren't implemented" in outs[0] + outs[1]:
        pytest.skip("platform cannot run 2-process GSPMD (CPU backend limit)")
    assert procs[0].returncode == 0, outs[0][-3000:]
    assert procs[1].returncode == 0, outs[1][-3000:]
    assert "DISPATCH DEMO OK" in outs[0], outs[0][-3000:]
    assert "DISPATCH FOLLOWER OK" in outs[1], outs[1][-3000:]
