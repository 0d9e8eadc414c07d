"""CPU rehearsal of chip_smoke.py's control flow (rehearsal 1 of the
`on-chip-measurement` guide): the script is imported, its device check is
stubbed HERE, and it serves tiny-llm with the kernels in interpret mode. No
option of the script exists for this — the script itself has no CPU leg.

What this pins: the phase order, that a phase which raises ends the run with
a non-zero exit and no result line, the shape of the last line, that the real
device check refuses the CPU, and that the four-chip option runs its own
phases and no other. What it cannot say: anything about the chip.
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve annotations through it
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop("chip_smoke", None)


def _fake_device(ctx):
    import jax

    d = jax.devices()[0]
    ctx["device"] = {"platform": d.platform, "kind": d.device_kind,
                     "count": len(jax.devices())}
    ctx["tag"] = "[cpu rehearsal]"


_fake_device.__name__ = "phase_device"


def test_refuses_without_a_tpu(smoke, capsys):
    """Under JAX_PLATFORMS=cpu (this suite) the script fails at once: one
    clear line, a non-zero exit, no result."""
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_run_phase_order_and_last_line(smoke, monkeypatch, capsys):
    monkeypatch.setenv("LLM_MCP_TPU_ATTN", "pallas")  # kernels, interpret mode
    monkeypatch.setenv("LLM_MCP_TPU_RAGGED_IMPL", "kernel")
    monkeypatch.setenv("TPU_PREFILL_CHUNK", "64")
    monkeypatch.setenv("TPU_WARMUP", "1")
    monkeypatch.setenv("TPU_WARMUP_BG", "0")
    monkeypatch.setattr(smoke, "SETTINGS", smoke.Settings(
        model="tiny-llm", embed_model="tiny-embed", max_slots=4,
        max_seq_len=512, max_tokens=4, request_timeout_s=300))
    monkeypatch.setattr(
        smoke, "SHARED_PREFIX",
        "You route requests across a small fleet of accelerator hosts. "
        "Answer briefly and name the device you would pick and why. "
        "device-00 and device-01 are idle; device-02 is full.")
    # interpret mode lowers to plain XLA: the marker is the chip run's to find
    monkeypatch.setattr(
        smoke, "_compiled_step_text",
        lambda gen, phase, key: "tpu_custom_call " * (2 if phase == "fused_rag" else 1))
    def served_reference(ctx):
        """The real phase, and then its teeth: the same prompt with other
        tokens than the ones the engine served is refused."""
        import numpy as np

        smoke.phase_served_reference(ctx)
        prompt, emitted = smoke._served_for(ctx, "Summarize this log.")
        allowed = np.flatnonzero(np.asarray(ctx["gen"]._allowed_mask))
        other = [int(allowed[(int(np.searchsorted(allowed, t)) + 97) % len(allowed)])
                 for t in emitted]
        with pytest.raises(AssertionError, match="under the reference's choice"):
            smoke._hold_to_reference(ctx, "other tokens", prompt, other, smoke.SERVED_TOL_REL)

    served_reference.__name__ = "phase_served_reference"
    swap = {smoke.phase_device: _fake_device, smoke.phase_served_reference: served_reference}
    phases = tuple(swap.get(p, p) for p in smoke.ONE_CHIP_PHASES)
    monkeypatch.setattr(smoke, "ONE_CHIP_PHASES", phases)

    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    ran = [ln[4:] for ln in lines if ln.startswith("--- ")]
    assert ran == ["device", "cache", "boot", "chat", "served_reference", "determinism",
                   "embeddings", "kernel_parity", "engine_checks", "shutdown"]
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": last["device"]}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert lines[-1] == json.dumps(last)  # exactly the object, nothing more
    out = "\n".join(lines)
    assert "greedy repeat: identical" in out
    for name in ("long prompt", "shared prefix B", "shared prefix C"):
        assert f"reference {name!r}: 4 served tokens" in out
    assert "kernel falls=0" in out and "prefix hits=" in out


def test_failed_phase_is_a_failed_run(smoke, monkeypatch, capsys):
    """No try/except turns a failed phase into a pass: the exception leaves
    main() (a traceback and exit code 1 for the process), later phases do not
    run, and the result line is never printed."""
    ran = []

    def phase_a(ctx):
        ran.append("a")
        ctx["device"] = {"platform": "tpu", "kind": "x", "count": 1}

    def phase_b(ctx):
        ran.append("b")
        raise AssertionError("chat: stream ended without data: [DONE]")

    def phase_c(ctx):
        ran.append("c")

    monkeypatch.setattr(smoke, "ONE_CHIP_PHASES", (phase_a, phase_b, phase_c))
    with pytest.raises(AssertionError, match="DONE"):
        smoke.main([])
    assert ran == ["a", "b"]
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_option_runs_only_its_own_phases(smoke, monkeypatch, capsys):
    names = [p.__name__ for p in smoke.FOUR_CHIP_PHASES]
    assert names == ["phase_device", "phase_cache", "phase_four_boot", "phase_four_shares",
                     "phase_four_chat", "phase_four_reference", "phase_four_shutdown"]
    one_chip_only = {p.__name__ for p in smoke.ONE_CHIP_PHASES} - {"phase_device", "phase_cache"}
    assert not one_chip_only & set(names)

    ran = []

    def stub(name):
        def phase(ctx):
            ran.append(name)
            ctx.setdefault("device", {"platform": "tpu", "kind": "x", "count": 4})
        phase.__name__ = name
        return phase

    monkeypatch.setattr(smoke, "FOUR_CHIP_PHASES", tuple(stub(n) for n in names))
    monkeypatch.setattr(smoke, "ONE_CHIP_PHASES", (stub("one_chip_phase"),))
    assert smoke.main(["--four-chips"]) == 0
    assert ran == names
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "tpu", "kind": "x", "count": 4}}
