"""Utils tests: token estimation, message flattening, think-splitting, config.

Parity targets: reference `router_test.go:11-97` (EstimateTokens,
MessagesToPrompt) and think-tag handling (`worker/llm_worker/main.py:207-219`).
"""

from llm_mcp_tpu.utils import (
    estimate_tokens,
    messages_to_prompt,
    split_think,
    getenv_int,
    getenv_bool,
    Config,
)


def test_estimate_tokens_floor():
    assert estimate_tokens("") == 256
    assert estimate_tokens("abc") == 256
    assert estimate_tokens("x" * 1024) == 256
    assert estimate_tokens("x" * 4096) == 1024


def test_messages_to_prompt():
    msgs = [
        {"role": "system", "content": "be nice"},
        {"role": "user", "content": "hi"},
    ]
    assert messages_to_prompt(msgs) == "system: be nice\nuser: hi"
    # content-parts form
    msgs = [{"role": "user", "content": [{"type": "text", "text": "a"}, {"type": "text", "text": "b"}]}]
    assert messages_to_prompt(msgs) == "user: a b"
    assert messages_to_prompt([]) == ""


def test_split_think():
    t, a = split_think("<think>hmm</think>hello")
    assert t == "hmm" and a == "hello"
    t, a = split_think("no think here")
    assert t == "" and a == "no think here"
    t, a = split_think("<think>unterminated")
    assert t == "unterminated" and a == ""
    t, a = split_think("")
    assert t == "" and a == ""


def test_env_helpers(monkeypatch):
    monkeypatch.setenv("X_INT", "42")
    monkeypatch.setenv("X_BAD", "nope")
    monkeypatch.setenv("X_BOOL", "true")
    assert getenv_int("X_INT", 1) == 42
    assert getenv_int("X_BAD", 7) == 7
    assert getenv_int("X_MISSING", 9) == 9
    assert getenv_bool("X_BOOL")
    assert not getenv_bool("X_MISSING")


def test_config_snapshot(monkeypatch):
    monkeypatch.setenv("DEVICE_MAX_CONCURRENCY", "5")
    monkeypatch.setenv("OPENROUTER_API_KEY", "sk-test")
    cfg = Config()
    assert cfg.device_max_concurrency == 5
    assert cfg.has_openrouter() and not cfg.has_openai()


def test_compile_cache_rule_one_variable_one_fixed_default(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, that directory and no other
    (jax reads it itself: enable_compile_cache hands jax.config no
    directory); where it is not, the fixed <checkout>/.jax_cache — never a
    temp name. The retired TPU_COMPILE_CACHE is not consulted."""
    import os

    import jax

    from llm_mcp_tpu.utils import config as ucfg

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("TPU_COMPILE_CACHE", str(tmp_path / "retired-knob"))
    assert ucfg.DEFAULT_COMPILE_CACHE == os.path.join(repo, ".jax_cache")
    assert ucfg.compile_cache_path() == ucfg.DEFAULT_COMPILE_CACHE

    placed = tmp_path / "placed-from-outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    assert ucfg.compile_cache_path() == str(placed)

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
    assert ucfg.enable_compile_cache() == str(placed) and placed.is_dir()
    assert [k for k, _ in updates] == ["jax_persistent_cache_min_compile_time_secs"]


def test_compile_cache_dir_that_cannot_be_used_is_counted_not_raised(monkeypatch, tmp_path):
    import jax

    from llm_mcp_tpu.utils import config as ucfg

    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker / "cache"))
    monkeypatch.setattr(jax.config, "update", lambda k, v: None)
    monkeypatch.setattr(ucfg, "compile_cache_failures", 0)
    assert ucfg.enable_compile_cache() is None
    assert ucfg.compile_cache_failures == 1  # chip_smoke.py prints this


def test_platform_question_has_two_answers(monkeypatch):
    """utils/platform.py: tpu or cpu; anything else raises, it is never
    silently 'not a TPU'."""
    import types

    import jax
    import pytest

    from llm_mcp_tpu.utils import platform

    assert platform.device_platform() == "cpu" and not platform.on_tpu()
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(platform="gpu")])
    with pytest.raises(RuntimeError, match="unsupported JAX platform 'gpu'"):
        platform.on_tpu()
