"""What the tests of every model family share, so that a program is paid for
once for all the assertions made of it (ROADMAP C12):

- `engine_for`: a module's cases that only call an engine's step programs
  (built, never started) share ONE engine an option set, handed to each case
  with the device state it was built with. One preset's engines at a time, and
  none outlives its module: engines that live on keep their executables mapped,
  and past 65,000 memory maps a process's next load segfaults (PR 54).
- `jitted`: a model function a test calls step after step, traced and compiled
  once for the shapes it is given (called bare, every call dispatches its
  primitives one by one and traces its scans again: a second a decode step).
- `compiled_once`: a kernel's parity cases that share shapes and knobs and differ
  in data run through one executable.
- `reference_for`, `reference_source`, `retrace`: the loaders every family's file
  wrote out, and a reference traced again without every other trace going with it.

tests/conftest.py ends a module's engines and forgets its compiled functions
when the module's last case has run (`module_ends`)."""

from __future__ import annotations

import functools
import gc
import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- the engines of the module that is running -----------------------------------

_held: dict[tuple, tuple] = {}  # (model, attn, options) -> (engine, the state it was built with)
_fresh: list = []  # engines of the TEST that is running (`engine_of_its_own`)

_STATE = ("_ck", "_cv", "_d_temp", "_d_topk", "_d_topp", "_d_last_tok")


def device_state(eng):
    """What an engine's step programs read and write on the device, on the host."""
    return jax.tree.map(np.asarray, tuple(getattr(eng, name) for name in _STATE))


def restore(eng, state) -> None:
    for name, value in zip(_STATE, jax.tree.map(jnp.asarray, state)):
        setattr(eng, name, value)


def _build(monkeypatch, model, attn, kw):
    from llm_mcp_tpu.executor import GenerationEngine

    if attn:
        monkeypatch.setenv("LLM_MCP_TPU_ATTN", attn)  # read at construction AND where a step is traced
    return GenerationEngine(model, **kw)


def engine_for(monkeypatch, model: str, attn: str = "", **kw):
    """The module's engine of these options: built by the first case that asks,
    and handed to every case as it was built (cache, state pool, sampling rows
    and token ring restored). For cases that call `eng._ops[...]` and read
    arrays back. A case that starts an engine, queues requests or patches its
    attributes takes `engine_of_its_own`."""
    key = (model, attn, tuple(sorted((k, repr(v)) for k, v in kw.items())))
    if any(k[0] != model for k in _held):
        end_engines()  # one preset's engines at a time
    if key in _held:
        if attn:
            monkeypatch.setenv("LLM_MCP_TPU_ATTN", attn)  # this case's traces read it too
    else:
        eng = _build(monkeypatch, model, attn, kw)
        _held[key] = (eng, device_state(eng))
    eng, built = _held[key]
    restore(eng, built)
    return eng


def engine_of_its_own(monkeypatch, model: str, attn: str = "", **kw):
    """An engine no other case sees; `case_ends` ends it (a built engine's
    watchdog keeps it, and its executables, until its stop event is set)."""
    _fresh.append(_build(monkeypatch, model, attn, kw))
    return _fresh[-1]


def _end(eng) -> None:
    """A started engine's test shuts it down itself; this ends a built one's
    watchdog and waits for the thread, which holds the engine, to be gone."""
    eng._stop_evt.set()
    for thread in threading.enumerate():
        if thread.name == "engine-watchdog" and getattr(getattr(thread, "_target", None), "__self__", None) is eng:
            thread.join(timeout=5)


MAPS_HIGH = 30_000  # of the 65,530 a process may hold (`vm.max_map_count`)


def memory_maps() -> int:
    with open("/proc/self/maps") as f:
        return sum(1 for _ in f)


def case_ends() -> None:
    """A case's own engines end with it; and where the process holds more than
    `MAPS_HIGH` memory maps (every executable is a few), every trace and every
    compiled function is dropped, the shared engines' too: they trace again. A
    module that steps many programs (tests/test_hybrid.py alone in one process)
    came to 52,000 before this valve."""
    while _fresh:
        _end(_fresh.pop())
    if memory_maps() > MAPS_HIGH:
        forget_compiled()


def forget_compiled() -> None:
    jitted.cache_clear()
    _compiled.clear()
    jax.clear_caches()
    gc.collect()


def end_engines() -> None:
    case_ends()
    while _held:
        _end(_held.popitem()[1][0])
    gc.collect()


def module_ends() -> None:
    """No engine and no compiled function of this file's outlives its module."""
    end_engines()
    jitted.cache_clear()
    _compiled.clear()
    assert not _held and not _fresh


# -- model functions, compiled once ----------------------------------------------


@functools.lru_cache(maxsize=None)
def jitted(fn, *static, **static_kw):
    """`jax.jit` of `fn` with its leading arguments (a configuration) and these
    keywords held static: one trace and one compile for every call of a shape."""
    return jax.jit(functools.partial(fn, *static, **static_kw))


def stepwise(fn):
    """`fn(cfg, *arrays, **keywords)` through `jitted`: the configuration and the
    keywords that are no arrays (`skey=32`, `attn_impl="pallas"`) static."""

    def call(cfg, *args, **kw):
        static = {k: v for k, v in kw.items() if not hasattr(v, "shape")}
        return jitted(fn, cfg, **static)(*args, **{k: v for k, v in kw.items() if k not in static})

    return call


_compiled: dict = {}


def compiled_once(key, fn, *args):
    """`fn(*args)` through ONE executable a `key`: lowered and compiled at the
    first call, then called with each case's data. For parity cases that share
    shapes and trace-time knobs and differ in the data (lengths, ids): `key`
    names everything the trace reads besides the operands' shapes, and the
    caller clears the dispatcher's own trace cache inside `fn` where a knob is
    read at trace time (a compiled executable reads none again)."""
    if key not in _compiled:
        _compiled[key] = jax.jit(fn).lower(*args).compile()
    return _compiled[key](*args)


# -- references -----------------------------------------------------------------


def reference_for(name: str):
    """benchmark/references/<name>.py as a module: the plain forward a family's
    program is held to, loaded by path as the harness loads it."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "references", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def retrace(ref) -> None:
    """The next call of a reference's forward traces it again (`ref.LOWER`, the
    one thing lowered or left out, is read where it is traced). In place of
    `jax.clear_caches()`, which drops every trace of the PROCESS with it: each
    engine and each compiled step of the worker then traces and lowers its
    programs again, seconds a program where its kernels are interpreted. A stale
    trace cannot pass for a fresh one: a control that read as the plain forward
    fails its test, and so does a plain forward that read as a control."""
    for fn in vars(ref).values():
        if callable(getattr(fn, "clear_cache", None)):
            fn.clear_cache()


def reference_source(name: str) -> str:
    """The reference's code after its docstring (which names the program's files)."""
    with open(os.path.join(ROOT, "benchmark", "references", name + ".py")) as f:
        return f.read().split('"""', 2)[2]
