"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Multi-chip sharding is tested without TPU hardware by running JAX's CPU
backend with 8 virtual host devices (the pattern recommended in SURVEY.md §4:
`XLA_FLAGS=--xla_force_host_platform_device_count=8`). Must run before the
first `import jax` anywhere in the test session.
"""

import os
import sys

# A test run writes no bytecode into the tree it tests (a `__pycache__/` beside
# the readers of benchmark/layer_metrics/ is a directory where a test of
# tests/benchmark/ lists files: ROADMAP B1 (0)); the children the tests start
# inherit the variable.
sys.dont_write_bytecode = True
os.environ.setdefault("PYTHONDONTWRITEBYTECODE", "1")

# Force the CPU whatever the session's environment preselects: unit tests
# target the virtual mesh; chip_smoke.py and the serving entry points use the
# real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache: engine tests rebuild the same tiny-model
# executables dozens of times across files; dedupe the compiles within (and
# across) suite runs. A cache of the CPU tests' own, inside the checkout and
# apart from the accelerator entry points' `.jax_cache/`, placed through the
# one rule everything follows (utils/config.enable_compile_cache:
# JAX_COMPILATION_CACHE_DIR where set). Set in the environment BEFORE jax is
# imported, so jax reads it itself and subprocess tests (distributed, slice,
# worker) inherit it. Compiles for a described chip keep out of it
# (tests/test_tpu_compile.py turns the cache off around them).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache_cpu_tests"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.2")

# Plugins (jaxtyping) may import jax before this conftest, freezing config
# defaults from the original env — override via jax.config as well.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update(
    "jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])

from llm_mcp_tpu.utils.config import enable_compile_cache  # noqa: E402

enable_compile_cache(min_compile_s=0.2)

# Serving boots warm up by default (CoreServer.start → boot_warmup); the
# dozens of tests that start a CoreServer around a tiny engine must not
# each pay the shape-zoo AOT sweep. Tests that exercise the planner
# (test_warmup.py) opt back in per-test via monkeypatch.
os.environ.setdefault("TPU_WARMUP", "0")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _a_cases_engines_end_with_it():
    """tests/family.py: an engine a case built for itself ends with the case."""
    import family

    yield
    family.case_ends()


@pytest.fixture(autouse=True, scope="module")
def _a_modules_engines_end_with_it():
    """tests/family.py: no shared engine, and no function compiled for a
    module's cases, outlives the module."""
    import family

    yield
    family.module_ends()


@pytest.fixture()
def db():
    from llm_mcp_tpu.state import Database

    d = Database(":memory:")
    yield d
    d.close()


@pytest.fixture()
def queue(db):
    from llm_mcp_tpu.state import JobQueue

    return JobQueue(db)


@pytest.fixture()
def catalog(db):
    from llm_mcp_tpu.state import Catalog

    return Catalog(db)
