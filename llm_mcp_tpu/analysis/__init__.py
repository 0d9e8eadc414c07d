"""llmtpu-lint: the repo-native static-analysis suite.

Five AST-only passes over the package — lock-order, donation-safety,
knob-registry, import-purity, registry-census — behind one runner with a
justified-allowlist baseline. Entry points:

- ``python -m llm_mcp_tpu.analysis`` (human report; ``--json`` for CI)
- ``scripts/lint_gate.py`` (CI gate)
- ``tests/test_analysis.py`` (tier-1: zero non-baselined findings)

See doc/static_analysis.md for the pass catalog and baseline workflow.
This package imports nothing heavier than ``ast`` — it must stay
runnable on a CPU-only host in well under the 30 s budget.
"""

from .core import (
    BASELINE_PATH,
    DEFAULT_CONFIG,
    BaselineEntry,
    Finding,
    PassResult,
    RepoIndex,
    SuiteResult,
    default_passes,
    parse_baseline,
    render_report,
    run_suite,
)

__all__ = [
    "BASELINE_PATH",
    "DEFAULT_CONFIG",
    "BaselineEntry",
    "Finding",
    "PassResult",
    "RepoIndex",
    "SuiteResult",
    "default_passes",
    "parse_baseline",
    "render_report",
    "run_suite",
]
