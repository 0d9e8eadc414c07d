"""llmtpu-lint core: the pass framework every analyzer plugs into.

The repo's correctness story leaned on runtime checks (OrderedLock rank
raises, the KERNEL_PARITY guard test, per-module subprocess import lints)
re-invented ad hoc in four test files. This package is the `go vet` the
Python/JAX rewrite never had: a shared AST/module index over the package,
a `Finding` type with a stable fingerprint (pass id + symbolic key, NO
line numbers — findings survive unrelated edits), an allowlist baseline so
only *new* violations fail, and a suite runner that every entry point
(`python -m llm_mcp_tpu.analysis`, `scripts/lint_gate.py`, the tier-1
test in tests/test_analysis.py) shares.

Design rules for passes:

- **AST only, never import.** A pass must never import the module it
  inspects — half the package pulls jax at import time, and the suite has
  to run on a proxy-only worker host in under 30 s. Anything a pass needs
  from a module (registry tuples, dict literals, docstrings) is extracted
  from the parse tree via the `literal_assignment` helpers here.
- **Symbolic keys.** A finding's `key` names the violation, not its
  coordinates: `nest:kvpool<-engine.stats@KVPool.admit`, not a line
  number. The baseline matches on `(pass_id, key)` so a baselined entry
  stays matched across reformatting, and a *moved* violation is still the
  same violation.
- **Config over hardcoding.** Every repo path a pass touches comes from
  `RepoIndex.config` (DEFAULT_CONFIG below) so tests can point a pass at
  fixture snippets in tmp dirs and assert it fires exactly once.
"""

from __future__ import annotations

import ast
import json
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Iterable

# Every path is repo-root-relative with forward slashes (normalized in
# RepoIndex.rel) so fingerprints are stable across platforms.
DEFAULT_CONFIG: dict = {
    # the package the suite walks
    "package": "llm_mcp_tpu",
    # documentation inputs
    "doc_readme": "doc/README.md",
    "doc_concurrency": "doc/concurrency.md",
    # registry-census inputs
    "kernel_module": "llm_mcp_tpu/kernels/attention.py",
    "parity_registry": "tests/test_kernel_parity.py",
    "engine_module": "llm_mcp_tpu/executor/engine.py",
    "dispatch_module": "llm_mcp_tpu/executor/dispatch.py",
    "zoo_module": "llm_mcp_tpu/executor/zoo.py",
    "perf_module": "llm_mcp_tpu/telemetry/perf.py",
    "recorder_module": "llm_mcp_tpu/telemetry/recorder.py",
    # knob-registry scan: the package plus the out-of-package readers the
    # operator doc documents
    "knob_extra_roots": ["scripts"],
    "knob_prefixes": ("TPU_", "LLM_MCP_TPU_"),
    # etypes the recorder census must explicitly list even if the engine
    # stops emitting them (tests/test_perf.py pinned these; wl/wf are the
    # workload-capture and latency-waterfall marks from telemetry/workload;
    # zoo/swap_in/swap_out are the model-zoo residency trail from
    # executor/zoo.py; cn_cmp/cnstep/cn_spec are the grammar-constrained
    # decoding trail from llm_mcp_tpu/constrain + the engine cn rounds)
    "required_etypes": (
        "pf_rag", "fused_rag", "perf", "wl", "wf",
        "zoo", "swap_in", "swap_out",
        "cn_cmp", "cnstep", "cn_spec",
    ),
}

BASELINE_PATH = "llm_mcp_tpu/analysis/baseline.txt"


@dataclass(frozen=True)
class Finding:
    """One violation: where it is and — via `key` — *what* it is.

    `path`/`line` are for humans and editors; `fingerprint` (pass_id +
    key) is what the baseline and the gate match on.
    """

    pass_id: str
    path: str
    line: int
    key: str
    message: str

    @property
    def fingerprint(self) -> str:
        return f"{self.pass_id}::{self.key}"

    def to_dict(self) -> dict:
        return {
            "pass": self.pass_id,
            "path": self.path,
            "line": self.line,
            "key": self.key,
            "message": self.message,
        }


class RepoIndex:
    """Shared parse-once AST loader over the repo tree.

    Passes ask for files by repo-relative path; parse results are cached
    so the five passes re-reading engine.py cost one parse. Missing files
    return None — a pass decides whether that is a finding (a registry
    moved) or a skip (an optional doc)."""

    def __init__(self, root: str, config: dict | None = None):
        self.root = os.path.abspath(root)
        self.config = dict(DEFAULT_CONFIG)
        if config:
            self.config.update(config)
        self._ast_cache: dict[str, ast.Module | None] = {}
        self._text_cache: dict[str, str | None] = {}
        self.parse_errors: list[Finding] = []

    # -- file access -------------------------------------------------------

    def rel(self, path: str) -> str:
        return os.path.relpath(path, self.root).replace(os.sep, "/")

    def abspath(self, relpath: str) -> str:
        return os.path.join(self.root, relpath.replace("/", os.sep))

    def exists(self, relpath: str) -> bool:
        return os.path.isfile(self.abspath(relpath))

    def text(self, relpath: str) -> str | None:
        if relpath not in self._text_cache:
            try:
                with open(self.abspath(relpath), encoding="utf-8") as fh:
                    self._text_cache[relpath] = fh.read()
            except OSError:
                self._text_cache[relpath] = None
        return self._text_cache[relpath]

    def ast(self, relpath: str) -> ast.Module | None:
        if relpath not in self._ast_cache:
            src = self.text(relpath)
            if src is None:
                self._ast_cache[relpath] = None
            else:
                try:
                    tree = ast.parse(src)
                    attach_parents(tree)
                    self._ast_cache[relpath] = tree
                except SyntaxError as exc:
                    self._ast_cache[relpath] = None
                    self.parse_errors.append(
                        Finding(
                            "framework", relpath, exc.lineno or 0,
                            f"syntax:{relpath}",
                            f"unparseable module: {exc.msg}",
                        )
                    )
        return self._ast_cache[relpath]

    # -- tree walks --------------------------------------------------------

    def package_files(self) -> list[str]:
        """Sorted repo-relative paths of every .py file in the package."""
        return self.files_under(self.config["package"])

    def files_under(self, relpath: str) -> list[str]:
        top = self.abspath(relpath)
        if os.path.isfile(top):
            return [relpath] if relpath.endswith(".py") else []
        out: list[str] = []
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(self.rel(os.path.join(dirpath, fn)))
        return sorted(out)


# -- AST extraction helpers shared by passes --------------------------------


def attach_parents(tree: ast.Module) -> None:
    """Thread `_lint_parent` links through the tree (ast has no parent
    pointers); RepoIndex does this on every parse so passes can walk up."""
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._lint_parent = node  # type: ignore[attr-defined]


def literal_assignment(tree: ast.Module, name: str) -> ast.expr | None:
    """The value expression of a module-level `name = <expr>` assignment
    (last one wins, matching runtime semantics)."""
    found: ast.expr | None = None
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    found = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            if isinstance(node.target, ast.Name) and node.target.id == name:
                found = node.value
    return found


def string_tuple(tree: ast.Module, name: str) -> list[str] | None:
    """A module-level tuple/list-of-strings assignment, e.g.
    DISPATCH_PHASES."""
    expr = literal_assignment(tree, name)
    if not isinstance(expr, (ast.Tuple, ast.List)):
        return None
    out = []
    for elt in expr.elts:
        if not (isinstance(elt, ast.Constant) and isinstance(elt.value, str)):
            return None
        out.append(elt.value)
    return out


def dict_string_keys(tree: ast.Module, name: str) -> list[str] | None:
    """String keys of a module-level dict literal (values may be anything,
    including lambdas — PHASE_COSTS)."""
    expr = literal_assignment(tree, name)
    if not isinstance(expr, ast.Dict):
        return None
    out = []
    for k in expr.keys:
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
            return None
        out.append(k.value)
    return out


def int_constants(tree: ast.Module) -> dict[str, int]:
    """Module-level NAME = <int literal> bindings — enough to resolve
    `rank=MIGRATION_LOCK_RANK`-style indirection without importing."""
    out: dict[str, int] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
            if (
                isinstance(tgt, ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, int)
                and not isinstance(node.value.value, bool)
            ):
                out[tgt.id] = node.value.value
    return out


def call_string_args(
    tree: ast.Module, attr_names: Iterable[str]
) -> dict[str, set[str]]:
    """First-argument string constants of every `<something>.name("...")`
    call, per name — the engine-side half of the registry censuses
    (`_compile_obs`, `_note_exec_shape`, `event`)."""
    out: dict[str, set[str]] = {a: set() for a in attr_names}
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in out
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            out[node.func.attr].add(node.args[0].value)
    return out


def walk_skipping_functions(node: ast.AST) -> Iterable[ast.AST]:
    """Walk a tree but do not descend into function/lambda bodies — the
    shape of "executed at import time"."""
    stack = [node]
    while stack:
        cur = stack.pop()
        yield cur
        for child in ast.iter_child_nodes(cur):
            if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            stack.append(child)


# -- baseline ----------------------------------------------------------------
#
# Format: one finding per line, `pass_id<spaces>key  # justification`.
# The justification comment is MANDATORY — a baseline entry is a decision,
# and decisions get written down. `parse_baseline` rejects bare entries so
# the file can't silently absorb violations.


@dataclass
class BaselineEntry:
    pass_id: str
    key: str
    justification: str
    line: int

    @property
    def fingerprint(self) -> str:
        return f"{self.pass_id}::{self.key}"


def parse_baseline(text: str, path: str = BASELINE_PATH) -> list[BaselineEntry]:
    entries: list[BaselineEntry] = []
    for lineno, raw in enumerate(text.splitlines(), start=1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        body, _, comment = line.partition("#")
        fields = body.split()
        if len(fields) != 2 or not comment.strip():
            raise ValueError(
                f"{path}:{lineno}: baseline entries are "
                f"'pass_id key  # justification' (justification required); "
                f"got {raw!r}"
            )
        entries.append(
            BaselineEntry(fields[0], fields[1], comment.strip(), lineno)
        )
    return entries


# -- suite -------------------------------------------------------------------


@dataclass
class PassResult:
    pass_id: str
    findings: list[Finding]
    seconds: float


@dataclass
class SuiteResult:
    results: list[PassResult]
    new: list[Finding] = field(default_factory=list)
    baselined: list[Finding] = field(default_factory=list)
    stale_baseline: list[BaselineEntry] = field(default_factory=list)
    baseline_error: str | None = None
    seconds: float = 0.0

    @property
    def findings(self) -> list[Finding]:
        return [f for r in self.results for f in r.findings]

    @property
    def ok(self) -> bool:
        return not self.new and self.baseline_error is None

    def to_dict(self) -> dict:
        return {
            "version": 1,
            "ok": self.ok,
            "seconds": round(self.seconds, 3),
            "passes": [
                {
                    "pass": r.pass_id,
                    "findings": len(r.findings),
                    "seconds": round(r.seconds, 3),
                }
                for r in self.results
            ],
            "new": [f.to_dict() for f in self.new],
            "baselined": [f.to_dict() for f in self.baselined],
            "stale_baseline": [
                {"pass": e.pass_id, "key": e.key, "line": e.line}
                for e in self.stale_baseline
            ],
            "baseline_error": self.baseline_error,
        }


def default_passes() -> list:
    """The six registered passes, in report order. Imported lazily so
    `core` stays importable from any of them."""
    from . import census, dispatch_surface, donation, imports_lint, knobs, lock_order

    return [
        lock_order.LockOrderPass(),
        donation.DonationSafetyPass(),
        knobs.KnobRegistryPass(),
        imports_lint.ImportPurityPass(),
        census.RegistryCensusPass(),
        dispatch_surface.DispatchSurfacePass(),
    ]


def run_suite(
    root: str,
    passes: list | None = None,
    config: dict | None = None,
    baseline_text: str | None = None,
) -> SuiteResult:
    """Run the passes over `root`, split findings into new vs baselined.

    `baseline_text=None` loads the committed baseline file (missing file
    == empty baseline); pass `""` to run baseline-free."""
    index = RepoIndex(root, config)
    results: list[PassResult] = []
    t_suite = time.monotonic()
    for p in passes if passes is not None else default_passes():
        t0 = time.monotonic()
        found = sorted(
            p.run(index), key=lambda f: (f.path, f.line, f.key)
        )
        results.append(PassResult(p.pass_id, found, time.monotonic() - t0))
    if index.parse_errors:
        results.insert(
            0, PassResult("framework", list(index.parse_errors), 0.0)
        )

    out = SuiteResult(results)
    if baseline_text is None:
        baseline_text = index.text(BASELINE_PATH) or ""
    try:
        entries = parse_baseline(baseline_text)
    except ValueError as exc:
        out.baseline_error = str(exc)
        entries = []
    allow = {e.fingerprint: e for e in entries}
    seen: set[str] = set()
    for f in out.findings:
        if f.fingerprint in allow:
            out.baselined.append(f)
            seen.add(f.fingerprint)
        else:
            out.new.append(f)
    out.stale_baseline = [e for e in entries if e.fingerprint not in seen]
    out.seconds = time.monotonic() - t_suite
    return out


def render_report(result: SuiteResult, json_mode: bool = False) -> str:
    if json_mode:
        return json.dumps(result.to_dict(), indent=2, sort_keys=True)
    lines: list[str] = []
    for r in result.results:
        lines.append(
            f"[{r.pass_id}] {len(r.findings)} finding(s) "
            f"({r.seconds * 1000:.0f} ms)"
        )
    if result.baseline_error:
        lines.append(f"BASELINE ERROR: {result.baseline_error}")
    for f in result.new:
        lines.append(f"  NEW {f.pass_id} {f.path}:{f.line}: {f.message}")
        lines.append(f"      key: {f.key}")
    for f in result.baselined:
        lines.append(
            f"  baselined {f.pass_id} {f.path}:{f.line}: {f.key}"
        )
    for e in result.stale_baseline:
        lines.append(
            f"  stale-baseline {e.pass_id} {e.key} "
            f"(baseline.txt:{e.line} matches nothing — delete the entry)"
        )
    verdict = "OK" if result.ok else "FAIL"
    lines.append(
        f"{verdict}: {len(result.new)} new, {len(result.baselined)} "
        f"baselined, {len(result.stale_baseline)} stale baseline entr"
        f"{'y' if len(result.stale_baseline) == 1 else 'ies'} "
        f"in {result.seconds:.2f}s"
    )
    return "\n".join(lines)


# Typing convenience for passes (duck-typed: anything with pass_id + run).
PassFn = Callable[[RepoIndex], list[Finding]]
