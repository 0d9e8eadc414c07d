#!/usr/bin/env python3
"""Static-analysis gate: run the llmtpu-lint suite and exit nonzero on
NEW findings.

    python scripts/lint_gate.py            # human report
    python scripts/lint_gate.py --json     # stable machine report (v1)

Reporting conventions: per-check [PASS]/[FAIL]/[SKIP] lines, skips warned
on stderr but never failed, a fail only for violations the baseline does
not justify. The suite (llm_mcp_tpu/analysis) is AST-only — no jax, no
package imports — so this gate runs anywhere Python runs, in seconds.

Exit codes: 0 clean (baselined findings allowed), 1 new findings or a
malformed baseline, 2 usage/environment error. Stale baseline entries
(matching nothing) are [SKIP]-warned, not failed — they mean debt was
paid; delete the entry in llm_mcp_tpu/analysis/baseline.txt.
"""

from __future__ import annotations

import os
import sys


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str]) -> int:
    json_mode = "--json" in argv
    bad = [a for a in argv if a not in ("--json",)]
    if bad:
        print(__doc__)
        print(f"lint_gate: unknown argument(s) {bad}", file=sys.stderr)
        return 2
    root = _repo_root()
    sys.path.insert(0, root)
    try:
        from llm_mcp_tpu.analysis import render_report, run_suite
    except ImportError as exc:
        print(f"lint_gate: cannot import the analysis suite: {exc}",
              file=sys.stderr)
        return 2

    result = run_suite(root)
    if json_mode:
        print(render_report(result, json_mode=True))
    else:
        for r in result.results:
            status = "FAIL" if any(
                f in result.new for f in r.findings
            ) else "PASS"
            print(f"  [{status}] {r.pass_id}: {len(r.findings)} finding(s) "
                  f"({r.seconds * 1000:.0f} ms)")
        for f in result.new:
            print(f"  [FAIL] {f.pass_id} {f.path}:{f.line}: {f.message}")
            print(f"         key: {f.key}")
        for f in result.baselined:
            print(f"  [PASS] baselined {f.pass_id} {f.key}")
        for e in result.stale_baseline:
            print(f"  [SKIP] stale baseline entry {e.pass_id} {e.key} "
                  f"(baseline.txt:{e.line})")
    if result.stale_baseline:
        print(
            "lint_gate: WARNING stale baseline entries match nothing — "
            "delete them from llm_mcp_tpu/analysis/baseline.txt: "
            + ", ".join(e.fingerprint for e in result.stale_baseline),
            file=sys.stderr,
        )
    if result.baseline_error:
        print(f"lint_gate: malformed baseline: {result.baseline_error}",
              file=sys.stderr)
        return 1
    if result.new:
        print(f"lint_gate: {len(result.new)} new finding(s)",
              file=sys.stderr)
        return 1
    if not json_mode:
        print("lint_gate: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
