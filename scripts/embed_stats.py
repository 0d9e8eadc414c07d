#!/usr/bin/env python3
"""One run of an embedding cell through the harness itself, with the engine's
own counters of the window beside the result line.

    chiprun -- python3 scripts/embed_stats.py --workload embed_batch --seed <n> --seconds 40 --trace 0

The arguments are `benchmark/run.py`'s and so is the result line. The line
`embed:` before it holds the window's difference of `EmbeddingEngine.stats()`
(`forwards`, `ahead`, the seconds) with `ahead_share` = ahead / forwards,
`host_locked_ms` and `forward_wait_ms` a forward, and `inflight_max` (a largest
value since boot, not a difference). Since PR 53 a call dispatches ahead of its
fetch, and `benchmark/run.py:EmbedTap` blocks every dispatch of a TRACED run, so
only an untraced run shows how often the mechanism engages: until a `benchmark`
PR enters `ahead / forwards` as a per-layer metric (PERF.md section 7), this is
how a builder reads it. `--hlo PATH` also writes the StableHLO text of the
engine's forward at the cell's (2, 512) shape there and prints its sha256: the
same text on two trees is the same program (it costs the set-up its tracing, so
not in a run whose `setup_s` is compared).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

SUMS = ("forwards", "ahead", "rows", "lock_wait_s", "forward_s", "host_locked_s")


def window_block(start: dict, end: dict) -> dict:
    """The window's difference of the sums a tree's `stats()` has (the parent
    of PR 53 has no `ahead`), and the ratios read from it."""
    d = {k: end[k] - start[k] for k in SUMS if k in end}
    n = d["forwards"] or 1
    if "ahead" in d:
        d["ahead_share"] = d["ahead"] / n
    d["host_locked_ms"] = 1e3 * d["host_locked_s"] / n
    d["forward_wait_ms"] = 1e3 * d["forward_s"] / n
    if "inflight_max" in end:
        d["inflight_max"] = end["inflight_max"]
    return d


def write_hlo(emb, path: str) -> None:
    import numpy as np

    tokens = np.zeros((emb.forward_tokens // 512, 512), np.int32)
    lengths = np.zeros((tokens.shape[0], emb.texts_per_row), np.int32)
    text = emb._fwd.lower(emb.params, tokens, lengths).as_text()
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)
    bench_run.say(f"forward at {tokens.shape}: StableHLO {len(text)} bytes, "
                  f"sha256 {hashlib.sha256(text.encode()).hexdigest()} -> {path}")


def main(argv: list[str]) -> int:
    hlo = ""
    if "--hlo" in argv:
        at = argv.index("--hlo")
        hlo, argv = argv[at + 1], argv[:at] + argv[at + 2:]
    boot, snapshot, measure = bench_run.boot, bench_run.snapshot, bench_run.measure

    def boot_and_lower(config):
        sut = boot(config)
        if hlo:
            write_hlo(sut["emb"], hlo)
        return sut

    def snapshot_with_embed(sut, compiles):
        return dict(snapshot(sut, compiles), embed=sut["emb"].stats(recent=False))

    def measure_and_say(*args, **kw):
        run = measure(*args, **kw)
        bench_run.say("embed: " + json.dumps(window_block(run["start"]["embed"], run["end"]["embed"])))
        return run

    bench_run.boot, bench_run.snapshot, bench_run.measure = boot_and_lower, snapshot_with_embed, measure_and_say
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
