"""The window's difference of the engine's account of rounds
(`perf_stats()["rounds"]`, `llm_mcp_tpu/telemetry/perf.py:RoundAccount`), for
the three readers that read it: every round of the window by step program, and
the stalls of the in-flight queue. None, without raising, from a program whose
snapshots lack the block (the parent's)."""

from __future__ import annotations

from benchmark import counters

MIXED_PROGRAM = "jit_mixed_round_fn"  # the mixed round's step program, as the trace names it
MIXED = "mixed_"  # its rows in the account, one a rung: mixed_128, mixed_256


def block(run: dict, edge: str) -> dict | None:
    return (run[edge].get("perf") or {}).get("rounds")


def by_program(run: dict) -> dict[str, dict[str, float]] | None:
    """{program: end minus start of every sum of its row}; a row the start
    lacks (a program first dispatched inside the window) starts at 0."""
    a, b = block(run, "start"), block(run, "end")
    if a is None or b is None:
        return None
    return {prog: {k: v - a["by_program"].get(prog, {}).get(k, 0) for k, v in row.items()}
            for prog, row in b["by_program"].items()}


def ms(row: dict) -> float | None:
    """Mean ms of the row's told rounds, on the host's clock between two
    blocked reads: beside the trace's, never in its place."""
    return 1e3 * row["device_s"] / row["told"] if row["told"] else None


def mixed(rows: dict) -> dict[str, float]:
    """The `mixed_*` rows' rounds together."""
    return sum(r["rounds"] for prog, r in rows.items() if prog.startswith(MIXED))


def log_rows(rows: dict) -> str:
    """program -> rounds, told rounds, ms a told round: what `mixed_round_ms`
    prints beside the trace's number."""
    return ", ".join(
        f"{prog} -> {int(r['rounds'])} rounds, {int(r['told'])} told, "
        + (f"{ms(r):.3f} ms" if r["told"] else "no ms")
        for prog, r in sorted(rows.items()) if r["rounds"])


def stalls(run: dict) -> dict | None:
    """The window's stalls: `count`, `seconds`, `excess_s`, `gc_s` and
    `by_phase` as differences, `longest_s` and `recent` from the rows whose
    `t` lies in the window (the account keeps the newest 16), and the
    window's seconds between the two snapshots."""
    a, b = block(run, "start"), block(run, "end")
    if a is None or b is None:
        return None
    out = {k: counters.delta(run, "perf", "rounds", "stalls", k) for k in ("count", "seconds", "excess_s", "gc_s")}
    out["by_phase"] = {}
    for ph, (n, s) in b["stalls"]["by_phase"].items():
        n0, s0 = a["stalls"]["by_phase"].get(ph, (0, 0.0))
        if n > n0:
            out["by_phase"][ph] = [n - n0, round(s - s0, 6)]
    w0, w1 = run["window_abs"]
    out["recent"] = [r for r in b["stalls"]["recent"] if w0 <= r["t"] < w1]
    out["longest_s"] = max((r["seconds"] for r in out["recent"]), default=0.0)
    out["window_s"] = (run["end"]["t"] - run["start"]["t"]) if "t" in run["start"] and "t" in run["end"] else w1 - w0
    out["gc_window_s"] = counters.delta(run, "perf", "rounds", "gc_s")
    return out
