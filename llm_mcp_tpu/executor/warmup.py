"""Warmup planner: cold start as a first-class, measured serving phase.

ROADMAP item 5's baseline is brutal: a fresh node pays ~248 s before its
first token (the serve path eats the whole executable zoo's XLA compiles
on demand), while a warm-cache boot pays ~21 s. Every ingredient for a
fix already exists and is measured — the CompileLedger knows exactly
which shapes cost what, PR 11 collapsed prefill to one executable per
pow2 T, and the persistent compile cache round-trips in tier-1. This
module is the missing orchestration: it takes the engine's *serving-shape
zoo* (the same (phase, key) vocabulary `_note_exec_shape` feeds the
ledger: admit/chunk/pf_rag/decode/fused/verify/restore), orders it by
measured compile cost x hit priority, AOT-compiles the **critical
prefix** synchronously at boot — first token needs exactly one admit
bucket + one prefill executable + one decode shape — and background-
compiles the rest on a low-priority thread while the engine serves.

Readiness is a three-state machine surfaced at `/v1/debug/warmup` and
honored by routing (a warming engine advertises reduced capacity via the
`warming` discovery tag instead of eating 4-minute TTFTs):

    cold -> first_token_ready -> fully_warm

Knobs: `TPU_WARMUP` (default 1; `0` is a TRUE no-op — no planner, no
synthetic compiles, byte-identical greedy output), `TPU_WARMUP_BG`
(default 1; `0` skips the background phase — only the critical prefix
warms). Background compiles only *stick* across boots when the
persistent compile cache is on (`utils/config.enable_compile_cache`): an AOT
lower().compile() populates the XLA cache that the serve path's jit
call then hits, skipping the dominant cost.

Like migration.py this module is deliberately engine-agnostic and
jax-free: the engine hands in a `compile_fn(phase, key) -> wall_s|None`
closure plus its zoo, and tests drive the planner with fakes (injected
slow compiles) without touching an accelerator stack.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

log = logging.getLogger("executor.warmup")

__all__ = [
    "READINESS_STATES",
    "WarmupPlanner",
    "WarmupStep",
    "key_str",
    "pack_priors",
    "plan_steps",
    "priors_from_table",
    "select_critical",
    "warmup_bg_enabled",
    "warmup_enabled",
]

READINESS_STATES = ("cold", "first_token_ready", "fully_warm")

# Phases an AOT compile can be synthesized for from the shape key alone
# (mirrors telemetry/perf.py WARMUP_PHASES — duplicated as a literal so
# this module stays importable standalone; tests pin the two in sync).
PLANNABLE_PHASES = ("admit", "chunk", "decode", "mixed", "pf_rag")

# Phases no first request dispatches. A mixed round needs a full batch already
# decoding, and the serve path first-dispatches both of its rungs with its
# first two rides (engine._stage_ride); the plan's step then records as `skip`.
# Unmeasured, they rank after every other shape: the plan's thread reaches
# them once traffic has had its chance, and compiles them only if it had none.
LATE_PHASES = ("mixed",)


def warmup_enabled() -> bool:
    """``TPU_WARMUP=0`` is a TRUE no-op: no planner object, no AOT
    compiles, no readiness tag — greedy output must be token-identical
    either way (warmup only moves *when* executables compile)."""
    return os.environ.get("TPU_WARMUP", "1") not in ("0", "false", "no")


def warmup_bg_enabled() -> bool:
    """``TPU_WARMUP_BG=0`` skips the background phase: only the critical
    prefix warms synchronously, the rest of the zoo compiles on first
    dispatch exactly as before."""
    return os.environ.get("TPU_WARMUP_BG", "1") not in ("0", "false", "no")


def key_str(key: tuple) -> str:
    """The CompileLedger's key encoding (engine `_compile_obs`):
    colon-joined str() of the tuple parts — priors from a ledger table or
    an imported warmup pack match plan steps through this."""
    return ":".join(str(p) for p in key)


@dataclass
class WarmupStep:
    """One executable shape in the plan. `status` lifecycle:
    pending -> done (compiled, wall recorded) | skip (phase unplannable,
    planner stopped, or the serve path dispatched the shape first) | fail
    (compile_fn raised)."""

    phase: str
    key: tuple
    priority: float = 0.0
    critical: bool = False
    status: str = "pending"
    wall_s: float = 0.0

    def as_dict(self) -> dict[str, Any]:
        return {
            "phase": self.phase,
            "key": key_str(self.key),
            "priority": round(self.priority, 6),
            "critical": self.critical,
            "status": self.status,
            "wall_s": round(self.wall_s, 4),
        }


def priors_from_table(table: list[dict[str, Any]]) -> dict[tuple, dict]:
    """Index CompileLedger aggregates (ledger.table() rows, or a warmup
    pack's exported plan) by (phase, key string) for priority scoring.
    Malformed rows are dropped, not raised — a stale pack must never
    block a boot."""
    priors: dict[tuple, dict] = {}
    for row in table or []:
        try:
            phase = str(row["phase"])
            ks = str(row["key"])
            count = max(1, int(row.get("count", 1)))
            total = float(row.get("total_s", 0.0))
        except (KeyError, TypeError, ValueError):
            continue
        priors[(phase, ks)] = {"count": count, "cost_s": total / count}
    return priors


def pack_priors(
    table: list[dict[str, Any]], cap: int = 256
) -> list[dict[str, Any]]:
    """Normalize ledger rows for cross-residency reuse (the model zoo
    captures these at swap-out and feeds them to the next swap-in's
    start_warmup). Keeps only well-formed rows, ordered by total compile
    seconds descending — the shapes worth re-warming first — capped so a
    long residency's ledger can't bloat the parked entry."""
    rows: list[dict[str, Any]] = []
    for row in table or []:
        try:
            rows.append({
                "phase": str(row["phase"]),
                "key": str(row["key"]),
                "count": max(1, int(row.get("count", 1))),
                "total_s": float(row.get("total_s", 0.0)),
            })
        except (KeyError, TypeError, ValueError):
            continue
    rows.sort(key=lambda r: -r["total_s"])
    return rows[: max(1, int(cap))]


def _score(phase: str, key: tuple, priors: dict[tuple, dict]) -> float:
    """Measured compile cost x hit priority when the ledger has seen the
    shape; otherwise a small shape-derived heuristic (smaller shapes score
    higher — they are what the first requests actually dispatch)."""
    p = priors.get((phase, key_str(key)))
    if p is not None:
        return p["cost_s"] * p["count"]
    size = 1.0
    for part in key:
        if isinstance(part, bool):
            continue
        if isinstance(part, (int, float)) and part > 0:
            size *= float(part)
    # unmeasured: rank below every measured shape, smallest-first within,
    # the late phases after all of those
    return 1.0 / (1.0 + size) * (1e-12 if phase in LATE_PHASES else 1e-6)


def select_critical(
    zoo: list[tuple[str, tuple]], priors: dict[tuple, dict]
) -> list[tuple[str, tuple]]:
    """The first-token prefix: exactly one admit bucket + one prefill
    executable + one decode shape. With priors, each slot takes its
    most-valuable measured shape (the fleet's actual first-hit traffic);
    cold, each takes its smallest — a single short greedy probe dispatches
    admit(1, min bucket) then decode(min Ba), and that probe is what
    start_warmup runs."""
    picks: list[tuple[str, tuple]] = []
    for slot in ("admit", ("chunk", "pf_rag"), "decode"):
        phases = (slot,) if isinstance(slot, str) else slot
        cands = [(ph, k) for ph, k in zoo if ph in phases]
        if not cands:
            continue
        measured = [c for c in cands if (c[0], key_str(c[1])) in priors]
        if measured:
            picks.append(max(measured, key=lambda c: _score(*c, priors)))
        else:
            # smallest shape = what a 1-request probe compiles anyway
            picks.append(min(cands, key=lambda c: _key_size(c[1])))
    return picks


def _key_size(key: tuple) -> float:
    size = 1.0
    for part in key:
        if isinstance(part, bool):
            continue
        if isinstance(part, (int, float)) and part > 0:
            size *= float(part)
    return size


def plan_steps(
    zoo: list[tuple[str, tuple]],
    priors: dict[tuple, dict] | None = None,
    critical: list[tuple[str, tuple]] | None = None,
) -> list[WarmupStep]:
    """Order the zoo into a plan: critical prefix first (in slot order),
    then the rest by descending priority (measured cost x hits, ties to
    smaller shapes). Duplicate (phase, key) entries collapse — pow2
    ladders from config enumeration and ledger-observed keys overlap."""
    priors = priors or {}
    if critical is None:
        critical = select_critical(zoo, priors)
    crit_set = {(ph, key_str(k)) for ph, k in critical}
    seen: set[tuple[str, str]] = set()
    crit_steps: list[WarmupStep] = []
    rest: list[WarmupStep] = []
    for ph, k in list(critical) + list(zoo):
        ident = (ph, key_str(k))
        if ident in seen:
            continue
        seen.add(ident)
        step = WarmupStep(
            phase=ph, key=tuple(k), priority=_score(ph, tuple(k), priors),
            critical=ident in crit_set,
        )
        (crit_steps if step.critical else rest).append(step)
    rest.sort(key=lambda s: (-s.priority, _key_size(s.key)))
    return crit_steps + rest


class WarmupPlanner:
    """Drives a plan through an engine-supplied compile hook and exposes
    the readiness state machine. `compile_fn(phase, key)` returns the
    compile wall in seconds, or None when the phase cannot be AOT-compiled
    (the step records as `skip` — it will compile on first real dispatch,
    exactly the pre-warmup behavior). Exceptions record as `fail` and
    never propagate: warmup is an accelerant, not a gate.

    `served(phase, key)` says whether the serve path has ALREADY first-
    dispatched that shape: the step then records as `skip` too. jit's own
    cache holds that executable and the persistent cache got it on the way,
    so lowering it again buys nothing, and it costs seconds of Python
    tracing under the GIL the serving threads need (v5e, PR 34: a cell's
    warm-up traffic first-dispatched 9 of its plan's 35 shapes, 4 of them
    before the plan's thread reached them, at 2.3-3.4 s a step). `fully_warm`
    keeps its meaning: every shape of the plan is compiled or already served."""

    def __init__(
        self,
        compile_fn: Callable[[str, tuple], float | None],
        steps: list[WarmupStep],
        *,
        throttle_s: float = 0.0,
        event: Callable[..., Any] | None = None,
        served: Callable[[str, tuple], bool] | None = None,
    ):
        self._compile_fn = compile_fn
        self._served = served
        self.steps = list(steps)
        self.throttle_s = max(0.0, float(throttle_s))
        self._event = event
        self._lock = threading.Lock()
        self._state = "cold"
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.started_at = time.time()
        self.first_token_ready_at: float | None = None
        self.fully_warm_at: float | None = None

    # -- state machine ------------------------------------------------------

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def _advance(self, state: str) -> None:
        with self._lock:
            # monotone: never move left (fully_warm cannot regress)
            if READINESS_STATES.index(state) <= READINESS_STATES.index(self._state):
                return
            self._state = state
            now = time.time()
            if state == "first_token_ready":
                self.first_token_ready_at = now
            elif state == "fully_warm":
                self.fully_warm_at = now
                if self.first_token_ready_at is None:
                    self.first_token_ready_at = now
        if self._event is not None:
            try:
                self._event("warmup", state=state,
                            t_s=round(time.time() - self.started_at, 3))
            except Exception:  # noqa: BLE001 — telemetry must not gate boot
                pass
        log.info("warmup state -> %s", state)

    # -- execution ----------------------------------------------------------

    def _run_step(self, step: WarmupStep) -> None:
        t0 = time.perf_counter()
        try:
            if self._served is not None and self._served(step.phase, step.key):
                wall = None
            else:
                wall = self._compile_fn(step.phase, step.key)
        except Exception as e:  # noqa: BLE001 — warmup never takes boot down
            step.status = "fail"
            step.wall_s = time.perf_counter() - t0
            log.warning("warmup compile %s %s failed: %s",
                        step.phase, step.key, e)
        else:
            if wall is None:
                step.status = "skip"
            else:
                step.status = "done"
                step.wall_s = float(wall)
        if self._event is not None:
            try:
                self._event(
                    "wu", phase=step.phase, key=key_str(step.key),
                    wall_ms=round(step.wall_s * 1e3, 1), outcome=step.status,
                    critical=step.critical,
                )
            except Exception:  # noqa: BLE001
                pass

    def run_critical(self) -> None:
        """Synchronous boot phase: compile the first-token prefix, then
        advertise first_token_ready. With an empty plan the engine is
        trivially warm."""
        for step in self.steps:
            if step.critical and step.status == "pending":
                self._run_step(step)
        self._advance("first_token_ready")
        if not any(s.status == "pending" for s in self.steps):
            self._advance("fully_warm")

    def start_background(self) -> None:
        """Compile the remaining zoo on a low-priority daemon thread while
        the engine serves; throttle_s sleeps between compiles keep the
        planner off the serve path's host CPU. Idempotent."""
        if not any(s.status == "pending" for s in self.steps):
            self._advance("fully_warm")
            return
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._bg_loop, name="warmup-bg", daemon=True
        )
        self._thread.start()

    def _bg_loop(self) -> None:
        for step in self.steps:
            if self._stop.is_set():
                break
            if step.status != "pending":
                continue
            self._run_step(step)
            if self.throttle_s:
                self._stop.wait(self.throttle_s)
        for step in self.steps:
            if step.status == "pending":
                step.status = "skip"  # stopped mid-plan: remainder on demand
        self._advance("fully_warm")

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=10)
            self._thread = None

    # -- telemetry ----------------------------------------------------------

    def stats(self) -> dict[str, Any]:
        with self._lock:
            state = self._state
        by_status: dict[str, int] = {}
        compiled_s = 0.0
        for s in self.steps:
            by_status[s.status] = by_status.get(s.status, 0) + 1
            if s.status == "done":
                compiled_s += s.wall_s
        return {
            "state": state,
            "steps": len(self.steps),
            "by_status": by_status,
            "critical": sum(1 for s in self.steps if s.critical),
            "bg_compiles_done": sum(
                1 for s in self.steps if s.status == "done" and not s.critical
            ),
            "compiled_s": round(compiled_s, 3),
            "started_at": self.started_at,
            "first_token_ready_s": (
                round(self.first_token_ready_at - self.started_at, 3)
                if self.first_token_ready_at else None
            ),
            "fully_warm_s": (
                round(self.fully_warm_at - self.started_at, 3)
                if self.fully_warm_at else None
            ),
            "plan": [s.as_dict() for s in self.steps],
        }
