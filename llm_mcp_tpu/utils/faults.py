"""Fault injection: deterministic, env-configurable failure seeding.

The reference has a rich failure-RECOVERY machinery (leases, retry budgets,
circuit breaker, offline propagation — SURVEY.md §5) but "fault injection:
none exists" is called out as a gap to close. This module closes it: any
subsystem can place a `maybe_fail("site")` probe on its hot path; operators
(and chaos tests) arm sites via one env var without touching code:

    FAULT_INJECT="worker.execute:0.3,engine.decode:0.05:delay=2"

Spec grammar (comma-separated):  site:probability[:key=value...]
  - probability in [0, 1] — chance each probe call trips
  - mode `delay=SECONDS` sleeps instead of raising (latency injection)
  - mode `error=MESSAGE` customizes the raised message

Draws come from a dedicated seeded RNG (`FAULT_SEED`, default 0) so chaos
runs are reproducible — the same seed trips the same calls. Probes are
no-ops (one dict lookup) when the site isn't armed; arming is read once at
first use and can be re-armed explicitly in tests via `configure()`.

Sites wired in-tree:
  worker.execute   — Executors.dispatch, before running any job kind
  worker.complete  — Worker.run_once, after execute / before reporting
                     (exercises lease-expiry reclaim: the job outcome is
                     computed but never reported, as if the worker died)
  engine.decode    — GenerationEngine decode loop (engine failure guards)
  engine.admit     — the read of a batched admission's first tokens, where
                     a poisoned admission surfaces (engine._read_admit)
  api.request      — HTTP request dispatch (client-visible 5xx)
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
from typing import Any

log = logging.getLogger("faults")


class FaultInjected(RuntimeError):
    """Raised by an armed probe. Deliberately a plain RuntimeError subclass:
    callers must survive it exactly as they would a real failure."""


class _Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._sites: dict[str, dict[str, Any]] = {}
        self._rngs: dict[str, random.Random] = {}
        self._seed = 0
        self._loaded = False
        self.trips: dict[str, int] = {}

    def configure(self, spec: str | None = None, seed: int | None = None) -> None:
        """Parse FAULT_INJECT-style spec. Explicit call re-arms (tests);
        passing None re-reads the environment. Every parse error is
        log-and-ignore — a chaos-config typo must never become a NEW
        failure mode in the component under test."""
        with self._lock:
            raw = os.environ.get("FAULT_INJECT", "") if spec is None else spec
            if seed is None:
                try:
                    seed = int(os.environ.get("FAULT_SEED", "0") or 0)
                except ValueError:
                    log.warning("bad FAULT_SEED %r; using 0",
                                os.environ.get("FAULT_SEED"))
                    seed = 0
            self._seed = seed
            self._sites = {}
            self._rngs = {}
            self.trips = {}
            for part in (raw or "").split(","):
                part = part.strip()
                if not part:
                    continue
                fields = part.split(":")
                if len(fields) < 2:
                    log.warning("fault spec %r missing probability; ignored", part)
                    continue
                site = fields[0].strip()
                try:
                    prob = float(fields[1])
                except ValueError:
                    log.warning("fault spec %r has bad probability; ignored", part)
                    continue
                opts: dict[str, Any] = {}
                bad = False
                for f in fields[2:]:
                    k, _, v = f.partition("=")
                    k, v = k.strip(), v.strip()
                    if k == "delay":
                        try:
                            opts[k] = float(v)
                        except ValueError:
                            log.warning("fault spec %r has bad delay; ignored", part)
                            bad = True
                            break
                    else:
                        opts[k] = v
                if bad:
                    continue
                self._sites[site] = {"prob": max(0.0, min(1.0, prob)), **opts}
                # per-site RNG: each site's trip sequence depends only on its
                # own call count, so multi-site / multi-threaded runs stay
                # reproducible per site under the same seed
                # string seeding is stable across processes (unlike hash())
                self._rngs[site] = random.Random(f"{seed}:{site}")
                log.warning("fault injection ARMED: %s p=%.2f %s", site, prob, opts)
            self._loaded = True

    def maybe_fail(self, site: str, detail: str = "") -> None:
        if not self._loaded:
            self.configure()
        # unarmed fast path: no lock — probes on hot paths (engine decode,
        # HTTP dispatch) must stay a single dict lookup when injection is off
        # (dict reads are atomic under the GIL; configure swaps whole entries)
        if site not in self._sites:
            return
        # read the site config and its RNG under ONE lock acquisition: a
        # concurrent configure() may swap both, and a half-read (cfg from the
        # old map, missing rng in the new one) must disarm, not KeyError in
        # the probed hot path
        with self._lock:
            cfg = self._sites.get(site)
            rng = self._rngs.get(site)
            if not cfg or rng is None:
                return
            trip = rng.random() < cfg["prob"]
            if trip:
                self.trips[site] = self.trips.get(site, 0) + 1
        if not trip:
            return
        if "delay" in cfg:
            d = cfg["delay"]
            log.warning("fault injected at %s: delay %.2fs %s", site, d, detail)
            time.sleep(d)
            return
        msg = cfg.get("error") or f"injected fault at {site}"
        log.warning("fault injected at %s: %s %s", site, msg, detail)
        raise FaultInjected(msg)

    def armed(self, site: str) -> bool:
        if not self._loaded:
            self.configure()
        return site in self._sites


_registry = _Registry()

configure = _registry.configure
maybe_fail = _registry.maybe_fail
armed = _registry.armed


def trip_counts() -> dict[str, int]:
    return dict(_registry.trips)
