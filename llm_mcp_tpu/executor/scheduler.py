"""Token-budget prefill/decode scheduler (stall-free continuous batching).

The Sarathi-Serve / vLLM insight: schedule prefill by *token budget inside
the decode round*, not by host wall-clock alternation. The engine loop asks
`decide()` once per iteration for a prefill token budget, stages that many
prompt tokens from mid-prefill slots, and fuses them into the same device
dispatch as the decode round — decode cadence never stalls behind a prefill
backlog, and TTFT is bounded by budget arithmetic instead of an
environment-tuned multiplier (the retired `TPU_PREFILL_BOOST`, whose
wall-clock budget let prefill monopolize the loop on a locally-attached
chip: 2428 → 464.7 tok/s serve, prefill 81–93% of window wall).

Policy, per round with active decode slots:

  fair_cap = decode_round_s / prefill_tok_s
      The prefill token count whose device time ≈ one decode round, so a
      fused round costs at most ~2× a pure decode round — in-flight streams'
      inter-token latency stays within 2× their no-backlog cadence.
  need = backlog_tokens / rounds_until_deadline
      The drain rate that activates the OLDEST mid-prefill prompt within
      `target_ttft_ms` of its arrival.
  budget = clamp(need, min_budget, fair_cap)
      `need > fair_cap` means the deadline is unreachable without starving
      decode; the starvation counter records it (telemetry: raise
      target_ttft_ms, add capacity, or shed load).

With ZERO active decode slots (pure-prefill window — e.g. a cold burst of
long prompts) there is no cadence to protect: the budget is the whole
backlog and chunks run back-to-back.

Both cost terms self-tune from measured dispatches (EMAs): decode-round
seconds from prefill-free rounds, per-token prefill seconds from standalone
chunk dispatches and from the fused rounds' time over the decode EMA. The
same object drives `GenerationEngine` and the multi-host `SliceEngine`
leader (followers replay dispatches and need no policy).
"""

from __future__ import annotations

import math
import time

from ..telemetry.recorder import get_recorder

__all__ = ["TokenBudgetScheduler", "parse_tenant_quotas"]

_EMA = 0.7  # keep-fraction; matches the engine's old decode-time smoothing

# Per-tenant quota burst window: a tenant's token bucket holds this many
# seconds of its rate, so short bursts ride through while sustained
# overload throttles within a couple of windows.
TENANT_BURST_S = 2.0


def parse_tenant_quotas(spec: str) -> dict[str, float]:
    """`TPU_TENANT_QUOTAS="alice=600,bob=300"` -> {"alice": 600.0, ...}.

    Values are tokens/second. A `*` key sets the default for tenants not
    named explicitly; tenants with no quota (and the empty tenant id) are
    unmetered. Malformed entries are dropped rather than raised — a typo'd
    quota must not take the serve path down."""
    out: dict[str, float] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        name, _, val = part.partition("=")
        try:
            rate = float(val)
        except ValueError:
            continue
        if name.strip() and rate > 0:
            out[name.strip()] = rate
    return out


class TokenBudgetScheduler:
    def __init__(
        self,
        *,
        target_ttft_ms: float = 2000.0,
        min_budget: int = 64,
        decode_seed_s: float = 0.05,
        prefill_tok_seed_s: float = 1e-4,
        tenant_quotas: dict[str, float] | None = None,
    ):
        self.target_ttft_s = max(1.0, float(target_ttft_ms)) / 1000.0
        # floor: a chunk dispatch costs ~a weight pass regardless of size, so
        # sub-floor budgets would pay full dispatch overhead per few tokens
        self.min_budget = max(1, int(min_budget))
        # EMA seeds — replaced by measurements after the first observed
        # dispatches; the seeds only shape the first few cold rounds
        self.decode_round_s = float(decode_seed_s)
        self.prefill_tok_s = float(prefill_tok_seed_s)
        self.last_budget = 0
        self.starved_rounds = 0
        self.verify_rounds = 0
        self.verify_tokens = 0
        # Pad-waste accounting (ragged-prefill line of record): dispatches
        # report both their TRUE token count and the DISPATCHED shape
        # (rows × bucket for the padded path, packed T for ragged). The
        # per-token cost EMA divides by the dispatched count — compute
        # scales with pads, and attributing pad time to true tokens
        # inflated the EMA and shrank fair_cap under mixed fill (the
        # pre-ragged bug this fixes). The cumulative totals feed the
        # prefill_pad_waste_pct stat of stats() (/v1/dashboard's prefill block).
        self.prefill_true_tokens = 0
        self.prefill_padded_tokens = 0
        self.pad_waste = 0.0  # EMA of per-dispatch waste fraction
        # Per-tenant quotas (model zoo tenancy): tokens/second per tenant,
        # enforced as token buckets holding TENANT_BURST_S of rate. The
        # EMA-costed budget machinery above stays global — quotas act at
        # ADMISSION (tenant_admit -> per-tenant 429), so an over-quota
        # tenant sheds at the door instead of starving in-flight streams.
        # Empty dict ⇒ every tenant unmetered ⇒ zero behavior change.
        self.tenant_quotas = {
            k: float(v) for k, v in (tenant_quotas or {}).items()
            if float(v) > 0
        }
        self._tenant_level: dict[str, float] = {}  # bucket fill, tokens
        self._tenant_ts: dict[str, float] = {}     # last refill stamp
        self.tenant_throttled: dict[str, int] = {}  # tenant -> 429 count
        self.tenant_charged: dict[str, int] = {}    # tenant -> tokens billed

    # -- cost observation --------------------------------------------------

    def observe_decode(self, round_s: float) -> None:
        """A prefill-free decode round's wall time (dispatch → fetch)."""
        if round_s > 0:
            self.decode_round_s = _EMA * self.decode_round_s + (1 - _EMA) * round_s

    def observe_prefill(
        self, tokens: int, seconds: float, padded_tokens: int = 0
    ) -> None:
        """A standalone chunk dispatch: `tokens` TRUE prompt tokens in
        `seconds`. `padded_tokens` is the dispatched token shape (≥ tokens;
        0 ⇒ unknown, treated as un-padded): the cost EMA divides by it —
        the device computed every pad — while the waste ratio records how
        much of the dispatch was pads. `seconds` of 0 (a first dispatch: its
        wall is a compile's) counts the tokens and the pads and teaches the
        cost EMA nothing."""
        if tokens <= 0:
            return
        comp = max(int(tokens), int(padded_tokens))
        self.prefill_true_tokens += int(tokens)
        self.prefill_padded_tokens += comp
        waste = 1.0 - tokens / comp
        self.pad_waste = _EMA * self.pad_waste + (1 - _EMA) * waste
        if seconds > 0:
            per = min(1.0, max(1e-8, seconds / comp))
            self.prefill_tok_s = _EMA * self.prefill_tok_s + (1 - _EMA) * per

    def observe_fused(
        self, round_s: float, prefill_tokens: int, padded_tokens: int = 0
    ) -> None:
        """A fused round: attribute the time over the decode EMA to its
        prefill tokens. Rounds faster than the EMA teach nothing (the
        residual would be negative)."""
        extra = round_s - self.decode_round_s
        if prefill_tokens > 0 and extra > 0:
            self.observe_prefill(
                prefill_tokens, extra, padded_tokens=padded_tokens
            )

    def observe_verify(self, tokens: int, seconds: float) -> None:
        """A speculative verify dispatch: `tokens` chunk positions (the base
        token plus drafts, summed over slots) in `seconds`. Verify rides the
        same chunked-prefill machinery as prompt chunks, so its per-token
        cost feeds the same EMA the budget arithmetic runs on."""
        self.verify_rounds += 1
        self.verify_tokens += max(0, int(tokens))
        self.observe_prefill(tokens, seconds)

    # -- policy ------------------------------------------------------------

    def fair_cap(self) -> int:
        """Prefill tokens whose estimated device time ≈ one decode round.
        The budget is granted in TRUE tokens but a padded dispatch computes
        its pads too — discount by the observed waste EMA so `cap` true
        tokens of staging still land ≈ one decode round of device time
        (under ragged prefill the waste EMA ≈ 0 and the discount vanishes)."""
        cap = self.decode_round_s / self.prefill_tok_s
        cap *= max(0.0, 1.0 - self.pad_waste)
        return max(self.min_budget, int(cap))

    def decide(
        self,
        backlog_tokens: int,
        n_active: int,
        oldest_wait_s: float,
        reserved_tokens: int = 0,
    ) -> int:
        """Prefill token budget for the next engine iteration.

        backlog_tokens: prompt tokens not yet written for mid-prefill slots.
        n_active: decoding slots this round (0 ⇒ pure-prefill window).
        oldest_wait_s: age of the oldest mid-prefill request.
        reserved_tokens: chunk tokens this iteration already owes elsewhere —
            a speculative verify dispatch costs chunk positions through the
            same machinery, so they come out of the round's prefill budget
            (the budget may drop to 0; the backlog waits a round rather than
            stacking verify + a full prefill chunk on one decode cadence).
        """
        if backlog_tokens <= 0:
            self.last_budget = 0
            return 0
        if n_active == 0:
            # pure-prefill window: no decode cadence to protect — run the
            # whole backlog back-to-back (the stale-budget bug this replaces
            # paced cold bursts in arbitrary 50 ms wall-clock slices)
            self.last_budget = backlog_tokens
            get_recorder().event(
                "budget", budget=backlog_tokens, backlog=backlog_tokens,
                n_active=0, starved=False,
            )
            return backlog_tokens
        cap = self.fair_cap()
        headroom_s = max(self.target_ttft_s - oldest_wait_s, self.decode_round_s)
        rounds_left = max(1.0, headroom_s / max(self.decode_round_s, 1e-6))
        need = int(math.ceil(backlog_tokens / rounds_left))
        starved = need > cap
        if starved:
            self.starved_rounds += 1
        budget = max(self.min_budget, min(need, cap))
        if reserved_tokens > 0:
            budget = max(0, budget - int(reserved_tokens))
        self.last_budget = budget
        # flight-recorder step event (telemetry/recorder.py): the decision
        # a post-mortem needs to explain a TTFT burn or a decode stall —
        # what budget was granted against what backlog, and whether the
        # deadline was already unreachable (starved)
        get_recorder().event(
            "budget", budget=budget, backlog=backlog_tokens,
            n_active=n_active, starved=starved,
        )
        return budget

    # -- per-tenant quotas -------------------------------------------------

    def _tenant_rate(self, tenant: str) -> float:
        """Quota for `tenant` in tokens/s; 0 ⇒ unmetered. The `*` entry is
        the default for tenants with no explicit row."""
        if not tenant or not self.tenant_quotas:
            return 0.0
        return self.tenant_quotas.get(tenant, self.tenant_quotas.get("*", 0.0))

    def _refill(self, tenant: str, rate: float, now: float) -> float:
        """Advance `tenant`'s bucket to `now` and return its level."""
        burst = rate * TENANT_BURST_S
        level = self._tenant_level.get(tenant, burst)
        last = self._tenant_ts.get(tenant, now)
        level = min(burst, level + rate * max(0.0, now - last))
        self._tenant_level[tenant] = level
        self._tenant_ts[tenant] = now
        return level

    def tenant_charge(
        self, tenant: str, tokens: int, now: float | None = None
    ) -> None:
        """Bill `tokens` (prompt + generated) against `tenant`'s bucket.
        The level may go negative — a large request pushes the tenant's
        next admission out proportionally — but is floored at one burst of
        debt so a single huge request can't lock a tenant out forever."""
        rate = self._tenant_rate(tenant)
        if rate <= 0 or tokens <= 0:
            return
        now = time.monotonic() if now is None else now
        level = self._refill(tenant, rate, now)
        burst = rate * TENANT_BURST_S
        self._tenant_level[tenant] = max(-burst, level - tokens)
        self.tenant_charged[tenant] = (
            self.tenant_charged.get(tenant, 0) + int(tokens)
        )

    def tenant_admit(
        self, tenant: str, now: float | None = None
    ) -> tuple[bool, float]:
        """Quota gate for one arriving request: (admit, retry_after_s).
        Unmetered tenants always admit. A drained bucket sheds with the
        seconds until it refills past zero — the API turns that into a
        per-tenant 429 + Retry-After."""
        rate = self._tenant_rate(tenant)
        if rate <= 0:
            return True, 0.0
        now = time.monotonic() if now is None else now
        level = self._refill(tenant, rate, now)
        if level >= 0.0:
            return True, 0.0
        self.tenant_throttled[tenant] = self.tenant_throttled.get(tenant, 0) + 1
        return False, -level / rate

    def tenant_stats(self) -> dict[str, dict[str, float]]:
        """Per-tenant quota detail for /v1/debug/perf and the dashboard."""
        now = time.monotonic()
        out: dict[str, dict[str, float]] = {}
        for tenant in sorted(
            set(self.tenant_quotas) - {"*"}
            | set(self._tenant_level) | set(self.tenant_throttled)
        ):
            rate = self._tenant_rate(tenant)
            out[tenant] = {
                "quota_tok_per_s": rate,
                "bucket_tokens": (
                    self._refill(tenant, rate, now) if rate > 0 else 0.0
                ),
                "throttled_total": float(self.tenant_throttled.get(tenant, 0)),
                "charged_tokens": float(self.tenant_charged.get(tenant, 0)),
            }
        return out

    def drain_estimate_s(
        self,
        n_waiting: int,
        mean_tokens: float,
        decode_chunk: int,
        max_slots: int,
    ) -> float:
        """EMA-costed estimate of seconds until `n_waiting` queued requests
        could start: waves of `max_slots` requests, each running
        `mean_tokens / decode_chunk` decode rounds at the observed round
        EMA. Feeds the API's shed path (`Retry-After` on 429) — a coarse
        but finite, self-tuning number beats a constant."""
        waves = math.ceil(max(1, int(n_waiting)) / max(1, int(max_slots)))
        rounds = max(1.0, float(mean_tokens) / max(1, int(decode_chunk)))
        round_s = self.decode_round_s if self.decode_round_s > 0 else 0.05
        return waves * rounds * round_s

    def stats(self) -> dict[str, float]:
        return {
            "prefill_token_budget": float(self.last_budget),
            "starved_rounds": float(self.starved_rounds),
            "decode_round_ema_ms": self.decode_round_s * 1000.0,
            "prefill_tok_cost_us": self.prefill_tok_s * 1e6,
            "fair_cap_tokens": float(self.fair_cap()),
            "verify_rounds": float(self.verify_rounds),
            "verify_tokens": float(self.verify_tokens),
            "prefill_true_tokens": float(self.prefill_true_tokens),
            "prefill_padded_tokens": float(self.prefill_padded_tokens),
            "prefill_pad_waste_pct": (
                100.0
                * (1.0 - self.prefill_true_tokens / self.prefill_padded_tokens)
                if self.prefill_padded_tokens
                else 0.0
            ),
            # per-tenant quota contract keys (flat rollups; detail in
            # tenant_stats()) — pinned by tests/test_scheduler.py
            "tenant_quota_tenants": float(len(self.tenant_quotas)),
            "tenant_throttled_total": float(
                sum(self.tenant_throttled.values())
            ),
            "tenant_charged_tokens": float(
                sum(self.tenant_charged.values())
            ),
        }
