"""HBM-aware KV pool: admission accounting, preemption policy, host offload.

The engines own a static `[layers, max_slots, heads, max_seq_len, head_dim]`
KV cache (plus int8-dict and MLA-latent variants) sized at construction; a
slot is pinned for a request's whole life and an overloaded engine simply
starves its admission queue. This module adds the memory-manager layer in the
style of vLLM's PagedAttention pool (Kwon et al., 2023) and Sarathi-Serve's
SLO-aware admission, without repaginating the cache:

  - **Per-slot state of fixed size** (`StatePool`, at the end): what a
    sequence owns of a linear-attention or state-space layer is not rows of
    the KV cache but one state of fixed size, whatever its length; of a
    WINDOW attention layer it is a ring of its last positions, a second kind
    of KV member that does not grow either. The pool beside the KV cache is
    neither paged nor shared by block, so a configuration with such layers
    runs with the features that take a sequence to be its KV blocks switched
    off, decided once where the pool is built.
  - **Accounting**: bytes per slot are measured from the live cache pytree
    (`pytree_nbytes`), so kv8's `{q: int8, s: scale}` dict and MLA's
    asymmetric latent k/v layouts are covered without layout-specific code.
  - **Admission**: `admit_ok(offered)` compares offered load (active slots +
    queued + preempted) against `watermark × max_slots`. Above the
    watermark the API sheds (429 + Retry-After) instead of queueing work
    that cannot run.
  - **Preemption**: `pick_victim` orders candidates by policy — "priority"
    (lowest priority, then longest-idle, then most-tokens-remaining),
    "idle" (longest-idle first), "tokens" (most-remaining first),
    "slo_debt" (largest per-tenant goodput surplus first — the tenant
    whose SLO ratio is furthest ABOVE its peers has the most slack to
    give back). Every policy first prefers candidates with a larger
    `slo_surplus` (the engine stamps it from the perf observatory's
    per-tenant goodput ratios); with tenancy off the key is absent,
    every surplus reads 0.0, and ordering is byte-identical to the
    pre-zoo policies. The
    engine snapshots the victim's committed KV rows to host memory
    (`jax.device_get` of a dynamic slice — exact by the committed-lengths
    invariant: rows past the committed length are dead and rewritten in
    place), frees the slot, and later restores via `device_put` + the
    `_insert_row` donation path. Greedy output is token-identical across a
    preempt/restore cycle (pinned by tests/test_memory_pool.py).

The pool itself is pure host-side bookkeeping — no jax imports, no device
calls — so the engines keep every device interaction in their own dispatch
paths and `TPU_KV_HOST_OFFLOAD=0` (pool never constructed) stays a true
no-op. All mutating entry points take an internal lock: the engine thread
mutates while API threads read `stats()`/`admission` concurrently.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

from ..utils.locks import OrderedLock

__all__ = ["BLOCK_OFF", "COUNTED_OFF", "ExpertCounts", "KVPool", "KVSnapshot", "RECURRENT_OFF", "StatePool",
           "build_state_pool",
           "pytree_nbytes", "bucket_len"]

POLICIES = ("priority", "idle", "tokens", "slo_debt")

# Thrash guards: at most one preemption per interval, and restores are
# aged past fairness after this many multiples of the scheduler's TTFT
# target (a low-priority snapshot cannot starve forever behind a stream
# of high-priority arrivals, and vice versa).
PREEMPT_MIN_INTERVAL_S = 1.0
RESTORE_AGING_TTFT_MULT = 2.0


def pytree_nbytes(tree: Any) -> int:
    """Total bytes of every array leaf in a nested dict/list/tuple pytree.

    Layout-agnostic HBM accounting: covers bf16 `[L,B,H,S,hd]`, the kv8
    `{"q": int8, "s": scale}` dict, and MLA's asymmetric latent k/v without
    enumerating layouts. Leaves only need `.size` and `.dtype.itemsize`
    (numpy and jax arrays both qualify)."""
    if isinstance(tree, dict):
        return sum(pytree_nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(pytree_nbytes(v) for v in tree)
    size = getattr(tree, "size", None)
    dtype = getattr(tree, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * int(dtype.itemsize)


def bucket_len(length: int, max_seq_len: int) -> int:
    """Power-of-two snapshot bucket >= length, capped at max_seq_len.

    Snapshot/restore traffic reuses the engines' pow2 executable buckets so
    a preempt/restore cycle compiles at most one slice shape per bucket
    instead of one per request length."""
    b = 1
    while b < length:
        b *= 2
    return max(1, min(b, max_seq_len))


@dataclass
class KVSnapshot:
    """A preempted slot's exact host-side state.

    `k_rows`/`v_rows` hold the committed KV rows `[0, bucket)` (host numpy,
    possibly a dict for kv8). Restore may write the whole bucket back: rows
    in `[length, bucket)` are dead by the committed-lengths invariant — the
    first post-restore decode round overwrites position `length` before any
    read attends to it."""

    req_id: str
    priority: int
    length: int
    bucket: int
    last_tok: int
    temperature: float
    top_k: int
    top_p: float
    k_rows: Any
    v_rows: Any
    nbytes: int
    preempted_at: float
    slot_obj: Any = None  # the engine's live slot record, reinstalled on restore
    # SliceEngine protocol: every process stores its own host copy of the
    # rows keyed by this id, so the "restore" command ships (slot, snap_id)
    # instead of the KV payload over the command channel. -1 = single-host.
    snap_id: int = -1
    # Paged KV (executor/paging.py): when the victim was admitted off a
    # shared prefix, `k_rows`/`v_rows` hold only the PRIVATE rows
    # `[shared_len, bucket)` — the shared rows stay pinned as block ids in
    # the paging ledger and are re-inserted on restore from `shared_entry`
    # (the prefix-cache entry's device arrays, kept alive by this
    # reference even across an eviction). 0 = whole-bucket snapshot.
    shared_len: int = 0
    shared_entry: Any = None
    # KV migration (executor/migration.py): the shared prefix's token key —
    # rides the wire so the DESTINATION engine can re-pin the prefix blocks
    # out of its own cache (`admit_shared`) instead of copying rows. None
    # for within-engine preemption, where shared_entry alone suffices.
    shared_key: Any = None
    # True for a snapshot that arrived over the transfer endpoint: restore
    # then records an engine.migrate_in span (not engine.restore), skips
    # the pool's restored counter, and pins shared blocks via admit_shared
    # rather than re-tabling parked pins it never had.
    migrated: bool = False
    # Physical paged KV (executor/physical.py): the prefix-pool row indices
    # backing the shared blocks, captured from the victim's live block table
    # at snapshot time. A PHYSICAL prefix entry keeps no device row copies,
    # so the migration wire's fallback rows gather from these pool rows —
    # which stay valid while the parked pins (or the exporting slot's table)
    # keep the ledger ids alive. None for contiguous entries.
    shared_pool_rows: Any = None


class KVPool:
    def __init__(
        self,
        *,
        max_slots: int,
        max_seq_len: int,
        bytes_per_slot: int,
        watermark: float = 1.5,
        policy: str = "priority",
        max_preempted: int | None = None,
    ):
        if policy not in POLICIES:
            raise ValueError(f"unknown preempt policy {policy!r}; expected one of {POLICIES}")
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.bytes_per_slot = int(bytes_per_slot)
        self.watermark = max(1.0, float(watermark))
        self.policy = policy
        # bound host memory: never hold more offloaded snapshots than slots
        self.max_preempted = int(max_preempted) if max_preempted else self.max_slots
        self._lock = OrderedLock("kvpool", rank=20)
        self._snaps: list[KVSnapshot] = []
        self._last_preempt_at = 0.0
        # cumulative counters (engines_info bridges deltas into Prometheus)
        self.preempted_total = 0
        self.restored_total = 0
        self.shed_total = 0
        self.offload_bytes_total = 0
        self.offload_seconds_total = 0.0
        self.restore_seconds_total = 0.0

    # -- accounting --------------------------------------------------------

    def hbm_bytes(self) -> int:
        return self.max_slots * self.bytes_per_slot

    def admit_ok(self, offered: float) -> bool:
        """True while offered load is under the oversubscription watermark.
        `offered` is in slot-equivalents: historically the integer count
        active + queued + preempted; with the paged-KV ledger it is the
        unique-block offered load / blocks_per_slot (executor/paging.py
        `offered_blocks`), which reduces to the same integer when nothing
        is shared. Side-effect free — callers that act on a shed decision
        record it via `note_shed()`."""
        return offered < self.watermark * self.max_slots

    def headroom(self, offered: float) -> float:
        """Fraction of shed-free capacity remaining, in [0, 1]. Advertised
        through device tags so the router de-ranks saturated devices."""
        cap = self.watermark * self.max_slots
        if cap <= 0:
            return 0.0
        return max(0.0, min(1.0, 1.0 - offered / cap))

    # -- preemption policy -------------------------------------------------

    def may_preempt(self, now: float | None = None) -> bool:
        """Rate limit + host-memory bound; side-effect free."""
        now = time.time() if now is None else now
        with self._lock:
            if len(self._snaps) >= self.max_preempted:
                return False
            return now - self._last_preempt_at >= PREEMPT_MIN_INTERVAL_S

    def pick_victim(self, candidates: list[dict]) -> dict | None:
        """Choose the slot to evict. Each candidate dict carries `priority`
        (int), `last_activity` (monotonic-ish seconds), `tokens_remaining`
        (int), optionally `slo_surplus` (float: the owning tenant's
        goodput_ratio surplus over the worst-served tenant), plus any
        engine-side handle keys (`slot`, ...). Returns the chosen
        candidate unmodified, or None when empty.

        SLO debt leads every policy: the slot whose tenant is furthest
        AHEAD of its SLO is preempted first — it has slack to give back,
        while preempting an already-behind tenant digs its debt deeper.
        Candidates without the key (single-tenant serving) all read 0.0,
        so ordering degrades exactly to the historical per-policy keys."""
        if not candidates:
            return None
        if self.policy == "idle":
            base = lambda c: (c["last_activity"], c["priority"], -c["tokens_remaining"])
        elif self.policy == "tokens":
            base = lambda c: (-c["tokens_remaining"], c["priority"], c["last_activity"])
        else:  # "priority"/"slo_debt": lowest priority, longest-idle, most-remaining
            base = lambda c: (c["priority"], c["last_activity"], -c["tokens_remaining"])
        key = lambda c: (-float(c.get("slo_surplus", 0.0)), *base(c))
        return min(candidates, key=key)

    # -- offload / restore bookkeeping --------------------------------------

    def offload(self, snap: KVSnapshot, seconds: float = 0.0) -> None:
        with self._lock:
            self._snaps.append(snap)
            self._last_preempt_at = max(self._last_preempt_at, snap.preempted_at)
            self.preempted_total += 1
            self.offload_bytes_total += int(snap.nbytes)
            self.offload_seconds_total += max(0.0, float(seconds))

    def preempted_count(self) -> int:
        with self._lock:
            return len(self._snaps)

    def has_preempted(self) -> bool:
        return self.preempted_count() > 0

    def peek_restore(self) -> KVSnapshot | None:
        """The snapshot next in line for restore (highest priority, then
        longest-preempted), without removing it."""
        with self._lock:
            if not self._snaps:
                return None
            return min(self._snaps, key=lambda s: (-s.priority, s.preempted_at))

    def pop_restore(self) -> KVSnapshot | None:
        with self._lock:
            if not self._snaps:
                return None
            snap = min(self._snaps, key=lambda s: (-s.priority, s.preempted_at))
            self._snaps.remove(snap)
            return snap

    def requeue(self, snap: KVSnapshot) -> None:
        """Put back a popped snapshot untouched (restore deferred by the
        fairness rule or by a missing free slot) — no counter moves."""
        with self._lock:
            self._snaps.append(snap)

    def discard(self, snap: KVSnapshot) -> None:
        """Drop a snapshot without restoring (owner aborted/finished)."""
        with self._lock:
            try:
                self._snaps.remove(snap)
            except ValueError:
                pass

    def note_restored(self, snap: KVSnapshot, seconds: float = 0.0) -> None:
        with self._lock:
            self.restored_total += 1
            self.restore_seconds_total += max(0.0, float(seconds))

    def note_shed(self, n: int = 1) -> None:
        with self._lock:
            self.shed_total += int(n)

    def drain(self) -> list[KVSnapshot]:
        """Remove and return every held snapshot (abort/shutdown paths: the
        engine errors each snapshot's waiter)."""
        with self._lock:
            snaps, self._snaps = self._snaps, []
            return snaps

    # -- telemetry -----------------------------------------------------------

    def stats(self) -> dict[str, float]:
        with self._lock:
            held = len(self._snaps)
            held_bytes = sum(int(s.nbytes) for s in self._snaps)
            return {
                "policy_" + self.policy: 1.0,  # which policy is live, greppable
                "watermark": float(self.watermark),
                "hbm_bytes": float(self.hbm_bytes()),
                "bytes_per_slot": float(self.bytes_per_slot),
                "preempted_held": float(held),
                "preempted_held_bytes": float(held_bytes),
                "preempted_total": float(self.preempted_total),
                "restored_total": float(self.restored_total),
                "shed_total": float(self.shed_total),
                "offload_bytes_total": float(self.offload_bytes_total),
                "offload_seconds_total": self.offload_seconds_total,
                "restore_seconds_total": self.restore_seconds_total,
            }


# What takes a sequence to be its KV blocks, and so cannot carry a per-slot
# state of fixed size yet, a recurrent state or a window layer's ring: each is
# off for a configuration with either, with the reason the log gives once and a
# counter of the times it would have engaged.
RECURRENT_OFF = {
    "prefix_cache": "a cached prefix would need the state (of a ring, its last window of "
                    "positions) as it stood at the block boundary; a block holds full-length rows alone",
    "offload": "a preempted slot's snapshot holds full-length KV rows, no state and no ring",
    "migration": "the wire format of a moved sequence holds full-length KV rows, no state and no ring",
    "speculation": "rejected drafts roll the KV cache back by arithmetic; a state cannot be, and a "
                   "ring has already lost the positions the drafts replaced",
    "ragged_prefill": "a packed chunk holds several prompts' tokens in one row: the chunked "
                      "recurrence carries one state a row, and the packed kernel masks no window "
                      "and writes by position, not by position modulo a ring",
}
# What a latent pair runs without where the expert counts ride its second member
# beside the rope keys (`CacheLayout.counted`): the three features that take the
# pair's members apart row by row outside the step programs. Ragged prefill and
# speculation hand the pair to the step programs whole and stay on.
COUNTED_OFF = {
    "prefix_cache": "a stored prefix is a slice of the pair's two members as bare rows; the second "
                    "member here is the rope keys AND the expert counts",
    "offload": "a preempted slot's snapshot cuts bare rows out of both members",
    "migration": "the wire format of a moved sequence holds bare rows of both members",
}
# What a configuration that generates by diffusion over blocks runs without
# (`cfg.block_len`; its round is `engine.block_round_fn`): every program that
# takes a step to yield one token a sequence, and, because its expert counts
# ride the dense pair's second member, what COUNTED_OFF lists. Each with the
# counter of the times it would have engaged (`perf_stats()["blocks"]["off"]`).
BLOCK_OFF = {
    **COUNTED_OFF,
    "speculation": "a draft continues a sequence token by token; a block's tokens are chosen together",
    "mixed_round": "a prompt rides a decode step's weight pass; a block round has no such step",
    "fused_round": "a chunk group rides a decode round's dispatch; it runs between two block rounds",
    "constrain": "an automaton masks the next token given the last; a block's positions unmask in any order",
    "ragged_prefill": "the packed prompt kernel masks causally inside a row, not by block",
}
# What the pool counts beside them, in the same block (`off`), that is NOT off:
# whole prompts ride a decode round's weight pass in a recurrent configuration
# too (models/hybrid.py: hybrid_mixed_step), and "mixed_round" counts the admit
# programs such a configuration still takes of its own (engine._own_reason says
# why each: no active rows, a compact round, a first token read at once, a
# prompt over the largest rung).
POOL_COUNTS = ("mixed_round",)


class StatePool:
    """Host-side book of the per-slot recurrent state beside the KV cache.

    The device arrays ride the engine's cache pair through every step program
    (models/hybrid.py: float32 S [Lk, slots, H / P, dk, P dv], P heads abreast
    so that a row is a whole number of lanes and the pool's bytes in HBM are
    its logical bytes (kernels/kda.py), and the convolution tails; a kind whose
    only state is its tail, models/shortconv.py, has no S and `layout` no such
    entry: kilobytes a slot where the others hold megabytes), one row a
    slot, allocated with the engine like the KV cache. A
    slot's row is claimed at admission and starts from zero there: a whole
    prompt's prefill writes the row outright, a chunked prefill's first chunk
    (start 0) never reads it. The pool counts what a per-layer metric reads:
    its bytes (`layout`: each member's shape, whose product times the item
    size they are), the slots alive, and the features it keeps off (`off`,
    with `POOL_COUNTS` beside them)."""

    def __init__(self, *, max_slots: int, nbytes: int, layout: dict[str, list[int]] | None = None):
        self.max_slots = int(max_slots)
        self.nbytes = int(nbytes)
        self.layout = dict(layout or {})
        self.bytes_per_slot = self.nbytes // max(1, self.max_slots)
        self.admitted_total = 0
        self.off = dict.fromkeys((*RECURRENT_OFF, *POOL_COUNTS), 0)

    def note_off(self, feature: str) -> None:
        self.off[feature] += 1

    def stats(self, live_slots: int) -> dict[str, Any]:
        return {
            "bytes": self.nbytes,
            "bytes_per_slot": self.bytes_per_slot,
            "layout": dict(self.layout),
            "slots": self.max_slots,
            "live_slots": int(live_slots),
            "live_bytes": int(live_slots) * self.bytes_per_slot,
            "admitted_total": self.admitted_total,
            "off": dict(self.off),
        }


class ExpertCounts:
    """Host-side book of the routed-expert layer's work, for a configuration
    whose step programs count it (models/moe.py:moe_share_ffn; the counts ride
    the cache pair as a member of their own and come back behind each decode
    round's tokens). `counts` [2][L][5]: rows routed, pairs on held experts,
    held experts touched, the fullest one's rows and calls, summed since boot
    over decode steps [0] and over prefills [1]."""

    def __init__(self, n_layers: int, *, held: int, router: int):
        self.counts = [[[0] * 5 for _ in range(n_layers)] for _ in range(2)]
        self.held = int(held)  # experts held here, of `router` scored
        self.router = int(router)
        self.n_layers = int(n_layers)

    def stats(self) -> dict[str, Any]:
        # `counts` is replaced whole at a round's fetch, never edited
        return {"counts": self.counts, "held": self.held, "router": self.router}


def _shapes(tree: Any, prefix: str = "") -> dict[str, list[int]]:
    """{dotted path: shape} of every array leaf of a nested dict."""
    if isinstance(tree, dict):
        return {k: v for name, sub in tree.items()
                for k, v in _shapes(sub, f"{prefix}{name}.").items()}
    return {prefix[:-1]: list(tree.shape)}


def build_state_pool(cfg: Any, max_slots: int, state: Any, log: Any) -> "StatePool | None":
    """The pool's book for a configuration whose layers keep a per-slot state
    of fixed size beside the full-length KV cache, recurrent layers or window
    layers on rings (`state` is the device tree the engine allocated for it),
    None for any other. The one place that says what such a configuration runs
    without."""
    if not getattr(cfg, "recurrent", False):
        return None
    ring = cfg.recurrent_kind == "win"
    pool = StatePool(max_slots=max_slots, nbytes=pytree_nbytes(state), layout=_shapes(state))
    log.info("%s: %.2f MB a slot, %d slots beside the KV cache",
             "window layers' rings" if ring else "recurrent state pool" if "S" in state
             else "convolution tails (no matrix state)",
             pool.bytes_per_slot / (1 << 20), max_slots)
    for feature, why in RECURRENT_OFF.items():
        log.info("%s is off for %s: %s", feature, cfg.name, why)
    return pool
