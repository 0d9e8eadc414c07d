"""KV migration: wire format + coordinator for engine-to-engine rebalancing.

PR 4's preempt path already produces the migration primitive — a
token-identical host snapshot of a slot's committed KV rows (memory.py
`KVSnapshot`) that restores through the donated insert path. This module
moves that snapshot *between* engines instead of round-tripping it within
one, in the style of DistServe (OSDI'24) / Splitwise (ISCA'24):

  - **Wire format**: `encode_payload`/`decode_payload` serialize a snapshot
    plus the request's continuation state (sampling params, generated text,
    tokenizer byte-carry) into `magic | version | header-json | raw blobs`.
    The tree codec covers every cache layout without enumerating them —
    bf16 GQA's bare array, kv8's `{"q","s"}` dict, the fused int8 payload's
    `v == {}` sentinel, and MLA's asymmetric latents are all just
    {ndarray | dict} trees. Paged private-only snapshots ride as-is: the
    shared prefix travels as a token key (re-pinned on the destination via
    `admit_shared` when its prefix cache holds the same entry) with the
    shared rows attached as a fallback for destinations that never saw the
    prefix.
  - **MigrationCoordinator**: the orchestration plane. Pumps prefill-role
    engines' outboxes to decode-capable targets (disaggregated mode,
    `TPU_ROLE=prefill|decode|both`) and drains a saturated engine — one
    whose `kv_headroom` fell under `drain_low` while a peer sits above
    `drain_high` — by moving offloaded snapshots, then plain queued
    requests, to the idle peer. Targets are duck-typed: a local engine
    (`migrate_import`) or an rpc proxy that ships the payload over the
    transfer endpoint and pumps the returned event stream.

This file is intentionally dependency-free (stdlib + numpy on the wire
path, no jax/grpc imports — pinned by tests/test_migration.py's
import-lint) so a CPU-only worker can decode and forward payloads without
an accelerator stack installed. Every device interaction stays in
engine.py's export/import hooks.

Locking: the coordinator's lock ranks BELOW every engine lock
(migration=5 < engine.stats=10 < kvpool=20 < paging=30, doc/concurrency.md)
because a tick holds it while calling into engine export/import paths that
take stats/pool/paging locks. No engine thread ever takes the migration
lock, so the reverse order cannot occur.
"""

from __future__ import annotations

import base64
import json
import logging
import struct
import threading
import time
from typing import Any, Callable

import numpy as np

from ..utils.locks import OrderedLock
from .memory import KVSnapshot, pytree_nbytes

log = logging.getLogger("executor.migration")

__all__ = [
    "MIGRATION_LOCK_RANK",
    "MigrationCoordinator",
    "decode_payload",
    "encode_payload",
    "merge_shared_rows",
    "wire_to_snapshot",
]

# doc/concurrency.md: below every engine-side lock — a coordinator tick
# holds this while calling export/import hooks that take ranks 10/20/30.
MIGRATION_LOCK_RANK = 5

_MAGIC = b"KVMG"
_VERSION = 1
_HDR = struct.Struct("<4sBBI")  # magic, version, flags, header_len

ROLES = ("prefill", "decode", "both")


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, reaching for ml_dtypes' extended registry
    (bfloat16, ...) only when plain numpy does not know it. ml_dtypes is a
    numpy extension independent of jax, and only payloads that actually
    carry such arrays need it — a CPU-only forwarder never resolves
    dtypes at all."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # deferred: never needed on the forward-only path

        return np.dtype(getattr(ml_dtypes, name))


def _encode_tree(tree: Any, blobs: list[bytes]) -> Any:
    """Depth-first walk appending each leaf's raw bytes to `blobs` and
    returning a JSON-able meta mirror of the structure. Decode replays the
    identical walk, so blob order is implied by the meta alone."""
    if tree is None:
        return None
    if isinstance(tree, dict):
        # {} is a live layout sentinel (fused int8 GQA's cv), not absence
        return {"m": {k: _encode_tree(v, blobs) for k, v in tree.items()}}
    arr = np.asarray(tree)
    blobs.append(arr.tobytes())
    return {"d": str(arr.dtype), "s": list(arr.shape)}


def _decode_tree(meta: Any, buf: memoryview, off: int) -> tuple[Any, int]:
    if meta is None:
        return None, off
    if "m" in meta:
        out = {}
        for k, sub in meta["m"].items():
            out[k], off = _decode_tree(sub, buf, off)
        return out, off
    dt = _np_dtype(meta["d"])
    shape = tuple(meta["s"])
    n = int(np.prod(shape, dtype=np.int64)) * dt.itemsize if shape else dt.itemsize
    arr = np.frombuffer(buf, dtype=dt, count=max(1, n // dt.itemsize), offset=off)
    return arr.reshape(shape).copy(), off + n


def encode_payload(header: dict[str, Any], trees: dict[str, Any]) -> bytes:
    """`header` is arbitrary JSON-able continuation state; `trees` maps
    names to {ndarray | dict | None} pytrees shipped as raw blobs."""
    blobs: list[bytes] = []
    meta = {name: _encode_tree(t, blobs) for name, t in trees.items()}
    hdr = json.dumps({"h": header, "t": meta}, separators=(",", ":")).encode()
    return b"".join([_HDR.pack(_MAGIC, _VERSION, 0, len(hdr)), hdr, *blobs])


def decode_payload(data: bytes) -> tuple[dict[str, Any], dict[str, Any]]:
    if len(data) < _HDR.size:
        raise ValueError("migration payload truncated")
    magic, version, _flags, hlen = _HDR.unpack_from(data)
    if magic != _MAGIC:
        raise ValueError("not a migration payload (bad magic)")
    if version != _VERSION:
        raise ValueError(f"migration payload version {version} != {_VERSION}")
    hdr = json.loads(bytes(data[_HDR.size : _HDR.size + hlen]))
    buf = memoryview(data)
    off = _HDR.size + hlen
    trees: dict[str, Any] = {}
    for name, meta in hdr["t"].items():
        trees[name], off = _decode_tree(meta, buf, off)
    return hdr["h"], trees


def merge_shared_rows(shared: Any, private: Any) -> Any:
    """Concatenate shared-prefix rows ahead of private rows along the seq
    axis (ALWAYS axis 3 across every layout) — the fallback when the
    destination's prefix cache cannot re-pin the shared blocks."""
    if isinstance(shared, dict):
        if not shared:
            return {}
        return {k: merge_shared_rows(shared[k], private[k]) for k in shared}
    return np.concatenate([np.asarray(shared), np.asarray(private)], axis=3)


def snapshot_header(snap: KVSnapshot, req: Any, slot: Any) -> dict[str, Any]:
    """Continuation state for `snap`'s request: everything the destination
    needs to resume emission mid-stream — sampling params for the device
    rows, generated text for stop-sequence scanning, the tokenizer's
    undecoded byte carry, and the prompt ids (prefix-cache key matching +
    usage accounting)."""
    return {
        "request_id": snap.req_id,
        "priority": snap.priority,
        "length": snap.length,
        "bucket": snap.bucket,
        "last_tok": snap.last_tok,
        "temperature": snap.temperature,
        "top_k": snap.top_k,
        "top_p": snap.top_p,
        "shared_len": snap.shared_len,
        "shared_key": list(snap.shared_key) if snap.shared_key else None,
        "max_tokens": int(req.max_tokens),
        "stop": list(req.stop),
        "prompt_ids": [int(t) for t in req.prompt_ids],
        "created_at": float(req.created_at),
        "trace_ctx": req.trace_ctx,
        "migrations": int(getattr(req, "migrations", 0)),
        "generated": int(slot.generated),
        "text": slot.text,
        "pending_b64": base64.b64encode(slot.pending).decode("ascii"),
        "prompt_len": int(slot.prompt_len),
        # grammar-constrained decoding: ship the raw spec + the ids the
        # automaton has consumed; the destination recompiles against its
        # own cache and replays to the same state (automaton internals
        # never cross the wire — they are engine-local memo tables)
        "constraint": getattr(req, "constraint", None),
        "logit_bias": getattr(req, "logit_bias", None),
        "cn_tokens": (
            [int(t) for t in slot.cn.consumed]
            if getattr(slot, "cn", None) is not None
            else None
        ),
    }


def wire_to_snapshot(data: bytes) -> tuple[dict[str, Any], KVSnapshot]:
    """Decode a payload into (header, KVSnapshot). The snapshot arrives
    with `slot_obj=None` and `snap_id=-1` — the importing engine installs
    its own slot record and a destination-local snap id. When the payload
    carried fallback shared rows and the header names a shared prefix, the
    caller decides: re-pin via the destination prefix cache (keep
    `shared_len`, drop the fallback) or merge the fallback rows back into
    a whole-bucket snapshot."""
    header, trees = decode_payload(data)
    snap = KVSnapshot(
        req_id=header["request_id"],
        priority=int(header["priority"]),
        length=int(header["length"]),
        bucket=int(header["bucket"]),
        last_tok=int(header["last_tok"]),
        temperature=float(header["temperature"]),
        top_k=int(header["top_k"]),
        top_p=float(header["top_p"]),
        k_rows=trees["k"],
        v_rows=trees["v"],
        nbytes=pytree_nbytes(trees["k"]) + pytree_nbytes(trees["v"]),
        preempted_at=time.time(),
        shared_len=int(header.get("shared_len") or 0),
        shared_key=tuple(header["shared_key"]) if header.get("shared_key") else None,
        migrated=True,
    )
    if snap.shared_len and trees.get("shared_k") is not None:
        # stash the fallback rows on the snapshot so the importer can merge
        # without re-decoding the payload
        snap.shared_entry = {"k": trees["shared_k"], "v": trees["shared_v"]}
    return header, snap


def flatten_to_whole_bucket(snap: KVSnapshot) -> None:
    """Fold fallback shared rows into the private rows, turning a paged
    private-only snapshot into a plain whole-bucket one (destination has no
    matching prefix entry to re-pin)."""
    if not snap.shared_len:
        return
    if snap.shared_entry is None:
        raise ValueError(
            f"snapshot {snap.req_id[:8]} has a {snap.shared_len}-token shared "
            "prefix but no fallback rows and no matching destination entry"
        )
    snap.k_rows = merge_shared_rows(snap.shared_entry["k"], snap.k_rows)
    snap.v_rows = merge_shared_rows(snap.shared_entry["v"], snap.v_rows)
    snap.nbytes = pytree_nbytes(snap.k_rows) + pytree_nbytes(snap.v_rows)
    snap.shared_len = 0
    snap.shared_entry = None
    snap.shared_key = None


class MigrationCoordinator:
    """Moves work between engines: outbox pumping (disaggregated
    prefill→decode handoff) and headroom-driven drain of a saturated
    engine. Engines are duck-typed — anything with `migrate_import`
    qualifies as a target (rpc.client.RemoteMigrationTarget ships the
    payload over the transfer endpoint), while sources additionally need
    the engine-side export hooks (`_migrate_outbox`, `migrate_export_one`,
    `migrate_steal_queued`).

    `tick()` is the whole control loop — call it from a periodic thread
    (`start()`) or an existing ticker (api/server.py). All bookkeeping sits
    under the rank-5 migration lock; engine calls happen while holding it,
    which is legal because every engine lock ranks higher."""

    def __init__(
        self,
        engines: dict[str, Any],
        *,
        roles: dict[str, str] | None = None,
        role: str = "both",
        drain_low: float = 0.25,
        drain_high: float = 0.5,
        burst: int = 2,
        interval_s: float = 0.5,
    ):
        if role not in ROLES:
            raise ValueError(f"unknown role {role!r}; expected one of {ROLES}")
        self.engines = dict(engines)
        self.roles = {n: (roles or {}).get(n, role) for n in self.engines}
        for n, r in self.roles.items():
            if r not in ROLES:
                raise ValueError(f"unknown role {r!r} for engine {n!r}")
        self.drain_low = float(drain_low)
        self.drain_high = float(drain_high)
        self.burst = max(1, int(burst))
        self.interval_s = float(interval_s)
        self._remote: dict[str, Any] = {}
        self._lock = OrderedLock("migration", rank=MIGRATION_LOCK_RANK)
        self._pressure = threading.Event()  # admission shed observed: drain now
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # cumulative counters (engines_info bridges deltas into Prometheus)
        self.snapshots_moved_total = 0
        self.requeues_total = 0
        self.bytes_total = 0
        self.failed_total = 0
        self.last_headroom_delta = 0.0
        # prefill-role engines flag every admitted request for export the
        # moment its prefill lands (engine.py _first_token)
        for n, eng in self.engines.items():
            if self.roles[n] == "prefill" and getattr(eng, "_migrate_outbox", None) is not None:
                eng.migrate_after_prefill = True

    # -- wiring ------------------------------------------------------------

    def add_remote(self, name: str, target: Any, role: str = "decode") -> None:
        """Register an import-only remote target (an rpc transfer proxy)."""
        if role not in ROLES:
            raise ValueError(f"unknown role {role!r}; expected one of {ROLES}")
        self._remote[name] = target
        self.roles[name] = role

    def add_engine(self, name: str, eng: Any, role: str = "both") -> None:
        """Elastic join: register a full local engine mid-flight. The next
        tick sees it as both drain target and (if saturated) drain source —
        a freshly warmed engine joining a shedding fleet starts absorbing
        the backlog within one interval, no coordinator restart."""
        if role not in ROLES:
            raise ValueError(f"unknown role {role!r}; expected one of {ROLES}")
        with self._lock:
            # swap, don't mutate: tick() iterates self.engines lock-free,
            # and in-place insertion mid-iteration would raise
            self.engines = {**self.engines, name: eng}
            self.roles = {**self.roles, name: role}
            if role == "prefill" and getattr(eng, "_migrate_outbox", None) is not None:
                eng.migrate_after_prefill = True
        self._pressure.set()  # drain toward the newcomer now, not next tick

    def note_pressure(self) -> None:
        """Admission-path hook: a shed decision (429) kicks the next tick
        into draining immediately instead of waiting out the interval."""
        self._pressure.set()

    def start(self) -> "MigrationCoordinator":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="kv-migration", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._pressure.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        # unshipped outbox items would otherwise strand their consumers in
        # out.get() forever — error them on the way down
        for eng in self.engines.values():
            outbox = getattr(eng, "_migrate_outbox", None)
            while outbox is not None and not outbox.empty():
                try:
                    item = outbox.get_nowait()
                except Exception:
                    break
                self._fail_item(item, "migration coordinator stopped")

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.tick()
            except Exception:
                log.exception("migration tick failed")
            self._pressure.wait(self.interval_s)
            self._pressure.clear()

    # -- control loop ------------------------------------------------------

    def _headroom(self, eng: Any) -> float | None:
        """Shed-free capacity fraction the drain trigger compares against.

        Two signals, take the min. Pool memory headroom alone is NOT
        enough: paged accounting counts shared prefix blocks once, so a
        uniform workload can hold block usage near zero while every slot
        is busy and the admit queue grows — the exact state a drain
        exists to relieve. Slot headroom measures that queue against a
        1.5x-slots oversubscription cap (the pool's default watermark),
        so a slot-saturated engine reads as drained-out (≈0) only once
        work is actually waiting, and a busy-but-unqueued engine stays
        above drain_low."""
        slot_h = None
        slots = float(getattr(eng, "max_slots", 0) or 0)
        if slots > 0:
            queued = float(eng.queue_depth()) if hasattr(eng, "queue_depth") else 0.0
            slot_h = max(
                0.0, 1.0 - (eng.slots_in_use() + queued) / (1.5 * slots)
            )
        ms = eng.memory_stats()
        if ms.get("enabled"):
            mem_h = float(ms.get("headroom", 0.0))
            return mem_h if slot_h is None else min(mem_h, slot_h)
        return slot_h

    def _targets(self, exclude: str) -> list[tuple[str, float]]:
        """Decode-capable engines by descending headroom, remotes last
        (their headroom is unknown — assume drain_high so a configured
        disaggregation peer is always eligible)."""
        out: list[tuple[str, float]] = []
        for n, eng in self.engines.items():
            if n == exclude or self.roles[n] == "prefill":
                continue
            if getattr(eng, "_migrate_in", None) is None:
                continue  # TPU_MIGRATE off on that engine: cannot import
            h = self._headroom(eng)
            if h is not None:
                out.append((n, h))
        out.sort(key=lambda t: -t[1])
        for n in self._remote:
            if n != exclude and self.roles[n] != "prefill":
                out.append((n, self.drain_high))
        return out

    def _resolve(self, name: str) -> Any:
        return self.engines.get(name) or self._remote[name]

    def _fail_item(self, item: dict[str, Any], msg: str) -> None:
        out = item.get("out")
        if out is None:
            return
        out.put({"type": "error", "error": msg})
        out.put({"type": "done", "finish_reason": "error", "usage": {}})

    def _ship(self, item: dict[str, Any], dest_name: str) -> bool:
        dest = self._resolve(dest_name)
        try:
            dest.migrate_import(item["payload"], out=item.get("out"))
        except Exception as e:
            log.exception("migrate of %s to %s failed", item.get("req_id", "?")[:8], dest_name)
            with self._lock:
                self.failed_total += 1
            self._fail_item(item, f"migration to {dest_name} failed: {e}")
            return False
        with self._lock:
            self.snapshots_moved_total += 1
            self.bytes_total += len(item["payload"])
        return True

    def tick(self) -> None:
        # 1. disaggregated handoff: pump every outbox (prefill-role engines
        # fill them; both-role engines only when a request was explicitly
        # flagged migrate_after_prefill)
        for name, eng in self.engines.items():
            outbox = getattr(eng, "_migrate_outbox", None)
            while outbox is not None and not outbox.empty():
                try:
                    item = outbox.get_nowait()
                except Exception:
                    break
                targets = self._targets(exclude=name)
                if not targets:
                    self._fail_item(item, "no decode-capable migration target")
                    with self._lock:
                        self.failed_total += 1
                    continue
                self._ship(item, targets[0][0])
        # 2. drain: saturated → idle
        rooms = {
            n: h
            for n, eng in self.engines.items()
            if getattr(eng, "_migrate_outbox", None) is not None
            and (h := self._headroom(eng)) is not None
        }
        if rooms:
            lo = min(rooms.values())
            hi = max(rooms.values())
            with self._lock:
                self.last_headroom_delta = hi - lo
            if lo <= self.drain_low:
                src_name = min(rooms, key=rooms.get)  # type: ignore[arg-type]
                targets = [
                    (n, h) for n, h in self._targets(exclude=src_name) if h >= self.drain_high
                ]
                if targets:
                    self._drain(src_name, targets[0][0])

    def _drain(self, src_name: str, dest_name: str) -> None:
        src = self.engines[src_name]
        dest = self._resolve(dest_name)
        for _ in range(self.burst):
            # offloaded snapshots first: they hold committed KV and their
            # consumers have waited longest
            item = src.migrate_export_one()
            if item is not None:
                if self._ship(item, dest_name):
                    log.info(
                        "drained snapshot %s: %s -> %s (%.1f KB)",
                        item.get("req_id", "?")[:8], src_name, dest_name,
                        len(item["payload"]) / 1024,
                    )
                continue
            # then plain queued requests — queued-behind-a-long-tail work
            # needs no KV at all, just a submit on the idle engine (local
            # targets only: the request object carries its consumer queue)
            req = src.migrate_steal_queued()
            if req is None:
                break
            if getattr(req, "migrations", 0) >= 1:
                # already re-homed once: moving it again risks ping-pong
                # (two engines whose headroom recovers alternately bounce
                # the queue head forever) — let it run where it sits
                src.submit(req)
                break
            if not hasattr(dest, "submit"):
                # remote target: cannot re-home a live consumer queue — put
                # the request back where its consumer expects it
                src.submit(req)
                break
            req.migrations = getattr(req, "migrations", 0) + 1
            dest.submit(req)
            with self._lock:
                self.requeues_total += 1
            log.info(
                "requeued %s: %s -> %s (no prefill spent)",
                req.request_id[:8], src_name, dest_name,
            )

    # -- telemetry ---------------------------------------------------------

    def stats(self) -> dict[str, float]:
        with self._lock:
            return {
                "enabled": 1.0,
                "snapshots_moved_total": float(self.snapshots_moved_total),
                "requeues_total": float(self.requeues_total),
                "bytes_total": float(self.bytes_total),
                "failed_total": float(self.failed_total),
                "headroom_delta": float(self.last_headroom_delta),
                "engines": float(len(self.engines)),
                "remotes": float(len(self._remote)),
            }
