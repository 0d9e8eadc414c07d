"""Physical half of the paged KV subsystem (vLLM PagedAttention, Kwon et
al. 2023): per-slot device block tables plus a prefix block pool.

``paging.PagedKVManager`` is the block *economy* — refcounted ids, no
bytes. This module makes those ids physical with one deliberate twist,
the **identity home**: a slot's private block at logical index ``j``
always lives at physical id ``slot * blocks_per_slot + j``, i.e. exactly
where the contiguous layout already put it. Only *shared* (prefix-pinned)
blocks resolve elsewhere — to rows of a separate device pool sized by the
prefix partition. Consequences:

- every existing KV **write** path (append kernels, chunked-prefill
  scatter, restore inserts, admission) is untouched — decode/prefill
  writes target private positions, and private positions are identity;
- a table row that references no shared blocks *is* the identity
  permutation, so the attention wrappers can runtime-detect the
  no-sharing case and keep the exact contiguous dispatch (raw-decode
  perf is not taxed by indirection it doesn't use);
- the table padding value for positions beyond a slot's ledger table is
  the identity home itself — a sentinel that is always safe to
  dereference (the kernels never read past ``nblk(length)``, and parked
  slots keep ``lengths == max_seq_len`` so they stream exactly one
  block).

Physical ids are ``[0, n_slots * blocks_per_slot)`` for arena homes and
``[pool_base, pool_base + pool_rows)`` for pool rows, with
``pool_base = n_slots * blocks_per_slot``; kernels and gather helpers
split on ``phys < pool_base``.

Pool rows are owned by ledger ids, not prefix keys: ``register_prefix``
maps a prefix entry's ledger ids to pool rows, and ``sweep`` reclaims a
row only once ``PagedKVManager.alive()`` says the ledger id died — an
evicted entry's rows stay readable while sharer pins keep the id alive.

Host bookkeeping is numpy-only; the device table is uploaded lazily on
``device_table()`` after mutations. A small lock guards the table since
free/preempt paths can race the engine loop.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Callable, Iterable

import numpy as np

log = logging.getLogger("llm_mcp_tpu.physical")


def pool_like(cache: Any, pool_rows: int, block_tokens: int, seq_len: int = 0) -> Any:
    """Allocate a prefix pool pytree mirroring a KV cache pytree.

    Every cache leaf is ``[L, B, heads, S, *rest]`` (rest may be empty —
    int8 scale planes are ``[L, B, heads, S]``); the pool leaf swaps the
    slot axis for ``pool_rows`` and the S axis for ``block_tokens``:
    ``[L, pool_rows, heads, block_tokens, *rest]``. One pool row holds
    one block's tokens across *all* layers, matching the ledger's
    bytes-per-block accounting. A leaf whose rows hold P = ``seq_len`` / S
    positions abreast (the latent pair's int8 rope keys,
    kernels/attention.py:rope_abreast) is pooled APART, a position a row,
    as everything cut out of the cache lies: ``rest`` is then a P-th as wide.
    """
    import jax
    import jax.numpy as jnp

    def leaf(c):
        P = max(1, seq_len // c.shape[3])
        rest = c.shape[4:] if P == 1 else (c.shape[4] // P,) + c.shape[5:]
        return jnp.zeros((c.shape[0], pool_rows, c.shape[2], block_tokens) + rest, dtype=c.dtype)

    return jax.tree.map(leaf, cache)


class PhysicalPool:
    """Device block tables + pool-row allocator over the ledger's ids."""

    def __init__(
        self,
        *,
        n_slots: int,
        seq_len: int,
        block_tokens: int,
        pool_rows: int,
    ):
        if seq_len % block_tokens:
            raise ValueError("seq_len must be a multiple of block_tokens")
        self.n_slots = int(n_slots)
        self.block_tokens = int(block_tokens)
        self.nbs = seq_len // self.block_tokens  # blocks per slot
        self.pool_rows = int(pool_rows)
        self.pool_base = self.n_slots * self.nbs

        self._identity = np.arange(self.pool_base, dtype=np.int32).reshape(
            self.n_slots, self.nbs
        )
        self.table = self._identity.copy()
        self._lock = threading.Lock()
        self._dirty = True
        self._dev: Any = None

        self._phys: dict[int, int] = {}  # ledger block id -> pool row
        self._free: list[int] = list(range(self.pool_rows - 1, -1, -1))

        self.rebuilds_total = 0
        self.cow_copies_total = 0
        self.missing_pins = 0  # shared pin with no pool mapping (bug tripwire)
        self.pool_rows_peak = 0

    # -- pool-row ownership --------------------------------------------------

    def register_prefix(self, ledger_ids: Iterable[int]) -> list[int] | None:
        """Map a prefix entry's ledger ids to fresh pool rows; None when
        the pool is out of rows (caller releases the ledger entry and
        skips the store — the partition and the pool are sized from the
        same budget, so this only fires when sweep is lagging pins)."""
        ids = list(ledger_ids)
        with self._lock:
            if len(self._free) < len(ids):
                return None
            rows = [self._free.pop() for _ in ids]
            for bid, row in zip(ids, rows):
                self._phys[bid] = row
            used = self.pool_rows - len(self._free)
            if used > self.pool_rows_peak:
                self.pool_rows_peak = used
            return rows

    def phys_of(self, ledger_id: int) -> int | None:
        """Physical id (pool_base + row) for a prefix-mapped ledger id."""
        with self._lock:
            row = self._phys.get(ledger_id)
            return None if row is None else self.pool_base + row

    def sweep(self, alive: Callable[[int], bool]) -> int:
        """Reclaim pool rows whose ledger id died. Called after prefix
        evictions and slot frees; cost is one dict scan."""
        with self._lock:
            dead = [bid for bid in self._phys if not alive(bid)]
            for bid in dead:
                self._free.append(self._phys.pop(bid))
            return len(dead)

    # -- table maintenance ---------------------------------------------------

    def rebuild(self, slot: int, ids: list[int], shared_n: int) -> bool:
        """Re-key one slot's table row from its ledger ``table_view``.
        Shared pins resolve through the pool map; everything else —
        private blocks, COW destinations, and padding past the ledger
        table — is the identity home. Returns True when the row changed."""
        row = self._identity[slot].copy()
        with self._lock:
            for j in range(min(shared_n, len(ids), self.nbs)):
                prow = self._phys.get(ids[j])
                if prow is None:
                    self.missing_pins += 1  # identity home = stale bytes; audited
                else:
                    row[j] = self.pool_base + prow
            if np.array_equal(row, self.table[slot]):
                return False
            self.table[slot] = row
            self._dirty = True
            self.rebuilds_total += 1
            return True

    def reset(self, slot: int) -> bool:
        """Back to identity (slot freed / preempted). Returns True when
        the row changed."""
        with self._lock:
            if np.array_equal(self.table[slot], self._identity[slot]):
                return False
            self.table[slot] = self._identity[slot]
            self._dirty = True
            return True

    def reset_all(self) -> None:
        with self._lock:
            self.table[:] = self._identity
            self._dirty = True

    def device_table(self) -> Any:
        """Device copy of the table, re-uploaded only after mutations."""
        import jax.numpy as jnp

        with self._lock:
            if self._dirty or self._dev is None:
                self._dev = jnp.asarray(self.table)
                self._dirty = False
            return self._dev

    # -- read-side helpers ---------------------------------------------------

    def row_sources(self, slot: int, nblocks: int) -> list[tuple[bool, int, int]]:
        """Host-side decode of one slot's first ``nblocks`` table entries
        for the rare gather paths (snapshot / prefix store / wire export):
        ``(in_arena, arena_row_or_pool_row, token_offset)`` per block."""
        out: list[tuple[bool, int, int]] = []
        with self._lock:
            row = self.table[slot, : max(0, min(nblocks, self.nbs))].tolist()
        for phys in row:
            if phys < self.pool_base:
                out.append((True, phys // self.nbs, (phys % self.nbs) * self.block_tokens))
            else:
                out.append((False, phys - self.pool_base, 0))
        return out

    def stats(self) -> dict[str, float]:
        with self._lock:
            return {
                "physical_pool_rows": float(self.pool_rows),
                "physical_pool_rows_used": float(self.pool_rows - len(self._free)),
                "physical_pool_rows_peak": float(self.pool_rows_peak),
                "physical_rebuilds_total": float(self.rebuilds_total),
                "physical_cow_copies_total": float(self.cow_copies_total),
                "physical_missing_pins": float(self.missing_pins),
            }
