"""Prefix-chain fingerprinting shared by engines and the router.

The paged ledger (executor/paging.py) keys a resident prefix entry on the
literal tuple of its token ids; the engine's prompt-prefix cache stores
pow2-floored lengths of those tuples. To make the *fleet* cache-aware the
router needs to compare a request's prompt against every peer's resident
chains without shipping token ids around, so both sides hash the same
thing the ledger keys: the block-aligned prefix chain, as a rolling
blake2b over block-sized runs of token ids (block size =
``TPU_KV_BLOCK_TOKENS``, the ledger's own unit). Because the hash at
boundary ``j`` commits to exactly ``ids[:j*bt]``, equal hashes mean equal
chains — the router never needs the ids back.

An engine advertises a **digest** of its resident chains through the
discovery tag channel (next to ``kv_headroom``):

- ``heads``: the top-K chains by stored length, as ``{chain_hash: tokens}``
  — an exact-match table for the common case (agent/system prompts shared
  by most traffic);
- ``bloom``: a small bloom filter over *every* boundary hash of every
  resident chain — catches partial matches (the peer holds a longer or
  shorter chain sharing our leading blocks) that fell out of the top-K.

``match_digest`` walks the request's boundary hashes longest-first: a
head hit is exact; a bloom hit is probabilistic (a false positive costs
one mispriced routing score, never correctness — admission re-checks the
real tuples). Everything here is stdlib-only so the router side stays
import-light.
"""

from __future__ import annotations

import hashlib
import os
import struct
from typing import Any, Iterable

# Digest sizing: 16 hex chars (64 bits) per chain hash keeps tag JSON
# small while making accidental collisions across a fleet's worth of
# chains (~thousands) negligible. The bloom is 512 bits / 4 probes by
# default: ~1% false-positive rate at ~50 boundary hashes per engine.
HASH_HEX = 16
DEFAULT_TOP_K = 8
DEFAULT_BLOOM_BITS = 512
DEFAULT_BLOOM_HASHES = 4
DIGEST_VERSION = 1


def prefix_route_enabled() -> bool:
    """``TPU_PREFIX_ROUTE=0`` is a true no-op: no hashing, no digest
    matching, no re-ranking — the router reproduces today's decisions
    byte-for-byte. Default on (scoring is inert until peers advertise
    digests, so the default costs nothing on single-engine fleets)."""
    return os.environ.get("TPU_PREFIX_ROUTE", "1") not in ("0", "false", "no")


def fetch_min_tokens() -> int:
    """Crossover length below which recomputing a prefix locally beats
    fetching its KV from a peer (``TPU_PREFIX_FETCH_MIN_TOKENS``). The
    default is one 256-token prefill chunk (fetch decode + device upload
    against chunked prefill); the crossover is not measured on the chip."""
    try:
        return int(os.environ.get("TPU_PREFIX_FETCH_MIN_TOKENS", "256"))
    except ValueError:
        return 256


def chain_hashes(ids: Iterable[int], block_tokens: int) -> list[tuple[int, str]]:
    """Rolling hash of a token chain at every ledger-block boundary, plus
    the (possibly unaligned) chain head.

    Returns ascending ``[(n_tokens, hash16), ...]`` where ``hash16``
    commits to exactly ``ids[:n_tokens]``: ``h_j = blake2b(h_{j-1} ||
    pack(ids[(j-1)*bt : j*bt]))``. The final element always covers the
    full chain, so a stored entry's *head hash* is ``chain_hashes(key,
    bt)[-1][1]`` — computed identically by the request side."""
    toks = list(ids)
    bt = max(1, int(block_tokens))
    out: list[tuple[int, str]] = []
    h = b""
    for start in range(0, len(toks), bt):
        run = toks[start : start + bt]
        d = hashlib.blake2b(digest_size=HASH_HEX // 2)
        d.update(h)
        d.update(struct.pack(f"<{len(run)}q", *run))
        h = d.digest()
        out.append((start + len(run), h.hex()))
    return out


def _bloom_bits(hash16: str, mbits: int, nh: int) -> list[int]:
    """Derive `nh` bloom probe positions from one 64-bit chain hash
    (split halves, double hashing — Kirsch-Mitzenmacher)."""
    v = int(hash16, 16)
    lo, hi = v & 0xFFFFFFFF, v >> 32
    return [(lo + i * hi) % mbits for i in range(nh)]


def build_digest(
    chains: Iterable[tuple[Iterable[int], int]],
    block_tokens: int,
    *,
    top_k: int = DEFAULT_TOP_K,
    mbits: int = DEFAULT_BLOOM_BITS,
    nh: int = DEFAULT_BLOOM_HASHES,
) -> dict[str, Any]:
    """Digest of an engine's resident prefix chains for the discovery tag
    channel. `chains` is ``[(token_ids, n_tokens), ...]`` — the ledger /
    prefix-cache snapshot (`engine.prefix_chains()`). JSON-serializable
    and compact: K head entries plus mbits/4 hex chars."""
    heads: dict[str, int] = {}
    bloom = bytearray(mbits // 8)
    ranked = sorted(chains, key=lambda c: -int(c[1]))
    for rank, (ids, n_tokens) in enumerate(ranked):
        bounds = chain_hashes(ids, block_tokens)
        if not bounds:
            continue
        if rank < top_k:
            heads[bounds[-1][1]] = int(n_tokens)
        for _, h in bounds:
            for bit in _bloom_bits(h, mbits, nh):
                bloom[bit // 8] |= 1 << (bit % 8)
    return {
        "v": DIGEST_VERSION,
        "bt": int(block_tokens),
        "heads": heads,
        "bloom": bytes(bloom).hex(),
        "mbits": mbits,
        "nh": nh,
    }


def merge_digests(digests: list[dict[str, Any]], top_k: int = DEFAULT_TOP_K) -> dict[str, Any] | None:
    """Union per-engine digests into one device tag (pooled engines).
    Blooms OR together when sized alike; heads keep the top-K longest."""
    digests = [d for d in digests if d and d.get("v") == DIGEST_VERSION]
    if not digests:
        return None
    if len(digests) == 1:
        return digests[0]
    base = digests[0]
    heads: dict[str, int] = {}
    bloom = bytearray(int(base["mbits"]) // 8)
    for d in digests:
        if int(d["mbits"]) != int(base["mbits"]) or int(d["bt"]) != int(base["bt"]):
            continue  # mismatched geometry never merges; first engine wins
        for h, n in d.get("heads", {}).items():
            heads[h] = max(int(n), heads.get(h, 0))
        raw = bytes.fromhex(d.get("bloom", ""))
        for i, b in enumerate(raw[: len(bloom)]):
            bloom[i] |= b
    top = dict(sorted(heads.items(), key=lambda kv: -kv[1])[:top_k])
    return {
        "v": DIGEST_VERSION,
        "bt": int(base["bt"]),
        "heads": top,
        "bloom": bytes(bloom).hex(),
        "mbits": int(base["mbits"]),
        "nh": int(base["nh"]),
    }


def match_digest(
    digest: dict[str, Any] | None,
    request_hashes: list[tuple[int, str]],
) -> tuple[int, bool]:
    """Longest resident-prefix match a peer's digest claims for a request.

    `request_hashes` is ``chain_hashes(prompt_ids, bt)`` computed by the
    caller with the digest's own ``bt`` (geometry mismatch → no match).
    Returns ``(matched_tokens, exact)``: a head hit is exact (the peer
    stores that very chain, length = the boundary we hashed); a bloom hit
    means the peer holds *some* chain through that boundary (possibly a
    false positive, which only misprices one score). Scanned longest-first
    so the first hit is the best claim."""
    if not digest or digest.get("v") != DIGEST_VERSION or not request_hashes:
        return 0, False
    heads = digest.get("heads") or {}
    try:
        bloom = bytes.fromhex(digest.get("bloom", ""))
        mbits = int(digest.get("mbits", 0))
        nh = int(digest.get("nh", 0))
    except (ValueError, TypeError):
        bloom, mbits, nh = b"", 0, 0
    for n_tokens, h in reversed(request_hashes):
        if h in heads:
            return n_tokens, True
        if mbits and nh and len(bloom) * 8 >= mbits:
            if all(bloom[b // 8] >> (b % 8) & 1 for b in _bloom_bits(h, mbits, nh)):
                return n_tokens, False
    return 0, False


def request_hashes_for(digest: dict[str, Any] | None, ids: list[int]) -> list[tuple[int, str]]:
    """Boundary hashes of a request's prompt in a digest's own geometry,
    dropping the head boundary when it covers the *whole* prompt — a hit
    must leave >= 1 suffix token (the engine cache's strict-prefix rule),
    so claiming the full prompt would promise savings admission can't
    deliver."""
    if not digest:
        return []
    bounds = chain_hashes(ids, int(digest.get("bt", 0) or 0))
    return [(n, h) for n, h in bounds if n < len(ids)]
