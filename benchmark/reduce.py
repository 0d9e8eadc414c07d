"""From the load generator's records to the end-to-end metrics.

Every function takes the records of one run and the measured window
`(w0, w1)`, both in seconds from the plan's start, and returns None where there
is nothing to read. What a metric measures is fixed here: a tail is the tail of
all requests due in the window, a rate is all the work of the window over all
of its seconds.
"""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    pos = (len(v) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ok(rec: dict) -> bool:
    """A request that did what was asked: 200 and a stream that ended in
    [DONE] with a finish reason, or vectors that all passed the child's checks.
    A stream may carry no content: with random weights about one sampled token
    in 259 (the byte tokenizer's ids) is EOS, so now and then the first one is,
    and a lone UTF-8 continuation byte is held back and never flushed. Tokens
    must then be none, or the reply an honest `stop`."""
    if rec.get("status") != 200 or rec.get("error") or rec.get("done") is None:
        return False
    if "inputs" in rec:
        return rec.get("bad", 1) == 0
    if rec.get("finish") not in ("stop", "length"):
        return False
    return bool(rec.get("events")) or rec.get("finish") == "stop"


def stream_span(rec: dict) -> tuple[float, float]:
    """First and last content delta of a stream; of one without content, the
    moment it ended (its first token was its last)."""
    ev = rec.get("events")
    return (ev[0], ev[-1]) if ev else (rec["done"], rec["done"])


def in_window(t: float | None, window: tuple[float, float]) -> bool:
    return t is not None and window[0] <= t < window[1]


def clock(rec: dict) -> float:
    """When the request was due: its schedule in an open loop, the moment its
    client sent it in a closed one."""
    return rec["due"] if rec.get("due") is not None else rec["sent"]


def ttfts_ms(records: list[dict], window, miss_ms: float) -> list[float]:
    """Time from due to the first content delta, of every request due inside
    the window. A failed or shed request counts as `miss_ms`, over any limit."""
    out = []
    for r in records:
        if not in_window(clock(r), window):
            continue
        out.append((stream_span(r)[0] - clock(r)) * 1e3 if ok(r) else miss_ms)
    return out


def gaps_ms(records: list[dict], window) -> list[float]:
    """Gaps between successive content deltas of every stream, counted where
    the later delta fell inside the window: what a reader of the stream sees."""
    out = []
    for r in records:
        ev = r.get("events") or []
        out += [(b - a) * 1e3 for a, b in zip(ev, ev[1:]) if in_window(b, window)]
    return out


def overlap_share(a: float, b: float, window) -> float:
    """Share of [a, b] inside the window; a point counts whole or not at all."""
    if b <= a:
        return 1.0 if in_window(a, window) else 0.0
    return max(0.0, min(b, window[1]) - max(a, window[0])) / (b - a)


def out_tokens_per_s(records: list[dict], window) -> float | None:
    """Completion tokens streamed inside the window over its seconds. A request
    that straddles an edge counts by the part of its first-to-last-delta
    interval inside."""
    tokens = 0.0
    for r in records:
        if ok(r):
            tokens += r["completion_tokens"] * overlap_share(*stream_span(r), window)
    return tokens / (window[1] - window[0]) if tokens else None


def embeddings_per_s(records: list[dict], window) -> float | None:
    """Input texts embedded inside the window over its seconds. A request that
    straddles an edge counts by the part of its sent-to-answered interval
    inside: with a handful of long requests in flight, whole requests alone
    would move the rate by one request's worth at each edge."""
    texts = 0.0
    for r in records:
        if ok(r) and "inputs" in r:
            texts += r["inputs"] * overlap_share(r["sent"], r["done"], window)
    return texts / (window[1] - window[0]) if texts else None


def late_ms(records: list[dict], window) -> list[float]:
    """How late the generator sent each request due in the window."""
    return [(r["sent"] - r["due"]) * 1e3 for r in records
            if r.get("due") is not None and in_window(r["due"], window) and "sent" in r]
