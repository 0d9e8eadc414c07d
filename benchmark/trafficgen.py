"""The one general traffic generator. Pure standard library: the load
generator's child process imports it and must never import JAX.

A traffic mix is a data file `benchmark/traffic/<name>.json`:

    endpoint            "chat" | "embeddings"
    loop                "open" (arrivals on a schedule) | "closed" (each client
                        sends its next request when the last one ends)
    rate_per_s          open loop: mean arrivals a second (fixed, never searched)
    clients             closed loop: number of clients
    prompt_tokens       a distribution (below): bytes of prompt text, which with
                        the byte tokenizer is tokens
    max_tokens          chat: a distribution of completion lengths
    temperature         chat: sampling temperature
    inputs_per_request  embeddings: texts in one POST
    dimensions          embeddings: Matryoshka width asked for
    preroll_s           seconds of the same traffic before the measured window
    stagger_first       closed chat: spread the first requests' lengths so that
                        the clients do not run in lock step

A distribution is {"dist": "const", "value": v}, {"dist": "uniform", "lo", "hi"}
or {"dist": "lognormal", "median", "sigma", "lo", "hi"} (clipped).

Every seed gets the SAME multiset of sizes, texts and arrival gaps, in another
order: sizes are the distribution's stratified quantiles, a text's words are
drawn from its size's rank among them, and the seed only shuffles. So the work
a window offers does not move with the seed, only its order does. A closed
loop's request of several texts (embeddings) is a hand dealt in turn from the
deck, the same hand under every seed (`_deal_hands`), because what such a
request costs follows from the sizes it holds together. (With random
weights a reply ends where EOS is sampled, and how soon depends on the prompt:
texts drawn from the seed made one seed's window hold 25% more requests than
another's; v5e, PR 23.)
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

WORDS = (
    "slot block token batch queue cache shard chunk round step page table "
    "router engine prefix decode prefill budget window stream client server "
    "device kernel tensor layer vector matrix scale norm head gate "
    "alpha bravo delta echo golf hotel india kilo lima mike "
    "amber basil cedar dune ember fjord grove heron iris jade "
    "seven eight nine north south east west over under between"
).split()


def quantile(dist: dict, u: float) -> float:
    """The distribution's value at probability u in (0, 1)."""
    kind = dist["dist"]
    if kind == "const":
        return float(dist["value"])
    if kind == "uniform":
        return dist["lo"] + u * (dist["hi"] - dist["lo"])
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
        return min(max(v, dist["lo"]), dist["hi"])
    raise ValueError(f"unknown distribution {kind!r}")


def deck(dist: dict, n: int, rng: random.Random) -> list[list[int]]:
    """n prompts as [size, rank]: the stratified quantiles of `dist` as whole
    sizes, each with its rank among them, shuffled."""
    out = [[max(1, round(quantile(dist, (i + 0.5) / n))), i] for i in range(n)]
    rng.shuffle(out)
    return out


def size_deck(dist: dict, n: int, rng: random.Random) -> list[int]:
    """n whole sizes alone (completion lengths), shuffled."""
    return [size for size, _rank in deck(dist, n, rng)]


def gap_deck(n: int, span_s: float, rng: random.Random) -> list[float]:
    """n arrival gaps of a Poisson process (stratified exponential quantiles),
    scaled to sum to span_s exactly, shuffled."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    k = span_s / sum(raw)
    out = [g * k for g in raw]
    rng.shuffle(out)
    return out


def text(n_bytes: int, rank: int, tag: str) -> str:
    """ASCII words cut to n_bytes: the words follow from `rank` alone, the
    first word is `tag`, the request's own within its run, so no two prompts
    share a prefix beyond the chat template."""
    rng = random.Random(rank)
    parts = [f"q{tag}"]
    size = len(parts[0])
    while size < n_bytes:
        w = WORDS[rng.randrange(len(WORDS))]
        parts.append(w)
        size += 1 + len(w)
    return " ".join(parts)[:n_bytes].rstrip().ljust(n_bytes, ".")


# closed-loop plans hold this many requests at most; a window never gets near
CLOSED_CAP = 4096
DECK = 64


def _deal(dealer, dist: dict, n: int, rng: random.Random) -> list:
    """n cards dealt from whole decks of DECK stratified quantiles."""
    out: list = []
    while len(out) < n:
        out += dealer(dist, DECK, rng)
    return out[:n]


def _deal_hands(dist: dict, n: int, per_req: int, rng: random.Random) -> list[list[list[int]]]:
    """n requests of per_req texts each, dealt in turn from whole decks: a
    deck is the stratified quantiles, longest first, DECK of them or the next
    multiple of per_req, and its card i goes to the deck's request i mod k (k
    requests a deck), as cards are dealt round a table. So the j-th request of
    every deck holds the same sizes and texts under every seed, an even sample
    of the whole distribution, and the seed shuffles them within the request
    alone. (A deck shuffled and then cut into requests gave each request a
    random half of it: the server packs a request's texts into rows of 512
    and runs a forward for every two rows, a half packed into 12 to 17 rows,
    and a window's forwards moved with the seed by half a percent; v5e, PR 40.)"""
    k = -(-DECK // per_req)
    size = k * per_req
    cards = [[max(1, round(quantile(dist, (i + 0.5) / size))), i] for i in reversed(range(size))]
    out: list[list[list[int]]] = []
    while len(out) < n:
        for j in range(k):
            hand = [list(c) for c in cards[j::k]]
            rng.shuffle(hand)
            out.append(hand)
    return out[:n]


def make_plan(traffic: dict, seed: int, seconds: float, *, model: str,
              preroll_s: float | None = None, salt: str = "r") -> dict:
    """The plan one run of the load generator executes: a list of requests in
    issue order, each {"i", "prompt": [[bytes, rank], ...], "max_tokens",
    "due"}; open loops carry `due` (seconds from the plan's start), closed
    loops none. The window is [preroll_s, preroll_s + seconds) from the plan's
    start. `salt` starts every prompt's first word: plans of one process (the
    warm-up's rounds, the window) take different ones, so that none repeats
    another's prompts."""
    rng = random.Random(seed)
    pre = float(traffic.get("preroll_s", 0.0) if preroll_s is None else preroll_s)
    loop, endpoint = traffic["loop"], traffic["endpoint"]
    per_req = int(traffic.get("inputs_per_request", 1)) if endpoint == "embeddings" else 1
    reqs: list[dict] = []
    if loop == "open":
        rate = float(traffic["rate_per_s"])
        t = 0.0
        for span in (pre, float(seconds)):  # the same rate before and inside
            n = round(rate * span)
            if n <= 0:
                t += span
                continue
            gaps = gap_deck(n, span, rng)
            plens = deck(traffic["prompt_tokens"], n * per_req, rng)
            mtoks = size_deck(traffic["max_tokens"], n, rng) if endpoint == "chat" else [0] * n
            for j in range(n):
                # a request is due in the middle of its gap's share of the span
                reqs.append({"due": t + gaps[j] * 0.5,
                             "prompt": plens[j * per_req:(j + 1) * per_req],
                             "max_tokens": mtoks[j]})
                t += gaps[j]
    elif loop == "closed":
        n = CLOSED_CAP
        if per_req > 1:
            hands = _deal_hands(traffic["prompt_tokens"], n, per_req, rng)
            plens = [card for hand in hands for card in hand]
        else:
            plens = _deal(deck, traffic["prompt_tokens"], n * per_req, rng)
        mtoks = _deal(size_deck, traffic["max_tokens"], n, rng) if endpoint == "chat" else [0] * n
        clients = int(traffic["clients"])
        for j in range(n):
            mt = mtoks[j]
            if traffic.get("stagger_first") and j < clients and endpoint == "chat":
                # first round only: client j's reply ends (j+1)/clients of the
                # way through a full one, so the clients stay out of step
                mt = max(1, round(mt * (j + 1) / clients))
            reqs.append({"prompt": plens[j * per_req:(j + 1) * per_req], "max_tokens": mt})
    else:
        raise ValueError(f"unknown loop {loop!r}")
    for i, r in enumerate(reqs):
        r["i"] = i
    return {
        "endpoint": endpoint, "loop": loop, "model": model, "seed": int(seed), "salt": salt,
        "clients": int(traffic.get("clients", 0)),
        "temperature": float(traffic.get("temperature", 0.0)),
        "dimensions": int(traffic.get("dimensions", 0)),
        "preroll_s": pre, "seconds": float(seconds), "requests": reqs,
    }
