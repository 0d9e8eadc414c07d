"""On-device token sampling: temperature / top-k / top-p, per-row parameters.

TPU-first design: sampling runs inside the jitted decode step so only the
sampled token ids ([B] int32) ever leave the device — the [B, vocab] logits
never cross HBM→host. A full-vocab sort per step would be wasteful on a 128k
vocab, so top-p operates within a fixed 64-candidate top-k window. For large
vocabs the window itself comes from the TPU-native `lax.approx_max_k`
(recall ~0.95; exact `lax.top_k` costs ~1.5 ms/step at B=64 on a 128k
vocab), so sampling is approximate twice over: the window may miss ~5% of
true top-64 ids, and top-p truncates within it. Greedy (temperature <= 0)
stays exact — it argmaxes the full logits row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_CANDIDATES = 64


def expand_mask(packed: jnp.ndarray, V: int) -> jnp.ndarray:
    """Unpack a `[..., ceil(V/32)] uint32` token bitmask to `[..., V]` bool.

    Bit layout matches the host-side constrain/masks.py packer: token id
    ``t`` lives at bit ``t & 31`` of word ``t >> 5``. The gather+shift
    compiles to a handful of vector ops — no host round-trip, so the
    packed words are all that crosses PCIe per constrained row."""
    ids = jnp.arange(V, dtype=jnp.uint32)
    word = packed[..., (ids >> 5).astype(jnp.int32)]
    return ((word >> (ids & jnp.uint32(31))) & jnp.uint32(1)).astype(jnp.bool_)


def apply_token_mask(
    logits: jnp.ndarray,  # [B, V] or [A, C, V]
    packed: jnp.ndarray | None,  # [B, W] / [A, C, W] uint32, or None
    bias_ids: jnp.ndarray | None = None,  # [B, NB] int32, -1 = pad
    bias_vals: jnp.ndarray | None = None,  # [B, NB] float32
) -> jnp.ndarray:
    """Constraint mask + `logit_bias` on one static-shape path.

    Bias is scattered densely FIRST (so a bias can reweight within the
    legal set), then illegal tokens go to -inf — a bias can never
    resurrect a token the automaton forbids. Bias rows are per-request
    ([B, NB]) and broadcast across chunk positions for 3-D verify
    logits; pad entries use id -1 (add 0 at column 0, harmless)."""
    V = logits.shape[-1]
    out = logits
    if bias_ids is not None and bias_vals is not None:
        B = bias_ids.shape[0]
        safe = jnp.maximum(bias_ids, 0)
        vals = jnp.where(bias_ids >= 0, bias_vals, 0.0).astype(logits.dtype)
        dense = jnp.zeros((B, V), dtype=logits.dtype)
        dense = dense.at[jnp.arange(B)[:, None], safe].add(vals)
        out = out + (dense[:, None, :] if logits.ndim == 3 else dense)
    if packed is not None:
        out = jnp.where(expand_mask(packed, V), out, -jnp.inf)
    return out


def sample_tokens(
    logits: jnp.ndarray,  # [B, V] float32
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 = disabled)
    top_p: jnp.ndarray,  # [B] float32 (1.0 = disabled)
    active: jnp.ndarray | None = None,  # [B] bool — rows whose sample matters
    exact: bool = False,  # static: force exact top-k windows (constrained rows)
) -> jnp.ndarray:
    """Sample one token per row. temperature<=0 → greedy argmax.

    Homogeneous batches take exact fast paths picked at RUNTIME (lax.cond —
    sampling params are device-resident per-slot arrays, so the mix isn't
    known at trace time): all-greedy is one argmax, and all plain
    temperature (no top-k/top-p anywhere) is exact Gumbel-argmax over the
    FULL vocab — both cheaper than the candidate-window machinery (measured
    ~1 ms/step at 8B B=112) and the Gumbel path is exact where the window
    is approximate. Mixed batches keep the windowed path below.

    `active` excludes parked/pad rows from the homogeneity reductions:
    those rows carry zero-init or stale params from a prior occupant and
    their sampled token is discarded anyway — without the mask one stale
    slot would silently disable the fast paths at partial occupancy."""
    B, V = logits.shape
    n_cand = min(_CANDIDATES, V)

    def _pred(cond: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(jnp.where(active, cond, True) if active is not None else cond)

    def _all_greedy(_):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _plain_temp(_):
        temp = jnp.maximum(temperature, 1e-6)[:, None]
        g = jax.random.gumbel(rng, (B, V), dtype=jnp.float32)
        return jnp.argmax(logits / temp + g, axis=-1).astype(jnp.int32)

    def _windowed(_):
        return _sample_windowed(
            logits, rng, temperature, top_k, top_p, n_cand, exact=exact
        )

    plain = _pred((top_k <= 0) & (top_p >= 1.0) & (temperature > 0.0))
    return jax.lax.cond(
        _pred(temperature <= 0.0),
        _all_greedy,
        lambda _: jax.lax.cond(plain, _plain_temp, _windowed, None),
        None,
    )


def _sample_windowed(
    logits: jnp.ndarray,
    rng: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    n_cand: int,
    exact: bool = False,
    with_p: bool = False,  # static: (tokens, each one's probability among what the filters left)
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # Top-K candidate window (per-row k applied by masking within the window).
    # approx_max_k uses the TPU-native approximate top-k (recall ~0.95 within
    # the window) — exact lax.top_k over a 128k vocab costs ~1.5 ms/step at
    # B=64, several times the logits head itself. Results come back sorted
    # descending, which the top-p prefix logic below relies on. Constrained
    # rows force `exact`: with a tiny automaton-legal set a 0.95-recall
    # window could miss EVERY legal token and sample from a -inf row.
    if V > 4 * n_cand and not exact:
        cand_logits, cand_idx = jax.lax.approx_max_k(
            logits, n_cand, recall_target=0.95, aggregate_to_topk=True
        )
    else:
        cand_logits, cand_idx = jax.lax.top_k(logits, n_cand)  # [B, C] desc
    k = jnp.where(top_k <= 0, n_cand, jnp.minimum(top_k, n_cand))
    pos = jnp.arange(n_cand)[None, :]
    k_mask = pos < k[:, None]

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = jnp.where(k_mask, cand_logits / temp, -jnp.inf)

    # Top-p within the window: keep the smallest prefix with cumprob >= p
    # (always keep the first candidate).
    probs = jax.nn.softmax(scaled, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    p_mask = (cum - probs) < top_p[:, None]  # prefix-exclusive cumsum < p
    p_mask = p_mask.at[:, 0].set(True)
    final = jnp.where(p_mask & k_mask, scaled, -jnp.inf)

    gumbel = jax.random.gumbel(rng, (B, n_cand), dtype=jnp.float32)
    choice = jnp.argmax(final + gumbel, axis=-1)  # [B]
    sampled = jnp.take_along_axis(cand_idx, choice[:, None], axis=1)[:, 0].astype(jnp.int32)

    tokens = jnp.where(temperature <= 0.0, greedy, sampled)
    if not with_p:
        return tokens
    p = jnp.take_along_axis(jax.nn.softmax(final, axis=-1), choice[:, None], axis=1)[:, 0]
    return tokens, jnp.where(temperature <= 0.0, 1.0, p)


def sample_tokens_p(
    logits: jnp.ndarray,  # [B, V] float32
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 = disabled)
    top_p: jnp.ndarray,  # [B] float32 (1.0 = disabled)
    active: jnp.ndarray | None = None,  # [B] bool: rows whose sample matters
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`sample_tokens` that also says how sure it was: (tokens [B] int32, p [B]
    float32), p the probability of the sampled token under the distribution it
    was drawn from, AFTER temperature, top-k and top-p (what a diffusion
    sampler's confidence rule compares; executor/engine.py:block_round_fn). A
    greedy row (temperature <= 0) is top-1: its token is the argmax and p is
    1.0. The same three runtime paths as `sample_tokens`, and the same draws:
    with one key the tokens are `sample_tokens`' own."""
    B, V = logits.shape
    n_cand = min(_CANDIDATES, V)

    def _pred(cond: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(jnp.where(active, cond, True) if active is not None else cond)

    def _all_greedy(_):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), jnp.ones((B,), jnp.float32)

    def _plain_temp(_):
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        g = jax.random.gumbel(rng, (B, V), dtype=jnp.float32)
        tok = jnp.argmax(scaled + g, axis=-1).astype(jnp.int32)
        top = jnp.take_along_axis(scaled, tok[:, None], axis=1)[:, 0]
        return tok, jnp.exp(top - jax.nn.logsumexp(scaled, axis=-1))

    def _windowed(_):
        return _sample_windowed(
            logits, rng, temperature, top_k, top_p, n_cand, with_p=True)

    plain = _pred((top_k <= 0) & (top_p >= 1.0) & (temperature > 0.0))
    return jax.lax.cond(
        _pred(temperature <= 0.0),
        _all_greedy,
        lambda _: jax.lax.cond(plain, _plain_temp, _windowed, None),
        None,
    )


def spec_verify(
    logits: jnp.ndarray,  # [A, C, V] float32 — position j scores offset j+1
    drafts: jnp.ndarray,  # [A, K] int32 drafted tokens, K = C - 1
    n_draft: jnp.ndarray,  # [A] int32 — valid drafts per row (<= K)
    rng: jax.Array,
    temperature: jnp.ndarray,  # [A]
    top_k: jnp.ndarray,  # [A] int32 (0 = disabled)
    top_p: jnp.ndarray,  # [A] float32 (1.0 = disabled)
    active: jnp.ndarray | None = None,  # [A] bool — rows whose result matters
    exact: bool = False,  # static: force exact top-k windows (constrained rows)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Accept/reject a deterministic draft against the target logits and
    sample the one token that always follows.

    The engine's n-gram drafter is deterministic — it puts probability 1 on
    its proposal — so standard speculative rejection sampling collapses to:
    accept draft ``d`` at position ``j`` with probability ``p_target(d)``
    (greedy rows: exact argmax equality), stop at the first rejection, and
    sample the next token from the RESIDUAL distribution — the target with
    the rejected token zeroed and renormalized. That marginal is exactly the
    target: ``p(d)·1 + (1 - p(d))·p(x)/(1 - p(d)) = p(x)``, so speculation
    never changes what the engine emits, only how many model calls it costs.

    When every draft is accepted the final token is a "bonus" sample from
    the unmasked target at the position after the last draft — `C = K + 1`
    positions of logits guarantee it exists.

    Distribution parity with `sample_tokens` is structural: the same three
    runtime paths (all-greedy / all plain temperature over the full vocab /
    candidate-window for rows with top-k/top-p), so speculative and
    non-speculative decode agree exactly wherever `sample_tokens` itself is
    exact, and share the same window approximation where it is not.

    Returns ``(n_acc [A] int32, final [A] int32)``: emitted tokens for row
    ``a`` are ``drafts[a, :n_acc[a]]`` followed by ``final[a]``.
    """
    A, C, V = logits.shape
    K = C - 1
    n_cand = min(_CANDIDATES, V)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [A, C]
    valid = jnp.arange(K, dtype=jnp.int32)[None, :] < n_draft[:, None]
    is_greedy = temperature <= 0.0
    rng_u, rng_f = jax.random.split(rng)
    u = jax.random.uniform(rng_u, (A, K), dtype=jnp.float32)

    def _pred(cond: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(jnp.where(active, cond, True) if active is not None else cond)

    def _count(acc: jnp.ndarray) -> jnp.ndarray:
        # longest accepted prefix: cumprod zeroes everything past the first
        # rejection
        return jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)

    def _finish(n_acc, sampled):
        pos_greedy = jnp.take_along_axis(greedy_tok, n_acc[:, None], axis=1)[:, 0]
        final = jnp.where(is_greedy, pos_greedy, sampled)
        return n_acc.astype(jnp.int32), final.astype(jnp.int32)

    def _mask_tok(n_acc):
        # the token to zero out of the residual: the first REJECTED draft.
        # When nothing was rejected (n_acc == n_draft) the final sample is
        # the unmasked bonus token — -1 matches no vocab id.
        rej = jnp.take_along_axis(
            drafts, jnp.minimum(n_acc, K - 1)[:, None], axis=1
        )[:, 0]
        return jnp.where(n_acc < n_draft, rej, -1)

    def _all_greedy(_):
        n_acc = _count((greedy_tok[:, :K] == drafts) & valid)
        return _finish(n_acc, jnp.zeros((A,), jnp.int32))

    def _full_vocab(_):
        temp = jnp.maximum(temperature, 1e-6)[:, None, None]
        scaled = logits / temp  # [A, C, V]
        lse = jax.nn.logsumexp(scaled, axis=-1)  # [A, C]
        d_logit = jnp.take_along_axis(
            scaled[:, :K], drafts[..., None], axis=-1
        )[..., 0]
        p_draft = jnp.exp(d_logit - lse[:, :K])  # [A, K]
        acc = jnp.where(is_greedy[:, None], greedy_tok[:, :K] == drafts, u < p_draft)
        n_acc = _count(acc & valid)
        pos_scaled = jnp.take_along_axis(
            scaled, n_acc[:, None, None], axis=1
        )[:, 0]  # [A, V]
        resid = jnp.where(
            jnp.arange(V, dtype=jnp.int32)[None, :] == _mask_tok(n_acc)[:, None],
            -jnp.inf,
            pos_scaled,
        )
        g = jax.random.gumbel(rng_f, (A, V), dtype=jnp.float32)
        return _finish(n_acc, jnp.argmax(resid + g, axis=-1))

    def _windowed(_):
        # the same candidate-window distribution _sample_windowed draws
        # from, applied per chunk position
        flat = logits.reshape(A * C, V)
        if V > 4 * n_cand and not exact:
            cand_logits, cand_idx = jax.lax.approx_max_k(
                flat, n_cand, recall_target=0.95, aggregate_to_topk=True
            )
        else:
            cand_logits, cand_idx = jax.lax.top_k(flat, n_cand)
        cand_logits = cand_logits.reshape(A, C, n_cand)
        cand_idx = cand_idx.reshape(A, C, n_cand).astype(jnp.int32)
        k = jnp.where(top_k <= 0, n_cand, jnp.minimum(top_k, n_cand))
        k_mask = jnp.arange(n_cand)[None, None, :] < k[:, None, None]
        temp = jnp.maximum(temperature, 1e-6)[:, None, None]
        scaled = jnp.where(k_mask, cand_logits / temp, -jnp.inf)
        probs = jax.nn.softmax(scaled, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        p_mask = (cum - probs) < top_p[:, None, None]
        p_mask = p_mask.at[:, :, 0].set(True)
        m = p_mask & k_mask
        wp = jnp.where(m, probs, 0.0)
        norm = jnp.maximum(jnp.sum(wp, axis=-1), 1e-9)  # [A, C]
        match = cand_idx[:, :K] == drafts[:, :, None]  # [A, K, n_cand]
        p_draft = jnp.sum(jnp.where(match, wp[:, :K], 0.0), axis=-1) / norm[:, :K]
        acc = jnp.where(is_greedy[:, None], greedy_tok[:, :K] == drafts, u < p_draft)
        n_acc = _count(acc & valid)
        take = lambda x: jnp.take_along_axis(x, n_acc[:, None, None], axis=1)[:, 0]
        w_scaled, w_idx, w_m = take(scaled), take(cand_idx), take(m)
        resid = jnp.where(
            w_m & (w_idx != _mask_tok(n_acc)[:, None]), w_scaled, -jnp.inf
        )
        g = jax.random.gumbel(rng_f, (A, n_cand), dtype=jnp.float32)
        choice = jnp.argmax(resid + g, axis=-1)
        sampled = jnp.take_along_axis(w_idx, choice[:, None], axis=1)[:, 0]
        return _finish(n_acc, sampled)

    plain = _pred((top_k <= 0) & (top_p >= 1.0))
    return jax.lax.cond(
        _pred(is_greedy),
        _all_greedy,
        lambda _: jax.lax.cond(plain, _full_vocab, _windowed, None),
        None,
    )
