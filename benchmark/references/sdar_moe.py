"""Plain float32 reference forward for the SDAR family (`model_type` `sdar_moe`):
Qwen3-MoE's stack (pre-norm RMSNorm, softmax GQA with an RMSNorm of each head's
query and key before rope, every layer's feed-forward routed experts: a softmax
router over all published experts in float32, the chosen probabilities
renormalised, no shared expert, no scaling factor), attended and generated by
BLOCK: positions lie in blocks of L, query i sees key j iff `j // L <= i // L`
(causal between blocks, bidirectional inside one, a prompt included), and the
logits are UNSHIFTED: the row at a position that holds the mask token is the
distribution of that position's OWN token. The chip under test may hold a SHARE
of the experts: the router scores all published experts, the experts [0,
num_experts held) add their part, and what the absent ones would add is left
out, here as in the program (references/exaone_moe.py's way).

Written from ISSUE 59's equations (the configuration's file lists what they
assume: the block length, the steps, the unmask rule, its threshold and the
mask's id are the released sampler's, which the published config does not
state). One unbatched sequence goes through one layer at a time in float32
`jax.numpy` at `Precision.HIGHEST`: attention is one [T, T] score matrix a head
under the block mask, every held expert is applied to every row and weighted by
the row's gate for it (0 where not chosen): no kernels, no cache, no grouped
products, no batching. It imports nothing from llm_mcp_tpu/models or kernels and
shares with them only the names of the parameter tree:

    params["embed"] [V, D], ["final_norm"] [D], ["lm_head"] [D, V]
    params["layers"], stacked over the L layers: attn_norm, ffn_norm [D], wq
        [D, H hd], wk, wv [D, Hkv hd], wo [H hd, D], q_norm, k_norm [hd], router
        [D, Er], w1e, w3e [E, D, F], w2e [E, F, D] (the E experts held)

`forward` is the logits at every position of one sequence; `logits` is what
`correctness.hold_to_reference` calls (one forward a block that holds a served
token: what a greedy block's first and only denoising pass chose from);
`generate_block` is the published sampler's loop for one block under an
explicit order of random draws (the CPU tests'). `sequential` unmasking is
absent: it is autoregression.

Departures from the published description, none a change of the mathematics:

- The layer is ONE jitted function that indexes the stacked tree
  (benchmark/reference.py's way): callers pad the sequence to a fixed length;
  causal between blocks, so what follows a block does not move it.
- Rotation is the split-half form (a head's first half paired with its
  second), the released code's `rotate_half`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
# The controls of SERVED_TOL_REL (below): the same forward with one thing the
# configuration states left out or lowered. None is the reference. "fp8" rounds
# every matrix and the left operand of every product to float8 e4m3 (the nearest
# floating precision under the stated bfloat16); "int8_head" rounds the head's
# matrix alone through int8 with one scale a column; "no_commit" reads ZERO keys
# and values at every position a commit pass would have written before the
# target's block (what the later blocks read when the commit pass is left out:
# rows of an empty cache behind the prefilled prompt, still under the row's
# length and so still in the softmax, at score 0);
# "causal" masks inside a block too (the mask a causal decoder applies).
# Set only by scripts/solar_tolerance.py and the tests (read when a layer is
# traced: clear jax's caches after a change), never by run.py.
LOWER: str | None = None
CONTROLS = ("fp8", "int8_head", "no_commit", "causal")


def _lower(x):
    if LOWER == "fp8":  # saturating, as a conversion to float8 is
        x = jnp.clip(x, -448.0, 448.0)
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    return x


def _mm(a, b):
    return jnp.matmul(_lower(a), b, precision=_HI)


# Served tokens against this forward, as a share of a row's max |logit|
# (`correctness.hold_to_reference`; one reading a seed = the worst of the 64
# served tokens of the reference request: a prompt of 6 bytes, 13 tokens as the
# chat endpoint renders them (12 prefilled by the engine's 1 x 32 admit program, 1
# fixed at the front of the first block), and 17 rounds of `block_round_fn`, each
# greedy block ONE denoising pass and a commit). A SHORT prompt and a LONG reply:
# from the third block on most keys of a softmax are ones a commit pass wrote, so
# what the comparison holds is the blocks' own path and not the prefill's. Behind
# a prompt of 200 bytes (the request this file first had: 16 tokens, 4 to 12
# generated keys of 212) a cache that was never committed read 0.000-0.025 and a
# causal mask 0.000-0.654, five seeds of six still correct (my chip runs, PR 59,
# calls 2 and C): the comparison could not see what the cell is there for.
# Readings by scripts/solar_tolerance.py --config sdar-30b-a3b-ep8-bf16, which
# serves the harness's own request (my chip runs, PR 59, second session, call F1
# from the archived tree, TPU v5 lite, the published widths, seeds 3200009000-39;
# the request's 6 bytes are "qref" and one letter, so replies repeat from seed
# to seed and so do readings):
# - the program, 40 seeds: 0.000-0.0142, median 0.0080, every seed correct; the
#   fifteen runs of the cell itself (calls F1, F2) 0.000-0.0117. With other short prompts (7 and
#   13 tokens, no chat rendering, 40 seeds each, call E2) 0.000-0.022: a reply of
#   64 tokens is a handful of distinct ids, and where two of them lie within a
#   hundredth of the row's largest logit the bfloat16 stream takes the other.
# - float8 ("fp8", the nearest floating precision under the stated bfloat16),
#   12 of those seeds: 0.548-0.761 (median 0.615), every seed not correct.
# - the structural controls, the same 12 seeds: the causal mask inside a block
#   0.688-0.844 (median 0.813); the commit pass left out ("no_commit": zero rows
#   in the softmax) 0.256-0.491 (median 0.331). Every seed of both NOT correct;
#   by the 0.45 this file first had, eleven of the commit-less twelve still were.
# - an int8 head ("int8_head"), 12 seeds: 0.000-0.0135, every seed correct: a
#   precision ABOVE float8's, which this comparison cannot tell from the program
#   and need not.
# The limit lies between the program's largest and float8's smallest with room
# on both sides (9 times 0.022, 0.36 of 0.548) AND under the commit-less
# control's smallest (0.78 of 0.256: the thin side). It is not widened for the
# router as the other share cells' are (K-EXAONE's share read up to 0.186,
# Solar-Open2's 0.16, where a moved eighth choice lands on a held expert): 165
# replies of 64 tokens have not met one here, and a limit over 0.25 could not
# see a lost commit on every seed.
SERVED_TOL_REL = 0.2

# -- what the configuration's file states beyond run.py's own tables -----------

HELD = {
    "num_experts": lambda c: c.n_experts,
    # the router keeps the published width while num_experts counts the held
    "published.num_experts": lambda c: c.router_width,
    # every layer's feed-forward is routed experts: a period of 1, no dense layer
    "decoder_sparse_step": lambda c: 1 if c.n_experts and not c.first_dense_layers else 0,
    "mlp_only_layers": lambda c: list(range(c.first_dense_layers)),
    # no layer slides: the first layer that would lies past the last
    "max_window_layers": lambda c: c.n_layers,
    "use_sliding_window": lambda c: bool(c.sliding_window),
}
ONLY: dict = {}
STATED: dict[str, str] = {}


def check(cfg) -> None:
    """Raises for a configuration these equations do not cover."""
    if not cfg.block_len:
        raise NotImplementedError(f"{cfg.name!r} generates one token a step: not this family")
    if (cfg.router_score != "softmax" or cfg.n_shared_experts or cfg.sliding_window
            or cfg.sliding_windows or not cfg.n_experts or cfg.first_dense_layers
            or cfg.routed_scaling_factor != 1.0):
        raise NotImplementedError(f"no plain SDAR reference for {cfg.name!r}")
    if (cfg.kv_lora_rank or cfg.gqa_layers or not cfg.qk_norm or cfg.qk_norm_whole
            or not cfg.use_rope or cfg.rope_factor > 1.0 or cfg.norm_placement != "input"
            or cfg.attn_softcap or cfg.logit_softcap or cfg.post_norms
            or cfg.norm_weight_offset or cfg.embed_scale or cfg.qkv_bias or cfg.attn_gate
            or cfg.act != "silu" or cfg.tie_embeddings or cfg.attn_multiplier
            or cfg.embed_multiplier != 1.0 or cfg.residual_multiplier != 1.0
            or cfg.logits_divisor != 1.0 or cfg.query_pre_attn_scalar):
        raise NotImplementedError(f"no plain SDAR reference for {cfg.name!r}")


# -- the tree ----------------------------------------------------------------------


def _at(leaf, *index):
    for i in index:
        leaf = jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
    leaf = leaf.astype(jnp.float32)
    return _lower(leaf) if leaf.ndim == 2 else leaf


def _rms(x, w, eps: float):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _swiglu(x, gate_w, up_w, down_w):
    g = _mm(x, gate_w)
    return _mm(g * _sigmoid(g) * _mm(x, up_w), down_w)


def _rotate(x, theta: float):
    """x [T, heads, hd] at positions 0..T-1: pairs (i, i + hd/2) turned by
    position / theta**(2 i / hd)."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def seen(cfg, T: int):
    """The block mask [T, T]: query i sees key j iff j // L <= i // L."""
    if LOWER == "causal":
        return jnp.tril(jnp.ones((T, T), bool))
    blk = jnp.arange(T) // cfg.block_len
    return blk[None, :] <= blk[:, None]


def _attention(cfg, stack, li, x, mask, unwritten):
    """`unwritten` [2] (lo, hi): the keys and values of the positions [lo, hi)
    are ZERO, as a cache row reads that no pass ever wrote (the control
    "no_commit"; (0, 0) for none). They stay in the softmax at score 0, as they
    do in a program that advances a row's length past rows it did not write."""
    T = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = _mm(x, _at(stack["wq"], li)).reshape(T, H, hd)
    k = _mm(x, _at(stack["wk"], li)).reshape(T, Hkv, hd)
    v = _mm(x, _at(stack["wv"], li)).reshape(T, Hkv, hd)
    q = _rms(q, _at(stack["q_norm"], li), cfg.norm_eps)
    k = _rms(k, _at(stack["k_norm"], li), cfg.norm_eps)
    q, k = _rotate(q, cfg.rope_theta), _rotate(k, cfg.rope_theta)
    pos = jnp.arange(T)
    written = ~((pos >= unwritten[0]) & (pos < unwritten[1]))[:, None, None]
    k, v = jnp.where(written, k, 0.0), jnp.where(written, v, 0.0)
    heads = []
    for head in range(H):  # one head at a time: [T, T] scores
        kv = head // (H // Hkv)
        s = jnp.where(mask, _mm(q[:, head], k[:, kv].T) * hd**-0.5, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        heads.append(_mm(p / jnp.sum(p, axis=-1, keepdims=True), v[:, kv]))
    return _mm(jnp.concatenate(heads, axis=-1), _at(stack["wo"], li))


def _experts(cfg, stack, li, x, held: int | None = None):
    """This chip's part of the routed experts' sum: experts [0, held) of the
    router's columns (`held` None: every expert the tree holds)."""
    z = _mm(x, _at(stack["router"], li))  # [T, Er]: every published expert
    scores = jnp.exp(z - jnp.max(z, axis=-1, keepdims=True))
    scores = scores / jnp.sum(scores, axis=-1, keepdims=True)
    top, chosen = jax.lax.top_k(scores, cfg.experts_per_tok)
    top = top / jnp.sum(top, axis=-1, keepdims=True) if cfg.norm_topk_prob else top
    onehot = chosen[:, :, None] == jnp.arange(scores.shape[-1])[None, None, :]
    gates = jnp.sum(jnp.where(onehot, top[:, :, None], 0.0), axis=1)  # [T, Er]

    def expert(e, out):  # e < the experts held here
        y = _swiglu(x, *(_at(stack[n], li, e) for n in ("w1e", "w3e", "w2e")))
        return out + y * jax.lax.dynamic_index_in_dim(gates, e, 1, keepdims=True)

    n = stack["w1e"].shape[1] if held is None else held
    return jax.lax.fori_loop(0, n, expert, jnp.zeros_like(x))


@partial(jax.jit, static_argnums=(0,))
def _layer(cfg, layers, li, h, mask, unwritten):
    """One pre-norm layer over h [T, D] under `mask` [T, T]."""
    h = h + _attention(cfg, layers, li, _rms(h, _at(layers["attn_norm"], li), cfg.norm_eps), mask,
                       unwritten)
    return h + _experts(cfg, layers, li, _rms(h, _at(layers["ffn_norm"], li), cfg.norm_eps))


def residual_stream(cfg, params, tokens: np.ndarray, unwritten: tuple[int, int] = (0, 0)):
    """The residual stream after the last layer [T, D] (float32) of one
    unbatched sequence under the block mask (`unwritten`: `_attention`)."""
    check(cfg)
    h = jnp.asarray(params["embed"])[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    mask, gone = seen(cfg, len(tokens)), jnp.asarray(unwritten, jnp.int32)
    for li in range(cfg.n_layers):
        h = _layer(cfg, params["layers"], jnp.int32(li), h, mask, gone)
    return h


def _int8_columns(w):
    """A matrix through int8 with one scale a column (the control "int8_head")."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(s, 1e-30)) * s


def _head(cfg, params, h, rows, cols) -> np.ndarray:
    out = _rms(h, jnp.asarray(params["final_norm"], jnp.float32), cfg.norm_eps)[jnp.asarray(rows)]
    head = jnp.asarray(params["lm_head"])[:, jnp.asarray(cols)].astype(jnp.float32)
    if LOWER == "int8_head":
        head = _int8_columns(head)
    return np.asarray(_mm(out, _lower(head)), np.float32)


def forward(cfg, params, tokens: np.ndarray, rows=None, cols=None,
            unwritten: tuple[int, int] = (0, 0)) -> np.ndarray:
    """Logits of one sequence under the block mask, UNSHIFTED: row t is the
    distribution of position t's OWN token (what a position that holds the mask
    is filled from). [T, V], or cut to the positions `rows` and the ids `cols`."""
    T = len(tokens)
    rows = np.arange(T) if rows is None else rows
    cols = np.arange(cfg.vocab_size) if cols is None else cols
    return _head(cfg, params, residual_stream(cfg, params, tokens, unwritten), rows, cols)


def logits(cfg, params, tokens: np.ndarray, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """What `correctness.hold_to_reference` holds the served tokens to: `tokens`
    is the prompt and all served tokens but the last, padded; `rows[0] + 1` is
    the prompt's length P and `len(rows)` the tokens served. Row k is the
    distribution served token k was chosen from, cut to the ids `cols`: the
    logits at its OWN position p = P + k of the sequence [every token before
    p's block ; the prompt's remainder where the block is the reply's first ;
    the mask to the block's end], which is what a greedy block's first and only
    denoising pass sees (greedy is top-1: every position's probability is 1, so
    the dynamic rule fills the whole block in that pass). One forward a block;
    the earlier blocks' tokens are teacher-forced from `tokens`, and the last
    served token is never needed: no pass conditions on it."""
    L, mask_id = cfg.block_len, cfg.mask_token_id
    tokens = np.asarray(tokens, np.int32)
    P, n = int(rows[0]) + 1, len(rows)
    out = np.zeros((n, len(cols)), np.float32)
    targets = P + np.arange(n)
    for b in np.unique(targets // L):
        seq = tokens.copy()
        fixed = max(b * L, min(P, (b + 1) * L))  # the block's positions that are the prompt's
        seq[fixed : (b + 1) * L] = mask_id
        seq[(b + 1) * L :] = 0  # what follows the block moves nothing in it
        mine = np.flatnonzero(targets // L == b)
        # never committed: every position a commit pass would have written before this block
        unwritten = (P - P % L, b * L) if LOWER == "no_commit" else (0, 0)
        out[mine] = forward(cfg, params, seq, targets[mine], cols, unwritten)
    return out


def unmask(cfg, block: np.ndarray, x0: np.ndarray, p: np.ndarray) -> np.ndarray:
    """One pass's unmask rule on one block [L]: of the positions that still hold
    the mask, n = L / denoise_steps are due; `low_confidence_dynamic` fills
    every one whose probability passes the threshold where at least n do, else
    the n of highest probability (ties: the earlier first);
    `low_confidence_static` is the else-arm alone."""
    L = len(block)
    due = L // cfg.denoise_steps
    masked = block == cfg.mask_token_id
    conf = np.where(masked, p, -np.inf)
    take = np.zeros(L, bool)
    take[np.argsort(-conf, kind="stable")[:due]] = True
    take &= masked
    if cfg.unmask_rule == "low_confidence_dynamic":
        high = masked & (p > cfg.unmask_threshold)
        if high.sum() >= due:
            take = high
    return np.where(take, x0, block)


def generate_block(cfg, params, before: np.ndarray, block: np.ndarray, sample) -> list[dict]:
    """The published sampler's loop for ONE block: `before` is every token
    before the block, `block` [L] the block as it starts (the prompt's remainder
    in front, then masks). `sample(pass_index, logits [L, V]) -> (x0 [L], p [L])`
    is the explicit order of random draws: each position's sampled token and its
    probability after the request's filters. Denoising passes (a full forward of
    [before ; block] each, nothing cached) until no mask is left; returns one
    record a pass: {"block": the block it saw, "logits": [L, V], "x0", "p",
    "after": the block it left}."""
    L = cfg.block_len
    block = np.asarray(block, np.int32).copy()
    passes = []
    while (block == cfg.mask_token_id).any():
        seq = np.concatenate([np.asarray(before, np.int32), block])
        lg = forward(cfg, params, seq, np.arange(len(before), len(before) + L))
        x0, p = sample(len(passes), lg)
        after = unmask(cfg, block, np.asarray(x0, np.int32), np.asarray(p, np.float32))
        passes.append({"block": block, "logits": lg, "x0": x0, "p": p, "after": after})
        block = after
    return passes
