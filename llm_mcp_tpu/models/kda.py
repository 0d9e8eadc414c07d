"""The gated delta rule as a linear-attention layer of the hybrid decoder
(models/hybrid.py), with two forms of gate (`cfg.lin_gates`): Kimi Delta
Attention ("kda": a decay a key channel) and Gated DeltaNet ("gdn": one decay
a head; Yang, Kautz, Hatamizadeh, arXiv 2412.06464).

For every sequence and head the layer keeps a float32 state S of
[keys dk, values dv] instead of keys and values of the past:

    q, k, v = SiLU(conv(x Wq)), SiLU(conv(x Wk)), SiLU(conv(x Wv))
              (causal depthwise convolution, `lin_conv` taps a channel)
    q, k    = q / |q| * dk**-0.5, k / |k|            (L2 over the head)
    beta_t  = sigmoid(x W_beta) (* 2 with `lin_neg_eigval`)          per head
    S'      = diag(exp(g_t)) S_{t-1}
    S_t     = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t     = S_t^T q_t
    y       = (RMSNorm_head(o_t) * gate) Wo

    kda: g_t = -exp(A_log_h) * softplus(x Wf_down Wf_up + dt_bias)  per channel
         gate = sigmoid(x Wg_down Wg_up)          (both through a low rank)
    gdn: g_t = -exp(A_log_h) * softplus(x W_a + dt_bias_h)          per head
         gate = SiLU(x W_g)                        (both at full rank)

What a slot owns of a layer is S and the convolution's tail: the last
`lin_conv - 1` rows of x [Wq | Wk | Wv] (before the convolution), so that the
next token, or the next chunk of a prompt, continues where this one stopped.

Two forms of one recurrence:

- `kda_chunk_scan`, for prompts: the sequence in chunks of `CHUNK` tokens, S
  carried from chunk to chunk. Inside a chunk the rank-1
  corrections depend on each other only through a unit lower-triangular
  system, solved once a chunk; the rest is products of [C, d] blocks. Every
  decay enters as exp of a difference of cumulative log decays that is <= 0
  (a later position against an earlier one), never as a quotient of two
  exponentials, so a channel that forgets fast cannot overflow. With one
  decay a head ("gdn", and Mamba-2's form) the chunks are ONE Pallas call a
  layer (kernels/kda.py:chunk_scan); with a decay a channel ("kda") a
  `lax.scan` of `_chunk_step`, which is also the kernel's reference.
- one token (`kda_decode`): the Pallas kernel of kernels/kda.py on the pool.

The layer's parameters (stacked [Lk, ...] under params["kda"]; Ck = H dk,
Cv = H dv, W = 2 Ck + Cv): wqkv_lin [D, W] (q | k | v), conv_w [taps, W],
A_log [H], w_beta [D, H], o_norm [dv], wo_lin [Cv, D]; with "kda" gates
wfg_down [D, 2r] (decay | output gate), wf_up [r, Ck], wg_up [r, Cv], dt_bias
[Ck]; with "gdn" gates w_a [D, H], dt_bias [H], wg_lin [D, Cv]."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.attention import _interpret, _note_fall
from ..kernels.kda import (
    chunk_scan, chunk_scan_tiles, heads_abreast, kda_decode_step, pack_state, unpack_state)
from ..ops.norms import rms_norm
from .configs import ModelConfig
from .quant import qdot

CHUNK = 32  # tokens a chunk of the prompt form; a chunk length divides the bucket
_HI = jax.lax.Precision.HIGHEST
_L2_EPS = 1e-6


def kda_sizes(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """(heads, key head size, value head size, taps)."""
    return cfg.lin_heads, cfg.lin_head_dim, cfg.lin_dv, cfg.lin_conv


def conv_width(cfg: ModelConfig) -> int:
    """Channels of x [Wq | Wk | Wv], which the convolution runs over."""
    H, dk, dv, _ = kda_sizes(cfg)
    return H * (2 * dk + dv)


def state_abreast(cfg: ModelConfig) -> int:
    """Heads side by side in the pool's layout (kernels/kda.py)."""
    return heads_abreast(cfg.lin_heads, cfg.lin_dv)


def step_kernel_name(cfg: ModelConfig) -> str:
    """The one-step state kernel's name in a trace, by the form of gate."""
    return f"{cfg.lin_gates}_decode_step"


def init_kda_params(cfg: ModelConfig, key: jax.Array, dtype, n_layers: int) -> dict[str, Any]:
    """Seeded stacked [Lk, ...] weights. Projections are normal with fan-in
    scaling like every other linear. The decay's two leaves follow the
    state-space convention so that no channel is degenerate: A = exp(A_log)
    log-uniform in [1, 16] a head, and dt_bias the inverse softplus of a step
    log-uniform in [1e-3, 1e-1] a channel (a head with "gdn" gates): with the
    projection's unit-variance output on top, alpha = exp(g) spreads from
    about 0.1 (a channel that forgets within a few tokens) to 0.999 (one that
    remembers for a thousand). The low rank of KDA's two gates is the key
    head size (Kimi Linear's convention): no configuration states another, so
    it is no field."""
    H, dk, dv, taps = kda_sizes(cfg)
    D, Ck, Cv, W, L = cfg.dim, H * dk, H * dv, conv_width(cfg), n_layers
    ks = jax.random.split(key, 10)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    per_head = cfg.lin_gates == "gdn"
    dt = jnp.exp(jax.random.uniform(
        ks[6], (L, H if per_head else Ck), jnp.float32, math.log(1e-3), math.log(1e-1)))
    params = {
        "wqkv_lin": w(ks[0], (L, D, W), D),
        "conv_w": w(ks[1], (L, taps, W), taps),
        "w_beta": w(ks[5], (L, D, H), D),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt), float32
        "A_log": jnp.log(jax.random.uniform(ks[7], (L, H), jnp.float32, 1.0, 16.0)),
        "o_norm": jnp.ones((L, dv), dtype),
        "wo_lin": w(ks[8], (L, Cv, D), Cv),
    }
    if per_head:
        params.update(w_a=w(ks[2], (L, D, H), D), wg_lin=w(ks[3], (L, D, Cv), D))
    else:
        r = dk
        params.update(wfg_down=w(ks[2], (L, D, 2 * r), D), wf_up=w(ks[3], (L, r, Ck), r),
                      wg_up=w(ks[4], (L, r, Cv), r))
    return params


def init_kda_state(cfg: ModelConfig, n_layers: int, slots: int, dtype) -> dict[str, jnp.ndarray]:
    """The pool: {"S": f32 [Lk, slots, H / P, dk, P dv] (P heads abreast, so
    that no row pads in HBM: kernels/kda.py), "conv": [Lk, slots, (taps-1) W]
    (a slot's tail rows end to end: an axis of 3 among the last two pads in
    HBM and, as the minor axis of a layout the compiler once chose for a chunk
    program, made a 2.6 GiB copy of a 63 MB pool)}."""
    H, dk, dv, taps = kda_sizes(cfg)
    P = state_abreast(cfg)
    return {
        "S": jnp.zeros((n_layers, slots, H // P, dk, P * dv), jnp.float32),
        "conv": jnp.zeros((n_layers, slots, (taps - 1) * conv_width(cfg)), dtype),
    }


def zero_state(cfg: ModelConfig, rows: int, dtype) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(S0 [rows, H / P, dk, P dv] f32: the pool's layout, tail0 [rows, taps-1, W])
    of fresh prompts."""
    H, dk, dv, taps = kda_sizes(cfg)
    P = state_abreast(cfg)
    return (jnp.zeros((rows, H // P, dk, P * dv), jnp.float32),
            jnp.zeros((rows, taps - 1, conv_width(cfg)), dtype))


def _gates(cfg: ModelConfig, kp: dict, x: jnp.ndarray):
    """x [..., D] -> (g f32 log decay <= 0: [..., H, dk] a channel, [..., H]
    a head; beta [..., H] f32; output gate [..., Cv] in x's dtype)."""
    H, dk, _, _ = kda_sizes(cfg)
    A = -jnp.exp(kp["A_log"].astype(jnp.float32))
    if cfg.lin_gates == "gdn":
        f = qdot(x, kp["w_a"]).astype(jnp.float32) + kp["dt_bias"].astype(jnp.float32)
        g = A * jax.nn.softplus(f)
        out_gate = jax.nn.silu(qdot(x, kp["wg_lin"]).astype(jnp.float32)).astype(x.dtype)
    else:
        r = dk
        low = qdot(x, kp["wfg_down"])
        f = qdot(low[..., :r], kp["wf_up"]).astype(jnp.float32) + kp["dt_bias"].astype(jnp.float32)
        g = A[:, None] * jax.nn.softplus(f.reshape(*f.shape[:-1], H, dk))
        out_gate = jax.nn.sigmoid(
            qdot(low[..., r:], kp["wg_up"]).astype(jnp.float32)).astype(x.dtype)
    beta = jax.nn.sigmoid(qdot(x, kp["w_beta"]).astype(jnp.float32))
    if cfg.lin_neg_eigval:
        beta = beta * 2.0
    return g, beta, out_gate


def _heads(cfg: ModelConfig, mixed: jnp.ndarray):
    """SiLU(conv(.)) rows [..., W] -> q, k [..., H, dk], v [..., H, dv]
    float32, q and k L2-normalised a head and q scaled by dk**-0.5."""
    H, d, dv, _ = kda_sizes(cfg)
    a = jax.nn.silu(mixed.astype(jnp.float32))
    lead, Ck = a.shape[:-1], H * d
    if d == dv:
        # three parts of one size are one array [3, H, d]: cut as three slices
        # the compiler laid Solar's out with six more copies an admit program
        # and a decode round 0.3 ms slower (v5e, PR 35)
        a = a.reshape(*lead, 3, H, d)
        q, k, v = a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]
    else:
        q = a[..., :Ck].reshape(*lead, H, d)
        k = a[..., Ck : 2 * Ck].reshape(*lead, H, d)
        v = a[..., 2 * Ck :].reshape(*lead, H, dv)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)

    return unit(q) * d**-0.5, unit(k), v


def output(cfg: ModelConfig, kp: dict, o: jnp.ndarray, out_gate: jnp.ndarray, dtype) -> jnp.ndarray:
    """o [..., H, dv] f32 -> the layer's output [..., D]."""
    o = rms_norm(o, kp["o_norm"], cfg.norm_eps).astype(dtype)
    return qdot(o.reshape(*o.shape[:-2], -1) * out_gate, kp["wo_lin"])


def _chunked(x, N: int):
    """[A, N C, H, ...] -> [N, A, H, C, ...]: a chunk an entry."""
    x = x.reshape(x.shape[0], N, x.shape[1] // N, *x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)


def _unchunked(o):
    """[N, A, H, C, dv] -> [A, N C, H, dv]."""
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)  # [A, N, C, H, dv]
    return o.reshape(o.shape[0], -1, *o.shape[3:])


def _chunk_step(C: int, per_head: bool, delta: bool):
    """One chunk of `kda_chunk_scan`'s recurrence as a scan's body: (S, (q, k,
    v, g[, beta])) -> (S after the chunk, o), a chunk's operands [A, H, C, .],
    beta [A, H, C, 1], g [A, H, C] where the decay is one a head."""
    t_idx = jnp.arange(C)
    earlier = t_idx[:, None] > t_idx[None, :]
    upto = t_idx[:, None] >= t_idx[None, :]
    eye = jnp.eye(C, dtype=jnp.float32)

    def step(S, xs):
        q, k, v, g, *beta = xs
        G = jnp.cumsum(g, axis=2)
        # decay from position s to position t >= s: exp(<= 0)
        if per_head:
            decay = jnp.exp(jnp.minimum(G[:, :, :, None] - G[:, :, None, :], 0.0))  # [A, H, t, s]
            kT = jnp.swapaxes(k, -1, -2)
            if delta:
                kk = jnp.where(earlier, jnp.matmul(k, kT, precision=_HI) * decay, 0.0)
            qk = jnp.where(upto, jnp.matmul(q, kT, precision=_HI) * decay, 0.0)
            G = G[..., None]  # [A, H, C, 1]: broadcasts over the keys below
        else:
            decay = jnp.exp(jnp.minimum(G[:, :, :, None, :] - G[:, :, None, :, :], 0.0))
            kd = k[:, :, None, :, :] * decay  # [A, H, t, s, dk]
            kk = jnp.where(earlier, jnp.sum(k[:, :, :, None, :] * kd, axis=-1), 0.0)
            qk = jnp.where(upto, jnp.sum(q[:, :, :, None, :] * kd, axis=-1), 0.0)
        eG = jnp.exp(G)
        if delta:
            (beta,) = beta
            rhs = beta * (v - jnp.matmul(k * eG, S, precision=_HI))
            U = jax.scipy.linalg.solve_triangular(
                eye + beta * kk, rhs, lower=True, unit_diagonal=True)  # [A, H, C, dv]
        else:
            U = v
        o = jnp.matmul(q * eG, S, precision=_HI) + jnp.matmul(qk, U, precision=_HI)
        k_out = k * jnp.exp(G[:, :, -1:, :] - G)  # carries a correction to the chunk's end
        S = eG[:, :, -1, :, None] * S + jnp.einsum(
            "ahck,ahcv->ahkv", k_out, U, precision=_HI)
        return S, o

    return step


def _kernel_name(g, k, v, beta, C: int) -> str:
    """The chunk kernel's name in a trace where these operands take it
    (kernels/kda.py:chunk_scan: one decay a head; `ssd_chunk_scan` without the
    delta rule, `gdn_chunk_scan` with it), "" where they take `_chunk_step`: a
    decay a key channel, which is another algorithm inside the chunk, and
    shapes Mosaic cannot tile (counted in `kernels.attention.reference_falls`)."""
    if g.ndim != 3:
        return ""
    name = "ssd_chunk_scan" if beta is None else "gdn_chunk_scan"
    H, dv = v.shape[2:]
    dk, W = k.shape[-1], heads_abreast(H, dv) * dv
    if not _interpret() and not chunk_scan_tiles(C, dk, W):
        _note_fall(name, f"C={C} H={H} dk={dk} dv={dv}: no legal tile", False)
        return ""
    return name


def kda_chunk_scan(q, k, v, g, beta, S0):
    """The recurrence over a whole (padded) sequence, chunk by chunk.

    q, k [A, T, H, dk], v [A, T, H, dv], g the log decay (0 at a padding
    position): [A, T, H, dk] a key channel, or [A, T, H] one a head; beta
    [A, T, H] (0 at a padding position); all float32. S0 and the state
    returned are IN THE POOL'S LAYOUT, [A, H / P, dk, P dv] with P =
    `heads_abreast(H, dv)` (kernels/kda.py; [A, H, dk, dv] itself where P is
    1), which is how the layer's callers hold them and how the kernel takes and
    leaves them. Returns (o [A, T, H, dv], S_T). A padding position leaves the
    state as it was (alpha 1, beta 0) and its output is never read.

    Without the delta rule (`beta` None: a Mamba-2 layer, models/ssm.py) the
    state takes the input as it is, U = V, and there is no system to solve; v
    is then 0 at a padding position. q and k may be ONE group for every head,
    [A, T, 1, dk]: every product below broadcasts them.

    Inside a chunk, with G the cumulative log decay (inclusive) and
    kk[t, s] = sum_d k_t k_s exp(G_t - G_s) for s < t, the corrections U solve
    (I + diag(beta) kk) U = beta (V - (K exp(G)) S0); then
    o = (Q exp(G)) S0 + qk U with qk[t, s] likewise for s <= t, and
    S_end = exp(G_end) S0 + (K exp(G_end - G))^T U. With one decay a head the
    decay between two positions is a [C, C] matrix and kk, qk are the products
    K K^T, Q K^T masked by it: that form is ONE Pallas call over the chunks
    (kernels/kda.py:chunk_scan), the state in VMEM from chunk to chunk. A decay
    a channel stands inside the sum over d, a [C, C, dk] tensor a chunk, and
    is a `lax.scan` of `_chunk_step`, which is also what the kernel is held to
    (tests/test_hybrid.py)."""
    A, T = v.shape[:2]
    C = math.gcd(T, CHUNK)
    N = T // C
    name = _kernel_name(g, k, v, beta, C)
    if not name:
        return _loop_chunk_scan(q, k, v, g, beta, S0)
    return chunk_scan(
        q, k, v, g, beta, S0, jnp.zeros((N,), bool), N,
        jnp.broadcast_to(jnp.arange(A)[:, None], (A, N)), chunk=C, rows=A, name=name)


def _loop_chunk_scan(q, k, v, g, beta, S0):
    """`kda_chunk_scan` as a `lax.scan` of `_chunk_step` (head-major inside)."""
    T, H, dv = v.shape[1:]
    C = math.gcd(T, CHUNK)
    N = T // C
    P = heads_abreast(H, dv)
    delta = beta is not None
    step = _chunk_step(C, g.ndim == 3, delta)
    S, o = jax.lax.scan(
        step, unpack_state(S0, P),
        (_chunked(q, N), _chunked(k, N), _chunked(v, N), _chunked(g, N),
         *([_chunked(beta[..., None], N)] if delta else [])))
    return _unchunked(o), pack_state(S, P)


def kda_packed_scan(q, k, v, g, beta, fresh, staged):
    """`kda_chunk_scan` over SEVERAL fresh sequences back to back in one row,
    each from a chunk boundary on (a mixed step's packed prompts,
    models/hybrid.py): a chunk marked in `fresh` [T / C] bool starts from zero
    state and not from its predecessor's. Only the first `staged` chunks (a
    traced count: those that hold tokens) are run; the positions behind them
    read o = 0. Returns (o [A, T, H, dv], the states by chunk in the pool's
    layout, [T / C, A, H / P, dk, P dv] as `kda_chunk_scan` has it: a
    sequence's own is the one after its last chunk RUN, and only that one is
    there to read; the kernel writes no other, and with nothing staged chunk
    0's reads zero)."""
    A, T = v.shape[:2]
    assert T % CHUNK == 0, (T, CHUNK)
    N = T // CHUNK
    name = _kernel_name(g, k, v, beta, CHUNK)
    if not name:
        return _loop_packed_scan(q, k, v, g, beta, fresh, staged)
    # a chunk's state goes to the slot of its sequence's last chunk run: the one
    # before the next fresh chunk, or before the first chunk not staged
    idx = jnp.arange(N)
    ahead = jnp.where(fresh[None, :] & (idx[None, :] > idx[:, None]), idx[None, :], N)
    last = jnp.maximum(jnp.minimum(jnp.min(ahead, axis=1), staged) - 1, 0)  # [N]; nothing staged: 0
    o, after = chunk_scan(
        q, k, v, g, beta, None, fresh, staged,
        jnp.arange(A)[:, None] * N + last[None, :], chunk=CHUNK, rows=A * N, name=name)
    return o, jnp.swapaxes(after.reshape(A, N, *after.shape[1:]), 0, 1)


def _loop_packed_scan(q, k, v, g, beta, fresh, staged):
    """`kda_packed_scan` as a `fori_loop` of `_chunk_step` to the last staged chunk."""
    A, T, H = v.shape[:3]
    N = T // CHUNK
    delta = beta is not None
    step = _chunk_step(CHUNK, g.ndim == 3, delta)
    xs = (_chunked(q, N), _chunked(k, N), _chunked(v, N), _chunked(g, N),
          *([_chunked(beta[..., None], N)] if delta else []))

    def chunk(i, carry):
        S, o, after = carry
        S, o_i = step(jnp.where(fresh[i], 0.0, S), jax.tree.map(lambda x: x[i], xs))
        return S, o.at[i].set(o_i), after.at[i].set(S)

    S0 = jnp.zeros((A, H, k.shape[-1], v.shape[-1]), jnp.float32)
    _, o, after = jax.lax.fori_loop(
        0, staged, chunk,
        (S0, jnp.zeros((N, A, H, CHUNK, v.shape[-1]), jnp.float32),
         jnp.zeros((N, *S0.shape), jnp.float32)))
    return _unchunked(o), pack_state(after, heads_abreast(H, v.shape[-1]))


def conv_chunk(tail0, nvalid, proj, conv_w):
    """The causal depthwise convolution over a chunk `proj` [A, T, W] that
    continues `tail0` [A, taps-1, W]: (its output before bias and activation,
    the tail as it stands after each row's `nvalid` positions)."""
    T, taps = proj.shape[1], conv_w.shape[0]
    full = jnp.concatenate([tail0.astype(proj.dtype), proj], axis=1)  # [A, taps-1+T, W]
    mixed = sum(
        full[:, j : j + T] * conv_w[j].astype(proj.dtype) for j in range(taps))
    tail = jax.vmap(
        lambda rows, n: jax.lax.dynamic_slice_in_dim(rows, n, taps - 1, axis=0)
    )(full, nvalid)  # rows [n, n + taps - 1) of `full` are the last taps-1 projections
    return mixed, tail


def conv_packed(proj, positions, last_idx, conv_w):
    """`conv_chunk` for whole FRESH prompts packed back to back in one row,
    `proj` [T, W], a token's place in its own prompt in `positions` [T]: (the
    convolution's output before bias and activation [T, W], each prompt's tail
    [R, taps-1, W] as it stands after its token at `last_idx` [R]). A prompt
    starts from a zero tail: a tap that reaches back past position 0 reads 0
    and not its neighbour's last rows."""
    T, taps = proj.shape[0], conv_w.shape[0]
    full = jnp.concatenate([jnp.zeros((taps - 1, proj.shape[1]), proj.dtype), proj])
    mixed = sum(
        jnp.where((positions >= taps - 1 - j)[:, None], full[j : j + T], 0)
        * conv_w[j].astype(proj.dtype) for j in range(taps))

    def tail_of(e):  # rows (e - taps + 1, e] of `proj`, those of this prompt
        rows = jax.lax.dynamic_slice_in_dim(full, e + 1, taps - 1, axis=0)
        own = positions[e] + 2 - taps + jnp.arange(taps - 1) >= 0
        return jnp.where(own[:, None], rows, 0)

    return mixed, jax.vmap(tail_of)(last_idx)


# The layer in the parts a step program composes it from (models/hybrid.py,
# `_RECURRENT`): `project` and `output` are products over rows, `operands` is
# row by row, and between them stand the convolution and the recurrence, which
# one token a row takes on the pool (`conv_step`, `step_rows`) and a prompt in
# chunks (`conv_chunk` and `scan_rows`, or packed in one row `conv_packed` and
# `scan_packed`). A mixed step runs the first kind ONCE over decode rows and
# prompt tokens stacked.


def project(cfg: ModelConfig, kp: dict, x: jnp.ndarray):
    """x [..., D] -> (the convolution's input x [Wq | Wk | Wv] [..., W], what
    `operands` needs beside the convolution's output: x, for the gates)."""
    return qdot(x, kp["wqkv_lin"]), x


def operands(cfg: ModelConfig, kp: dict, mixed: jnp.ndarray, x: jnp.ndarray):
    """The convolution's output [..., W] and the layer's input -> (the
    recurrence's (q, k, v, g, beta), float32; the output gate for `output`)."""
    q, k, v = _heads(cfg, mixed)
    g, beta, out_gate = _gates(cfg, kp, x)
    return (q, k, v, g, beta), out_gate


def step_rows(cfg: ModelConfig, S, layer, slot_ids, live, ops):
    """One token a row on the pool's states: (o [Ba, H, dv], the states)."""
    q, k, v, g, beta = ops
    return kda_decode_step(
        S, layer, slot_ids, live, q, k, v, jnp.exp(g), beta, name=step_kernel_name(cfg))


def _masked(ops, valid):
    """The recurrence's operands with `valid` [A, T] (a row's tokens) applied:
    a padding position leaves the state alone."""
    q, k, v, g, beta = ops
    g = jnp.where(valid.reshape(*valid.shape, *(1,) * (g.ndim - 2)), g, 0.0)
    return q, k, v, g, jnp.where(valid[..., None], beta, 0.0)


def scan_rows(ops, valid, S0):
    """Prompts [A, T, ...] in chunks from S0, `valid` [A, T] their tokens:
    `kda_chunk_scan`'s returns."""
    return kda_chunk_scan(*_masked(ops, valid), S0)


def scan_packed(ops, valid, fresh, staged):
    """Fresh prompts packed in one row [1, T, ...]: `kda_packed_scan`'s returns."""
    return kda_packed_scan(*_masked(ops, valid), fresh, staged)


def kda_prefill(
    cfg: ModelConfig,
    kp: dict,  # this layer's weights (un-stacked)
    x: jnp.ndarray,  # [A, T, D] the layer's input of a chunk (or a whole prompt)
    nvalid: jnp.ndarray,  # [A] int32: valid positions of each row
    S0: jnp.ndarray,  # [A, H / P, dk, P dv] f32: the pool's layout
    tail0: jnp.ndarray,  # [A, taps-1, W]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The layer over a chunk that continues (S0, tail0): (y [A, T, D], S,
    tail), the last two as they stand after each row's `nvalid` positions."""
    T = x.shape[1]
    with jax.named_scope(f"{cfg.lin_gates}_prefill"):
        proj, side = project(cfg, kp, x)  # [A, T, W]
        mixed, tail = conv_chunk(tail0, nvalid, proj, kp["conv_w"])
        ops, out_gate = operands(cfg, kp, mixed, side)
        o, S = scan_rows(ops, jnp.arange(T)[None, :] < nvalid[:, None], S0)
        return output(cfg, kp, o, out_gate, x.dtype), S, tail.astype(tail0.dtype)


def kda_decode(
    cfg: ModelConfig,
    kp: dict,
    x: jnp.ndarray,  # [Ba, D] the layer's input, one token a row
    state: dict,  # the pool (init_kda_state)
    layer: jnp.ndarray,  # int32 scalar: the pool's layer
    slot_ids: jnp.ndarray | None,  # [Ba] int32 pool rows; None: row b is slot b, all of them
    live: jnp.ndarray,  # [Ba] bool: a parked or padding row moves nothing
) -> tuple[jnp.ndarray, dict]:
    """One token through the layer on the pool: (y [Ba, D], the pool)."""
    proj, side = project(cfg, kp, x)  # [Ba, W]
    mixed, conv, slot_ids = conv_step(state["conv"], layer, slot_ids, live, proj, kp["conv_w"])
    ops, out_gate = operands(cfg, kp, mixed, side)
    o, S = step_rows(cfg, state["S"], layer, slot_ids, live, ops)
    return output(cfg, kp, o, out_gate, x.dtype), {"S": S, "conv": conv}


def conv_step(tails, layer, slot_ids, live, proj, conv_w):
    """One token of the causal depthwise convolution on the pool's tails
    [Lk, slots, (taps-1) W]: (the convolution's output [Ba, W] before bias and
    activation, the tails with `proj` [Ba, W] shifted in on the live rows, the
    rows' slot ids). `slot_ids` None: row b is slot b, all of them."""
    Ba, W = proj.shape
    taps = conv_w.shape[0]
    whole = slot_ids is None  # the full batch: the layer's tails are one block, no scatter
    tail = (jax.lax.dynamic_index_in_dim(tails, layer, 0, keepdims=False) if whole
            else tails[layer, slot_ids])  # [Ba, (taps-1) W]: a slot's rows end to end
    # the taps as slices of the flat row, W a whole number of lanes: as
    # [Ba, taps, W] every step re-laid the rows out twice (68 us a layer and
    # step at Solar's width, 0.8 ms a round: v5e, PR 35)
    full = jnp.concatenate([tail.astype(proj.dtype), proj], axis=-1)  # [Ba, taps W]
    conv_w = conv_w.astype(proj.dtype)
    mixed = sum(full[:, j * W : (j + 1) * W] * conv_w[j] for j in range(taps))
    new_tail = jnp.where(live[:, None], full[:, W:].astype(tail.dtype), tail)
    if whole:
        # a scatter of 64 rows runs row after row on the chip (a seventh of the
        # device in Olmo-Hybrid's cell, 15 layers x 4 steps a round); the block does not
        conv = jax.lax.dynamic_update_slice(tails, new_tail[None], (layer, 0, 0))
        slot_ids = jnp.arange(Ba, dtype=jnp.int32)
    else:
        conv = tails.at[layer, slot_ids].set(new_tail)
    return mixed, conv, slot_ids


