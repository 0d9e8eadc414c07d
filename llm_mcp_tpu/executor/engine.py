"""Continuous-batching TPU generation engine.

This is the subsystem the reference *does not have*: it streams someone else's
tokens over HTTP (Ollama `/api/chat` NDJSON → SSE transform,
`core/internal/api/handlers.go:2427-2587`). Here the decode hot loop runs
in-process on TPU and the API layer streams tokens straight out of it.

Design (SURVEY.md §7 "hard parts"):

  - **Slots**: the engine owns a static-shape KV cache of `max_slots`
    sequences. The reference's per-device concurrency cap
    (`handlers.go:212-246`) maps to free slots in this batch.
  - **Continuous batching**: requests join/leave the running batch at chunk
    boundaries; one jitted decode step serves all active slots.
  - **Chunked dispatch**: decode runs `decode_chunk` steps per device call via
    `lax.scan`, so the [K, B] token block is the only per-chunk host sync —
    dispatch overhead is amortized K×, while SSE streaming granularity stays
    at K tokens.
  - **Bucketed prefill**: prompts pad to power-of-two buckets; each bucket
    compiles once. Prompt KV inserts into the slot via a donated
    dynamic-update — no cache copies.
  - **On-device sampling**: logits never leave HBM (ops/sampling.py).
  - **Sharding**: with a mesh, params/cache shard per parallel/sharding.py
    (TP over ICI); the engine code is identical on 1 chip and N chips.

Threading: one engine thread owns the device loop; requests arrive on a
queue; each request streams tokens out through its own `queue.Queue`, which
the aiohttp layer bridges to SSE without head-of-line blocking.
"""

from __future__ import annotations

import base64
import logging
import os
import queue
import tempfile
import threading
import time
import uuid
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels.attention import (
    AttnStream,
    BlockAttnStream,
    mla_stream_block,
    pallas_supported,
    ragged_prefill_max_tokens,
    resolve_attn_impl,
    resolve_decode_impl,
    resolve_ragged_impl,
    rope_apart,
    rope_put,
)
from ..utils.faults import maybe_fail
from ..utils.platform import on_tpu
from ..models.configs import ModelConfig, resolve_config
from ..models.kda import CHUNK as RECURRENCE_CHUNK
from ..models.moe import share_form
from ..models.weights import load_llama_checkpoint
from ..models.llama import (
    init_llama_params,
    llama_prefill,
    llama_prefill_chunk_batch,
    llama_prefill_chunk_ragged,
    llama_decode_step,
    _cache_shape,
    block_attn_arm,
    block_denoise,
    block_pass,
    mixed_step_q8,
    mixed_step_supported,
)
from .. import constrain
from ..ops.sampling import apply_token_mask, sample_tokens, spec_verify
from ..parallel.sharding import (
    llama_param_specs, named_shardings, shard_pytree,
    supports_ragged_prefill,
)
from ..routing import prefix as prefix_fp
from ..telemetry import perf
from ..telemetry import recorder as flight
from ..telemetry import tracing
from ..telemetry import workload
from . import compile_watch
from .common import fine_bucket, pow2_bucket
from .dispatch import DispatchBackend, GSPMDBackend, LocalArraysBackend
from .drafter import NGramDrafter
from .memory import (
    build_state_pool,
    ExpertCounts,
    KVPool,
    KVSnapshot,
    RESTORE_AGING_TTFT_MULT,
    bucket_len,
    pytree_nbytes,
)
from . import migration
from .paging import PagedKVManager
from .layout import CacheLayout
from .physical import PhysicalPool
from .scheduler import TokenBudgetScheduler, parse_tenant_quotas
from .tokenizer import ByteTokenizer, Tokenizer, load_tokenizer
from ..utils.locks import OrderedLock

log = logging.getLogger("engine")

_DONE = object()


def _tree2(fn, a, b):
    """Apply fn(leaf_a, leaf_b) through the cache's dict nesting ({} is the
    fused int8 layout's live placeholder, not absence)."""
    if isinstance(a, dict):
        if not a:
            return {}
        return {k: _tree2(fn, a[k], b[k]) for k in a}
    return fn(a, b)


def _put_rows(c, rows, slot, start):
    """Rows [L, 1, H, n, *rest] cut out of a cache (positions on axis 3) into
    positions [start, start + n) of cache row `slot`, in place (a
    dynamic_update_slice: an advanced-index scatter would copy the whole
    payload). The latent pair's int8 rope keys lie P positions abreast in the
    cache and apart wherever they are cut out of it (a prompt's rows, a prefix
    entry, a pool's block, a host copy), so theirs is `rope_put`."""
    if c.ndim == 5 and c.shape[4] != rows.shape[4]:
        return rope_put(c, rows, (0, slot), start)
    return jax.lax.dynamic_update_slice(
        c, rows.astype(c.dtype), (0, slot, 0, start) + (0,) * (c.ndim - 4))


def _slot_rows(c, slot, abreast: int = 1):
    """One cache row [L, 1, H, S, *rest] with its positions in order on axis 3:
    the leaf's own, or of the rope keys that lie `abreast`, pulled apart."""
    return rope_apart(jax.lax.dynamic_slice_in_dim(c, slot, 1, 1), abreast)


def _cow_block_raw(ck, cv, pk, pv, slot, blk, prow):
    """Physical copy-on-write: copy ONE prefix-pool block (pool row `prow`)
    into a slot's arena at block index `blk` — the boundary block of an
    unaligned prefix hit. Whole-block always (the suffix prefill overwrites
    the tail past the stored length), so there is exactly one executable no
    matter where inside the block the prefix ends."""

    def one(arena, pool):
        z = (0,) * (arena.ndim - 4)
        bt = pool.shape[3]
        seg = jax.lax.dynamic_slice(
            pool, (0, prow, 0, 0) + z,
            (pool.shape[0], 1, pool.shape[2], bt) + pool.shape[4:],
        )
        return _put_rows(arena, seg, slot, blk * bt)

    return _tree2(one, ck, pk), _tree2(one, cv, pv)


_cow_block_fn = partial(jax.jit, donate_argnums=(0, 1))(_cow_block_raw)


def _pool_put_arena_raw(pk, pv, ck, cv, row, off, prow):
    """Prefix store: copy one block of arena KV (slot row `row`, token
    offset `off`) into pool row `prow`."""

    def one(pool, arena):
        z = (0,) * (arena.ndim - 4)
        bt = pool.shape[3]
        src = row
        if arena.ndim == 5 and arena.shape[4] != pool.shape[4]:  # rope keys abreast
            arena, src = _slot_rows(arena, row, arena.shape[4] // pool.shape[4]), 0
        seg = jax.lax.dynamic_slice(
            arena, (0, src, 0, off) + z,
            (arena.shape[0], 1, arena.shape[2], bt) + arena.shape[4:],
        )
        return jax.lax.dynamic_update_slice(
            pool, seg.astype(pool.dtype), (0, prow, 0, 0) + z
        )

    return _tree2(one, pk, ck), _tree2(one, pv, cv)


_pool_put_arena_fn = partial(jax.jit, donate_argnums=(0, 1))(_pool_put_arena_raw)


def _pool_put_pool_raw(pk, pv, src_row, dst_row):
    """Prefix store when the storing slot's block itself resolves to the
    pool (a sharer storing a longer prefix): pool-row → pool-row copy."""

    def one(pool, _):
        z = (0,) * (pool.ndim - 4)
        seg = jax.lax.dynamic_slice(
            pool, (0, src_row, 0, 0) + z,
            (pool.shape[0], 1, pool.shape[2], pool.shape[3]) + pool.shape[4:],
        )
        return jax.lax.dynamic_update_slice(pool, seg, (0, dst_row, 0, 0) + z)

    return _tree2(one, pk, pk), _tree2(one, pv, pv)


_pool_put_pool_fn = partial(jax.jit, donate_argnums=(0, 1))(_pool_put_pool_raw)


def _pool_put_host_raw(pk, pv, hk, hv, prow):
    """Remote prefix import: upload ONE wire-decoded host block (shaped
    [L, 1, heads, block_tokens, *rest], zero-padded past the chain's
    tail) into pool row `prow`. Block-shaped on purpose: one executable
    regardless of the imported chain's length."""

    def one(pool, blk):
        z = (0,) * (pool.ndim - 4)
        return jax.lax.dynamic_update_slice(
            pool, blk.astype(pool.dtype), (0, prow, 0, 0) + z
        )

    return _tree2(one, pk, hk), _tree2(one, pv, hv)


_pool_put_host_fn = partial(jax.jit, donate_argnums=(0, 1))(_pool_put_host_raw)


def _host_block(x, off: int, bt: int):
    """Slice one block [off, off+bt) of a wire-decoded host KV tree on the
    token axis, zero-padding a short tail to block shape (the pad is dead:
    admission COWs the boundary block and the suffix prefill overwrites
    past the stored length). Dict-aware ({} = fused-int8 live sentinel)."""
    if isinstance(x, dict):
        if not x:
            return {}
        return {k: _host_block(v, off, bt) for k, v in x.items()}
    seg = x[:, :, :, off : off + bt]
    if seg.shape[3] < bt:
        pad = [(0, 0)] * seg.ndim
        pad[3] = (0, bt - seg.shape[3])
        seg = np.pad(seg, pad)
    return np.ascontiguousarray(seg)


def _has_safetensors(weights_dir: str) -> bool:
    return bool(weights_dir) and os.path.isdir(weights_dir) and any(
        f.endswith(".safetensors") for f in os.listdir(weights_dir)
    )


@dataclass
class GenRequest:
    prompt_ids: list[int]
    max_tokens: int = 256
    temperature: float = 0.7
    top_k: int = 0
    top_p: float = 1.0
    stop: list[str] = field(default_factory=list)
    # KV-pool preemption rank (memory.py): higher survives longer. Only read
    # when TPU_KV_HOST_OFFLOAD is on; 0 keeps every request equal.
    priority: int = 0
    request_id: str = field(default_factory=lambda: uuid.uuid4().hex)
    # filled by the engine
    out: "queue.Queue[Any]" = field(default_factory=queue.Queue)
    created_at: float = field(default_factory=time.time)
    # the same moment on the clock every span and sample is on
    # (time.monotonic): a batch's wait, a slot's vacancy
    arrived_t: float = field(default_factory=time.monotonic)
    # tracing: wire context captured on the submitting thread; the engine
    # loop records admit/prefill/decode child spans against it retroactively
    # (the loop thread never blocks on the tracer)
    trace_ctx: str = ""
    admitted_at: float = 0.0  # stamped when the loop pops the request
    # KV migration (migration.py): export this request's KV the moment its
    # prefill lands, instead of decoding here — the disaggregated-mode
    # handoff (TPU_ROLE=prefill). Only read when TPU_MIGRATE is on.
    migrate_after_prefill: bool = False
    # hop count: how many times this request has already been re-homed.
    # The coordinator refuses to move a request twice — without the cap a
    # drain can ping-pong the queue head between two engines whose headroom
    # recovers alternately, and the bounced request starves.
    migrations: int = 0
    # latency waterfall (telemetry/workload.py): admission-shed backoff the
    # client spent before this submit landed. Stamped by the serving layer
    # (api handlers) — the engine only ever reads it.
    shed_wait_s: float = 0.0
    # Tenancy (model zoo): the API-key-derived tenant id this request bills
    # against. "" (the default) is unmetered — per-tenant quotas, goodput
    # ledgers, and SLO-debt preemption all key off a non-empty value, so
    # single-tenant serving never touches any of that machinery.
    tenant: str = ""
    # Grammar-constrained decoding (constrain/): the constraint spec dict
    # ({"type": "json_schema"|"json_object"|"regex"|"choice", ...}) and the
    # parsed logit_bias pairs [(token_id, bias), ...]. None/None means
    # unconstrained — the request never touches the constrain subsystem.
    constraint: dict | None = None
    logit_bias: list | None = None
    # engine-filled: the compiled per-request SlotAutomaton, attached when
    # the loop pops the request (so the FIRST sampled token is already
    # masked) and handed to the slot at activation. Never set when
    # TPU_CONSTRAIN=0.
    cn: Any = None


@dataclass
class _Slot:
    req: GenRequest
    generated: int = 0
    text: str = ""
    pending: bytes = b""
    prompt_len: int = 0
    first_token_at: float = 0.0
    # lifecycle flags for the pipelined decode loop: emission of a round can
    # run AFTER the slot's table entry was freed (fast finish-scan) or
    # errored (abort) — both must stop any later deferred emission for this
    # request (the consumer already received its terminal event)
    done: bool = False
    aborted: bool = False
    # self-speculative decoding: the slot's n-gram index over its own token
    # history (drafter.py), fed by _process_token; None when TPU_SPEC=0
    spec: Any = None
    # constrained decoding: the request's SlotAutomaton cursor (constrain/
    # masks.py), advanced by _process_token on every emitted token. None for
    # unconstrained requests and always None when TPU_CONSTRAIN=0 — the
    # loop's cn_active/active split keys off this field.
    cn: Any = None
    spec_drafted: int = 0  # draft tokens proposed for this request
    spec_accepted: int = 0  # draft tokens accepted by verify
    # KV pool: last emission wall time, the "idle" preemption policy's
    # victim signal. Only stamped when the pool is on (hot-path no-op rule).
    last_emit: float = 0.0
    # Paged KV: when admitted off a prefix-cache hit, the entry and its
    # stored length — a preemption of this slot snapshots only the rows
    # past shared_len (the shared blocks stay pinned in the paging ledger
    # and restore re-inserts them from the entry's device arrays).
    shared_entry: Any = None
    shared_len: int = 0
    # perf observatory (telemetry/perf.py): wall of this slot's previous
    # emission (anchor for the next round's inter-token gap) + lifetime
    # ITL accumulation, folded into the decode span and goodput ledger
    # at finish
    perf_last_emit: float = 0.0
    last_text_t: float = 0.0  # time.monotonic() of the previous text event
    # ... and where that event stood in the device's order: the admissions
    # dispatched before what brought it, and their padded tokens
    adm_mark: tuple = (0, 0)
    itl_s_total: float = 0.0
    itl_samples: int = 0
    # latency waterfall (telemetry/workload.py): synchronous prefill
    # dispatch wall attributed to this request (token-share of each batch /
    # chunk dispatch), inter-token gap beyond the stall threshold, and wall
    # spent parked off-slot by preemption. _finish_slot clamps these into
    # an exact partition of the request's measured wall.
    prefill_compute_s: float = 0.0
    stall_s: float = 0.0
    preempted_s: float = 0.0


@dataclass
class _DispatchedRound:
    """A decode round in flight on device: dispatched, not yet fetched.
    `entries` pins (slot index, slot OBJECT, out column) at dispatch time —
    by fetch time the table entry may hold None or a different request, and
    identity decides whether the column's tokens still belong to anyone."""

    out: Any  # device array [K, Ba] (un-fetched)
    entries: list  # [(b, _Slot, col)]
    base: Any  # np lengths snapshot at dispatch
    t0: float
    rid: int = 0  # monotonic round id (slot-reuse cooling fence)
    prefill_tokens: int = 0  # fused chunk-group tokens (scheduler cost attribution)
    prefill_padded: int = 0  # dispatched token shape incl. pads (pad-waste EMA)
    # for the device seconds told where the round ends (_round_ended):
    phase: str = "decode"  # decode / fused / fused_rag
    dx: int = 0  # engine._dx_n after this dispatch: rid-1's + 1 = back to back
    t_disp: float = 0.0  # time.perf_counter() when the jit call returned
    # the account of rounds (telemetry/perf.py:RoundAccount): the step
    # program's key, and `ended` once the first read that waited for the
    # program has returned (a ride's read, else the fetch)
    prog: str = "plain"  # plain / mixed_<rung> / fused / fused_rag
    ended: bool = False
    # (host_s, wait_s) where the perf observatory sampled this dispatch
    sample: tuple | None = None
    # (admissions dispatched before this round since boot, their padded
    # tokens): the difference of two rounds' is what the device ran between
    # them beside the rounds (_put_text)
    mark: tuple = (0, 0)


@dataclass
class _DispatchedAdmit:
    """A batched admission in flight on device: `admit_fn` dispatched, its
    sampled first tokens not yet read. It waits in the same queue as the
    decode rounds, in device order, and is read when it is the oldest item
    there. `entries` pins (slot index, slot OBJECT, prompt tokens) like a
    round's: the rows are seated and decoding from the next dispatch on, and
    identity decides at the read whether a first token still has a taker."""

    toks0: Any  # device array [Ab] (un-fetched)
    entries: list  # [(b, _Slot, P)]
    t0: float  # time.perf_counter() before the dispatch
    t_call: float  # ... when the jit call returned
    first: bool  # first dispatch of its shape: the CompileLedger's, no sample
    aid: int = 0  # the ring's `admit_prog` event of this dispatch
    rid: int = 0  # the mixed round that carried it (no program of its own, no aid)
    round: Any = None  # that round's _DispatchedRound: this read ends it
    # (admissions dispatched up to and including this one, their padded
    # tokens): where its first tokens stand in the device's order (_put_text)
    mark: tuple = (0, 0)


@dataclass
class _Ride:
    """A batch of whole prompts staged to ride the next full-batch decode
    round's first step (`mixed_round_fn`): popped from the queue, their slots
    reserved, the packed buffer built; nothing dispatched or seated yet."""

    batch: list  # [(slot, GenRequest, ids)]
    rung: int  # the packed buffer's length T
    tokens: Any  # np [T] int32
    rowids: Any  # np [T] int32 (pads = the descriptor rows R)
    positions: Any  # np [T] int32 (pads = max_seq_len)
    ipack: Any  # np [3 R + 2] int32 (mixed_round_fn)
    fpack: Any  # np [2 R] float32
    held_by: str = ""  # why the batch closed
    adm: Any = None  # its _DispatchedAdmit, once the round is dispatched


@dataclass
class _PendingRound:
    """A fetched decode round awaiting (deferred) emission."""

    out: Any  # np [K, Ba]
    entries: list  # [(b, _Slot, col)]
    base: Any
    rid: int = 0
    mark: tuple = (0, 0)  # the dispatched round's
    prog: str = "plain"  # the dispatched round's


@dataclass
class _PrefillState:
    """A slot whose prompt is mid-way through chunked prefill. The slot is
    reserved (not decodable, not free) until the last chunk lands."""

    req: GenRequest
    ids: list[int]
    done: int = 0  # tokens already written into the cache
    # terminal error already delivered by the stall watchdog — activation
    # and chunk failure paths must not double-publish
    aborted: bool = False
    # Paged KV: prefix-cache hit provenance, carried through the chunked
    # suffix prefill onto the activated _Slot (see _start_cached)
    shared_entry: Any = None
    shared_len: int = 0
    # latency waterfall: prefill dispatch wall accumulated while this
    # prompt was mid-chunk (token-share of each group dispatch), copied
    # onto the activated _Slot's prefill_compute_s
    prefill_s: float = 0.0
    # a block configuration's prompt past its last whole block (P mod L
    # tokens): never prefilled, they start the slot's first block (_seat)
    tail: list = field(default_factory=list)


@dataclass
class _PrefillGroup:
    """A staged chunked-prefill group: up to admit_batch mid-prefill slots'
    next chunks sharing (bucket, skey), total valid tokens bounded by the
    token-budget scheduler. Dispatched either FUSED into a decode round
    (fused_step_fn — the stall-free path) or standalone when no decode rows
    are active (pure-prefill window, back-to-back)."""

    metas: list  # [(slot, _PrefillState, n)] — n = valid tokens this chunk
    tokens: Any  # np [Ab, bucket] (ragged: np [T] packed token buffer)
    slots_arr: Any  # np [Ab] (ragged: np [R])
    starts_arr: Any  # np [Ab] (ragged: np [R])
    nv_arr: Any  # np [Ab] (ragged: np [R])
    bucket: int  # ragged: the packed buffer length T
    skey: int
    n_tokens: int  # total valid tokens staged (≤ the round's budget)
    # dispatch-plane group id: once dispatched, the group's boundary logits
    # ([Ab, V]; ragged [R, V]) park on the op-owned _x_logits[gid] until the
    # activation sample ("bsample") pops them
    gid: int = 0
    # Ragged packed descriptors (tentpole path — _stage_ragged_group). metas
    # row i ↔ descriptor row i, so finish/fail indexing is shared with the
    # bucketed path.
    ragged: bool = False
    rowids_arr: Any = None  # np [T] — row id per packed token (pads = R)
    positions_arr: Any = None  # np [T] — cache position (pads = max_seq_len)
    last_idx_arr: Any = None  # np [R] — packed index of each row's last token


class GenerationEngine:
    def __init__(
        self,
        model: str | ModelConfig = "tiny-llm",
        *,
        mesh=None,
        params: Any = None,
        tokenizer: Tokenizer | None = None,
        max_slots: int = 8,
        max_seq_len: int = 512,
        dtype: Any = jnp.bfloat16,
        seed: int = 0,
        decode_chunk: int = 4,
        weights_dir: str = "",
        quant: str = "",
        kv_quant: str = "",
        prefill_chunk: int = 512,
        admit_batch: int = 4,
        decode_compact: str = "auto",
        prompt_cache_mb: int = 256,
        prefill_buckets: str = "fine",
        target_ttft_ms: float = 2000.0,
        backend: DispatchBackend | None = None,
    ):
        # a config.json beside the weights is authoritative: any supported-
        # family checkpoint serves without a catalog entry (models/configs.py
        # resolve_config — the reference's serve-any-name parity,
        # discovery.go:482-560)
        self.cfg = resolve_config(model, weights_dir)
        if self.cfg.recurrent and mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                f"{self.cfg.name}: recurrent layers and an expert share run on "
                "one chip; no mesh axis shards the state pool or the share yet")
        # Generation by diffusion over blocks (`cfg.block_len`): a round is a
        # BLOCK (`_build_decode`: block_round_fn in the decode round's place),
        # the tokens a round gives a row, `decode_chunk`, ARE the block, and
        # everything below follows from the configuration: no switch
        self._block = int(self.cfg.block_len)
        if self._block:
            if decode_chunk != self._block:
                raise ValueError(
                    f"{self.cfg.name}: a round is a block of {self._block} tokens; "
                    f"decode_chunk={decode_chunk} (TPU_DECODE_CHUNK) must be {self._block}")
            if mesh is not None and mesh.size > 1:
                raise NotImplementedError(
                    f"{self.cfg.name}: the block round and the expert share run on one chip")
            if (self.cfg.unmask_rule not in ("low_confidence_dynamic", "low_confidence_static")
                    or self.cfg.denoise_steps < 1 or self._block % self.cfg.denoise_steps
                    or not 0 <= self.cfg.mask_token_id < self.cfg.vocab_size):
                raise ValueError(
                    f"{self.cfg.name}: unmask_rule {self.cfg.unmask_rule!r} with "
                    f"{self.cfg.denoise_steps} steps over blocks of {self._block}, "
                    f"mask id {self.cfg.mask_token_id} of {self.cfg.vocab_size}")
        elif share_form(self.cfg) and not (self.cfg.kv_lora_rank or self.cfg.recurrent):
            raise NotImplementedError(
                f"{self.cfg.name}: the dense family's decode step has no expert share "
                "(models/llama.py: prefill, chunk and block passes alone take moe_share_ffn)")
        self.mesh = mesh
        # Dispatch plane (dispatch.py): every device mutation the loop makes
        # goes through ONE funnel (_dx) that forwards the (op, payload) step
        # to the backend before executing it locally. LocalArraysBackend is
        # a no-op (today's single-process path, zero overhead); GSPMDBackend
        # serializes the step-program to follower processes so the SAME op
        # closures replay there — multi-controller JAX requires every
        # process to execute every device computation in the same order.
        self._backend = backend if backend is not None else LocalArraysBackend()
        self._spmd = bool(self._backend.spmd)
        if self._spmd and mesh is None:
            raise ValueError("a GSPMD dispatch backend requires a mesh")
        # non-empty = the dispatch plane died with this error. Under a GSPMD
        # backend a poisoned dispatch cannot be recovered (followers already
        # executed the step; re-initializing device state is not replayable),
        # so the engine goes dead instead of rebuilding (_recover_cache).
        self.dead: str = ""
        self._dead_lock = threading.Lock()  # atomizes submit vs death
        if self._spmd:
            from jax.sharding import NamedSharding, PartitionSpec

            # identity jit with a replicated out_sharding: the reshard that
            # turns a host array (or a sharded global) into a fully-
            # replicated global every process can device_get locally
            self._repl_sharding = NamedSharding(mesh, PartitionSpec())
            self._put_repl = jax.jit(
                lambda x: x, out_shardings=self._repl_sharding
            )
        self.dtype = dtype
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.decode_chunk = decode_chunk
        # admission prompt buckets: "fine" adds 1.5x midpoint rungs between
        # the pow2 sizes (common.py:fine_bucket) — ~12% mean pad waste in
        # the prefill weight pass instead of ~25%, for one extra executable
        # per octave ("pow2" opts out)
        self.prefill_fine = (prefill_buckets or "fine").lower() != "pow2"
        self.tokenizer: Tokenizer = tokenizer or load_tokenizer(weights_dir)

        hd = self.cfg.resolved_head_dim
        # Prefill and decode resolve separately: flash-prefill is a real win
        # (no O(S²) score materialization) while decode is fastest on the
        # fused XLA einsum path — see kernels/attention.py:resolve_decode_impl.
        self.attn_impl = (
            resolve_attn_impl(mesh) if pallas_supported(max_seq_len, hd) else "xla"
        )

        # weight-only int8 (TPU_QUANT=int8 via Config.tpu_quant): decode is
        # weight-bandwidth bound, so halving weight bytes ≈ halves step time
        # (models/quant.py)
        self.quant = quant
        if self.quant and self.quant != "int8":
            log.warning("unknown quant mode %r (supported: int8); serving unquantized",
                        self.quant)
            self.quant = ""
        # int8 KV cache (TPU_KV_QUANT=int8): once weights are int8, decode
        # becomes cache-bandwidth bound — halving KV bytes buys another
        # ~25-40% step time at 8B and doubles the (slots × context) that
        # fits beside the weights. Reads route through the s8-MXU pallas
        # kernel (kernels/attention.py:decode_attend_q8).
        self.kv_quant = kv_quant
        if self.kv_quant and self.kv_quant != "int8":
            log.warning("unknown kv_quant mode %r (supported: int8); using %s cache",
                        self.kv_quant, jnp.dtype(dtype).name)
            self.kv_quant = ""
        if self.cfg.kv_lora_rank:
            # MLA (models/mla.py): whole prompts prefill expanded and
            # query-blocked (mla_prefill), chunked prefill runs the absorbed
            # form against the latent cache (mla_prefill_chunk_batch, or
            # packed, mla_prefill_chunk_ragged) — long prompts interleave with
            # decode rounds and the prompt-prefix KV cache applies, exactly
            # as for the GQA families, unless the pair's second member also
            # carries the expert layer's counts (a share of DeepSeek-V3-style
            # experts, `CacheLayout.counted`: memory.COUNTED_OFF lists what
            # such a configuration runs without). int8 latents
            # (kv_quant=int8): ~7x fewer cache bytes than bf16 GQA K/V;
            # decode runs the s8-MXU kernel
            # (kernels/attention.py:decode_attend_q8_mla) — whole-S tiles
            # at serving context lengths (every row's S positions a step,
            # whatever its fill: perf_stats()["decode_attn"] counts both),
            # blocked HBM streaming with a dynamic trip count past its VMEM
            # budget (S=32k included); the XLA dequant-then-dot path remains
            # only for cache lengths no 128-multiple block divides. An
            # admission never rides a decode round on a latent cache
            # (`_ride_off`).
            if self.kv_quant:
                log.info(
                    "MLA int8 latents: ~2x context capacity vs bf16 "
                    "latents; s8-MXU decode kernel (whole-S at serving "
                    "lengths, blocked streaming at long context)"
                )
        # which cache this engine holds and what that rules out: decided here,
        # once, before anything is allocated (executor/layout.py)
        layout = self._layout = CacheLayout(
            self.cfg, max_slots, max_seq_len, dtype, self.kv_quant == "int8", mesh)
        self.decode_impl = resolve_decode_impl(
            mesh,
            quantized=layout.int8,
            seq_len=max_seq_len,
            head_dim=hd,
            n_kv_heads=self.cfg.n_kv_heads,
            n_heads=self.cfg.n_heads,
        )
        # Slot compaction: decode rounds dispatch only the ACTIVE rows
        # (pow2-bucketed) instead of the full max_slots batch — the weights
        # pass, sampling, and (on the kernels' scalar-prefetch indirection)
        # cache traffic all scale with occupancy instead of capacity. "auto"
        # enables it for the int8 cache (whose kernels take slot_ids);
        # "on" forces it for bf16 too (xla gather path), "off" disables.
        # ("auto" stays single-chip: under a mesh the compact batch's dynamic
        # row gathers would cut across the dp/tp cache sharding — XLA inserts
        # collectives per layer and the "optimization" inverts. "on" overrides
        # for configs whose mesh doesn't shard the slot axis.)
        dc = (decode_compact or "auto").lower()
        if dc not in ("auto", "on", "off"):
            log.warning("unknown decode_compact mode %r (auto|on|off); using auto", dc)
            dc = "auto"
        single_chip = mesh is None or mesh.size == 1
        self.decode_compact = dc == "on" or (
            dc == "auto" and layout.int8 and single_chip
        )
        # chunked prefill: bound the per-iteration prefill work so admissions
        # interleave with decode rounds (0 disables; sp prefill is whole-prompt
        # by design — the sp axis itself bounds per-chip work)
        self.prefill_chunk = max(0, prefill_chunk)
        # batched admission: up to admit_batch short prompts prefill in ONE
        # dispatch — at 8B the prompt weight pass dominates admission cost,
        # and a starved admission path caps how many slots ever decode
        # (measured: 102 tok/s vs 1.8k+ at B=64 with per-request prefill)
        self.admit_batch = max(1, admit_batch)
        # Token-budget scheduler (scheduler.py): prefill rides INSIDE decode
        # rounds under a per-round token budget self-tuned from measured
        # per-token prefill vs decode-round cost, clamped so the oldest
        # mid-prefill prompt still activates within target_ttft_ms.
        self.target_ttft_ms = max(1.0, float(target_ttft_ms))
        self._sched = TokenBudgetScheduler(
            target_ttft_ms=self.target_ttft_ms,
            min_budget=min(64, self.prefill_chunk) if self.prefill_chunk else 1,
            tenant_quotas=parse_tenant_quotas(
                os.environ.get("TPU_TENANT_QUOTAS", "")
            ),
        )
        self._last_active_n = 0  # decode rows in the most recent dispatch

        pspecs = llama_param_specs(self.cfg)
        if self.quant == "int8":
            from ..models.quant import quantized_specs

            pspecs = quantized_specs(pspecs)
        def _init_born_sharded():
            # init runs as ONE GSPMD program with explicit out_shardings: no
            # device (and, multi-controller, no process) ever materializes
            # the full tree. Creating it on the default device and sharding
            # it afterwards is 16 GB on chip 0 for bf16 8B — an OOM on the
            # four-chip host the mesh exists for.
            if self.quant == "int8":
                from ..models.quant import init_llama_params_quantized

                init_params = partial(
                    init_llama_params_quantized, self.cfg,
                    jax.random.PRNGKey(seed), scale_dtype=dtype,
                )
            else:
                init_params = partial(
                    init_llama_params, self.cfg, jax.random.PRNGKey(seed),
                    dtype=dtype,
                )
            with mesh:
                return jax.jit(init_params, out_shardings=self._ns(pspecs))()

        if self._spmd:
            # Multi-controller placement: shard_pytree's device_put only
            # works on fully-addressable inputs, so the tree is born sharded
            # and checkpoints stream per-process shards via
            # make_array_from_callback.
            if params is None and _has_safetensors(weights_dir):
                params = self._load_checkpoint_global(
                    self.cfg, weights_dir, dtype, mesh, self._ns(pspecs),
                    quant=self.quant,
                )
            elif params is None:
                params = _init_born_sharded()
            self.params = params
        else:
            if params is None and _has_safetensors(weights_dir):
                # Real checkpoint: stream safetensors shards straight into
                # (sharded) HBM — already placed.
                params = load_llama_checkpoint(self.cfg, weights_dir, dtype=dtype, mesh=mesh)
            elif params is None and mesh is not None:
                params = _init_born_sharded()
            elif params is None:
                if self.quant == "int8":
                    # Direct int8 init: an 8B bf16 tree (16 GB) cannot be
                    # materialized-then-quantized inside one v5e chip's HBM.
                    # ONE jitted program, not an eager op per tensor: each
                    # eager randint over a [32, 4096, 14336] tensor is its
                    # own XLA compile, and on an empty cache those were
                    # ~280 s of a 330 s boot on the chip. Integer draws and
                    # constant scales: bit-identical to the eager tree.
                    from ..models.quant import init_llama_params_quantized

                    params = jax.jit(partial(
                        init_llama_params_quantized, self.cfg,
                        jax.random.PRNGKey(seed), scale_dtype=dtype,
                    ))()
                else:
                    params = init_llama_params(self.cfg, jax.random.PRNGKey(seed), dtype=dtype)
            if self.quant == "int8":
                from ..models.quant import quantize_params

                params = quantize_params(params)  # no-op on already-int8 trees
            if (
                self.quant == "int8"
                and mesh is None
                and os.environ.get("LLM_MCP_TPU_FUSE_QKV", "1") != "0"
            ):
                # w8a8 layer-pass restructure: concat wq|wk|wv and w1|w3
                # post-quantization (bitwise-exact — models/quant.py:
                # fuse_layer_weights). Single-chip only: the fused output axis
                # interleaves head groups and cannot shard over tp.
                from ..models.quant import fuse_layer_weights

                params = fuse_layer_weights(params)
            if mesh is not None:
                params = shard_pytree(params, pspecs, mesh)  # no-op when placed
            self.params = params

        cache = layout.allocate()
        self._ck = cache["k"]
        self._cv = cache["v"]
        # Layers with a per-slot state of fixed size (models/hybrid.py: a
        # recurrent state, or a window layer's ring): it rides the cache pair's
        # second member through every step program, and its book
        # (memory.StatePool) is the one place that says which features such a
        # configuration runs without. None for a configuration whose every
        # layer keeps full-length KV rows.
        self._state_pool = build_state_pool(
            self.cfg, max_slots,
            self._cv[layout.slot_member] if layout.slot_member else None, log)
        # the expert layer's counts, where the step programs carry them (a
        # member of the cache pair of its own, beside the state): None else
        self._experts = (
            ExpertCounts(int(self._cv["moe"].shape[1]), held=self.cfg.n_experts,
                         router=self.cfg.router_width)
            if isinstance(self._cv, dict) and "moe" in self._cv else None)
        # what the blocked int8 decode-attention arm streams, where decode
        # rounds run it (int8 GQA cache read by the Pallas kernel): None else
        # (and never for a block configuration: no decode arm runs, and what a
        # block pass's attention streams is in perf_stats()["blocks"]["attn"])
        self._attn_stream = None
        if layout.fused and self.decode_impl == "pallas" and not self._block:
            self._attn_stream = AttnStream(self._ck["q"].shape, kv_heads=self.cfg.n_kv_heads)
        elif layout.latent and layout.int8 and self.decode_impl == "pallas":
            # the latent arms: whole-S where it fits, else blocks of the prefix
            self._attn_stream = AttnStream(
                self._ck["q"].shape, block_tokens=mla_stream_block(
                    max_seq_len, self.cfg.kv_lora_rank, self.cfg.qk_rope_head_dim, self.cfg.n_heads),
                positions_abreast=max_seq_len // layout.kv_rows(self._ck, self._cv)["v"]["q"].shape[3])
        # and what the window arm streams of the window layers' rings
        self._win_stream = (
            AttnStream(self._cv["win"]["k"]["q"].shape, window=self.cfg.sliding_window,
                       max_seq_len=max_seq_len, kv_heads=self.cfg.n_kv_heads)
            if self._attn_stream is not None and layout.slot_member == "win" else None)
        if self._spmd:
            # named out_sharding kinds for _shard_out: host-read outputs come
            # back fully replicated (every process device_gets locally — the
            # slice decode_fn convention), cache and pool outputs keep their specs
            pool_sh = self._ns(layout.pool_specs())
            self._out_kinds = {
                "repl": self._repl_sharding,
                **self._ns(layout.specs()),
                "pk": pool_sh["k"],
                "pv": pool_sh["v"],
            }

        # Host-side mirrors of per-slot device state. Invariant: only ACTIVE
        # (decoding) slots hold an in-range length; free/reserved slots park
        # at max_seq_len so the decode step's unconditional per-row K/V
        # scatter (models/llama.py w_idx) is out-of-bounds for them — JAX
        # drops OOB scatter writes, so parked rows are never touched. Without
        # this, decode rounds would write garbage rows inside a slot that is
        # mid-chunked-prefill (stale length 0) and corrupt its prompt KV.
        self._lengths = np.full(max_slots, max_seq_len, dtype=np.int32)
        # (a block configuration's is a slot's next block as it starts, [B, L]:
        # masks, or a prompt's last P mod L tokens and masks)
        self._last_tok = np.zeros(
            (max_slots, self._block) if self._block else max_slots, dtype=np.int32)
        self._temp = np.zeros(max_slots, dtype=np.float32)
        self._topk = np.zeros(max_slots, dtype=np.int32)
        self._topp = np.ones(max_slots, dtype=np.float32)
        self._slots: list[_Slot | None] = [None] * max_slots
        self._prefills: dict[int, _PrefillState] = {}
        self._prefill_q: deque[int] = deque()

        self._rng_counter = 0
        self._base_key = jax.random.PRNGKey(seed + 1)

        # Sampling mask: model vocab may be padded beyond the tokenizer's
        # (MXU-friendly shapes) and control ids (pad/bos) must never be
        # sampled — only real text ids and eos are allowed.
        allowed = np.ones(self.cfg.vocab_size, dtype=bool)
        allowed[self.tokenizer.vocab_size :] = False
        for bad in (self.tokenizer.pad_id, self.tokenizer.bos_id):
            if bad != self.tokenizer.eos_id and 0 <= bad < self.cfg.vocab_size:
                allowed[bad] = False
        if self._block:
            # a block is done when no position holds the mask: a sampler that
            # could emit the mask's id would leave a block that never is,
            # whatever ids the tokenizer covers
            allowed[self.cfg.mask_token_id] = False
        self._allowed_mask = jnp.asarray(allowed) if not allowed.all() else None

        (self._decode_fn, self._fused_fn, self._fused_ragged_fn,
         self._mixed_fn) = self._build_decode()
        mask = self._allowed_mask
        cfg_ = self.cfg
        skey_base = self._base_key

        # the RNG key derives from the counter INSIDE the jit (fold_in of a
        # closed-over base key is a traced constant): an eagerly-folded key
        # would be a process-local device array, which cannot ride into a
        # GSPMD program beside global operands
        sample1 = jax.jit(
            lambda logits, counter, temp, topk, topp: sample_tokens(
                jnp.where(mask, logits, -jnp.inf) if mask is not None else logits,
                jax.random.fold_in(skey_base, counter), temp, topk, topp,
            ),
            **self._shard_out(["repl"]),
        )

        self._sample1 = sample1

        # constrained sibling of _sample1: same engine mask, then the
        # automaton mask + logit_bias, then EXACT sampling (approx top-k
        # could miss a tiny legal set entirely). Built lazily here but only
        # ever TRACED when a constrained batch reaches bsample — under
        # TPU_CONSTRAIN=0 no request carries cn, so this executable never
        # exists and the kill switch stays a zero-trace no-op.
        sample1_cn = jax.jit(
            lambda logits, counter, temp, topk, topp, masks, bids, bvals: sample_tokens(
                apply_token_mask(
                    jnp.where(mask, logits, -jnp.inf) if mask is not None else logits,
                    masks, bids, bvals,
                ),
                jax.random.fold_in(skey_base, counter), temp, topk, topp,
                exact=True,
            ),
            **self._shard_out(["repl"]),
        )

        self._sample1_cn = sample1_cn

        impl = self.attn_impl

        # Long-context path: with an sp axis in the mesh, prefill runs
        # sequence-parallel (ring attention over sp, Megatron TP over tp —
        # parallel/ring.py:llama_prefill_sp): per-chip activations are
        # [B, S/sp, D] and no full-sequence score matrix ever materializes,
        # so prompts whose attention would blow a single chip's HBM still
        # prefill. Decode is unchanged (its per-step work is tiny).
        # The sp kernel covers every dense family — windows/softcaps thread
        # into the ring masks, int8 weights dequant inside the shard_map —
        # so long context composes with quantization (the 8B int8 target).
        # MoE keeps the GSPMD prefill: experts ride the ep axis, not sp.
        # MLA keeps GSPMD too: the ring kernels are GQA-shaped (an MLA tree
        # has no wq/wk/wv) — its long-context prefill memory is bounded by
        # the query-blocked form instead (models/mla.py).
        self.sp = 1
        if mesh is not None and not cfg_.n_experts and not cfg_.kv_lora_rank:
            axes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if (
                axes.get("sp", 1) > 1
                and axes.get("dp", 1) == 1  # engine prefills one prompt at a time
                and axes.get("pp", 1) == 1
                and axes.get("ep", 1) == 1
                and cfg_.n_kv_heads % axes.get("tp", 1) == 0
                and cfg_.vocab_size % axes.get("tp", 1) == 0
            ):
                self.sp = axes["sp"]

        # Ragged packed prefill (kernels/attention.py ragged_* family): the
        # chunked-prefill path of record when available. Fixed-shape packed
        # token buffer + per-row (slot, start, len) descriptors → zero pad
        # compute and ONE executable per (T, layout) instead of the bucketed
        # (Ab, bucket, skey) zoo. TPU_RAGGED_PREFILL=0 restores the bucketed
        # path bit-identically (the gate only selects the staging branch).
        # Gated to the same single-program regime as the prefix cache: no sp
        # ring, no mesh, and the model families the ragged kernels cover
        # (windows/softcaps stay bucketed).
        self.ragged_prefill = (
            os.environ.get("TPU_RAGGED_PREFILL", "1")
            not in ("", "0", "false", "no", "off")
            and self.prefill_chunk > 0
            and self.sp == 1
            and supports_ragged_prefill(mesh)
            and not cfg_.sliding_window
            and not cfg_.attn_softcap
            and self._runs("ragged_prefill")
        )
        # Sharded plane: the packed-buffer math is GSPMD-safe (tp shards the
        # head axis, pp the layer axis; neither touches the token packing),
        # but the pallas kernels themselves run on fully-addressable arrays
        # only — force the xla impl whenever the mesh spans devices.
        if mesh is not None and mesh.size > 1:
            self._ragged_impl = "xla" if self.ragged_prefill else ""
        else:
            self._ragged_impl = resolve_ragged_impl() if self.ragged_prefill else ""
        if self.ragged_prefill:
            hd = cfg_.resolved_head_dim
            cap = min(
                max(self.admit_batch * self.prefill_chunk, 1),
                ragged_prefill_max_tokens(
                    hd,
                    cfg_.n_kv_heads,
                    latent=cfg_.kv_lora_rank,
                    rope_dim=cfg_.qk_rope_head_dim if cfg_.kv_lora_rank else 0,
                ),
            )
            # pow2 floor: packed buffer lengths ride the pow2 ladder (the
            # kernel tiles T by block_q and asserts divisibility), so the cap
            # itself must sit on the ladder or a full group would bucket past
            # the VMEM budget ragged_prefill_max_tokens derived.
            self._ragged_cap = 1 << (cap.bit_length() - 1)
            log.info(
                "ragged prefill enabled: impl=%s cap=%d tokens",
                self._ragged_impl, self._ragged_cap,
            )
        else:
            self._ragged_cap = 0

        self.pp_prefill = 1  # >1 when whole-prompt prefill rides the stage scan
        if self.sp > 1:
            from ..parallel.ring import llama_prefill_sp

            log.info("sequence-parallel prefill enabled: sp=%d", self.sp)

            def _prefill_body(params, tokens, lengths):
                logits, ks, vs = llama_prefill_sp(cfg_, params, tokens, lengths, mesh)
                return logits, *layout.entries(ks, vs)

        else:
            # Pipeline-parallel prefill (parallel/pipeline.py): with a pp
            # axis in the mesh, whole-prompt admission runs the bit-parity
            # GPipe stage scan — layer-sharded params stay stage-local
            # instead of all-gathering per layer, so a model too big for one
            # slice's HBM serves across stages. Decode and chunked prefill
            # keep the generic GSPMD path (their per-call work is small and
            # correctness is sharding-independent). TPU_PP_PREFILL=0 falls
            # back to the single-stage scan (the parity reference).
            pp_ = 1
            if mesh is not None and not cfg_.n_experts and not cfg_.kv_lora_rank:
                pp_ = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pp", 1)
            use_pp = (
                pp_ > 1
                and self.sp == 1
                and cfg_.n_layers % pp_ == 0
                and os.environ.get("TPU_PP_PREFILL", "1")
                not in ("", "0", "false", "no", "off")
            )
            self.pp_prefill = pp_ if use_pp else 1
            if use_pp:
                from ..parallel.pipeline import pipeline_prefill

                log.info("pipeline-parallel prefill enabled: pp=%d", pp_)

                def _prefill_body(params, tokens, lengths):
                    # microbatch count must divide B (pipeline_prefill
                    # asserts); B that doesn't split falls back to M=1
                    m = pp_ if tokens.shape[0] % pp_ == 0 else 1
                    logits, ks, vs = pipeline_prefill(
                        cfg_, params, tokens, lengths, mesh,
                        n_microbatches=m, attn_impl=impl,
                    )
                    return logits, *layout.entries(ks, vs)

            else:

                # jax.jit caches one executable per input shape, so prompt
                # buckets (power-of-two padded) each compile once without any
                # manual cache. quant_kv quantizes per layer INSIDE the
                # prefill scan: the stacked bf16 prompt KV of a batched
                # admission never materializes (llama_prefill docstring).
                def _prefill_body(params, tokens, lengths):
                    return llama_prefill(
                        cfg_, params, tokens, lengths, attn_impl=impl, quant_kv=layout.int8
                    )

        def _insert_row(ck, cv, ks, vs, i, slot):
            if layout.wrapped:
                # the full-length rows as for any family, and the row's
                # recurrent state (or its ring) into the pool beside them
                # (a counted latent pair has none: the counts land once a call)
                from ..models.hybrid import insert_state_row

                ck, v = _insert_kv(ck, cv["v"], ks, vs["v"], i, slot)
                return ck, dict(cv, v=v, **insert_state_row(cv, vs, i, slot))
            return _insert_kv(ck, cv, ks, vs, i, slot)

        def _insert_kv(ck, cv, ks, vs, i, slot):
            # ks/vs: batched prompt KV [L, A, Hkv, bucket, hd] in the cache's
            # own form (layout.entries) → write row `i` at [:, slot, :, :bucket],
            # leaf by leaf, whatever the layout's tree. `i`/`slot` are traced
            # scalars; the dynamic_update_slice form updates the donated cache
            # in place (an advanced-index scatter would copy the full cache
            # payload).
            def put(c, rows):
                return _put_rows(c, jax.lax.dynamic_slice_in_dim(rows, i, 1, 1), slot, 0)

            return jax.tree.map(put, ck, ks), jax.tree.map(put, cv, vs)

        mask_ = self._allowed_mask
        base_key_ = self._base_key

        @partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5, 6),
                 **self._shard_out(["k", "v", "repl", "repl", "repl", "repl",
                                   "repl"]))
        def admit_fn(params, ck, cv, d_temp, d_topk, d_topp, d_last, tokens,
                     ipack, fpack, cn=None):
            """Fused admission: prefill + cache insert + sampling-param
            update + first-token sample in ONE dispatch.

            The unfused form cost ~9+3A host<->device round trips per
            admission batch (separate transfers for every small array, a
            dispatch per cache-row insert, a sync for the sampled tokens),
            each with its own dispatch and transfer set-up cost on the host,
            and admission dominated the serve loop.
            Fused: tokens + 2 packed arrays up, one dispatch, one [Ab]
            fetch.

            The sampled first tokens also land in `d_last` (the
            device-resident last-token ring the pipelined decode loop reads
            its round inputs from): the device stream is in-order, so any
            decode round dispatched after this admission sees tok0 without
            the host ever staging it.

            ipack i32 [3*Ab+2]: slots, prompt lengths, top_k, A (live row
            count), rng counter. fpack f32 [2*Ab]: temperature, top_p.

            A configuration that generates by diffusion over blocks
            (`cfg.block_len` L) samples NOTHING here: `tokens` are each
            prompt's first L * (P // L) tokens, `lengths` that many, and
            ipack carries Ab * L more ints, each slot's first block as it
            starts (the prompt's last P mod L tokens, then masks), which land
            in `d_last` [B, L]; `toks0` is zeros, read for the program's end.
            """
            Ab = tokens.shape[0]
            slots = ipack[:Ab]
            lengths = ipack[Ab : 2 * Ab]
            topks = ipack[2 * Ab : 3 * Ab]
            live_n = ipack[3 * Ab]
            counter = ipack[3 * Ab + 1]
            temps = fpack[:Ab]
            topps = fpack[Ab:]

            logits, ks, vs = _prefill_body(params, tokens, lengths)

            def body(i, cc):
                ck, cv = cc
                # pad rows (i >= live_n) duplicate garbage prompts — they
                # must not write ANY cache row
                return jax.lax.cond(
                    i < live_n,
                    lambda cc: _insert_row(cc[0], cc[1], ks, vs, i, slots[i]),
                    lambda cc: cc,
                    (ck, cv),
                )

            with jax.named_scope("kv_append"):
                ck, cv = jax.lax.fori_loop(0, Ab, body, (ck, cv))
                if isinstance(vs, dict) and "moe" in vs:  # the prefill's expert counts, once a call
                    from ..models.hybrid import add_counts

                    cv = add_counts(cv, vs)
            # sampling params live ON DEVICE between rounds (decode gathers
            # them by slot id — never re-transferred per round). Pad rows
            # scatter to row B: out of bounds, dropped (the same invariant
            # the KV parking relies on).
            row = jnp.where(jnp.arange(Ab) < live_n, slots, d_temp.shape[0])
            d_temp = d_temp.at[row].set(temps)
            d_topk = d_topk.at[row].set(topks)
            d_topp = d_topp.at[row].set(topps)
            if cfg_.block_len:
                first = ipack[3 * Ab + 2 :].reshape(Ab, cfg_.block_len)
                return (ck, cv, d_temp, d_topk, d_topp, d_last.at[row].set(first),
                        jnp.zeros((Ab,), jnp.int32))
            with jax.named_scope("sample"):
                if mask_ is not None:
                    logits = jnp.where(mask_, logits, -jnp.inf)
                # constrained admission: automaton masks + logit_bias for
                # the FIRST sampled token. cn rides at the END defaulting to
                # None (the paged=None pattern) so unconstrained admissions
                # keep the exact executable traced before this subsystem
                # existed.
                if cn is not None:
                    logits = apply_token_mask(logits, cn[0], cn[1], cn[2])
                key = jax.random.fold_in(base_key_, counter)
                # pad rows duplicate garbage prompts/params — keep them out
                # of the sampler's homogeneity reductions (fast-path
                # selection)
                toks0 = sample_tokens(
                    logits, key, temps, topks, topps,
                    active=jnp.arange(Ab) < live_n,
                    exact=cn is not None,
                )
            d_last = d_last.at[row].set(toks0)
            return ck, cv, d_temp, d_topk, d_topp, d_last, toks0

        @partial(jax.jit, donate_argnums=(0, 1), **self._shard_out(["k", "v"]))
        def insert_cached_fn(ck, cv, pk, pv, slots, live_n):
            """Prefix-cache hit admission: write ONE cached prompt-prefix's
            KV rows into N slots in one dispatch. pk/pv: the stored rows
            [L, 1, Hkv, P0, hd] (int8 {"q","s"} pytree when the cache is).
            The suffix then prefills through the ordinary chunked path
            (start=P0) — reading these rows as its past; sampling params
            are set at activation as usual."""

            def body(i, cc):
                ck, cv = cc
                return jax.lax.cond(
                    i < live_n,
                    lambda cc: _insert_row(cc[0], cc[1], pk, pv, 0, slots[i]),
                    lambda cc: cc,
                    (ck, cv),
                )

            ck, cv = jax.lax.fori_loop(0, slots.shape[0], body, (ck, cv))
            return ck, cv

        @partial(jax.jit, donate_argnums=(0, 1), **self._shard_out(["k", "v"]))
        def insert_at_fn(ck, cv, pk, pv, slot, start):
            """Paged restore, private tail: write pk/pv [L, 1, Hkv, R, hd]
            (int8 {"q","s"} pytree when the cache is) into slot's rows
            [start, start+R). R is EXACT — never pow2-padded — because a
            padded R with start+R > S would make dynamic_update_slice CLAMP
            the start index backwards and overwrite the shared prefix rows
            just re-inserted below it. Restore guarantees start+R = bucket
            <= S, so the traced start is never clamped."""
            def put(c, rows):
                return _put_rows(c, rows, slot, start)

            return jax.tree.map(put, ck, pk), jax.tree.map(put, cv, pv)

        @partial(jax.jit, donate_argnums=(1, 2), static_argnames=("skey",),
                 **self._shard_out(["repl", "k", "v"]))
        def prefill_chunk_fn(params, ck, cv, tokens, slots, starts, nvalid, skey,
                             paged=None):
            # `paged` rides at the END so the donation indices above never
            # move; the pool is NOT donated (entries outlive every dispatch)
            return llama_prefill_chunk_batch(
                cfg_, params, ck, cv, tokens, slots, starts, nvalid, skey=skey,
                paged=paged,
            )

        @partial(jax.jit, donate_argnums=(1, 2), static_argnames=("skey",),
                 **self._shard_out(["repl", "k", "v"]))
        def ragged_chunk_fn(params, ck, cv, tokens, rowids, positions, slots,
                            starts, last_idx, skey, paged=None):
            # standalone ragged dispatch (pure-prefill window); same trailing-
            # `paged` / donation contract as prefill_chunk_fn
            return llama_prefill_chunk_ragged(
                cfg_, params, ck, cv, tokens, rowids, positions, slots,
                starts, last_idx, skey=skey, paged=paged,
                impl=self._ragged_impl,  # read at trace time: set below
            )

        self._admit_fn = admit_fn
        self._insert_cached_fn = insert_cached_fn
        self._insert_at_fn = insert_at_fn
        self._prefill_chunk_fn = prefill_chunk_fn
        self._ragged_chunk_fn = ragged_chunk_fn
        # Prompt-prefix KV cache (vLLM-style prefix reuse, exact-prefix
        # match): production chat traffic repeats long shared prefixes
        # (system prompts, few-shot preambles) across requests; their KV is
        # a pure function of the weights, so re-prefilling them per request
        # is pure waste. Entries store device-resident KV rows for a prompt
        # PREFIX; a hit copies the rows into the slot (one fused dispatch
        # per hit group) and only the suffix runs through chunked prefill.
        # LRU by bytes; 0 disables. Gated to chunked prefill + sp == 1
        # (the sp path prefills whole prompts by design).
        self._prefix_cache: "OrderedDict[tuple, dict]" = OrderedDict()
        # secondary index: stored-prefix length → {key: entry}. Stored
        # lengths are pow2-floored (_maybe_store_prefix), so a lookup is
        # O(log max_seq_len) dict probes instead of a linear scan comparing
        # every entry's full key (_match_prefix). Kept exactly in sync with
        # _prefix_cache at the insert and evict sites.
        self._prefix_by_len: dict[int, dict[tuple, dict]] = {}
        self._prefix_cache_bytes = 0
        # Gated to chunked prefill + sp == 1 only (the sp path prefills
        # whole prompts by design). The old single-chip gate is LIFTED:
        # entries are eager slices of the (possibly sharded) global cache,
        # and every entry mutation flows through the dispatch plane, so the
        # prefix tier runs identically on local arrays, a local mesh, and
        # the GSPMD leader/follower plane.
        self._prefix_budget = (
            int(prompt_cache_mb) * (1 << 20)
            if self.prefill_chunk > 0 and self.sp == 1 and self._runs("prefix_cache")
            else 0
        )
        self._recent_prompts: deque[tuple] = deque(maxlen=16)
        self.prefix_cache_hits = 0
        self.prefix_cache_misses = 0
        # Fleet prefix tier (routing/prefix.py): _prefix_pub mirrors the
        # resident chain set {key: stored_tokens} behind its own lock so
        # digest building (discovery refresh thread) and match probes
        # (serve threads) never touch the engine-thread-owned OrderedDict.
        # prefix_export/prefix_import park work on _prefix_rpc_in; the
        # engine thread services it in _admit_pending, where touching
        # _prefix_cache and dispatching pool uploads is safe.
        self._prefix_pub: dict[tuple, int] = {}
        self._prefix_pub_lock = threading.Lock()
        self._prefix_rpc_in: "queue.Queue[tuple]" = queue.Queue()
        self.prefix_exports_total = 0
        self.prefix_export_bytes_total = 0
        self.prefix_imports_total = 0
        self.prefix_import_bytes_total = 0
        self.prefix_import_rejects_total = 0
        # device-resident sampling params (see admit_fn docstring); host
        # mirrors (self._temp/_topk/_topp) stay the source of truth for
        # rebuild after a poisoned dispatch consumed the donated buffers.
        # Under GSPMD these are born replicated globals (jnp.asarray would
        # make process-local arrays no jit may mix with global operands).
        _up = self._put_repl if self._spmd else jnp.asarray
        self._d_temp = _up(self._temp)
        self._d_topk = _up(self._topk)
        self._d_topp = _up(self._topp)
        # device-resident last-token ring: decode rounds read their input
        # tokens from it and write their final tokens back, admissions write
        # first samples — so dispatching round N+1 never waits for round N's
        # fetch (decode_chunk_fn docstring). Host mirror: self._last_tok
        # (updated at fetch, for recovery after a poisoned dispatch).
        self._d_last_tok = _up(self._last_tok)
        # Pipeline depth: how many decode rounds may be in flight before the
        # oldest is fetched. At depth 1 the chip idles through everything
        # the host does between two rounds (the device->host fetch, token
        # commit, SSE emission, the next round's scheduling); depth 2 lets
        # round N+1 run on the device while the host works through round N.
        # The cost: a slot that finishes decodes up to d-1 extra discarded
        # rounds before the host sees the finish, and freed slots cool for
        # the in-flight rounds that still reference them (_free_slot).
        # Default: 2 on the TPU, 1 on the CPU (host and "device" share the
        # cores there, so there is nothing to overlap and sequential-
        # generate tests would only pay the finished-slot waste).
        depth_env = os.environ.get("TPU_PIPELINE_DEPTH", "")
        if depth_env:
            self.pipeline_depth = max(1, int(depth_env))
        else:
            self.pipeline_depth = 2 if on_tpu() else 1
        # round ids: fence for slot-reuse cooling (a freed slot may still be
        # referenced by rounds dispatched before the free was observed)
        self._rid_dispatched = 0
        self._rid_fetched = 0
        self._cooling: dict[int, int] = {}
        # everything dispatched and not yet read, in device order: decode
        # rounds and batched admissions. The engine thread only ever blocks
        # on the oldest item (_run).
        self._inflight: deque[_DispatchedRound | _DispatchedAdmit] = deque()
        # admission's account (perf_stats()["admit"]): a record an admit
        # program dispatched, how often the queued read engages, and why a
        # slot stood empty. slot -> [time.monotonic() of its free, ... of the
        # fetch that ended its cooling fence (None until then)]
        self._adm = perf.AdmitAccount()
        self._vacant: dict[int, list] = {}
        # admissions riding a decode round (_stage_ride, mixed_round_fn): the
        # rungs this cache holds, the descriptor rows of the packed buffer,
        # why the configuration keeps admit_fn (_ride_off), and what stood
        # against a ride in the loop's current iteration
        self._ride_rungs = tuple(r for r in self.RIDE_RUNGS if r <= max_seq_len)
        if self.cfg.recurrent:
            # the largest alone: `hybrid_mixed_step` runs the recurrence over the
            # chunks that hold tokens whatever the rung, so a smaller rung saves
            # the padding rows' share of the stacked products and no more, and
            # costs a second executable of a period of unlike layers to trace
            # and lower at its first ride while every stream waits (both
            # measured: PERF.md section 6, PR 42)
            self._ride_rungs = self._ride_rungs[-1:]
        self._ride_rows = 1 << max(0, self.admit_batch - 1).bit_length()
        # a riding prompt starts at a multiple of this in the packed buffer: the
        # recurrence's chunk where there is one, which resets at a chunk's start
        self._ride_align = RECURRENCE_CHUNK if self.cfg.recurrent else 1
        self._ride_why = "" if (
            mesh is None and not self._spmd and self.sp == 1 and layout.int8
            and self.decode_impl == "pallas" and mixed_step_supported(self.cfg)
            and self._ride_rungs) else "other"
        self._ride_state = "other"

        # Self-speculative decoding (draft-and-verify): a host-side n-gram
        # drafter (drafter.py — prompt-lookup over each slot's own history)
        # proposes up to TPU_SPEC_K tokens; one chunk-machinery model call
        # verifies them all (_build_verify), accepting the longest agreeing
        # prefix — exact greedy equality at temp=0, rejection sampling
        # otherwise (ops/sampling.py:spec_verify). Rejected positions roll
        # back by arithmetic alone: the cache rows past the accepted
        # position are dead under the parked-slot OOB invariant (chunk reads
        # mask key_pos < starts, decode attends < length, later writes
        # overwrite in place). TPU_SPEC=0 is a hard kill switch: none of
        # the spec code runs and the decode path is byte-identical. Gated
        # to sp == 1 (the sp prefill path never chunks; verify rides the
        # chunk machinery).
        self.spec_k = max(0, int(os.environ.get("TPU_SPEC_K", "") or 7))
        self.spec_min_ngram = max(
            1, int(os.environ.get("TPU_SPEC_MIN_NGRAM", "") or 2)
        )
        self.spec_max_ngram = max(self.spec_min_ngram, 3)
        self.spec_enabled = (
            os.environ.get("TPU_SPEC", "1") != "0"
            and self.spec_k > 0
            and self.sp == 1
            and self._runs("speculation")
        )
        # verify-round throughput counters (speculation_stats; engine-thread
        # writers, lock-free like total_tokens)
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_calls = 0
        # adaptive throttle: drafts that keep getting rejected make a verify
        # round strictly worse than a decode round (1 emitted token per slot
        # vs decode_chunk) — back off for a while after a low-acceptance call
        self._spec_cooldown = 0
        self._verify_fn = self._build_verify() if self.spec_enabled else None

        # Grammar-constrained decoding (constrain/): schema/regex/choice
        # specs compile to byte automata lifted to packed token bitmasks,
        # one SlotAutomaton cursor per constrained slot, masks fused into
        # sampling (admit / cnstep / bsample / verify). TPU_CONSTRAIN=0 is
        # a hard kill switch mirroring TPU_SPEC=0: the compiler is never
        # constructed, no request ever carries `cn`, every jitted path
        # keeps its cn=None trailing operand — zero new executables traced
        # and token-identical greedy output.
        self.constrain_enabled = constrain.constrain_enabled() and self._runs("constrain")
        self.cn_bias_max = max(
            1, int(os.environ.get("LLM_MCP_TPU_CN_BIAS_MAX", "") or 64)
        )
        self._constrain = (
            constrain.ConstraintCompiler(
                self.tokenizer, self.cfg.vocab_size,
                cache_size=int(os.environ.get("TPU_CONSTRAIN_CACHE", "") or 64),
            )
            if self.constrain_enabled
            else None
        )
        # constrained-traffic counters (constrain_stats; engine-thread
        # writers, lock-free like the spec counters)
        self.cn_requests = 0
        self.cn_tokens = 0
        self.cn_illegal = 0  # automaton-illegal emissions — must stay 0
        self.cn_finished = 0
        self.cn_finished_accepting = 0
        self.cn_spec_drafted = 0
        self.cn_spec_accepted = 0
        self.cn_mask_s = 0.0  # host wall building/gathering mask rows
        # masked single-step decode for constrained slots (built lazily on
        # first constrained traffic — never traced otherwise)
        self._cn_step_fn = None

        # HBM-aware KV pool (memory.py): admission watermark + slot
        # preemption with host offload. TPU_KV_HOST_OFFLOAD=0 (default)
        # never constructs the pool — every hot-path touch point is guarded
        # `if self._pool is not None`, so the off state is a true no-op
        # (byte-identical scheduler decisions vs the pool-less engine).
        self._pool = None
        if self._runs("offload") and os.environ.get(
                "TPU_KV_HOST_OFFLOAD", "0") not in ("", "0", "false", "no", "off"):
            self._pool = KVPool(
                max_slots=max_slots,
                max_seq_len=max_seq_len,
                bytes_per_slot=pytree_nbytes({"k": self._ck, "v": self._cv})
                // max(1, max_slots),
                watermark=float(os.environ.get("TPU_ADMIT_WATERMARK", "") or 1.5),
                policy=os.environ.get("TPU_PREEMPT_POLICY", "") or "priority",
            )
            log.info(
                "KV pool enabled: %.1f MB/slot, watermark %.2f, policy %s",
                self._pool.bytes_per_slot / (1 << 20),
                self._pool.watermark,
                self._pool.policy,
            )

        # Paged KV ledger (paging.py): refcounted block tables + COW prefix
        # sharing over the slot arena. Pure host bookkeeping (no device
        # calls), so it is ALWAYS constructed — the block economy feeds
        # telemetry unconditionally, and when the pool is on, admission's
        # offered load becomes unique-block accounting (_offered_load).
        cache_bytes = pytree_nbytes(layout.kv_rows(self._ck, self._cv))
        self._paging = PagedKVManager(
            max_slots=max_slots,
            max_seq_len=max_seq_len,
            bytes_per_token=cache_bytes // max(1, max_slots * max_seq_len),
            prefix_budget_bytes=self._prefix_budget,
        )
        self._snap_ctr = 0  # KVSnapshot ids for the paging ledger's parked pins
        log.info(
            "paged KV: %d-token blocks, %d/slot, %d arena + %d prefix blocks",
            self._paging.block_tokens, self._paging.blocks_per_slot,
            self._paging.slot_partition, self._paging.prefix_partition,
        )

        # Physical half of the paged ledger (physical.py): per-slot device
        # block tables + a prefix block pool, so prefix-hit admission is
        # PIN-ONLY (zero row copies — sharers read the one pool copy through
        # the table) instead of duplicating entry rows into every slot.
        # TPU_PAGED_PHYSICAL=0 is a true escape hatch: no tables, no pool,
        # every dispatch takes the exact pre-physical trace. Gated to the
        # same chunked-prefill world as the prefix cache itself
        # (_prefix_budget > 0 implies all of that), plus block sizes the
        # attention kernels' paged arms accept.
        self._phys: PhysicalPool | None = None
        self._pool_k = self._pool_v = None
        self._cow_fn = _cow_block_fn
        self._pool_arena_fn = _pool_put_arena_fn
        self._pool_pool_fn = _pool_put_pool_fn
        self._pool_host_fn = _pool_put_host_fn
        bt_ = self._paging.block_tokens
        if (
            os.environ.get("TPU_PAGED_PHYSICAL", "1")
            not in ("", "0", "false", "no", "off")
            and self._prefix_budget > 0
            and self._paging.prefix_partition >= 1
            and max_seq_len % bt_ == 0
            and bt_ in (32, 64, 128, 256)
        ):
            self._phys = PhysicalPool(
                n_slots=max_slots, seq_len=max_seq_len, block_tokens=bt_,
                pool_rows=self._paging.prefix_partition,
            )
            # honest HBM accounting peak (paging_stats() hbm_bytes_ratio_peak):
            # contiguous-equivalent bytes ÷ physically-resident bytes,
            # sampled at every shared admission (the sharing peak)
            self._phys_hbm_peak_ratio = 1.0
            self._phys_hbm_peak = (0.0, 0.0)
            pools = layout.allocate_pools(self._paging.prefix_partition, bt_)
            self._pool_k, self._pool_v = pools["k"], pools["v"]
            if self._spmd:
                self._cow_fn = jax.jit(
                    _cow_block_raw, donate_argnums=(0, 1),
                    **self._shard_out(["k", "v"]),
                )
                self._pool_arena_fn = jax.jit(
                    _pool_put_arena_raw, donate_argnums=(0, 1),
                    **self._shard_out(["pk", "pv"]),
                )
                self._pool_pool_fn = jax.jit(
                    _pool_put_pool_raw, donate_argnums=(0, 1),
                    **self._shard_out(["pk", "pv"]),
                )
                self._pool_host_fn = jax.jit(
                    _pool_put_host_raw, donate_argnums=(0, 1),
                    **self._shard_out(["pk", "pv"]),
                )
            log.info(
                "physical paged KV: [%d, %d] block table + %d-row prefix pool"
                " (%.1f MB)",
                max_slots, self._phys.nbs, self._phys.pool_rows,
                pytree_nbytes({"k": self._pool_k, "v": self._pool_v}) / (1 << 20),
            )

        # KV migration (migration.py): engine-to-engine snapshot transfer.
        # TPU_MIGRATE=0 (default) keeps both queues None — every hot-path
        # touch point is guarded `is not None`, so the off state is a true
        # no-op exactly like the pool's. The outbox carries wire payloads a
        # MigrationCoordinator ships out; the inbox carries decoded
        # snapshots the run loop restores into free slots.
        self._migrate_outbox: "queue.Queue[dict] | None" = None
        self._migrate_in: "queue.Queue[tuple] | None" = None
        # engine-level prefill-role flag: a coordinator sets it (or tests
        # do) so every admitted request exports after its prefill lands;
        # per-request GenRequest.migrate_after_prefill overrides ad hoc
        self.migrate_after_prefill = False
        self.migrated_out_total = 0
        self.migrated_in_total = 0
        self.migrate_out_bytes_total = 0
        self.migrate_in_bytes_total = 0
        if self._runs("migration") and os.environ.get(
                "TPU_MIGRATE", "0") not in ("", "0", "false", "no", "off"):
            self._migrate_outbox = queue.Queue()
            self._migrate_in = queue.Queue()
            log.info("KV migration enabled (TPU_MIGRATE)")

        self._admit: "queue.Queue[GenRequest]" = queue.Queue()
        self._stop_evt = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None

        # Flight recorder + anomaly detectors + compile ledger
        # (telemetry/recorder.py, TPU_FLIGHT knobs; doc/observability.md).
        # The recorder/ledger are process-shared (like the tracer) so all
        # engines land events in one ring; the anomaly monitor is per-engine
        # because its detectors watch THIS engine's cadence/TTFT/leaks.
        self._flight = flight.get_recorder()
        self._ledger = flight.get_compile_ledger()
        self._anomaly = flight.AnomalyMonitor(
            self._flight, target_ttft_ms=self.target_ttft_ms
        )
        # Perf observatory (telemetry/perf.py): ITL/TPOT timelines, goodput
        # accounting, and sampled steady-state phase attribution with
        # roofline MFU/MBU — the CompileLedger's steady-state complement.
        # Per-engine (its roofline is this engine's model shape); stdlib
        # module, so the engine hands it plain scalars only.
        self._perf = perf.PerfObservatory(
            shape=perf.ModelShape.from_config(self.cfg),
            active_layout=layout.name,
            paged=self._phys is not None,
            block_tokens=self._paging.block_tokens,
            weight_bytes_per_param=(
                1.0 if self.quant == "int8" else jnp.dtype(dtype).itemsize
            ),
            target_ttft_ms=self.target_ttft_ms,
            device_kind=jax.devices()[0].device_kind,
        )
        # the block rounds' book and the counters of what such a configuration
        # runs without (perf_stats()["blocks"]): None for every other
        self._block_book = self._perf.count_blocks(layout.without, BlockAttnStream(
            block_attn_arm(self.cfg, self._ck, self.decode_impl)[0],
            _cache_shape(self._ck))) if self._block else None
        # Workload capture + latency waterfall (telemetry/workload.py).
        # The capture ring is process-shared (like the flight recorder) so
        # a fleet of engines streams one trace; the waterfall is per-engine
        # — its stage windows describe THIS engine's scheduling. Both are
        # stdlib modules; the engine hands them plain scalars/lists only.
        self._workload = workload.get_workload()
        self._waterfall = workload.LatencyWaterfall()
        # wall of the previous round completion: the sampled "wait" bucket
        # (scheduler/host gap between consecutive device rounds)
        self._perf_mark = time.perf_counter()
        # the previous round's end, for the device seconds told: (rid, dx,
        # time.perf_counter() when the first read that waited for its program
        # returned, and whether that read blocked: the round had not ended
        # when the host asked)
        self._prev_end: tuple[int, int, float, bool] = (0, 0, 0.0, False)
        # shapes dispatched for the first time, so far: a stall whose interval
        # holds one is named after it (_retired)
        self._firsts = 0
        # the key _note_exec_shape has just opened, until _dx dispatches it
        # inside the annotation `engine.first_dispatch`
        self._first_key: tuple | None = None
        self._dx_n = 0  # device dispatches through _dx, all ops
        # when the last first dispatch on the engine's thread ended: rounds
        # whose life holds a compile teach the scheduler's cost model nothing
        self._first_end = 0.0
        # watchdog/compile-grace state transition counts (satellite of the
        # shed-while-compiling post-mortem gap): bridged to
        # llmtpu_watchdog_transitions_total{state=...} by engines_info
        self.watchdog_transitions: dict[str, int] = {}
        self._last_round_ts = time.time()  # decode-cadence stall signal
        # On-demand jax.profiler capture (/v1/debug/profile, or auto-armed
        # for the next N loop steps after an anomaly dump when
        # TPU_FLIGHT_PROFILE_STEPS > 0). All state transitions happen on
        # the engine thread; other threads only set the pending request.
        self._profile_pending: tuple[int, str] | None = None
        self._profile_left = 0
        self._profile_dir = ""
        _psteps = int(os.environ.get("TPU_FLIGHT_PROFILE_STEPS", "0") or 0)
        if _psteps > 0:
            self._flight.add_dump_callback(
                lambda info, n=_psteps: self.start_profile(n)
            )
        # paged ledger tap: COW / pin / unpin / snapshot ops become flight
        # events (the callback runs under the paging lock — the recorder's
        # lock-free append is the only thing it may do)
        self._paging.on_ops = self._paging_event

        # Stall watchdog: a wedged accelerator (a hung runtime, a chip
        # another process took) leaves the engine thread stuck in a device
        # call it can never be interrupted out of. The loop stamps
        # progress each iteration; when in-flight work exists and the stamp
        # goes stale past TPU_STALL_TIMEOUT_S (default 600 s — first 8B
        # compiles legitimately take minutes), the watchdog sheds load:
        # new submits are rejected, queued-but-unadmitted requests get
        # error events (their consumers would otherwise hang), and
        # stall_seconds() lets the serving layer flip the device offline
        # so routing fails over (the reference's analog maps connection
        # errors to device-offline: worker/llm_worker/main.py:189-196 —
        # a wedged XLA runtime produces no error to map, only silence).
        self.last_progress = time.time()
        self.stall_timeout_s = float(
            os.environ.get("TPU_STALL_TIMEOUT_S", "600") or 0
        )
        self.stalled = False
        # First-time executable shapes (a new compact-decode bucket, a new
        # chunked-prefill (bucket, skey), a new admit bucket) legitimately
        # compile — minutes on a cold cache over a slow link. Dispatching a
        # never-seen shape extends a grace window so the watchdog doesn't
        # shed a healthy engine mid-compile; the cost is that a real wedge
        # during that window is detected one timeout later.
        self._seen_exec_shapes: set[tuple] = set()
        # (phase, ledger key) of the plannable shapes whose first real
        # dispatch has RETURNED: the warm-up plan skips them (warmup.py)
        self._served_shapes: set[tuple[str, str]] = set()
        self._compile_grace_until = 0.0
        # Warmup planner (executor/warmup.py): built by start_warmup() at
        # boot (serving entrypoints), None on the plain
        # test path and under TPU_WARMUP=0 — readiness then reads as
        # fully_warm (an unwarmed engine is not "warming", it is simply
        # pre-warmup-era cold, and must route exactly as before).
        self._warmup = None
        if self.stall_timeout_s > 0:
            threading.Thread(
                target=self._watchdog, name="engine-watchdog", daemon=True
            ).start()

        # rolling stats for dashboard/benchmarks. Rank 10 (doc/concurrency.md):
        # lowest rank, so holding it permits taking the pool/paging locks but
        # never the reverse — today no engine path nests it with either.
        self.stats_lock = OrderedLock("engine.stats", rank=10)
        self.total_tokens = 0
        self.total_requests = 0
        # requests failed with an error event (poisoned rounds, failed
        # prefills, cache loss): the engine's block at /v1/dashboard
        self.total_errors = 0
        # cleanly finished requests + their completion tokens: the ratio is
        # the mean completion length a shed's Retry-After is estimated from
        self.finished_requests = 0
        self.finished_tokens = 0
        # rolling client-observed TTFT samples (ts, ttft_ms): the planner
        # records p50/p95 into `benchmarks` so routing's latency constraint
        # sees REAL serve percentiles (reference analog: probe scripts
        # writing p50/p95 rows, scripts/probe_openrouter_models.py:113-124)
        self._ttft_window: deque[tuple[float, float]] = deque(maxlen=1024)
        self._window: list[tuple[float, int]] = []  # (ts, tokens) for tps
        # engine-loop wall-clock by phase (serve budget breakdown): decode
        # dispatch staging, round fetch-wait, admission, chunked prefill,
        # token emission, idle. /v1/dashboard shows it as `phase_s`.
        self._phase_s: dict[str, float] = {
            k: 0.0 for k in ("dispatch", "fetch", "admit", "prefill", "emit", "idle")
        }

        # Dispatch-plane device state owned by the op closures (replicated
        # by construction on followers, because only ops mutate it):
        # per-group prefill logits parked between the chunk dispatch and the
        # activation sample, keyed by the leader-assigned group id riding
        # the payload; prefix-entry device rows keyed by entry id.
        self._x_logits: dict[int, Any] = {}
        self._x_prefix: dict[int, tuple] = {}
        self._gid_ctr = 0
        self._eid_ctr = 0
        self._ops = self._build_ops()

    # -- dispatch plane ----------------------------------------------------

    def _ns(self, specs):
        """PartitionSpec tree → NamedSharding tree on this engine's mesh."""
        return named_shardings(self.mesh, specs)

    def _shard_out(self, kinds: list[str]) -> dict:
        """out_shardings kwargs for a jit definition: empty on the local
        plane (XLA chooses), explicit under GSPMD so host-read outputs come
        back fully replicated (every process device_gets its copy locally —
        no separate collective) and cache/pool outputs keep their specs.
        kinds name _out_kinds entries positionally: "repl", "k", "v",
        "pk", "pv"."""
        if not self._spmd:
            return {}
        outs = tuple(self._out_kinds[k] for k in kinds)
        return {"out_shardings": outs if len(outs) > 1 else outs[0]}

    def _fetch(self, tree):
        """Device→host fetch that is legal on every plane: local arrays
        device_get directly; under GSPMD a sharded global is resharded to
        fully-replicated first (device_get only addresses local shards)."""
        if self._spmd:
            tree = jax.tree.map(self._put_repl, tree)
        return jax.device_get(tree)

    @staticmethod
    def _load_checkpoint_global(cfg, ckpt_dir, dtype, mesh, shardings, quant: str = ""):
        """Every process reads the safetensors dir (standard multi-host
        practice) and contributes ONLY its addressable shards via
        make_array_from_callback — the full tree is never resident per
        process beyond the mmap'd host file."""
        from contextlib import nullcontext

        from ..models.weights import hf_to_llama_params, read_checkpoint_dir

        host = hf_to_llama_params(cfg, read_checkpoint_dir(ckpt_dir))
        if quant == "int8":
            from ..models.quant import quantize_params

            # quantize the host tree BEFORE placement so its structure matches
            # the quantized PartitionSpecs; pin the work to the CPU backend —
            # the tree must stay host-resident until make_array_from_callback
            # streams per-process shards
            try:
                cpu = jax.local_devices(backend="cpu")[0]
            except RuntimeError:
                cpu = None
            with jax.default_device(cpu) if cpu is not None else nullcontext():
                host = quantize_params(host)
        elif quant:
            raise NotImplementedError(
                f"engine quant={quant!r} with a checkpoint (only 'int8' is supported)"
            )

        def up(arr, sharding):
            a = np.asarray(arr)
            # int8 payloads must keep their dtype; only float leaves
            # (weights, scales, norms) follow the engine compute dtype
            if dtype is not None and np.issubdtype(a.dtype, np.floating):
                a = a.astype(dtype)
            return jax.make_array_from_callback(
                a.shape, sharding, lambda idx: a[idx]
            )

        return jax.tree.map(up, host, shardings)

    def _dx(self, op: str, *args):
        """THE dispatch funnel: every device mutation the scheduling loop
        makes goes through here — the backend sees the serialized (op,
        payload) step first (followers will replay the same closure from
        the same payload), then the op executes locally. Payloads are
        host-only values (numpy/int/str/bytes trees); device state lives on
        `self` and is read/written by the op closures alone. A step that
        RAISES under GSPMD kills the engine: the frame already fanned out,
        so followers executed (or wedged on) the same op and no local
        recovery can put every process back in the same state."""
        self._backend.emit(op, args)
        self._dx_n += 1
        first, self._first_key = self._first_key, None
        try:
            if first is None:
                return self._ops[op](*args)
            # the call that puts a shape on the device for the first time
            # (_note_exec_shape opened its key): jit traces, lowers and
            # compiles or loads inside it, and on the profiler's host plane
            # that time carries the ledger's key
            with TraceAnnotation("engine.first_dispatch",
                                 key=":".join(str(k) for k in first)):
                return self._ops[op](*args)
        except Exception as e:
            if self._spmd:
                self._mark_dead(f"dispatch {op!r} failed: {e}")
            raise

    def run_follower(self) -> None:
        """Blocking step-program replay loop for non-leader processes of a
        GSPMD backend: every received (op, payload) step executes the SAME
        op closure the leader ran, so device state stays replicated.
        Returns on the leader's stop command."""
        self._backend.run_follower(self._ops)

    def _paged_payload(self):
        """Host-side paged-dispatch descriptor riding the op payload: the
        numpy block table (policy state followers don't have), or None when
        the physical pool is off."""
        return self._phys.table if self._phys is not None else None

    def _paged_from(self, tbl):
        """Rebuild a jit `paged` argument from an op payload. Local plane:
        use the cached device table (one upload per mutation, not per
        dispatch). GSPMD: the numpy table enters the jit directly as a
        replicated operand."""
        if tbl is None:
            return None
        dev = tbl if self._spmd else self._phys.device_table()
        return {"tbl": dev, "k": self._pool_k, "v": self._pool_v}

    def _mark_dead(self, msg: str) -> None:
        """Poisoned dispatch under a GSPMD backend: the step already went
        out to followers and device state cannot be rebuilt replayably —
        the engine goes dead (submits reject, the loop exits, followers get
        the stop command from the loop tail)."""
        with self._dead_lock:
            if not self.dead:
                self.dead = msg or "dispatch failed"
        self._stop_evt.set()
        self._wake.set()

    def _build_ops(self) -> dict:
        """The step-program vocabulary: op name → closure holding ALL the
        device work of that step. Closures take host-only payloads, read
        and write device state through `self`, and are the ONLY code that
        touches jits/eager device ops after __init__ — the dispatch-surface
        lint pass reconciles this registry against dispatch.DISPATCH_OPS
        and the engine's _dx call sites both ways."""
        ops: dict[str, Any] = {}

        def op_admit(tokens, ipack, fpack, cn=None):
            # jits read via self._admit_fn at call time (tests monkeypatch it)
            (self._ck, self._cv, self._d_temp, self._d_topk, self._d_topp,
             self._d_last_tok, toks0) = self._admit_fn(
                self.params, self._ck, self._cv, self._d_temp, self._d_topk,
                self._d_topp, self._d_last_tok, tokens, ipack, fpack, cn=cn,
            )
            return toks0

        ops["admit"] = op_admit

        def op_insert(eid, slots, live_n):
            pk, pv = self._x_prefix[eid]
            self._ck, self._cv = self._insert_cached_fn(
                self._ck, self._cv, pk, pv, slots, np.int32(live_n)
            )

        ops["insert"] = op_insert

        def op_insrows(hk, hv, slots, live_n):
            # host KV rows ride the payload (restore / migrate-in: the
            # follower never saw this KV) and enter the jit as replicated
            # numpy operands
            self._ck, self._cv = self._insert_cached_fn(
                self._ck, self._cv, hk, hv, slots, np.int32(live_n)
            )

        ops["insrows"] = op_insrows

        def op_insat(hk, hv, slot, start):
            self._ck, self._cv = self._insert_at_fn(
                self._ck, self._cv, hk, hv, np.int32(slot), np.int32(start)
            )

        ops["insat"] = op_insat

        def op_chunk(gid, tokens, slots, starts, nvalid, skey, tbl):
            logits, self._ck, self._cv = self._prefill_chunk_fn(
                self.params, self._ck, self._cv, tokens, slots, starts,
                nvalid, skey=skey, paged=self._paged_from(tbl),
            )
            self._x_logits[gid] = logits

        ops["chunk"] = op_chunk

        def op_ragged(gid, tokens, rowids, positions, slots, starts,
                      last_idx, skey, tbl):
            logits, self._ck, self._cv = self._ragged_chunk_fn(
                self.params, self._ck, self._cv, tokens, rowids, positions,
                slots, starts, last_idx, skey=skey, paged=self._paged_from(tbl),
            )
            jax.block_until_ready(self._ck)
            self._x_logits[gid] = logits

        ops["ragged"] = op_ragged

        def op_bsample(gid, rows, slots_fin, temps, topks, topps, counter,
                       cn=None, first=None):
            # activation sample off a parked chunk group's boundary logits +
            # the sampling-param/token-ring writes for the finishing slots
            # (`first`: a block configuration's, whose ring holds each slot's
            # first block as it starts and which samples nothing here)
            logits = self._x_logits.pop(gid, None)
            if logits is None or len(rows) == 0:
                return None
            if first is not None:
                self._d_temp = self._d_temp.at[slots_fin].set(temps)
                self._d_topk = self._d_topk.at[slots_fin].set(topks)
                self._d_topp = self._d_topp.at[slots_fin].set(topps)
                self._d_last_tok = self._d_last_tok.at[slots_fin].set(first)
                return None
            if cn is not None:
                # constrained activation (chunked-prefill and prefix-hit
                # admissions): the masked sibling jit — only ever traced
                # when constrained traffic reaches this path
                toks0 = self._sample1_cn(
                    logits[rows], np.int32(counter), temps, topks, topps,
                    cn[0], cn[1], cn[2],
                )
            else:
                toks0 = self._sample1(
                    logits[rows], np.int32(counter), temps, topks, topps
                )
            self._d_temp = self._d_temp.at[slots_fin].set(temps)
            self._d_topk = self._d_topk.at[slots_fin].set(topks)
            self._d_topp = self._d_topp.at[slots_fin].set(topps)
            self._d_last_tok = self._d_last_tok.at[slots_fin].set(toks0)
            return toks0

        ops["bsample"] = op_bsample

        def op_decode(kind, gid, packed, p_args, compact, skey, tbl):
            paged = self._paged_from(tbl)
            if kind == "plain":
                out, self._ck, self._cv, self._d_last_tok = self._decode_fn(
                    self.params, self._ck, self._cv, packed, self._d_temp,
                    self._d_topk, self._d_topp, self._d_last_tok,
                    compact=compact, paged=paged,
                )
                return out
            if kind == "mixed":
                (out, toks0, self._ck, self._cv, self._d_temp, self._d_topk,
                 self._d_topp, self._d_last_tok) = self._mixed_fn(
                    self.params, self._ck, self._cv, packed, self._d_temp,
                    self._d_topk, self._d_topp, self._d_last_tok, *p_args,
                    paged=paged,
                )
                return out, toks0
            fn = self._fused_fn if kind == "fused" else self._fused_ragged_fn
            out, logits, self._ck, self._cv, self._d_last_tok = fn(
                self.params, self._ck, self._cv, packed, self._d_temp,
                self._d_topk, self._d_topp, self._d_last_tok, *p_args,
                compact=compact, skey=skey, paged=paged,
            )
            self._x_logits[gid] = logits
            return out

        ops["decode"] = op_decode

        def op_verify(tokens, slots, starts, nvalid, drafts, ndraft,
                      counter, skey, tbl, cn=None):
            (n_acc, final, self._ck, self._cv,
             self._d_last_tok) = self._verify_fn(
                self.params, self._ck, self._cv, self._d_last_tok,
                self._d_temp, self._d_topk, self._d_topp, tokens, slots,
                starts, nvalid, drafts, ndraft, np.int32(counter),
                skey=skey, paged=self._paged_from(tbl), cn=cn,
            )
            return n_acc, final

        ops["verify"] = op_verify

        def op_cnstep(packed, masks, bids, bvals, tbl):
            # masked single-step decode for constrained slots. The jit is
            # built on first use — leader and follower alike only ever
            # trace it when constrained traffic actually dispatches here,
            # which is what keeps TPU_CONSTRAIN=0 a zero-trace no-op.
            if self._cn_step_fn is None:
                self._cn_step_fn = self._build_cn_step()
            out, self._ck, self._cv, self._d_last_tok = self._cn_step_fn(
                self.params, self._ck, self._cv, packed, self._d_temp,
                self._d_topk, self._d_topp, self._d_last_tok, masks, bids,
                bvals, paged=self._paged_from(tbl),
            )
            return out

        ops["cnstep"] = op_cnstep

        def op_samprow(b, temp, topk, topp, last):
            # single-slot sampling-state restore (preempt-restore path)
            self._d_temp = self._d_temp.at[b].set(np.float32(temp))
            self._d_topk = self._d_topk.at[b].set(np.int32(topk))
            self._d_topp = self._d_topp.at[b].set(np.float32(topp))
            self._d_last_tok = self._d_last_tok.at[b].set(np.int32(last))

        ops["samprow"] = op_samprow

        def op_snap(b, Lb, start, srcs):
            # host copies of slot b's committed KV rows [start, Lb); the
            # physical-table indirection rides the payload as (in_arena,
            # row, off) triples so followers slice the same sources
            bt = self._paging.block_tokens

            def cut(arr, pool):
                if isinstance(arr, dict):
                    if not arr:  # fused GQA: "v" is the empty-dict placeholder
                        return {}
                    return {
                        k: cut(arr[k], None if pool is None else pool[k])
                        for k in arr
                    }
                P = self.max_seq_len // arr.shape[3]  # the rope keys' positions abreast
                if srcs is None:
                    return self._fetch(_slot_rows(arr, b, P)[:, :, :, start:Lb])
                parts = [
                    _slot_rows(arr, row, P)[:, :, :, off : off + bt]
                    if in_arena
                    else pool[:, row : row + 1]
                    for in_arena, row, off in srcs
                ]
                whole = jnp.concatenate(parts, axis=3) if len(parts) > 1 else parts[0]
                return self._fetch(whole[:, :, :, start:Lb])

            return cut(self._ck, self._pool_k), cut(self._cv, self._pool_v)

        ops["snap"] = op_snap

        def op_pfxput(eid, slot, p0):
            # park a slot's prefix rows [0, p0) as a device prefix entry
            def cut(c, _):
                return _slot_rows(c, slot, self.max_seq_len // c.shape[3])[:, :, :, :p0]

            pk, pv = _tree2(cut, self._ck, self._ck), _tree2(cut, self._cv, self._cv)
            self._x_prefix[eid] = (pk, pv)
            return pk, pv

        ops["pfxput"] = op_pfxput

        def op_pfxdrop(eid):
            self._x_prefix.pop(eid, None)

        ops["pfxdrop"] = op_pfxdrop

        def op_pfximp(eid, hk, hv):
            # fleet-tier import: wire-decoded host rows become a device
            # entry (replicated under GSPMD — any consistent placement
            # works; insert jits reshard on use)
            up = self._put_repl if self._spmd else jnp.asarray
            pk = jax.tree.map(up, hk)
            pv = jax.tree.map(up, hv)
            self._x_prefix[eid] = (pk, pv)
            return pk, pv

        ops["pfximp"] = op_pfximp

        def op_pfxexp(eid):
            pk, pv = self._x_prefix[eid]
            return self._fetch((pk, pv))

        ops["pfxexp"] = op_pfxexp

        def op_poolexp(prows, p0):
            # physical-entry export: gather the entry's pool rows into one
            # contiguous [L, 1, H, p0, ...] host tree (dict-aware)
            def cut(pool):
                if isinstance(pool, dict):
                    if not pool:
                        return {}
                    return {k: cut(pool[k]) for k in pool}
                parts = [pool[:, r : r + 1] for r in prows]
                whole = jnp.concatenate(parts, axis=3) if len(parts) > 1 else parts[0]
                return self._fetch(whole[:, :, :, :p0])

            return cut(self._pool_k), cut(self._pool_v)

        ops["poolexp"] = op_poolexp

        def op_cow(slot, blk, prow):
            self._ck, self._cv = self._cow_fn(
                self._ck, self._cv, self._pool_k, self._pool_v,
                np.int32(slot), np.int32(blk), np.int32(prow),
            )

        ops["cow"] = op_cow

        def op_pput(kind, a, b, prow):
            # prefix-pool row stores: "arena" copies a slot block (a=row,
            # b=off), "pool" copies a pool row (a=src_row), "host" uploads a
            # wire-decoded block (a=hk, b=hv)
            if kind == "arena":
                self._pool_k, self._pool_v = self._pool_arena_fn(
                    self._pool_k, self._pool_v, self._ck, self._cv,
                    np.int32(a), np.int32(b), np.int32(prow),
                )
            elif kind == "pool":
                self._pool_k, self._pool_v = self._pool_pool_fn(
                    self._pool_k, self._pool_v, np.int32(a), np.int32(prow)
                )
            else:
                self._pool_k, self._pool_v = self._pool_host_fn(
                    self._pool_k, self._pool_v, a, b, np.int32(prow)
                )

        ops["pput"] = op_pput

        return ops

    # -- jit builders ------------------------------------------------------

    def _build_decode(self):
        cfg = self.cfg
        K = self.decode_chunk
        mask = self._allowed_mask
        impl = self.decode_impl
        base_key = self._base_key

        def sample_step(logits, ck, lens, rng, temp, topk, topp):
            """A decode step's sample: (tokens [Ba], the key carried on)."""
            with jax.named_scope("sample"):
                if mask is not None:
                    logits = jnp.where(mask, logits, -jnp.inf)
                rng, sub = jax.random.split(rng)
                # parked rows (lens >= S) carry stale params from a prior
                # occupant — exclude them from fast-path selection
                S_cache = (ck["q"] if isinstance(ck, dict) else ck).shape[3]
                new = sample_tokens(
                    logits, sub, temp, topk, topp, active=lens < S_cache
                )
            return new, rng

        def plain_step(params, temp, topk, topp, slot_ids=None, paged=None):
            """The scan body of one plain decode step over the carry (ck, cv,
            tokens, lengths, rng): decode_body's K steps, and the K - 1 that
            follow mixed_round_fn's first."""
            def step(carry, _):
                ck, cv, toks, lens, rng = carry
                logits, ck, cv = llama_decode_step(
                    cfg, params, ck, cv, toks, lens, attn_impl=impl,
                    slot_ids=slot_ids, paged=paged,
                )
                new, rng = sample_step(logits, ck, lens, rng, temp, topk, topp)
                return (ck, cv, new, lens + 1, rng), new

            return step

        def with_counts(out, cv):
            """The expert layer's running counts ride the round's one fetch
            as rows behind the K rows of tokens [K, Ba] (_complete_round)."""
            if not (isinstance(cv, dict) and "moe" in cv):
                return out
            Ba = out.shape[1]
            moe = cv["moe"].reshape(-1)
            moe = jnp.pad(moe, (0, -moe.shape[0] % Ba)).reshape(-1, Ba)
            return jnp.concatenate([out, moe.astype(out.dtype)])

        def decode_body(params, ck, cv, packed, d_temp, d_topk, d_topp,
                        d_last, compact, paged=None):
            """One decode round (K fused steps) — traced body shared by
            decode_chunk_fn and fused_step_fn.

            All per-round host inputs ride ONE packed i32 transfer (every
            separate host->device transfer is its own dispatch): compact → [lengths | slot_ids | counter] (2*Ba+1), full →
            [lengths | counter] (B+1). The round's INPUT TOKENS never touch
            the host: they come from `d_last`, the device-resident
            last-token ring that this round (and admissions) write — so the
            NEXT round can be dispatched before this one's output is ever
            fetched, and the decode chain rides the device stream while the
            host trails behind fetching outputs for emission (the pipelined
            loop, _run). The RNG key derives from the counter on device;
            sampling params are the device-resident arrays, gathered by
            slot id on the compact path (row i serves cache row
            slot_ids[i] — _dispatch_decode)."""
            if compact:
                Ba = (packed.shape[0] - 1) // 2
                lengths = packed[:Ba]
                slot_ids = packed[Ba : 2 * Ba]
                tokens = d_last[slot_ids]
                temp = d_temp[slot_ids]
                topk = d_topk[slot_ids]
                topp = d_topp[slot_ids]
            else:
                Ba = packed.shape[0] - 1
                lengths = packed[:Ba]
                slot_ids = None
                tokens = d_last
                temp, topk, topp = d_temp, d_topk, d_topp
            rng = jax.random.fold_in(base_key, packed[-1])

            (ck, cv, last, _, _), out = jax.lax.scan(
                plain_step(params, temp, topk, topp, slot_ids, paged),
                (ck, cv, tokens, lengths, rng), None, length=K
            )
            # write the round's final tokens back into the ring. Compact pad
            # rows all target the same inactive row (duplicate-index set:
            # last write wins on garbage) — harmless, admission overwrites
            # on reuse and the device stream is in-order.
            if compact:
                d_last = d_last.at[slot_ids].set(last)
            else:
                d_last = last
            return with_counts(out, cv), ck, cv, d_last  # out: [K, Ba]

        @partial(jax.jit, donate_argnums=(1, 2, 7), static_argnames=("compact",),
                 **self._shard_out(["repl", "k", "v", "repl"]))
        def decode_chunk_fn(params, ck, cv, packed, d_temp, d_topk, d_topp,
                            d_last, compact, paged=None):
            return decode_body(params, ck, cv, packed, d_temp, d_topk,
                               d_topp, d_last, compact, paged=paged)

        @partial(
            jax.jit, donate_argnums=(1, 2, 7),
            static_argnames=("compact", "skey"),
            **self._shard_out(["repl", "repl", "k", "v", "repl"]),
        )
        def fused_step_fn(params, ck, cv, packed, d_temp, d_topk, d_topp,
                          d_last, p_tokens, p_slots, p_starts, p_nvalid,
                          compact, skey, paged=None):
            """Fused scheduler step: one decode round (K steps for the
            active rows) AND one budget-bounded prefill chunk group in the
            SAME dispatch (the token-budget scheduler's stall-free shape —
            decode cadence never waits behind a host-paced prefill phase,
            and the chunk group costs at most ~one extra decode round of
            device time by budget construction).

            Decode rows and the chunk group's slots are DISJOINT (mid-
            prefill slots are reserved, parked at length=S, and never in the
            active set), so running the chunk after the decode scan on the
            threaded cache is value-identical to two separate dispatches.
            The prefill logits return un-fetched; activation samples from
            them only when a prompt's last chunk landed."""
            out, ck, cv, d_last = decode_body(
                params, ck, cv, packed, d_temp, d_topk, d_topp, d_last,
                compact, paged=paged,
            )
            p_logits, ck, cv = llama_prefill_chunk_batch(
                cfg, params, ck, cv, p_tokens, p_slots, p_starts, p_nvalid,
                skey=skey, paged=paged,
            )
            return out, p_logits, ck, cv, d_last

        @partial(
            jax.jit, donate_argnums=(1, 2, 7),
            static_argnames=("compact", "skey"),
            **self._shard_out(["repl", "repl", "k", "v", "repl"]),
        )
        def fused_ragged_fn(params, ck, cv, packed, d_temp, d_topk, d_topp,
                            d_last, p_tokens, p_rowids, p_positions, p_slots,
                            p_starts, p_last_idx, compact, skey, paged=None):
            """fused_step_fn's ragged twin: the chunk group rides the packed
            token buffer + per-row descriptors instead of [Ab, bucket] pads,
            so ONE executable per (T, compact) covers every fill mix (the
            bucketed zoo minted one per (Ab, bucket, skey)). Same disjoint-
            slot argument as fused_step_fn."""
            out, ck, cv, d_last = decode_body(
                params, ck, cv, packed, d_temp, d_topk, d_topp, d_last,
                compact, paged=paged,
            )
            p_logits, ck, cv = llama_prefill_chunk_ragged(
                cfg, params, ck, cv, p_tokens, p_rowids, p_positions,
                p_slots, p_starts, p_last_idx, skey=skey, paged=paged,
                impl=self._ragged_impl,  # read at trace time
            )
            return out, p_logits, ck, cv, d_last

        if cfg.recurrent:
            from ..models.hybrid import hybrid_mixed_step as mixed_step
        else:
            mixed_step = mixed_step_q8

        @partial(jax.jit, donate_argnums=(1, 2, 4, 5, 6, 7),
                 **self._shard_out(["repl", "repl", "k", "v", "repl", "repl",
                                   "repl", "repl"]))
        def mixed_round_fn(params, ck, cv, packed, d_temp, d_topk, d_topp,
                           d_last, p_tokens, p_rowids, p_positions, ipack,
                           fpack, paged=None):
            """A full-batch decode round whose FIRST step carries admitted
            prompts through its pass over the weights (`mixed_step_q8`, or
            `hybrid_mixed_step` for a stack with recurrent layers: the
            configuration decides, where this is traced); the other K - 1
            steps are `decode_chunk_fn`'s. What `admit_fn` followed
            by a plain round gives, for one weight pass less: the prompts'
            rows land in their slots, their sampling parameters and first
            tokens are written where `admit_fn` writes them (the slots are
            parked rows of this round and decode from the next one on).

            `packed` is the plain round's [lengths | counter]. p_tokens,
            p_rowids, p_positions [T]: whole prompts packed back to back (pads:
            rowid R, position S). ipack i32 [3 R + 2]: slots, each prompt's last
            packed index, top_k, the live prompt count, the admission's rng
            counter; fpack f32 [2 R]: temperature, top_p."""
            R = fpack.shape[0] // 2
            slots, last_idx, topks = ipack[:R], ipack[R : 2 * R], ipack[2 * R : 3 * R]
            live = jnp.arange(R) < ipack[3 * R]
            temps, topps = fpack[:R], fpack[R:]
            Ba = packed.shape[0] - 1
            lengths = packed[:Ba]
            rng = jax.random.fold_in(base_key, packed[-1])
            logits, ck, cv = mixed_step(
                cfg, params, ck, cv, d_last, lengths, p_tokens, p_rowids,
                p_positions, slots, last_idx, paged=paged,
            )
            new, rng = sample_step(logits[:Ba], ck, lengths, rng, d_temp, d_topk, d_topp)
            with jax.named_scope("sample"):  # the prompts' first tokens, as admit_fn samples them
                p_logits = logits[Ba:]
                if mask is not None:
                    p_logits = jnp.where(mask, p_logits, -jnp.inf)
                toks0 = sample_tokens(
                    p_logits, jax.random.fold_in(base_key, ipack[3 * R + 1]),
                    temps, topks, topps, active=live,
                )

            out = new[None]
            if K > 1:
                (ck, cv, new, _, _), rest = jax.lax.scan(
                    plain_step(params, d_temp, d_topk, d_topp, paged=paged),
                    (ck, cv, new, lengths + 1, rng), None, length=K - 1)
                out = jnp.concatenate([out, rest])
            # a pad prompt scatters to row B: out of bounds, dropped
            row = jnp.where(live, slots, Ba)
            d_temp = d_temp.at[row].set(temps)
            d_topk = d_topk.at[row].set(topks)
            d_topp = d_topp.at[row].set(topps)
            d_last = new.at[row].set(toks0)
            return with_counts(out, cv), toks0, ck, cv, d_temp, d_topk, d_topp, d_last

        if not cfg.block_len:
            return decode_chunk_fn, fused_step_fn, fused_ragged_fn, mixed_round_fn

        L, MASK = cfg.block_len, cfg.mask_token_id

        def block_round_fn(params, ck, cv, packed, d_temp, d_topk, d_topp,
                           d_last, compact, paged=None):
            """One BLOCK round, in the decode round's place for a configuration
            that generates by diffusion over blocks: ONE program that fills
            each row's block of L positions and commits it.

            `packed` is the decode round's ([lengths | slot_ids | counter] or
            [lengths | counter]); a row's length IS its block's first position,
            a multiple of L. The blocks as they start come from `d_last`
            [B, L], device-resident like the decode round's token ring: masks,
            or behind an admission the prompt's last P mod L tokens and masks
            (admit_fn). Denoising passes (`llama.block_denoise`: the L
            positions against the cache of every earlier block and, whole,
            against each other, NO cache write, then the unmask rule on device)
            repeat while a live row holds a mask, `denoise_steps` of them at
            most: the host never learns the count before the fetch and does
            not need to. The commit pass
            (`llama.block_pass(commit=True)`) runs the final tokens once more
            and writes their keys and values. Out: the tokens [L, Ba] in
            position order (a first block's leading P mod L rows are the
            prompt's own), one row of each row's denoising passes, then the
            expert counts as a decode round appends them; every live row's
            `d_last` is L masks again, so the next round is dispatched before
            this one is fetched."""
            assert paged is None, "a block configuration runs without the prefix pool"
            if compact:
                Ba = (packed.shape[0] - 1) // 2
                starts, slot_ids = packed[:Ba], packed[Ba : 2 * Ba]
                first = d_last[slot_ids]
                temp, topk, topp = d_temp[slot_ids], d_topk[slot_ids], d_topp[slot_ids]
            else:
                Ba = packed.shape[0] - 1
                starts, slot_ids, first = packed[:Ba], None, d_last
                temp, topk, topp = d_temp, d_topk, d_topp
            live = starts < (ck["q"] if isinstance(ck, dict) else ck).shape[3]
            counted = isinstance(cv, dict) and "moe" in cv

            def masks_left(carry):
                # a pass fills at least L / denoise_steps of a row's masks and
                # the sampler cannot emit the mask (`_allowed_mask`), so no
                # block outlives `denoise_steps` passes: the bound only keeps a
                # fault from spinning on the chip
                return jnp.any((carry[0] == MASK) & live[:, None]) & (carry[4] < cfg.denoise_steps)

            def denoise(carry):
                tokens, passes, rng, moe, n = carry
                rng, sub = jax.random.split(rng)
                new, cv_p, _ = block_denoise(
                    cfg, params, ck, dict(cv, moe=moe) if counted else cv, tokens,
                    slot_ids, starts, live, sub, temp, topk, topp, allowed=mask, attn_impl=impl)
                passes = passes + (jnp.any(tokens == MASK, axis=1) & live)
                return new, passes, rng, cv_p["moe"] if counted else moe, n + 1

            tokens, passes, _, moe, _ = jax.lax.while_loop(
                masks_left, denoise,
                (first, jnp.zeros((Ba,), jnp.int32), jax.random.fold_in(base_key, packed[-1]),
                 cv["moe"] if counted else jnp.zeros((), jnp.int32), jnp.int32(0)))
            with jax.named_scope("block.commit"):
                _, ck, cv = block_pass(
                    cfg, params, ck, dict(cv, moe=moe) if counted else cv, tokens,
                    slot_ids, starts, live, commit=True, attn_impl=impl)
            fresh = jnp.where(live[:, None], MASK, first)
            d_last = fresh if slot_ids is None else d_last.at[slot_ids].set(fresh)
            out = jnp.concatenate([tokens.T, passes[None]])  # [L + 1, Ba]
            return with_counts(out, cv), ck, cv, d_last

        # in a trace the block round is THE plain round, as the decode round is
        # for every other configuration (perf.PLAIN_ROUND_TRACE_NAME says why)
        assert decode_chunk_fn.__name__ == perf.PLAIN_ROUND_TRACE_NAME
        block_round_fn.__name__ = block_round_fn.__qualname__ = perf.PLAIN_ROUND_TRACE_NAME
        block_round_fn = jax.jit(
            block_round_fn, donate_argnums=(1, 2, 7), static_argnames=("compact",),
            **self._shard_out(["repl", "k", "v", "repl"]))
        return block_round_fn, fused_step_fn, fused_ragged_fn, mixed_round_fn

    def _build_verify(self):
        """Jitted speculative verify: ONE model call over [token, draft_1..
        draft_K] per slot through the chunked-prefill machinery (multi-
        position KV writes for free), full-position logits, then
        accept/reject + the follow-on sample on device (spec_verify). Only
        two [A] int arrays (accepted counts, final tokens) ever reach the
        host — the accepted drafts themselves are already known host-side.

        Pad rows carry slot id B: every cache scatter and the token-ring
        write drop out of bounds (the admission-path invariant), and their
        clamped param gathers are excluded from the sampler's homogeneity
        reductions via `active`."""
        cfg = self.cfg
        mask = self._allowed_mask
        base_key = self._base_key
        B = self.max_slots

        @partial(jax.jit, donate_argnums=(1, 2, 3), static_argnames=("skey",),
                 **self._shard_out(["repl", "repl", "k", "v", "repl"]))
        def verify_fn(params, ck, cv, d_last, d_temp, d_topk, d_topp,
                      tokens, slots, starts, nvalid, drafts, ndraft,
                      counter, skey, paged=None, cn=None):
            logits, ck, cv = llama_prefill_chunk_batch(
                cfg, params, ck, cv, tokens, slots, starts, nvalid,
                skey=skey, all_logits=True, paged=paged,
            )  # [A, C, V]
            if mask is not None:
                logits = jnp.where(mask, logits, -jnp.inf)
            # constrained verify rounds: per-POSITION automaton masks
            # ([A, C, W] — row j constrains the token at draft offset j)
            # applied BEFORE accept/reject, so the draft acceptance test
            # and the rejection-resampling residual both see the
            # renormalized masked target — distribution-exact under the
            # constraint. cn=None (unconstrained rounds) keeps the
            # pre-existing executable (the paged=None trailing pattern).
            if cn is not None:
                logits = apply_token_mask(logits, cn[0], cn[1], cn[2])
            temp = d_temp[slots]
            topk = d_topk[slots]
            topp = d_topp[slots]
            rng = jax.random.fold_in(base_key, counter)
            n_acc, final = spec_verify(
                logits, drafts, ndraft, rng, temp, topk, topp,
                active=slots < B, exact=cn is not None,
            )
            # the round's final token into the device ring: the next decode
            # round reads its input from d_last without host staging
            d_last = d_last.at[slots].set(final)
            return n_acc, final, ck, cv, d_last

        return verify_fn

    def _build_cn_step(self):
        """Masked SINGLE-step decode for constrained slots (op "cnstep").

        Constrained slots cannot ride the K-step pipelined scan: the mask
        for step j+1 depends on the token sampled at step j, which only the
        host-side automaton can produce. So constrained traffic decodes one
        committed-exact masked step per loop iteration — compact packed
        [lengths | slot_ids | counter] exactly like decode_body's compact
        path, plus the packed [Ba, W] mask rows and [Ba, NB] bias arrays.
        Built lazily on the first constrained dispatch; under
        TPU_CONSTRAIN=0 it never exists (zero-trace kill switch)."""
        cfg = self.cfg
        mask = self._allowed_mask
        impl = self.decode_impl
        base_key = self._base_key

        @partial(jax.jit, donate_argnums=(1, 2, 7),
                 **self._shard_out(["repl", "k", "v", "repl"]))
        def cn_step_fn(params, ck, cv, packed, d_temp, d_topk, d_topp,
                       d_last, masks, bids, bvals, paged=None):
            Ba = (packed.shape[0] - 1) // 2
            lengths = packed[:Ba]
            slot_ids = packed[Ba : 2 * Ba]
            tokens = d_last[slot_ids]
            temp = d_temp[slot_ids]
            topk = d_topk[slot_ids]
            topp = d_topp[slot_ids]
            rng = jax.random.fold_in(base_key, packed[-1])
            logits, ck, cv = llama_decode_step(
                cfg, params, ck, cv, tokens, lengths, attn_impl=impl,
                slot_ids=slot_ids, paged=paged,
            )
            if mask is not None:
                logits = jnp.where(mask, logits, -jnp.inf)
            logits = apply_token_mask(logits, masks, bids, bvals)
            S_cache = (ck["q"] if isinstance(ck, dict) else ck).shape[3]
            new = sample_tokens(
                logits, rng, temp, topk, topp, active=lengths < S_cache,
                exact=True,
            )
            d_last = d_last.at[slot_ids].set(new)
            return new, ck, cv, d_last

        return cn_step_fn

    def stall_seconds(self) -> float:
        """Age of the engine loop's last progress stamp. Large values with
        in-flight work mean the thread is wedged inside an uninterruptible
        device call (serving layer: flip the device offline, fail over).
        Zero while a first-time executable shape may still be compiling."""
        if time.time() < self._compile_grace_until:
            return 0.0
        return max(0.0, time.time() - self.last_progress)

    def _watchdog(self) -> None:
        poll = min(30.0, max(1.0, self.stall_timeout_s / 4))
        while not self._stop_evt.wait(timeout=poll):
            self.check_anomalies()  # decode-cadence stall, paged-leak growth
            age = self.stall_seconds()
            if age > self.stall_timeout_s:
                if not self.stalled:
                    self.stalled = True
                    self._watchdog_transition("stalled")
                    log.error(
                        "engine stalled: no loop progress for %.0f s "
                        "(wedged device call?); shedding queued load", age,
                    )
                # Drain requests the blocked loop can never admit — their
                # consumers would hang past any reasonable client timeout.
                # Re-check staleness per pop: if the loop resumed we must
                # not steal legitimate requests.
                drained = 0
                while self.stall_seconds() > self.stall_timeout_s:
                    try:
                        req = self._admit.get_nowait()
                    except queue.Empty:
                        break
                    self._count_error()
                    req.out.put(
                        {"type": "error",
                         "error": "engine stalled: accelerator unresponsive"}
                    )
                    req.out.put(_DONE)
                    drained += 1
                if drained:
                    self._watchdog_transition("shed")
                    log.error("engine watchdog errored %d queued requests", drained)
                # In-flight consumers must not hang forever either: deliver
                # their terminal errors now. The wedged loop cannot race us
                # (it is blocked inside a device call); if it resumes
                # anyway, the aborted flag + identity guards turn its later
                # emissions into no-ops against dead queues, and the slots
                # self-clean through the normal finish path.
                for s in list(self._slots):
                    if (
                        s is not None and not s.aborted and not s.done
                        and self.stall_seconds() > self.stall_timeout_s
                    ):
                        s.aborted = True
                        self._count_error()
                        s.req.out.put(
                            {"type": "error",
                             "error": "engine stalled: accelerator unresponsive"}
                        )
                        s.req.out.put(_DONE)
                for st in list(self._prefills.values()):
                    if (
                        not st.aborted
                        and self.stall_seconds() > self.stall_timeout_s
                    ):
                        st.aborted = True
                        self._count_error()
                        st.req.out.put(
                            {"type": "error",
                             "error": "engine stalled: accelerator unresponsive"}
                        )
                        st.req.out.put(_DONE)
                # preempted-and-offloaded requests wait on restore, which the
                # wedged loop will never perform — their consumers must not
                # hang either (pool.drain() removes the snapshots, so a
                # resuming loop cannot double-deliver)
                if self._pool is not None and (
                    self.stall_seconds() > self.stall_timeout_s
                ):
                    for snap in self._pool.drain():
                        self._paging.drop_snap(snap.snap_id)
                        s = snap.slot_obj
                        if s is None or s.aborted or s.done:
                            continue
                        s.aborted = True
                        self._count_error()
                        s.req.out.put(
                            {"type": "error",
                             "error": "engine stalled: accelerator unresponsive"}
                        )
                        s.req.out.put(_DONE)
                    self._phys_sweep()
            elif self.stalled:
                self.stalled = False
                self._watchdog_transition("recovered")
                log.warning("engine loop recovered after stall")

    def _next_counter(self) -> int:
        """RNG stream position. The hot paths ship the counter inside their
        packed int transfer and fold it into the base key ON DEVICE — a
        host-side fold_in is one more dispatch per round."""
        self._rng_counter += 1
        return self._rng_counter

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "GenerationEngine":
        if self._thread is None:
            # leader-side channel setup first (blocking accept of every
            # follower) — the loop must never emit into a half-built channel
            self._backend.start()
            self._thread = threading.Thread(target=self._run, name="gen-engine", daemon=True)
            self._thread.start()
        return self

    def shutdown(self) -> None:
        self._stop_evt.set()
        self._wake.set()
        if self._warmup is not None:
            # stop the background AOT thread first: a compile in flight
            # holds jit internals the teardown below must not race
            self._warmup.stop()
        if self._thread:
            self._thread.join(timeout=10)
            self._thread = None
        # release the followers (idempotent — the loop tail already sent
        # stop on a dead engine) and drop the command channel
        self._backend.stop()
        self._backend.close()
        # Drain every waiter — callers blocked in req.out.get() must not
        # deadlock when the engine stops mid-request.
        self._abort_all("engine shutdown")
        while True:
            try:
                req = self._admit.get_nowait()
            except queue.Empty:
                break
            req.out.put({"type": "error", "error": "engine shutdown"})
            req.out.put(_DONE)
        while self._migrate_in is not None and not self._migrate_in.empty():
            # migrated-in snapshots never restored: their consumers block
            # on queues this engine now owns — error them like queued work
            try:
                _snap, _header, _nbytes, s = self._migrate_in.get_nowait()
            except queue.Empty:
                break
            s.req.out.put({"type": "error", "error": "engine shutdown"})
            s.req.out.put(_DONE)

    # -- warmup (executor/warmup.py; ROADMAP item 5) -----------------------

    def _runs(self, feature: str) -> bool:
        """Whether this configuration runs `feature`: all but what its cache's
        layout rules out (`CacheLayout.without`, which is `memory.RECURRENT_OFF`,
        the one list, where a slot member rides the pair: its reasons are
        logged where the pool is built, and the pool counts the times each
        would have engaged; `memory.COUNTED_OFF` where a latent pair's second
        member carries the expert counts). A mixed round is not among them: a recurrent
        configuration's admissions ride too (`hybrid_mixed_step`)."""
        return feature not in self._layout.without

    def _note_off(self, feature: str) -> None:
        """A feature this configuration runs without would have engaged: the
        book that keeps it off counts it (the state pool's, or the block
        rounds'); a configuration with neither counts nothing."""
        book = self._block_book if self._state_pool is None else self._state_pool
        if book is not None:
            book.note_off(feature)

    @property
    def state_dtype(self) -> str:
        """The recurrent state pool's precision ("" without one): the matrix
        state's, or the convolution tails' for a kind whose only state they are;
        a configuration's file states it (`program.expect`)."""
        if self._layout.slot_member != "state":
            return ""
        state = self._cv["state"]
        return str(state.get("S", state["conv"]).dtype)

    def _layer_leaf_dtype(self, *names: str) -> str:
        """Precision of the first of `names` among the layers' leaves (the
        stacked ones', or a leading dense layer's own: `params["first"]`, or
        the latent family's `params["dense_layers"]`): "int8" for a quantised
        one, "" where there is none."""
        params = self.params if isinstance(self.params, dict) else {}
        layers = {**next(iter(params.get("first", ())), {}), **params.get("dense_layers", {}),
                  **params.get("layers", {})}
        leaf = next((layers[k] for k in names if k in layers), None)
        if leaf is None:
            return ""
        return "int8" if isinstance(leaf, dict) else str(leaf.dtype)

    @property
    def weights_dtype(self) -> str:
        """The layers' dense feed-forward matrices' precision ("" where every
        layer's feed-forward is routed experts: `expert_dtype`; a leading
        dense layer's where the rest are); a
        configuration's file states it (`program.expect`)."""
        return self._layer_leaf_dtype("w13", "w1")

    @property
    def expert_dtype(self) -> str:
        """The routed expert banks' precision ("" without routed experts)."""
        return self._layer_leaf_dtype("w1e")

    def warmup_shape_zoo(self) -> list[tuple[str, tuple]]:
        """The engine's serving-shape zoo: the (phase, shape key) pairs its
        config can dispatch, in `_note_exec_shape`'s own vocabulary — the
        same keys the CompileLedger aggregates, so an imported warmup plan
        (prior boots' measurements) matches these entries by string.

        Enumeration is DELIBERATELY first-hit-bounded, not exhaustive:
        admit and decode ladders are small and fully listed; chunked
        prefill lists only the zero-context skey (every boot's first long
        prompt — later skeys depend on live context lengths and ride the
        ledger priors instead); fused/verify depend on the live fill mix
        and never enumerate from config (warmup.py PLANNABLE_PHASES)."""
        zoo: list[tuple[str, tuple]] = []
        phys = self._phys is not None
        S = self.max_seq_len
        buckets: list[int] = []
        n = 1
        while True:
            b = self._bucket(n)
            if not buckets or b > buckets[-1]:
                buckets.append(b)
            if b >= S:
                break
            n = b + 1
        ab_cap = 1 << max(0, self.admit_batch - 1).bit_length()
        # whole prompts reach an admit program up to prefill_chunk tokens (a
        # longer one is chunked), several of them up to _admit_tokens_max
        tok_cap = self._admit_tokens_max()
        ab = 1
        while ab <= ab_cap:
            for bk in buckets:
                if not tok_cap or ab * bk <= tok_cap:
                    zoo.append(("admit", (ab, bk)))
            ab <<= 1
        B = self.max_slots
        if self.decode_compact:
            ba = min(8, B)
            while ba < B:
                zoo.append(("decode", (ba, True, phys)))
                ba <<= 1
        zoo.append(("decode", (B, False, phys)))
        if not self._ride_off():
            # the full batch only, a rung an executable (_stage_ride)
            zoo.extend(("mixed", (t, phys)) for t in self._ride_rungs)
        if self.ragged_prefill and self._ragged_cap:
            skey0 = 0 if self._ragged_impl == "kernel" else min(128, S)
            t = min(32, self._ragged_cap)
            while t <= self._ragged_cap:
                zoo.append(("pf_rag", (t, skey0, phys)))
                t <<= 1
        elif self.prefill_chunk > 0:
            skey0 = min(128, S)
            cap = self._bucket(self.prefill_chunk)
            rows = 1
            while rows <= ab_cap:
                for bk in [b for b in buckets if b <= cap]:
                    zoo.append(("chunk", (rows, bk, skey0, phys)))
                rows <<= 1
        return zoo

    @staticmethod
    def parse_ledger_key(ks: str) -> tuple:
        """Invert `_compile_obs`'s colon-joined key encoding back into a
        typed tuple — shape keys only ever carry ints and bools (the
        dispatch-surface lint pins the vocabulary), so the round-trip is
        exact for every real ledger row."""
        out: list = []
        for part in ks.split(":"):
            if part == "True":
                out.append(True)
            elif part == "False":
                out.append(False)
            else:
                try:
                    out.append(int(part))
                except ValueError:
                    out.append(part)
        return tuple(out)

    def _warmup_key_fits(self, phase: str, key: tuple) -> bool:
        """Whether a plan step's shape key is dispatchable by THIS engine's
        config. The compile ledger is process-shared and warmup packs ship
        between hosts, so priors can carry shapes from other configs — an
        admit bucket beyond max_seq_len fails to lower (the cache operand
        is too small), a decode batch beyond max_slots was never built.
        Out-of-config keys record skip, like the phys-flag mismatches."""
        try:
            cap = self._bucket(self.max_seq_len)
            ab_cap = 1 << max(0, self.admit_batch - 1).bit_length()
            if phase == "admit":
                ab, bucket = int(key[0]), int(key[1])
                return (1 <= ab <= ab_cap and 0 < bucket <= cap
                        and self._bucket(bucket) == bucket)
            if phase == "decode":
                return 1 <= int(key[0]) <= self.max_slots
            if phase == "mixed":
                return not self._ride_off() and int(key[0]) in self._ride_rungs
            if phase == "chunk":
                rows, bucket, skey = int(key[0]), int(key[1]), int(key[2])
                return (self.prefill_chunk > 0 and 1 <= rows <= ab_cap
                        and 0 < bucket <= cap and 0 <= skey <= cap)
            if phase == "pf_rag":
                t, skey = int(key[0]), int(key[1])
                return (bool(self.ragged_prefill and self._ragged_cap)
                        and 1 <= t <= self._ragged_cap and 0 <= skey <= cap)
            return True
        except (TypeError, ValueError, IndexError):
            return False

    def warmup_compile(self, phase: str, key: tuple) -> float | None:
        """AOT-compile one executable shape via jit lower().compile() —
        the warmup planner's compile hook. This populates the persistent
        XLA compile cache (utils/config.enable_compile_cache), NOT jit's
        dispatch cache: the first real dispatch of the shape still traces,
        then loads the cached executable instead of paying the 1-2 min XLA
        compile (the ledger's `trace_s`/`lower_s`/`backend_s` say how long
        each part of that first dispatch still takes).
        Returns the compile wall, or None for phases whose argument shapes
        cannot be synthesized from the key alone (fused/verify/restore —
        they compile on first real dispatch, exactly as before warmup)."""
        if phase not in perf.WARMUP_PHASES:
            return None
        t0 = time.perf_counter()
        compile_watch.begin()  # the zoo's thread; closed by _compile_obs
        lowered = self.warmup_lower(phase, key)
        if lowered is None:
            compile_watch.end()
            return None
        lowered.compile()
        wall = time.perf_counter() - t0
        self._compile_obs(phase, key, wall, src="warmup")
        return wall

    def warmup_lower(self, phase: str, key: tuple):
        """Lower one step program at the shapes of a ledger key, or None
        when the key does not fit this engine's config."""
        call = self.warmup_operands(phase, key)
        if call is None:
            return None
        fn, args, kwargs = call
        return fn.lower(*args, **kwargs)

    def warmup_operands(self, phase: str, key: tuple):
        """(jitted step program, args, kwargs) to lower it with at the shapes
        of a ledger key, or None when the key does not fit this engine.

        The operands are ShapeDtypeStruct mirrors of the live params/cache/
        sampling arrays, so the lowered module (and its cache key) matches
        what the serve path will build: under a mesh they carry the arrays'
        NamedShardings; off one they carry NONE, as the live call's module
        does (an explicit single-device sharding is written into the module's
        arguments, which makes it another module under another cache key: the
        plan's compile then serves no first dispatch, and on a cold cache
        every shape compiles twice).
        scripts/rehearse_tpu_compile.py re-places the same operands on
        described chips, which is how the whole step programs meet the TPU
        compiler on a machine without one."""
        if not self._warmup_key_fits(phase, key):
            return None  # stale prior from a different engine config

        from jax.sharding import NamedSharding

        def own(x):
            # Under a mesh only arrays placed ON the mesh keep their
            # sharding. The small per-slot arrays (sampling params, the
            # last-token ring, block tables) sit on the default device,
            # uncommitted: a real dispatch moves them where the program
            # runs, but a ShapeDtypeStruct carrying "device 0" beside
            # mesh-sharded weights is "incompatible devices" — which is how
            # every AOT warmup compile of a sharded engine failed on a real
            # four-chip host.
            sh = getattr(x, "sharding", None)
            if self.mesh is None or not isinstance(sh, NamedSharding):
                return None
            return sh

        def sds(tree):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=own(x)),
                tree,
            )

        def host(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype)

        phys = self._phys is not None
        paged = sds(self._paged_from(self._paged_payload())) if phys else None
        P, CK, CV = sds(self.params), sds(self._ck), sds(self._cv)
        sampling = (sds(self._d_temp), sds(self._d_topk), sds(self._d_topp),
                    sds(self._d_last_tok))
        rows = max(1, self.admit_batch)

        def packed(ba, compact):
            return host(((2 * ba + 1,) if compact else (self.max_slots + 1,)))

        if phase == "admit":
            ab, bucket = int(key[0]), int(key[1])
            return self._admit_fn, (
                P, CK, CV, *sampling,
                host((ab, bucket)), host((3 * ab + 2 + ab * self._block,)),
                host((2 * ab,), jnp.float32),
            ), {}
        if phase == "decode":
            ba, compact = int(key[0]), bool(key[1])
            if bool(key[2]) != phys:
                return None  # stale prior from a different pool config
            return self._decode_fn, (
                P, CK, CV, packed(ba, compact), *sampling,
            ), dict(compact=compact, paged=paged)
        if phase == "mixed":
            t, r = int(key[0]), self._ride_rows
            if bool(key[1]) != phys:
                return None
            return self._mixed_fn, (
                P, CK, CV, packed(self.max_slots, False), *sampling,
                host((t,)), host((t,)), host((t,)), host((3 * r + 2,)),
                host((2 * r,), jnp.float32),
            ), dict(paged=paged)
        if phase == "chunk":
            rws, bucket, skey = int(key[0]), int(key[1]), int(key[2])
            if bool(key[3]) != phys:
                return None
            return self._prefill_chunk_fn, (
                P, CK, CV, host((rws, bucket)), host((rws,)),
                host((rws,)), host((rws,)),
            ), dict(skey=skey, paged=paged)
        if phase == "pf_rag":
            t, skey = int(key[0]), int(key[1])
            if bool(key[2]) != phys:
                return None
            return self._ragged_chunk_fn, (
                P, CK, CV, host((t,)), host((t,)), host((t,)),
                host((rows,)), host((rows,)), host((rows,)),
            ), dict(skey=skey, paged=paged)
        if phase == "fused_rag":
            ba, compact, t, skey = (
                int(key[0]), bool(key[1]), int(key[2]), int(key[3]))
            if bool(key[4]) != phys:
                return None
            return self._fused_ragged_fn, (
                P, CK, CV, packed(ba, compact), *sampling,
                host((t,)), host((t,)), host((t,)),
                host((rows,)), host((rows,)), host((rows,)),
            ), dict(compact=compact, skey=skey, paged=paged)
        return None

    def start_warmup(self, priors: list[dict] | None = None):
        """Build and run the warmup plan (TPU_WARMUP=0: a TRUE no-op —
        returns None, no planner, no compiles, greedy output is
        token-identical either way). The critical first-token prefix (one
        admit bucket + one prefill executable + one decode shape) compiles
        SYNCHRONOUSLY before this returns; the rest of the zoo compiles on
        a low-priority background thread while the engine serves
        (TPU_WARMUP_BG=0 skips it). `priors` takes CompileLedger table
        rows — the live ledger's, or an imported warmup pack's — to order
        the plan by measured compile cost x hit count. Idempotent."""
        from . import warmup as warmup_mod

        if not warmup_mod.warmup_enabled():
            return None
        if self._warmup is not None:
            return self._warmup
        rows = list(priors or [])
        rows.extend(self._ledger.table())
        prior_idx = warmup_mod.priors_from_table(rows)
        zoo = self.warmup_shape_zoo()
        for (ph, ks) in list(prior_idx):
            # measured shapes from prior boots join the zoo with exact
            # typed keys; unplannable phases ride along and record as skip
            key = self.parse_ledger_key(ks)
            if (ph, key) not in zoo:
                zoo.append((ph, key))
        steps = warmup_mod.plan_steps(zoo, prior_idx)
        self._warmup = warmup_mod.WarmupPlanner(
            self.warmup_compile, steps,
            throttle_s=float(os.environ.get("TPU_WARMUP_THROTTLE_S", "0.05") or 0),
            event=self._flight.event,
            served=lambda phase, key: (
                (phase, warmup_mod.key_str(key)) in self._served_shapes),
        )
        self._warmup.run_critical()
        if warmup_mod.warmup_bg_enabled():
            self._warmup.start_background()
        else:
            for s in self._warmup.steps:
                if s.status == "pending":
                    s.status = "skip"
            self._warmup.start_background()  # immediate fully_warm
        return self._warmup

    def warmup_priors(self) -> list[dict]:
        """This engine's compile-ledger rows in warmup-prior form — what
        the model zoo captures at swap-out so the NEXT residency's
        start_warmup() re-plans from measured compile cost × hit count
        (executor/warmup.py: pack_priors)."""
        from . import warmup as warmup_mod

        return warmup_mod.pack_priors(self._ledger.table())

    def warmup_stats(self) -> dict[str, Any]:
        """Readiness + plan progress for /v1/debug/warmup and the router's
        warming tag. No planner (warmup off / plain test boot) reads as
        fully_warm with zero steps: an unwarmed engine routes exactly as
        the pre-warmup era."""
        if self._warmup is None:
            return {"state": "fully_warm", "steps": 0, "enabled": False}
        st = self._warmup.stats()
        st["enabled"] = True
        return st

    # -- public API --------------------------------------------------------

    def submit(self, req: GenRequest) -> GenRequest:
        if self.dead:
            req.out.put(
                {"type": "error", "error": f"engine dead: {self.dead}"}
            )
            req.out.put(_DONE)
            return req
        if self._stop_evt.is_set():
            req.out.put({"type": "error", "error": "engine shutdown"})
            req.out.put(_DONE)
            return req
        if self.stalled:
            # fail fast instead of queueing behind a wedged device call —
            # the router sees the device offline and falls back to cloud
            self._count_error()
            req.out.put(
                {"type": "error", "error": "engine stalled: accelerator unresponsive"}
            )
            req.out.put(_DONE)
            return req
        self._admit.put(req)
        self._wake.set()
        return req

    def generate_stream(
        self,
        prompt: str,
        *,
        max_tokens: int = 256,
        temperature: float = 0.7,
        top_k: int = 0,
        top_p: float = 1.0,
        stop: list[str] | None = None,
        priority: int = 0,
        tenant: str = "",
        constraint: dict | None = None,
        logit_bias: list | None = None,
    ) -> Iterator[dict[str, Any]]:
        """Yield {"type":"token","text":...} events then a final
        {"type":"done", "usage":..., "finish_reason":...}."""
        ids = self.tokenizer.encode(prompt)
        req = GenRequest(
            prompt_ids=ids,
            max_tokens=max_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            stop=stop or [],
            priority=priority,
            trace_ctx=tracing.current_traceparent(),
            tenant=tenant,
            constraint=constraint,
            logit_bias=logit_bias,
        )
        self.submit(req)
        while True:
            evt = req.out.get()
            if evt is _DONE:
                return
            yield evt
            if evt.get("type") == "done":
                return

    def generate(self, prompt: str, **kw: Any) -> dict[str, Any]:
        """Non-streaming: returns {"text", "usage", "finish_reason"}."""
        text_parts: list[str] = []
        final: dict[str, Any] = {}
        for evt in self.generate_stream(prompt, **kw):
            if evt["type"] == "token":
                text_parts.append(evt["text"])
            elif evt["type"] == "done":
                final = evt
            elif evt["type"] == "error":
                raise RuntimeError(evt.get("error", "generation failed"))
        return {
            "text": "".join(text_parts),
            "usage": final.get("usage", {}),
            "finish_reason": final.get("finish_reason", "stop"),
        }

    def prefix_cache_stats(self) -> dict[str, int]:
        """Snapshot for dashboards/metrics (the cache itself is engine-thread
        private state — callers must not reach into it)."""
        return {
            "entries": len(self._prefix_cache),
            "bytes": self._prefix_cache_bytes,
            "hits": self.prefix_cache_hits,
            "misses": self.prefix_cache_misses,
        }

    def phase_budget(self) -> dict[str, float]:
        """Accumulated engine-loop wall-clock seconds per phase. Snapshot at
        two points and subtract to budget a window (`phase_s` at
        /v1/dashboard)."""
        return dict(self._phase_s)

    def ttft_percentiles(
        self, window_s: float = 600.0
    ) -> tuple[float, float, int]:
        """(p50_ms, p95_ms, n) of client-observed TTFT over the recent
        window — nearest-rank, matching scripts/probe_models.py."""
        now = time.time()
        with self.stats_lock:
            vals = sorted(v for t, v in self._ttft_window if now - t <= window_s)
        if not vals:
            return 0.0, 0.0, 0
        n = len(vals)
        p50 = vals[max(0, (n + 1) // 2 - 1)]
        p95 = vals[max(0, min(n - 1, int(n * 0.95 + 0.5) - 1))]
        return p50, p95, n

    def scheduler_stats(self) -> dict[str, float]:
        """Token-budget scheduler observability (telemetry/metrics.py gauges
        + the starvation counter): the live prefill token budget, decode
        batch occupancy, and cost-model EMAs."""
        out = self._sched.stats()
        out["decode_batch_occupancy"] = (
            self._last_active_n / self.max_slots if self.max_slots else 0.0
        )
        return out

    def scheduler_tenant_stats(self) -> dict[str, dict[str, float]]:
        """Per-tenant quota detail (token-bucket level, throttle and
        charge counters) for /v1/debug/perf. Empty without quotas."""
        return self._sched.tenant_stats()

    def speculation_stats(self) -> dict[str, float]:
        """Self-speculative decoding observability (telemetry/metrics.py
        gauges + the engines_info speculation block): cumulative drafted /
        accepted / emitted token counts, verify-call count, and the derived
        acceptance rate and tokens-per-verify-call."""
        drafted = float(self.spec_drafted)
        calls = float(self.spec_calls)
        return {
            "enabled": 1.0 if self._verify_fn is not None else 0.0,
            "k": float(self.spec_k),
            "min_ngram": float(self.spec_min_ngram),
            "drafted_tokens": drafted,
            "accepted_tokens": float(self.spec_accepted),
            "emitted_tokens": float(self.spec_emitted),
            "verify_calls": calls,
            "accept_rate": (self.spec_accepted / drafted) if drafted else 0.0,
            "tok_per_call": (self.spec_emitted / calls) if calls else 0.0,
        }

    def constrain_stats(self) -> dict[str, Any]:
        """Constrained-decoding observability (/v1/debug/constrain): traffic
        counters, the token-level validity proof (illegal_tokens must be 0 —
        the mask makes illegal emission impossible by construction; the
        counter is the check), per-token host mask cost, spec-composition
        acceptance, and the schema compile-cache economics."""
        toks = float(self.cn_tokens)
        fin = float(self.cn_finished)
        drafted = float(self.cn_spec_drafted)
        out: dict[str, Any] = {
            "enabled": 1.0 if self._constrain is not None else 0.0,
            "requests": float(self.cn_requests),
            "tokens": toks,
            "illegal_tokens": float(self.cn_illegal),
            "finished": fin,
            "finished_accepting": float(self.cn_finished_accepting),
            # token-level validity: every constrained token was automaton-
            # legal AND every finished constrained request ended accepting
            "schema_valid_rate": (
                (self.cn_finished_accepting / fin) if fin else 1.0
            ) if self.cn_illegal == 0 else 0.0,
            "mask_us_per_tok": (self.cn_mask_s * 1e6 / toks) if toks else 0.0,
            "spec_drafted": drafted,
            "spec_accepted": float(self.cn_spec_accepted),
            "spec_accept_rate": (
                (self.cn_spec_accepted / drafted) if drafted else 0.0
            ),
        }
        if self._constrain is not None:
            out["cache"] = self._constrain.stats()
        return out

    def _offered_load(self) -> float:
        """Offered load the admission watermark compares against, in
        slot-equivalents. Only meaningful with the pool on.

        Paged accounting (paging.py:offered_blocks): unique blocks
        referenced by live tables and parked snapshots count ONCE — shared
        prefixes are paid for once no matter how many slots pin them — plus
        each request's committed decode growth (`wants`: length + tokens
        remaining + one decode chunk, the promise admission already made),
        snapshot restore needs, and the admit queue priced at the EMA
        private-block cost. With zero sharing this reduces exactly to the
        old integer `occupied + queued + preempted` accounting."""
        queued = self._admit.qsize()
        if self._pool is None:
            return float(self.slots_in_use() + queued)
        mgr = self._paging
        S = self.max_seq_len
        K = self.decode_chunk
        wants: dict[int, int] = {}
        for b, s in enumerate(self._slots):
            if s is None or s.done or s.aborted:
                continue
            rem = max(0, s.req.max_tokens - s.generated)
            wants[b] = min(int(self._lengths[b]) + rem + K, S)
        for slot, st in list(self._prefills.items()):
            if st.aborted:
                continue
            wants[slot] = min(len(st.ids) + max(0, st.req.max_tokens) + K, S)
        return mgr.offered_blocks(wants, queued) / max(1, mgr.blocks_per_slot)

    def memory_stats(self) -> dict[str, float]:
        """KV pool observability (engines_info memory block + dashboard +
        llmtpu_kv_* metrics). {"enabled": 0.0} when TPU_KV_HOST_OFFLOAD is
        off — the pool doesn't exist and nothing else is meaningful."""
        pool = self._pool
        if pool is None:
            return {"enabled": 0.0}
        out = pool.stats()
        out["enabled"] = 1.0
        offered = self._offered_load()
        out["offered"] = float(offered)
        out["headroom"] = pool.headroom(offered)
        return out

    def paging_stats(self) -> dict[str, float]:
        """Paged-KV block economy (engines_info paging block + dashboard +
        llmtpu_kv_block* metrics). Always available — the ledger is pure
        host bookkeeping and runs regardless of the pool."""
        out = self._paging.stats()
        out["enabled"] = 1.0
        out["leaks"] = float(self._paging.leak_count())
        if self._phys is not None:
            out.update(self._phys.stats())
            out["physical"] = 1.0
            contig, phys = self._phys_hbm_peak
            out["hbm_bytes_contiguous_equiv_peak"] = contig
            out["hbm_bytes_physical_peak"] = phys
            out["hbm_bytes_ratio_peak"] = self._phys_hbm_peak_ratio
        else:
            out["physical"] = 0.0
        return out

    def admission_state(self, tenant: str = "") -> tuple[bool, float]:
        """(shed, retry_after_s) for the API's load-shedding gate. SIDE-
        EFFECT FREE except the tenant-quota throttle counter — dashboards
        and the jobs claim path call the zero-arg form; only a caller that
        actually rejects work records it via note_shed(). A non-empty
        `tenant` additionally consults that tenant's token-bucket quota
        (scheduler.tenant_admit): over-quota tenants shed HERE, per
        tenant, even while the pool itself has headroom. (False, 0.0)
        with zero pool bookkeeping when pool and quotas are both off."""
        if tenant:
            ok, retry = self._sched.tenant_admit(tenant)
            if not ok:
                return True, min(600.0, max(1.0, retry))
        pool = self._pool
        if pool is None:
            return False, 0.0
        offered = self._offered_load()
        if pool.admit_ok(offered):
            return False, 0.0
        with self.stats_lock:
            fr, ft = self.finished_requests, self.finished_tokens
        mean_tokens = (ft / fr) if fr else 64.0
        n_waiting = self._admit.qsize() + pool.preempted_count()
        retry = self._sched.drain_estimate_s(
            max(1, n_waiting), mean_tokens, self.decode_chunk, self.max_slots
        )
        return True, min(600.0, max(1.0, retry))

    def note_shed(self, n: int = 1, tenant: str = "") -> None:
        """Record that the API shed work on this engine's behalf (429 or a
        deferred job claim). A non-empty `tenant` also charges the shed to
        that tenant's goodput ledger (per-tenant 429 visibility)."""
        if self._pool is not None:
            self._pool.note_shed(n)
        if tenant:
            self._perf.note_tenant_shed(tenant, n)
        in_grace = time.time() < self._compile_grace_until
        self._flight.event("shed", n=n, in_grace=in_grace)
        if in_grace:
            # the post-mortem distinction this PR exists for: work dropped
            # because a compile held the loop, not because of a real wedge
            self._watchdog_transition("shed_in_grace")
        self._anomaly.signal("shed_in_grace", in_grace=in_grace, shed=n)

    def current_tps(self, window_s: float = 10.0) -> float:
        now = time.time()
        with self.stats_lock:
            self._window = [(t, n) for t, n in self._window if now - t <= window_s]
            toks = sum(n for _, n in self._window)
        return toks / window_s

    def slots_in_use(self) -> int:
        return sum(1 for s in self._slots if s is not None) + len(self._prefills)

    def queue_depth(self) -> int:
        """Requests accepted by submit() but not yet admitted to a slot."""
        return self._admit.qsize()

    # -- engine loop -------------------------------------------------------

    def _bucket(self, n: int) -> int:
        # sp prefill shards the bucket over the sp axis — keep it divisible;
        # and on the pallas prefill path every rung must be a legal flash
        # block shape (192 is not: S >= 128 needs S % 128 == 0, sub-128
        # rungs must be pow2 — kernels/attention.py:pallas_supported).
        # Midpoint rungs failing either rule fall back to the pow2 rung.
        if self.prefill_fine:
            b = fine_bucket(n, self.max_seq_len)
            ok_sp = b % max(self.sp, 1) == 0
            ok_impl = self.attn_impl != "pallas" or pallas_supported(
                b, self.cfg.resolved_head_dim
            )
            if ok_sp and ok_impl:
                return max(b, self.sp)
        return max(pow2_bucket(n, self.max_seq_len), self.sp)

    def _recover_cache(self) -> bool:
        """Re-allocate the KV cache if a failed dispatch consumed the donated
        buffers (donate_argnums invalidates inputs even when execution
        raises); without this every later round would see a deleted Array.
        Returns True when a re-allocation happened (all slot KV was lost)."""
        try:
            leaves = jax.tree.leaves(
                {"k": self._ck, "v": self._cv,
                 "p": (self._d_temp, self._d_topk, self._d_topp,
                       self._d_last_tok),
                 "x": ({} if self._pool_k is None
                       else {"k": self._pool_k, "v": self._pool_v})}
            )
            deleted = any(x.is_deleted() for x in leaves)
        except AttributeError:
            deleted = False
        if not deleted:
            return False
        if self._spmd:
            # The poisoned step already fanned out: followers executed (or
            # wedged on) the same dispatch, and freshly-allocated buffers
            # here could never be re-synchronized through replay. The engine
            # goes dead instead — submits reject, the loop exits, followers
            # get the stop command from the loop tail.
            self._mark_dead("kv cache lost in a failed dispatch")
            return True
        # the device sampling rows and token ring are also donated; host
        # mirrors are the source of truth, so rebuilding them is lossless
        # (the ring may lag by the in-flight rounds that were lost — their
        # slots were failed/aborted, so no live stream reads the stale rows)
        self._d_temp = jnp.asarray(self._temp)
        self._d_topk = jnp.asarray(self._topk)
        self._d_topp = jnp.asarray(self._topp)
        self._d_last_tok = jnp.asarray(self._last_tok)
        log.warning("KV cache buffers were donated into a failed dispatch; re-allocating")
        cache = self._layout.allocate()
        self._ck = cache["k"]
        self._cv = cache["v"]
        if self._phys is not None:
            # the physical pools ride the same donation paths (_pool_put_*
            # donate them; _cow_block_fn donates the arena they feed) — any
            # prefix entry's pool bytes are now suspect, so drop them all.
            # _abort_all follows every _recover_cache()=True return and
            # resets the per-slot tables + sweeps the id map.
            pools = self._layout.allocate_pools(
                self._paging.prefix_partition, self._paging.block_tokens)
            self._pool_k, self._pool_v = pools["k"], pools["v"]
            while self._prefix_cache:
                self._evict_lru_prefix()
            self._phys.reset_all()
        return True

    def _count_error(self, n: int = 1) -> None:
        """All total_errors bumps go through here: the counter is written
        from both the engine and watchdog threads, so it must always be under
        stats_lock."""
        with self.stats_lock:
            self.total_errors += n

    def _note_exec_shape(self, *key) -> bool:
        """Record a dispatch shape; first sighting opens a compile-grace
        window equal to the stall timeout (see __init__). Returns True on
        first sighting — the caller times that dispatch into the compile
        ledger (_compile_obs): jit traces+compiles synchronously inside the
        first call of a shape, so its wall time IS the compile time."""
        if key in self._seen_exec_shapes:
            return False
        self._seen_exec_shapes.add(key)
        self._firsts += 1
        self._first_key = key
        # what JAX reports on this thread until _compile_obs goes to the
        # ledger entry
        compile_watch.begin()
        now = time.time()
        in_grace = now < self._compile_grace_until
        self._compile_grace_until = max(
            self._compile_grace_until, now + self.stall_timeout_s
        )
        if not in_grace:
            # one transition per grace EPISODE, not per shape — overlapping
            # first sightings extend the same open window
            self._watchdog_transition("compile_grace")
        return True

    def _watchdog_transition(self, state: str) -> None:
        """Count a watchdog/compile-grace state transition and journal it:
        `llmtpu_watchdog_transitions_total{state=...}` + a recorder event,
        so "shed while compiling" is distinguishable from a real wedge in
        post-mortems. Called from the engine loop, the watchdog thread, and
        the API's shed path — hence stats_lock."""
        with self.stats_lock:
            self.watchdog_transitions[state] = (
                self.watchdog_transitions.get(state, 0) + 1
            )
        self._flight.event("watchdog", state=state)

    def _compile_obs(self, phase: str, key: tuple, wall_s: float,
                     src: str = "serve", planned: bool = True) -> None:
        """First dispatch of an executable shape → compile ledger entry +
        recorder event (the ROADMAP item-5 cold-start measurement).
        `src` is provenance: "serve" for real dispatches, "warmup" for the
        planner's AOT compiles — /v1/debug/compiles shows whether the
        serve path ever ate a cold compile warmup should have absorbed.
        `planned=False`: the program dispatched is not the one a plan step
        of this key compiles (a constrained admission's), so the plan must
        not take the shape for served."""
        ks = ":".join(str(p) for p in key)
        e = self._ledger.observe(
            phase, ks, wall_s, src=src, parts=compile_watch.end()
        )
        if src == "serve":
            self._first_end = time.perf_counter()
            if planned:
                self._served_shapes.add((phase, ks))
        self._flight.event(
            "compile", phase=phase, key=ks,
            wall_ms=round(wall_s * 1e3, 1), hit=e["hit"],
            trace_ms=round(e["trace_s"] * 1e3, 1),
            lower_ms=round(e["lower_s"] * 1e3, 1),
            backend_ms=round(e["backend_s"] * 1e3, 1),
        )

    def _paging_event(self, ops: list[tuple]) -> None:
        """Paged-ledger observer (paging.py on_ops): sharing-relevant block
        ops → flight events. Runs under the rank-30 paging lock, so it only
        performs lock-free recorder appends."""
        for op in ops:
            kind = op[0]
            if kind == "pin":
                self._flight.event("pin", slot=op[1], blocks=len(op[2]))
            elif kind == "cow":
                self._flight.event("cow", slot=op[1], src=op[2], dst=op[3])
            elif kind == "free":
                self._flight.event("unpin", slot=op[1], blocks=len(op[2]))
            elif kind == "snap":
                self._flight.event(
                    "snap", snap_id=op[1], slot=op[2],
                    shared=len(op[3]), private=len(op[4]),
                )

    # -- physical paged KV (block tables + prefix pool, physical.py) -------

    def _phys_reset(self, slot: int) -> None:
        """Slot released (free/preempt): its table row back to identity,
        then reclaim pool rows whose ledger ids just died. Driven from the
        mutator call sites, never from on_ops — the observer runs under the
        paging lock and sweep/table_view re-take it."""
        if self._phys is None:
            return
        if self._phys.reset(slot):
            self._flight.event("pg_tbl", slot=slot, action="reset")
        self._phys.sweep(self._paging.alive)

    def _phys_sweep(self) -> None:
        """Reclaim pool rows after a pin-dropping mutation that re-keys no
        table (drop_snap, prefix_release outside the eviction path)."""
        if self._phys is not None:
            self._phys.sweep(self._paging.alive)

    def _phys_rebuild(self, slot: int) -> None:
        """Re-key one slot's device table row from the ledger's view (after
        pin / restore mutations)."""
        if self._phys is None:
            return
        ids, sn = self._paging.table_view(slot)
        if self._phys.rebuild(slot, ids, sn):
            self._flight.event("pg_tbl", slot=slot, action="rebuild", shared=sn)

    def _phys_admit(self, slot: int, ent: dict, ops: list[tuple]) -> None:
        """Physical side of a shared admission (prefix hit or migrated-in
        re-pin): execute the ledger's COW op as ONE whole-block device copy
        out of the entry's pool row, then rebuild the slot's table row.
        Exactly one boundary block ever copies — aligned stored lengths
        copy nothing at all."""
        if self._phys is None:
            return
        for op in ops:
            if op[0] != "cow":
                continue
            prow = self._phys.phys_of(op[2])
            if prow is None:  # tripwire: unmapped entry block (audited)
                self._phys.missing_pins += 1
                continue
            blk = int(ent["P"]) // self._paging.block_tokens
            first = self._note_exec_shape("cow")
            t0 = time.perf_counter()
            self._dx("cow", int(slot), int(blk), int(prow - self._phys.pool_base))
            if first:
                self._compile_obs("cow", (self._paging.block_tokens,),
                                  time.perf_counter() - t0)
            self._phys.cow_copies_total += 1
            self._flight.event("pg_cow", slot=slot, blk=blk,
                               pool_row=prow - self._phys.pool_base)
        self._phys_rebuild(slot)
        self._phys_note_hbm()

    def _phys_note_hbm(self) -> None:
        """Sample the honest HBM ledger at a shared admission: what the
        live working set physically occupies (unique blocks — identity
        homes + pool rows, each resident ONCE) against what the
        pre-physical contiguous engine held for the same set (every
        sharer's full row copy, plus the prefix entries' own device rows).
        The peak ratio is `hbm_bytes_ratio_peak` of paging_stats()."""
        st = self._paging.stats()
        bb = float(self._paging.bytes_per_block)
        used = st["blocks_used"]
        if bb <= 0 or used <= 0:
            return
        phys = used * bb
        contig = st["logical_blocks"] * bb + float(self._prefix_cache_bytes)
        ratio = contig / phys
        if ratio > self._phys_hbm_peak_ratio:
            self._phys_hbm_peak_ratio = ratio
            self._phys_hbm_peak = (contig, phys)

    def _store_prefix_physical(self, slot: int, key: tuple, p0: int) -> bool:
        """Copy a freshly-registered prefix entry's blocks [0, p0) into the
        prefix pool, gathered through the STORING slot's own table (a sharer
        storing a longer prefix reads its shared blocks from the pool, not
        its stale arena rows). False → pool rows unavailable; the caller
        already holds the ledger registration and must release it."""
        ids = self._paging.prefix_ids(key)
        if ids is None:
            return False
        rows = self._phys.register_prefix(ids)
        if rows is None:
            return False
        srcs = self._phys.row_sources(slot, len(ids))
        for j, prow in enumerate(rows):
            in_arena, src_row, off = srcs[j]
            first = self._note_exec_shape("pool_put", in_arena)
            t0 = time.perf_counter()
            if in_arena:
                self._dx("pput", "arena", int(src_row), int(off), int(prow))
            else:
                self._dx("pput", "pool", int(src_row), 0, int(prow))
            if first:
                self._compile_obs("pool_put", (in_arena,),
                                  time.perf_counter() - t0)
        return True

    @staticmethod
    def _tid(req: "GenRequest") -> str:
        """Request's 32-hex trace id for recorder events — a flight dump
        stitches into /v1/traces through it ("" when the request arrived
        without trace context)."""
        ids = tracing.parse_traceparent(req.trace_ctx)
        return ids[0] if ids else ""

    def check_anomalies(self) -> None:
        """Feed the poll-style anomaly detectors (decode-cadence stall,
        paged-leak growth). Read-only over host state, so safe from any
        thread; called by the watchdog loop and engines_info refreshes.
        Event-style detectors (TTFT burn, spec collapse, ping-pong,
        shed-in-grace) are fed at their hot-path sites instead."""
        now = time.time()
        if now >= self._compile_grace_until:
            # inside grace a first-time shape may legitimately be compiling
            # for minutes — cadence gaps there are not stalls
            busy = sum(1 for s in self._slots if s is not None)
            self._anomaly.signal(
                "decode_stall",
                gap_s=now - self._last_round_ts,
                ema_s=self._sched.decode_round_s,
                busy=busy,
            )
        self._anomaly.signal("paged_leak", leak_count=self._paging.leak_count())

    def flight_stats(self) -> dict[str, Any]:
        """Flight-recorder observability block (engines_info + dashboard):
        ring health, anomaly dump counts, watchdog transition counts, and
        the compile ledger's summary."""
        rec = self._flight.stats()
        with self.stats_lock:
            transitions = dict(self.watchdog_transitions)
        return {
            "enabled": 1.0 if self._flight.enabled else 0.0,
            "events_total": float(rec["events_total"]),
            "dropped_events": float(rec["dropped_events"]),
            "dumps": float(rec["dumps"]),
            "last_dump_path": rec["last_dump_path"],
            "anomaly": self._anomaly.stats(),
            "watchdog_transitions": transitions,
            "compile": self._ledger.stats(),
        }

    def anomaly_history(self, limit: int = 20) -> list[dict[str, Any]]:
        return self._anomaly.history(limit)

    def observe_stream_write(self, t_put: float) -> None:
        """The HTTP handler has written the SSE frame of the text event this
        engine put at time.monotonic() `t_put` (the event's `t`): one
        `stream_lag` sample, the handler's share of a reader's gap."""
        self._perf.observe_sample("stream_lag", time.monotonic() - t_put)

    def perf_stats(self) -> dict[str, Any]:
        """Perf-observatory block (/v1/debug/perf, engines_info, benchmark/):
        ITL percentiles, goodput split, sampled per-phase host/device/wait
        attribution, the four-layout roofline and the account of rounds
        (`rounds`: every round by step program, the device seconds of those
        that could tell them, the stalls of the in-flight queue by the loop's
        phase, telemetry/perf.py:RoundAccount), with admission's account
        (`admit`: sums over the admit programs dispatched, `programs`,
        `prompts`, `rows_padded`, `true_tokens`, `padded_tokens`, `queued_sum`
        = requests left in the queue behind each, `by_shape`, `held_by` = why
        a batch closed; the admissions read from the in-flight queue, `reads`,
        of them `reads_blocked` still had to wait for the device and
        `reads_at_once` were read where they were dispatched; `vacancy` = a
        slot's empty time by owner, telemetry/perf.py:AdmitAccount), and for a
        configuration with recurrent
        layers the state pool's block (`state_pool`), for one whose step
        programs count the expert layer's work that block (`experts`), for one
        whose decode rounds read an int8 cache through the blocked attention
        arm what that arm streams (`decode_attn`). Read-only over the observatory's own
        lock, so safe from any thread."""
        out = {**self._perf.stats(), "admit": self._adm.stats()}
        if self._state_pool is not None:
            # the recurrent state pool's book: bytes, slots alive (seated or
            # mid-prefill), features off
            live = sum(s is not None for s in self._slots) + len(self._prefills)
            out["state_pool"] = self._state_pool.stats(live)
        if self._experts is not None:
            out["experts"] = self._experts.stats()
        if self._attn_stream is not None:
            out["decode_attn"] = self._attn_stream.stats()
            if self._win_stream is not None:  # the window layers' arm, by its own book
                out["decode_attn"]["window"] = self._win_stream.stats()
        out["kv_kinds"] = self._kv_kinds()
        return out

    def _kv_kinds(self) -> dict[str, dict[str, int]]:
        """The KV cache by kind of layer: `full` (every position of
        `max_seq_len` a slot) and, where window layers keep rings, `window`
        (`ring_len` positions a slot): layers, bytes, positions held a layer
        and positions live a layer (the seated rows' lengths; a window layer's
        row holds at most its window)."""
        lens = self._lengths[self._lengths < self.max_seq_len].astype(np.int64)
        out = {"full": {
            "layers": self.cfg.n_attn_layers,
            "bytes": pytree_nbytes(self._layout.kv_rows(self._ck, self._cv)),
            "positions": self.max_slots * self.max_seq_len,
            "live_positions": int(lens.sum())}}
        if self._layout.slot_member == "win":
            out["window"] = {
                "layers": self.cfg.n_layers - self.cfg.n_attn_layers,
                "bytes": pytree_nbytes(self._cv["win"]),
                "positions": self.max_slots * self.cfg.ring_len,
                "live_positions": int(np.minimum(lens, self.cfg.sliding_window).sum())}
        return out

    def drain_itl_samples(self) -> list[float]:
        """ITL samples (seconds) since the last drain — engines_info feeds
        them to the llmtpu_itl_seconds histogram exactly once."""
        return self._perf.drain_itl()

    def waterfall_stats(self) -> dict[str, Any]:
        """Latency-waterfall block (/v1/debug/latency + engines_info):
        per-stage percentiles, cumulative stage seconds (the
        llmtpu_latency_stage_seconds delta bridge reads these), and the
        stage-coverage ratio. Lock-guarded inside, safe from any thread."""
        return self._waterfall.stats()

    def waterfall_recent(self, limit: int = 32) -> list[dict[str, Any]]:
        """Most recent per-request waterfall rows (newest last)."""
        return self._waterfall.recent(limit)

    def workload_stats(self) -> dict[str, Any]:
        """Workload-capture block: the process-shared ring's health."""
        return self._workload.stats()

    # -- on-demand profiler capture (/v1/debug/profile) --------------------

    def start_profile(self, steps: int, trace_dir: str = "") -> dict[str, Any]:
        """Arm a jax.profiler capture for the next `steps` engine-loop
        iterations. Callable from any thread (API handler, anomaly dump
        callback); the engine thread performs the actual start/stop so the
        capture brackets real device work. Idempotent while one is armed
        or running."""
        steps = max(1, int(steps))
        d = trace_dir or os.environ.get("TPU_FLIGHT_PROFILE_DIR") or os.path.join(
            tempfile.gettempdir(), "llmtpu-profile"
        )
        if self._profile_left > 0 or self._profile_pending is not None:
            return self.profile_status()
        self._profile_pending = (steps, d)
        self._wake.set()
        return self.profile_status()

    def profile_status(self) -> dict[str, Any]:
        pending = self._profile_pending
        return {
            "active": self._profile_left > 0,
            "steps_left": int(self._profile_left),
            "pending_steps": int(pending[0]) if pending else 0,
            "trace_dir": self._profile_dir or (pending[1] if pending else ""),
        }

    def _profile_tick(self) -> None:
        """Engine-thread-only: start a pending capture, count down a live
        one, stop at zero. jax.profiler failures (unsupported backend, dir
        permissions) disarm quietly — profiling must never take the serve
        loop down."""
        if self._profile_pending is not None:
            steps, d = self._profile_pending
            self._profile_pending = None
            try:
                os.makedirs(d, exist_ok=True)
                jax.profiler.start_trace(d)
            except Exception:
                log.exception("jax.profiler start failed; capture disarmed")
                return
            self._profile_left = steps
            self._profile_dir = d
            self._flight.event("profile", action="start", steps=steps, dir=d)
            log.info("profiler capture started: %d steps -> %s", steps, d)
            return
        if self._profile_left > 0:
            self._profile_left -= 1
            if self._profile_left == 0:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    log.exception("jax.profiler stop failed")
                self._flight.event("profile", action="stop", dir=self._profile_dir)
                log.info("profiler capture finished -> %s", self._profile_dir)

    def _abort_all(self, error: str) -> None:
        """Fail every in-flight request — decoding slots AND mid-prefill
        reservations. Called when the KV cache had to be re-allocated: all
        per-slot state on device is gone."""
        for i, s in enumerate(self._slots):
            if s is not None:
                s.aborted = True
                self._count_error()
                s.req.out.put({"type": "error", "error": error})
                s.req.out.put(_DONE)
                self._free_now(i)
        for slot in list(self._prefills):
            st = self._prefills.pop(slot)
            self._paging.free_slot(slot)
            self._phys_reset(slot)
            self._count_error()
            st.req.out.put({"type": "error", "error": error})
            st.req.out.put(_DONE)
        self._prefill_q.clear()
        if self._pool is not None:
            # offloaded snapshots were waiting on a restore that will never
            # come (their KV rows on device are gone with everyone else's)
            for snap in self._pool.drain():
                self._paging.drop_snap(snap.snap_id)
                s = snap.slot_obj
                if s is None or s.aborted or s.done:
                    continue
                s.aborted = True
                self._count_error()
                s.req.out.put({"type": "error", "error": error})
                s.req.out.put(_DONE)
            self._phys_sweep()

    def _free_slot(self, reserved: set[int] | None = None) -> int | None:
        for i, s in enumerate(self._slots):
            if s is None and i not in self._prefills and (
                reserved is None or i not in reserved
            ):
                fence = self._cooling.get(i)
                if fence is not None:
                    if fence > self._rid_fetched:
                        # an in-flight round dispatched before this slot was
                        # freed may still write its cache rows / token ring
                        # entry — reuse only once every such round is fetched
                        continue
                    del self._cooling[i]
                return i
        return None

    # -- KV pool: preemption with host offload -----------------------------

    def _aging_s(self) -> float:
        """Seconds after which a waiter (queue head or offloaded snapshot)
        overrides priority fairness — bounds starvation in both directions."""
        return RESTORE_AGING_TTFT_MULT * self.target_ttft_ms / 1000.0

    def _preempt_wanted(self) -> bool:
        """Should this iteration preempt a slot for the queue head? Only
        when plain admission cannot proceed (no free slot), a victim exists,
        the pool's rate/host-memory guards pass, and the head either
        outranks the lowest-priority active stream or has aged past the
        TTFT deadline (equal-priority load sheds at the API watermark
        instead of thrashing slots here)."""
        pool = self._pool
        if pool is None or self._admit.empty() or not pool.may_preempt():
            return False
        live = [s for s in self._slots if s is not None and not s.done and not s.aborted]
        if not live or self._free_slot() is not None:
            return False
        try:
            # the engine thread is the queue's only consumer, so peeking the
            # head without popping is stable
            head = self._admit.queue[0]
        except IndexError:
            return False
        min_pri = min(s.req.priority for s in live)
        return head.priority > min_pri or (
            time.time() - head.created_at > self._aging_s()
        )

    def _snapshot_rows(self, b: int, Lb: int, start: int = 0):
        """Host copies of slot b's committed KV rows [start, Lb) — one slice
        per cache tree ("q"+"s" for kv8; k/v last dims differ under MLA but
        the seq axis is ALWAYS axis 3, so the same slice covers every
        layout. start > 0 is the paged private-only snapshot: rows [0, start)
        are a shared prefix whose blocks stay pinned in the paging ledger.

        Physical mode: when the snapshot range overlaps the slot's SHARED
        blocks, their arena rows are stale (the bytes live in the prefix
        pool) — resolve block-by-block through the table and concatenate.
        Private blocks are identity homes, so a private-only snapshot
        (start >= shared tokens) keeps the plain contiguous slice."""
        srcs = None
        bt = self._paging.block_tokens
        if self._phys is not None:
            _, sn = self._paging.table_view(b)
            if sn > 0 and start < sn * bt:
                srcs = self._phys.row_sources(b, -(-Lb // bt))
        return self._dx("snap", int(b), int(Lb), int(start), srcs)

    def _preempt_one(self) -> bool:
        """Offload one victim slot to host memory and free it. The caller
        has DRAINED the pipeline (pending emitted, in-flight fetched), so
        the host mirrors are committed-exact: lengths/last_tok describe
        exactly the KV rows on device and the snapshot rolls back to a
        token-identical resume point."""
        pool = self._pool
        # SLO debt (model zoo tenancy): preemption prefers the slot whose
        # tenant is furthest AHEAD of the worst-served tenant's goodput
        # ratio — surplus, not idleness, picks who gives capacity back.
        # With no tenants the ratio map is empty, every surplus is 0.0,
        # and pick_victim's ordering is byte-identical to the pre-zoo
        # policies (true no-op).
        ratios = self._perf.tenant_goodput_ratios()
        floor_ratio = min(ratios.values()) if ratios else 0.0
        cands = []
        for b, s in enumerate(self._slots):
            if s is None or s.done or s.aborted:
                continue
            cands.append({
                "slot": b,
                "priority": s.req.priority,
                "last_activity": s.last_emit or s.first_token_at,
                "tokens_remaining": max(0, s.req.max_tokens - s.generated),
                "slo_surplus": (
                    ratios.get(s.req.tenant, floor_ratio) - floor_ratio
                    if ratios and s.req.tenant else 0.0
                ),
            })
        victim = pool.pick_victim(cands)
        if victim is None:
            return False
        b = victim["slot"]
        s = self._slots[b]
        L = int(self._lengths[b])
        t0 = time.perf_counter()
        Lb = bucket_len(L, self.max_seq_len)
        # Paged private-only offload: a slot admitted off a prefix hit only
        # snapshots rows [shared_len, Lb) — the shared rows' blocks stay
        # pinned (ids, zero bytes) and restore re-inserts them from the
        # entry's device arrays. shared_len < Lb always holds (a hit is a
        # STRICT prefix and both are pow2), but guard anyway.
        p0 = s.shared_len if (0 < s.shared_len < Lb and s.shared_entry) else 0
        if self._phys is not None and p0 % self._paging.block_tokens:
            # an unaligned boundary's COW tokens live ONLY in this slot's
            # arena (the entry keeps no row copies to rebuild them from) and
            # its pool partial-block is NOT pinned by the parked snapshot —
            # park nothing shared, snapshot the whole bucket instead
            p0 = 0
        pool_rows = self._shared_pool_rows(b, p0)
        k_rows, v_rows = self._snapshot_rows(b, Lb, start=p0)
        dt = time.perf_counter() - t0
        snap_id = self._snap_ctr
        self._snap_ctr += 1
        snap = KVSnapshot(
            req_id=s.req.request_id,
            priority=s.req.priority,
            length=L,
            bucket=Lb,
            last_tok=int(self._last_tok[b]),
            temperature=float(self._temp[b]),
            top_k=int(self._topk[b]),
            top_p=float(self._topp[b]),
            k_rows=k_rows,
            v_rows=v_rows,
            nbytes=pytree_nbytes(k_rows) + pytree_nbytes(v_rows),
            preempted_at=time.time(),
            slot_obj=s,
            snap_id=snap_id,
            shared_len=p0,
            shared_entry=s.shared_entry if p0 else None,
            shared_pool_rows=pool_rows,
        )
        pool.offload(snap, dt)
        # ledger: park the shared pins under snap_id, free the private tail
        # — BEFORE _free_now, whose free_slot would drop the whole table
        self._paging.preempt_slot(b, snap_id)
        # free WITHOUT terminal events: the request is suspended, not dead —
        # its consumer stays blocked in out.get() until restore resumes
        # emission. (Post-drain there are no rounds in flight, so this sets
        # no cooling fence.)
        self._free_now(b)
        if s.req.trace_ctx:
            tracing.get_tracer().record(
                "engine.preempt", snap.preempted_at - dt, snap.preempted_at,
                parent=s.req.trace_ctx,
                attrs={
                    "request_id": s.req.request_id,
                    "slot": b,
                    "kv_tokens": L,
                    "offload_bytes": snap.nbytes,
                    "policy": pool.policy,
                },
            )
        self._flight.event(
            "preempt", trace_id=self._tid(s.req),
            request_id=s.req.request_id[:8], slot=b, kv_tokens=L,
            offload_bytes=snap.nbytes, wall_ms=round(dt * 1e3, 1),
        )
        log.info(
            "preempted slot %d (req %s, %d tokens, %.1f MB) in %.1f ms",
            b, s.req.request_id[:8], L, snap.nbytes / (1 << 20), dt * 1e3,
        )
        return True

    def _restore_pending(self) -> bool:
        """Restore offloaded snapshots into free slots, highest priority /
        longest-preempted first. A queued request of >= priority keeps its
        claim on the next free slot unless the snapshot has aged past the
        TTFT deadline (the mirror of _preempt_wanted's fairness rule)."""
        pool = self._pool
        restored = False
        while pool.has_preempted():
            snap = pool.pop_restore()
            if snap is None:
                break
            s = snap.slot_obj
            if s is None or s.done or s.aborted:
                # terminal events already delivered; drop the rows and the
                # ledger's parked shared pins
                self._paging.drop_snap(snap.snap_id)
                self._phys_sweep()
                continue
            aged = time.time() - snap.preempted_at > self._aging_s()
            head = None
            try:
                head = self._admit.queue[0]
            except IndexError:
                pass
            if head is not None and head.priority >= snap.priority and not aged:
                pool.requeue(snap)
                break
            slot = self._free_slot()
            if slot is None:
                pool.requeue(snap)
                break
            try:
                self._restore_snapshot(slot, snap)
            except Exception as e:
                log.exception("restore of preempted slot failed")
                # contiguous path: the ledger still parks this snap's pins
                # (restore_slot runs only after the device inserts succeed)
                # — release them. Physical path: the pins may already be
                # re-tabled (it pins BEFORE the inserts so the boundary COW
                # lands first) — free the half-built table too.
                self._paging.free_slot(slot)
                self._phys_reset(slot)
                self._paging.drop_snap(snap.snap_id)
                self._phys_sweep()
                s.aborted = True
                self._count_error()
                s.req.out.put({"type": "error", "error": str(e)})
                s.req.out.put(_DONE)
                if self._recover_cache():
                    self._abort_all("kv cache lost in failed restore")
                break
            restored = True
        return restored

    def _restore_snapshot(self, b: int, snap: KVSnapshot) -> None:
        """device_put the snapshot's rows and re-activate its slot. Writing
        the full pow2 bucket is exact: rows in [length, bucket) are dead by
        the committed-lengths invariant, and the first post-restore decode
        round writes the real token's KV at position `length` before any
        read attends there."""
        s = snap.slot_obj
        # latency waterfall: wall spent parked off-slot is its own stage,
        # not decode (clamped into the partition at finish)
        s.preempted_s += max(0.0, time.time() - snap.preempted_at)
        t0 = time.perf_counter()
        ledgered = False
        if snap.shared_len and snap.shared_entry is not None:
            # Paged two-stage restore, private rows at start=shared_len. R
            # is exact, never padded (insert_at_fn docstring: padding would
            # clamp the start). The shared prefix comes back two ways:
            # contiguous entries re-insert their device row copies; PHYSICAL
            # entries re-pin — the rebuilt table row resolves the shared
            # blocks into the prefix pool, zero rows move.
            ent = snap.shared_entry
            if "k" in ent:
                first = self._note_exec_shape("restore", snap.shared_len)
                eid = ent.get("eid")
                if eid is None:  # entry predates the plane (raw test pokes)
                    self._eid_ctr += 1
                    eid = ent["eid"] = self._eid_ctr
                    self._x_prefix[eid] = (ent["k"], ent["v"])
                self._dx("insert", eid, np.asarray([b], dtype=np.int32), 1)
            else:
                # ledger pins FIRST: a migrated-in adopt with an unaligned
                # stored length redoes the boundary COW out of the entry's
                # pool row here, and the private insert below then overwrites
                # that block's tail from snap.k_rows — order matters
                if snap.migrated and snap.shared_key is not None:
                    ops = self._paging.admit_shared(
                        b, snap.shared_key, snap.length
                    )
                else:
                    ops = self._paging.restore_slot(
                        b, snap.snap_id, snap.length
                    )
                self._phys_admit(b, ent, ops)
                ledgered = True
                first = self._note_exec_shape("restore", snap.shared_len)
            R = snap.bucket - snap.shared_len
            first = self._note_exec_shape("restore_at", R) or first
            self._dx(
                "insat", snap.k_rows, snap.v_rows, int(b),
                int(snap.shared_len),
            )
        else:
            # one executable per (bucket, group=1) — same cache as prefix-hit
            # admission, so a restore compiles nothing the serve loop hasn't
            first = self._note_exec_shape("restore", snap.bucket)
            self._dx(
                "insrows", snap.k_rows, snap.v_rows,
                np.asarray([b], dtype=np.int32), 1,
            )
        # device sampling rows + token ring, then host mirrors (the source
        # of truth for recovery), then the table entry
        self._dx(
            "samprow", int(b), float(snap.temperature), int(snap.top_k),
            float(snap.top_p), int(snap.last_tok),
        )
        self._lengths[b] = snap.length
        self._last_tok[b] = snap.last_tok
        self._temp[b] = snap.temperature
        self._topk[b] = snap.top_k
        self._topp[b] = snap.top_p
        self._slots[b] = s
        self._vacant.pop(b, None)  # a restore's vacancy is not booked (_seat)
        # ledger: re-table the parked shared pins + a fresh private tail.
        # A MIGRATED snapshot has no parked pins on this engine — when its
        # shared-prefix key matched our own cache, the blocks pin through
        # the ordinary admit_shared path instead, the same refcount++ a
        # local prefix hit performs (re-pin, never copy).
        if not ledgered:
            if snap.migrated and snap.shared_len and snap.shared_key is not None:
                self._paging.admit_shared(b, snap.shared_key, snap.length)
            else:
                self._paging.restore_slot(b, snap.snap_id, snap.length)
            # a whole-bucket physical restore still re-pins parked shared
            # blocks (forced-unaligned preempts park them) — re-key the row
            self._phys_rebuild(b)
        dt = time.perf_counter() - t0
        if first:
            self._compile_obs(
                "restore", (snap.bucket, snap.shared_len), dt
            )
        if self._pool is not None and not snap.migrated:
            self._pool.note_restored(snap, dt)
        self._flight.event(
            "migrate_in" if snap.migrated else "restore",
            trace_id=self._tid(s.req), request_id=s.req.request_id[:8],
            slot=b, kv_tokens=snap.length, wall_ms=round(dt * 1e3, 1),
        )
        if s.req.trace_ctx:
            now = time.time()
            tracing.get_tracer().record(
                "engine.migrate_in" if snap.migrated else "engine.restore",
                now - dt, now,
                parent=s.req.trace_ctx,
                attrs={
                    "request_id": s.req.request_id,
                    "slot": b,
                    "kv_tokens": snap.length,
                    "preempted_s": round(now - snap.preempted_at, 3),
                    **({"bytes": snap.nbytes} if snap.migrated else {}),
                },
            )
        log.info(
            "restored req %s into slot %d (%d tokens) after %.1f s off-device",
            s.req.request_id[:8], b, snap.length,
            time.time() - snap.preempted_at,
        )

    # -- KV migration: engine-to-engine transfer (migration.py) ------------

    def _host_tree(self, x):
        """Host copy of a cache subtree — dict-aware ({} is the fused int8
        layout's live sentinel, not absence)."""
        if isinstance(x, dict):
            if not x:
                return {}
            return {k: jax.device_get(v) for k, v in x.items()}
        return jax.device_get(x)

    def _shared_pool_rows(self, b: int, p0: int) -> list[int] | None:
        """Pool-row indices backing slot b's shared blocks [0, p0) — read
        from the live table BEFORE preempt/export frees it (the prefix
        entry itself may be LRU-evicted later, taking its id list with it
        while sharer pins keep the rows alive)."""
        if self._phys is None or p0 <= 0:
            return None
        bt = self._paging.block_tokens
        srcs = self._phys.row_sources(b, p0 // bt)
        if any(in_arena for in_arena, _, _ in srcs):
            self._phys.missing_pins += 1  # tripwire: shared block not pooled
            return None
        return [row for _, row, _ in srcs]

    def _wire_item(self, snap: KVSnapshot, source: str) -> dict[str, Any]:
        """Serialize a host-side snapshot into an outbox item. When the
        snapshot is paged private-only, the shared prefix ships as its
        token KEY (the destination re-pins matching blocks out of its own
        prefix cache via admit_shared) plus the entry's rows as a fallback
        for destinations that never saw the prefix. Records the
        engine.migrate_out span + counters."""
        s = snap.slot_obj
        req = s.req
        t0 = time.perf_counter()
        shared_k = shared_v = None
        if snap.shared_len and snap.shared_entry is not None:
            key = snap.shared_entry.get("key")
            if key is None:
                # entry predates the ledger (tests poke entries in raw):
                # fold into a whole-bucket snapshot, nothing to re-pin
                snap.k_rows = migration.merge_shared_rows(
                    self._host_tree(snap.shared_entry["k"]), snap.k_rows
                )
                snap.v_rows = migration.merge_shared_rows(
                    self._host_tree(snap.shared_entry["v"]), snap.v_rows
                )
                snap.shared_len = 0
            elif "k" in snap.shared_entry:
                snap.shared_key = key
                if snap.shared_entry.get("eid") is not None:
                    shared_k, shared_v = self._dx(
                        "pfxexp", snap.shared_entry["eid"]
                    )
                else:  # entry predates the plane (raw test pokes)
                    shared_k = self._host_tree(snap.shared_entry["k"])
                    shared_v = self._host_tree(snap.shared_entry["v"])
            elif snap.shared_pool_rows is not None:
                # PHYSICAL entry: no device row copies exist — the fallback
                # rows gather from the prefix-pool rows captured at snapshot
                # time (still alive: the parked pins / exporting table hold
                # their ledger ids)
                snap.shared_key = key
                shared_k, shared_v = self._dx(
                    "poolexp", list(snap.shared_pool_rows), snap.shared_len
                )
            else:
                # tripwire: physical entry with no resolvable pool rows —
                # ship the key alone; only a destination with a matching
                # cache entry can adopt (others fail the restore cleanly)
                snap.shared_key = key
        header = migration.snapshot_header(snap, req, s)
        payload = migration.encode_payload(
            header,
            {"k": snap.k_rows, "v": snap.v_rows,
             "shared_k": shared_k, "shared_v": shared_v},
        )
        dt = time.perf_counter() - t0
        with self.stats_lock:
            self.migrated_out_total += 1
            self.migrate_out_bytes_total += len(payload)
        self._flight.event(
            "migrate_out", trace_id=self._tid(req),
            request_id=req.request_id[:8], kv_tokens=snap.length,
            wire_bytes=len(payload), source=source,
        )
        if req.trace_ctx:
            now = time.time()
            tracing.get_tracer().record(
                "engine.migrate_out", now - dt, now,
                parent=req.trace_ctx,
                attrs={
                    "request_id": req.request_id,
                    "kv_tokens": snap.length,
                    "bytes": len(payload),
                    "source": source,
                },
            )
        log.info(
            "migrate-out %s: %d tokens, %.1f KB (%s) in %.1f ms",
            req.request_id[:8], snap.length, len(payload) / 1024, source, dt * 1e3,
        )
        return {"payload": payload, "out": req.out, "req_id": req.request_id}

    def _exports_after_prefill(self, req: GenRequest) -> bool:
        """Disaggregated mode: this engine spends the prefill, emits the
        first token and hands the slot to a decode-role peer."""
        return self._migrate_outbox is not None and bool(
            req.migrate_after_prefill or self.migrate_after_prefill
        )

    def _migrate_export_slot(self, b: int, s: _Slot) -> None:
        """Disaggregated-mode export, engine thread, straight after
        activation: the slot's rows [0, P) are committed (the activating
        dispatch was fetched) and no in-flight round touches this slot (it
        was not active when any was dispatched), so the snapshot is
        committed-exact by the same argument as a drained preempt. The
        first token was already emitted from the prefill logits here; the
        destination resumes at position `length` with `last_tok`."""
        L = int(self._lengths[b])
        Lb = bucket_len(L, self.max_seq_len)
        p0 = s.shared_len if (0 < s.shared_len < Lb and s.shared_entry) else 0
        if self._phys is not None and p0 % self._paging.block_tokens:
            p0 = 0  # same unaligned-boundary rule as _preempt_one
        pool_rows = self._shared_pool_rows(b, p0)
        k_rows, v_rows = self._snapshot_rows(b, Lb, start=p0)
        snap = KVSnapshot(
            req_id=s.req.request_id,
            priority=s.req.priority,
            length=L,
            bucket=Lb,
            last_tok=int(self._last_tok[b]),
            temperature=float(self._temp[b]),
            top_k=int(self._topk[b]),
            top_p=float(self._topp[b]),
            k_rows=k_rows,
            v_rows=v_rows,
            nbytes=pytree_nbytes(k_rows) + pytree_nbytes(v_rows),
            preempted_at=time.time(),
            slot_obj=s,
            shared_len=p0,
            shared_entry=s.shared_entry if p0 else None,
            shared_pool_rows=pool_rows,
        )
        item = self._wire_item(snap, source="prefill")
        # free WITHOUT terminal events: the request is handed off, not dead
        # — its consumer stays blocked in out.get() until the destination
        # resumes emission into the same queue
        self._free_now(b)
        self._migrate_outbox.put(item)

    def migrate_export_one(self) -> dict[str, Any] | None:
        """Coordinator-thread drain hook: pop one offloaded snapshot from
        the pool and serialize it for transfer. The snapshot's rows already
        live on host (the preempt path device_get them), so no engine-loop
        coordination is needed — pool pops are atomic, and a parked slot is
        touched by nobody until whoever popped its snapshot restores it."""
        self._note_off("migration")
        if self._migrate_outbox is None or self._pool is None:
            return None
        snap = self._pool.pop_restore()
        if snap is None:
            return None
        s = snap.slot_obj
        if s is None or s.done or s.aborted:
            # terminal events already delivered — drop rows + parked pins
            self._paging.drop_snap(snap.snap_id)
            self._phys_sweep()
            return None
        item = self._wire_item(snap, source="pool")
        # the rows (shared fallback included) ride the wire: release the
        # parked shared pins this engine was holding for the restore that
        # will now happen elsewhere
        self._paging.drop_snap(snap.snap_id)
        self._phys_sweep()
        return item

    def migrate_steal_queued(self) -> GenRequest | None:
        """Coordinator-thread drain hook: pop the oldest queued-but-not-
        admitted request (the one stuck longest behind the long tail). It
        holds no KV — re-homing it is a plain submit on the idle engine,
        with the consumer queue riding along on the request object."""
        if self._migrate_outbox is None:
            return None
        try:
            return self._admit.get_nowait()
        except queue.Empty:
            return None

    def migrate_import(self, payload: bytes, out: "queue.Queue[Any] | None" = None) -> GenRequest:
        """Decode a wire payload and queue its snapshot for restore on the
        engine loop. `out` re-homes an existing consumer queue (local
        transport: the source engine's request keeps streaming from the
        same queue object); None creates a fresh one (transfer RPC: the
        service pumps it back over the response stream). Returns the
        reconstructed request. Raises when migration is off or the payload
        cannot run here — callers error the original consumer."""
        if not self._runs("migration"):
            self._note_off("migration")
            raise RuntimeError(
                f"KV migration is off for {self.cfg.name}: " + (
                    "a moved sequence would leave its recurrent state behind"
                    if self._state_pool is not None else self._layout.without["migration"]))
        if self._migrate_in is None:
            raise RuntimeError("KV migration disabled (TPU_MIGRATE=0)")
        if self._stop_evt.is_set() or self.stalled:
            raise RuntimeError("engine unavailable for migrate-in")
        header, snap = migration.wire_to_snapshot(payload)
        if snap.bucket > self.max_seq_len:
            raise ValueError(
                f"snapshot bucket {snap.bucket} exceeds destination "
                f"max_seq_len {self.max_seq_len}"
            )
        req = GenRequest(
            prompt_ids=[int(t) for t in header["prompt_ids"]],
            max_tokens=int(header["max_tokens"]),
            temperature=snap.temperature,
            top_k=snap.top_k,
            top_p=snap.top_p,
            stop=list(header.get("stop") or []),
            priority=snap.priority,
            request_id=snap.req_id,
            created_at=float(header.get("created_at") or time.time()),
            trace_ctx=header.get("trace_ctx") or "",
            migrations=int(header.get("migrations") or 0) + 1,
            constraint=header.get("constraint"),
            logit_bias=header.get("logit_bias"),
        )
        if out is not None:
            req.out = out
        now = time.time()
        s = _Slot(
            req=req,
            generated=int(header.get("generated") or 0),
            text=header.get("text") or "",
            pending=base64.b64decode(header.get("pending_b64") or ""),
            prompt_len=int(header.get("prompt_len") or len(req.prompt_ids)),
            first_token_at=now,
            last_emit=now,
        )
        snap.slot_obj = s
        # each import is one hop for this request — the ping-pong detector
        # fires when the drain policy shuttles the same KV back and forth
        self._anomaly.signal("migration_pingpong", request_id=req.request_id)
        self._migrate_in.put((snap, header, len(payload), s))
        self._wake.set()
        return req

    def migrate_import_stream(self, payload: bytes) -> Iterator[dict[str, Any]]:
        """Transfer-RPC adapter: import, then yield the resumed request's
        events until terminal — the service streams them back to the source
        host, which pumps them into the original consumer queue."""
        req = self.migrate_import(payload)
        while True:
            evt = req.out.get()
            if evt is _DONE:
                return
            yield evt
            if evt.get("type") == "done":
                return

    def _migrate_restore_pending(self) -> bool:
        """Engine thread: restore migrated-in snapshots into free slots.
        Peek-then-pop — the engine thread is the inbox's only consumer, so
        an item stays queued (not requeued) while no slot is free."""
        restored = False
        while not self._migrate_in.empty():
            slot = self._free_slot()
            if slot is None:
                break
            try:
                snap, header, nbytes, s = self._migrate_in.get_nowait()
            except queue.Empty:
                break
            snap.snap_id = self._snap_ctr
            self._snap_ctr += 1
            if snap.shared_len:
                # paged pin handoff: same key at the same stored length in
                # OUR prefix cache → adopt the local entry; its blocks
                # re-pin (refcount++) through admit_shared in
                # _restore_snapshot instead of copying rows. Otherwise fold
                # the shipped fallback rows into a whole-bucket restore.
                ent = (
                    self._prefix_cache.get(snap.shared_key)
                    if snap.shared_key is not None
                    else None
                )
                if ent is not None and int(ent["P"]) == snap.shared_len:
                    snap.shared_entry = ent
                    self._prefix_cache.move_to_end(snap.shared_key)
                else:
                    try:
                        migration.flatten_to_whole_bucket(snap)
                    except ValueError as e:
                        self._count_error()
                        s.req.out.put({"type": "error", "error": str(e)})
                        s.req.out.put(_DONE)
                        continue
            if self._constrain is not None and (
                header.get("constraint") or header.get("logit_bias")
            ):
                # rebuild the automaton cursor HERE (engine thread — the
                # compile cache is not locked) and replay the consumed ids
                # so masking resumes mid-constraint on this host
                try:
                    s.req.cn = self._constrain.make(
                        header.get("constraint"), header.get("logit_bias")
                    )
                except constrain.GrammarError as e:
                    self._count_error()
                    s.req.out.put(
                        {"type": "error", "error": f"constraint: {e}"}
                    )
                    s.req.out.put(_DONE)
                    continue
                self.cn_requests += 1
                s.req.cn.replay(
                    [int(t) for t in header.get("cn_tokens") or []]
                )
                s.cn = s.req.cn
            try:
                self._restore_snapshot(slot, snap)
            except Exception as e:
                log.exception("migrate-in restore failed")
                self._paging.free_slot(slot)
                self._phys_reset(slot)
                self._paging.drop_snap(snap.snap_id)
                self._phys_sweep()
                s.aborted = True
                self._count_error()
                s.req.out.put({"type": "error", "error": str(e)})
                s.req.out.put(_DONE)
                if self._recover_cache():
                    self._abort_all("kv cache lost in failed migrate-in")
                break
            with self.stats_lock:
                self.migrated_in_total += 1
                self.migrate_in_bytes_total += int(nbytes)
            restored = True
        return restored

    def migration_stats(self) -> dict[str, float]:
        """Cumulative migration counters for engines_info/dashboard —
        {"enabled": 0.0} when TPU_MIGRATE is off (mirrors memory_stats)."""
        if self._migrate_outbox is None:
            return {"enabled": 0.0}
        with self.stats_lock:
            return {
                "enabled": 1.0,
                "migrated_out_total": float(self.migrated_out_total),
                "migrated_in_total": float(self.migrated_in_total),
                "migrate_out_bytes_total": float(self.migrate_out_bytes_total),
                "migrate_in_bytes_total": float(self.migrate_in_bytes_total),
                "outbox_depth": float(self._migrate_outbox.qsize()),
                "inbox_depth": float(self._migrate_in.qsize()),
            }

    def _run(self) -> None:
        """Pipelined serving loop. Whatever the loop dispatches (decode
        rounds, batched admissions) joins ONE queue in device order, and the
        engine thread only ever blocks on the OLDEST item of it: a round is
        fetched, an admission's first tokens are read. The host's work —
        token emission (tokenizer + queue puts, the dominant host cost at
        8B B=80), admissions, prefill staging — overlaps the device's
        compute instead of serializing with it (measured: the serialized
        loop idled the chip down to ~2.0k tok/s against a 4.8k raw decode
        loop; the reference never faces this — Ollama owns its hot loop),
        and a round that has ended is never left waiting behind a read of
        something queued after it.

        Order within one iteration:
          1. who needs committed history drains the queue first (`drain`):
             a preemption, a speculative verify round (which then replaces
             steps 2-6)
          2. constrained slots run their synchronous masked round
          3. stage a prefill chunk group under the token-budget scheduler's
             budget (scheduler.py — bounded so the group costs ~one decode
             round of device time), and dispatch round N FUSED with it
             (fused_step_fn: decode never stalls behind prefill; with no
             active decode rows the group runs standalone, back-to-back);
             advance chunk progress and activate finished prompts
          4. emit the round fetched last iteration (overlapped with the
             device's time on the rounds in flight)
          5. read the admissions that are now the oldest items in flight:
             every round before them is fetched AND emitted, so a first
             token goes out on time and before its slot's first round
          6. admit: dispatch the queue's next batches (_start_batch seats
             their rows, so round N+1 carries them) — behind everything in
             flight, read at step 5 of a later iteration; a batch that needs
             its tokens' value at once is read where it is dispatched
          7. once `pipeline_depth` ROUNDS are in flight (or the batch went
             idle), block on the oldest items up to and including the oldest
             round: its fast finish-scan frees finishing slots and advances
             host mirrors (emission itself is step 4 of the next iteration)
        """
        tracing.name_os_thread("gen-engine")  # its line in a profiler trace
        pending: _PendingRound | None = None
        inflight = self._inflight
        K = self.decode_chunk
        S = self.max_seq_len
        # wall-clock budget per loop phase: where an engine-loop second
        # actually goes (phase_budget())
        phase = self._phase_s
        # the same vocabulary on the profiler's host plane, beside the
        # device's lines: a device gap is named after the phase that covers
        # it. With no profiler session an annotation is a flag test.
        span = {k: f"engine.{k}" for k in phase}

        def timed(key, fn, *a, rid=0, prog=""):
            t0 = time.perf_counter()
            # fetch and emit are handed their round; dispatch says which
            # one it is about to make, and of which step program (the
            # account's key: perf_stats()["rounds"]["by_program"])
            rid = rid or (getattr(a[0], "rid", 0) if a else 0)
            args = {"rid": rid, "prog": prog} if prog else {"rid": rid} if rid else {}
            try:
                with TraceAnnotation(span[key], **args):
                    return fn(*a)
            finally:
                phase[key] += time.perf_counter() - t0

        def drain_failed(e: Exception, also: list[int] = ()) -> None:
            # a poisoned item invalidates every LATER in-flight item too
            # (they consumed the same donated buffer chain): fail all of
            # their live slots, a round's rows and an admission's alike —
            # plus `also` (the active set of a dispatch that raised BEFORE
            # entering the deque: without it those slots would stay active,
            # re-dispatch, and re-raise forever while their consumers hang)
            # — drop the items, recover the cache
            slots: set[int] = {b for b in also if self._slots[b] is not None}
            while inflight:
                d = inflight.popleft()
                slots.update(
                    b for b, s, _ in d.entries if self._slots[b] is s
                )
            self._rid_fetched = self._rid_dispatched  # nothing left in flight
            self._fail_round(sorted(slots), e)

        def emit_pending() -> None:
            nonlocal pending
            if pending is not None:
                timed("emit", self._emit_round, pending)
                pending = None

        def retire_oldest() -> bool:
            """Block on the oldest item in flight, which every caller has
            made sure follows an EMITTED round: an admission's first tokens
            are read and go out, a round is fetched into `pending`. False
            when the item was poisoned (drain_failed has answered everyone
            in flight)."""
            nonlocal pending
            item = inflight.popleft()
            try:
                if isinstance(item, _DispatchedAdmit):
                    timed("admit", self._read_admit, item)
                else:
                    pending = timed("fetch", self._complete_round, item)
            except Exception as e:  # poisoned execution surfaces at the read
                inflight.appendleft(item)  # drain fails its slots too
                drain_failed(e)
                return False
            return True

        def drain() -> bool:
            """Commit everything in flight, in device order: who needs
            committed history (host mirrors exact, every first token read)
            calls this. False when an item was poisoned."""
            emit_pending()
            while inflight:
                if not retire_oldest():
                    return False
                emit_pending()
            return True

        while not self._stop_evt.is_set():
            # watchdog stamp: idle loops iterate (the _wake wait times out),
            # so staleness only accrues while a device call blocks. A
            # resuming loop clears the stall flag itself — waiting for the
            # watchdog's next poll (up to 30 s) would keep rejecting
            # submits from an engine that is demonstrably serving again.
            self.last_progress = time.time()
            self._backend.idle()  # liveness beacon while the queue is quiet
            if self.stalled:
                self.stalled = False
                self._watchdog_transition("recovered")
                log.warning("engine loop resumed; clearing stall flag")
            if self._profile_pending is not None or self._profile_left > 0:
                self._profile_tick()
            if self._pool is not None and self._preempt_wanted():
                # Preemption needs committed-exact host mirrors: lengths
                # advance optimistically at dispatch and last_tok updates at
                # the fetch (a round's) or the read (an admission's), so
                # drain the queue first, before snapshotting the victim's
                # rows.
                if drain() and self._preempt_wanted():
                    # re-check: the drain may have finished slots, making a
                    # free slot appear without any eviction
                    self._preempt_one()
            # dispatchable = active rows whose next K writes still fit. Rows
            # at the cap wait (un-dispatched) for their in-flight round's
            # fetch, where the fast-scan cap rule finishes them.
            active = [
                i for i, s in enumerate(self._slots)
                if s is not None and self._lengths[i] + K <= S
            ]
            # Constrained slots leave the pipelined path entirely: their
            # next mask depends on their previous token, so each round is
            # synchronous and committed-exact (_cn_round — masked verify
            # when drafts compose, masked single step otherwise). They are
            # never in `inflight`, so no drain is needed here, and they
            # must never leak into the UNMASKED spec rounds below.
            cn_active = [i for i in active if self._slots[i].cn is not None]
            active = [i for i in active if self._slots[i].cn is None]
            if cn_active:
                try:
                    timed("dispatch", self._cn_round, cn_active)
                except Exception as e:
                    # cn jits donate the cache chain like decode rounds: a
                    # poisoned dispatch invalidates in-flight rounds too
                    emit_pending()
                    drain_failed(e, also=cn_active)
            if self._verify_fn is not None and active:
                if self._spec_cooldown > 0:
                    self._spec_cooldown -= 1
                elif self._stage_spec(active) is not None:
                    # Speculative verify round (majority of active slots have
                    # an n-gram draft). Acceptance is data-dependent, so the
                    # optimistic-length pipelining contract doesn't hold:
                    # drain the queue (emitting in device order — drafts
                    # must continue the COMMITTED history) and run the
                    # verify synchronously. Iterations without a draft
                    # majority leave the pipelined path untouched.
                    if drain():
                        # re-draft against the post-drain history (slots may
                        # have finished; tokens arrived). Constrained slots
                        # stay filtered out — they already ran their masked
                        # round above and must not join an unmasked verify.
                        active = [
                            i for i, s in enumerate(self._slots)
                            if s is not None and self._lengths[i] + K <= S
                            and s.cn is None
                        ]
                        entries = self._stage_spec(active) if active else None
                        if entries is not None:
                            # verify tokens count against the round's prefill
                            # token budget like prefill chunks (scheduler.py)
                            reserved = sum(1 + len(d) for _, d in entries)
                            group = timed(
                                "prefill", self._stage_prefill_group,
                                len(active), reserved,
                            )
                            try:
                                timed("dispatch", self._spec_round, entries)
                            except Exception as e:
                                if group is not None:
                                    self._fail_prefill_group(group, e)
                                    group = None
                                drain_failed(e, also=active)
                            else:
                                if group is not None:
                                    timed("prefill",
                                          self._dispatch_prefill_group, group)
                            timed("admit", self._admit_pending)
                            continue
            # Token-budget scheduling (see scheduler.py): stage up to
            # `prefill_token_budget` prompt tokens from mid-prefill slots,
            # then FUSE the chunk group into the decode dispatch — decode
            # cadence never stalls behind a prefill backlog, and the group's
            # device time is capped at ~one decode round by construction.
            group = timed("prefill", self._stage_prefill_group, len(active))
            if group is not None and active and self._block:
                # no round of a block configuration carries a chunk group
                # (memory.BLOCK_OFF): it runs as a program of its own, here,
                # between two block rounds
                self._note_off("fused_round")
                timed("prefill", self._dispatch_prefill_group, group)
                group = None
            # Whole prompts ride a full-batch round's first step (a weight
            # pass shared with the decode rows, mixed_round_fn) where the
            # configuration allows: staged HERE, before the dispatch, so the
            # round of this iteration carries them; what may not ride, and
            # every admission while nothing decodes or the round is compact
            # or carries a chunk group, takes admit_fn below as ever.
            riding = self._round_carries(len(active), group)
            ride = timed("admit", self._stage_ride) if riding else None
            if active:
                try:
                    # tokens come from the device ring, lengths advance
                    # optimistically — this dispatch does NOT wait for any
                    # earlier round's fetch (decode_chunk_fn docstring)
                    disp = timed("dispatch", self._dispatch_decode, active, group,
                                 *(() if ride is None else (ride,)),
                                 rid=self._rid_dispatched + 1,
                                 prog=self._round_prog(group, ride))
                    if ride is not None:
                        # its first tokens are the round's own output: read
                        # before the round, so they go out a fetch earlier
                        inflight.append(ride.adm)
                    inflight.append(disp)
                except Exception as e:  # a poisoned dispatch must not kill the loop
                    # deliver already-fetched tokens BEFORE the error events
                    # — _fail_round marks these same slot objects aborted,
                    # which would silently drop up to K computed tokens per
                    # stream
                    emit_pending()
                    if group is not None:
                        self._fail_prefill_group(group, e)
                        group = None
                    if ride is not None:
                        self._fail_ride(ride, e)
                    drain_failed(e, also=active)
                else:
                    if group is not None:
                        # advance chunk progress + activate finished prompts
                        # (samples from the fused round's prefill logits)
                        timed("prefill", self._finish_prefill_group, group)
            elif group is not None:
                # pure-prefill window: nothing decoding, so the group runs as
                # a standalone chunk dispatch — back-to-back, no wall pacing
                # (the stale-budget alternation this replaces paced cold
                # bursts in arbitrary 50 ms slices)
                timed("prefill", self._dispatch_prefill_group, group)
            emit_pending()
            # admissions that are now the oldest items in flight: every
            # round dispatched before them is fetched and emitted, and they
            # are ready or one admit program away
            while inflight and isinstance(inflight[0], _DispatchedAdmit):
                if not retire_oldest():
                    break
            admitted = timed("admit", self._admit_pending, riding) or ride is not None
            # block on the OLDEST round only once the pipeline is full (or
            # the batch went idle): up to pipeline_depth rounds chain on
            # device without a host sync, so the fetch and the host's work
            # on it overlap compute instead of serializing with it. With
            # nothing decoding, an admission dispatched just now is the
            # oldest item and is read here: an idle engine's first token
            # does not wait for an iteration.
            rounds = sum(isinstance(d, _DispatchedRound) for d in inflight)
            if inflight and (rounds >= self.pipeline_depth or not active):
                while inflight and pending is None:
                    if not retire_oldest():
                        break
            elif not (active or cn_active or admitted or group is not None
                      or inflight):
                t_idle = time.perf_counter()
                self._perf.rounds.unchain()  # nothing in flight, nothing to dispatch
                with TraceAnnotation(span["idle"]):
                    self._wake.wait(timeout=0.05)
                self._wake.clear()
                phase["idle"] += time.perf_counter() - t_idle
        # flush the deferred emission (consumers of slots the fast-scan
        # already freed would otherwise never see their done event), then
        # read and emit what was still in flight at shutdown: their
        # consumers' streams end cleanly instead of hanging mid-queue
        drain()
        if self.dead:
            # dead-on-poison: fail live slots and everything still queued —
            # their consumers must not hang on a loop that will never
            # iterate again
            self._abort_all(f"engine dead: {self.dead}")
            while True:
                try:
                    req = self._admit.get_nowait()
                except queue.Empty:
                    break
                req.out.put(
                    {"type": "error", "error": f"engine dead: {self.dead}"}
                )
                req.out.put(_DONE)
        # release the followers: replay ends exactly where the leader's
        # scheduling loop ends (clean shutdown and dead engine alike)
        self._backend.stop()

    def _fail_round(self, slots: list[int], e: Exception) -> None:
        log.exception("decode round failed; failing %d active slots", len(slots))
        for b in slots:
            s = self._slots[b]
            if s is not None:
                s.aborted = True
                self._count_error()
                s.req.out.put({"type": "error", "error": str(e)})
                s.req.out.put(_DONE)
                self._free_now(b)
        if self._recover_cache():
            # mid-prefill KV lives in the same buffers
            self._abort_all("kv cache lost in failed decode round")

    def _cn_attach(self, req: GenRequest) -> bool:
        """Compile the request's constraint (and/or logit_bias) into the
        per-slot automaton cursor on ``req.cn``. Compilation is host-only
        and LRU-cached by schema hash; a bad spec errors the request here
        (the API already 400s well-formed-but-unsupported specs, this is
        the engine-side backstop). Returns False when the request died."""
        if self._block_book is not None and (req.constraint or req.logit_bias):
            # a block's positions unmask in any order: no automaton masks them
            # (memory.BLOCK_OFF); the request is refused, not served unmasked
            self._note_off("constrain")
            self._count_error()
            req.out.put({"type": "error", "error":
                         f"constraint: {self.cfg.name} generates by diffusion over "
                         "blocks; constrained decoding is off for it"})
            req.out.put(_DONE)
            return False
        if self._constrain is None or not (req.constraint or req.logit_bias):
            return True
        before = self._constrain.stats_d["misses"]
        t0 = time.perf_counter()
        try:
            req.cn = self._constrain.make(req.constraint, req.logit_bias)
        except constrain.GrammarError as e:
            self._count_error()
            req.out.put({"type": "error", "error": f"constraint: {e}"})
            req.out.put(_DONE)
            return False
        self.cn_requests += 1
        self._flight.event(
            "cn_cmp",
            miss=self._constrain.stats_d["misses"] > before,
            states=req.cn.cc.n_states() if req.cn.cc is not None else 0,
            us=int((time.perf_counter() - t0) * 1e6),
        )
        return True

    def _cn_payload(self, cns: list, n_rows: int):
        """Pack (masks, bias_ids, bias_vals) dispatch operands for a round
        of ``n_rows`` rows where row i serves cursor ``cns[i]`` (None =
        unconstrained). Returns None when nothing is constrained — the op
        closures then call the unmasked executable, so plain traffic never
        traces a masked variant. Pad/unconstrained rows get all-ones masks
        and empty bias (mask-add of 0 over everything = identity)."""
        if not any(cn is not None for cn in cns):
            return None
        t0 = time.perf_counter()
        W = constrain.mask_words(self.cfg.vocab_size)
        NB = self.cn_bias_max
        masks = np.full((n_rows, W), 0xFFFFFFFF, dtype=np.uint32)
        bids = np.full((n_rows, NB), -1, dtype=np.int32)
        bvals = np.zeros((n_rows, NB), dtype=np.float32)
        for i, cn in enumerate(cns):
            if cn is None:
                continue
            masks[i] = cn.mask_row()
            nb = min(len(cn.bias_ids), NB)
            if nb:
                bids[i, :nb] = cn.bias_ids[:nb]
                bvals[i, :nb] = cn.bias_vals[:nb]
        self.cn_mask_s += time.perf_counter() - t0
        return masks, bids, bvals

    def _pop_request(self) -> tuple[GenRequest, list[int]] | None:
        """The queue's next request that wants a slot, with its prompt cut to
        what the cache holds; None when the queue is empty. A request that
        asks for no tokens is answered here."""
        while True:
            try:
                req = self._admit.get_nowait()
            except queue.Empty:
                return None
            req.admitted_at = time.time()
            ids = req.prompt_ids
            # Leave room for at least one decode chunk after the prompt.
            max_prompt = self.max_seq_len - self.decode_chunk
            if len(ids) > max_prompt:  # keep the tail (left-truncation)
                ids = ids[-max_prompt:]
            if req.max_tokens > 0:
                return req, list(ids)
            req.out.put(
                {
                    "type": "done",
                    "finish_reason": "length",
                    "usage": {
                        "prompt_tokens": len(ids),
                        "completion_tokens": 0,
                        "total_tokens": len(ids),
                    },
                    "ttft_ms": 0.0,
                }
            )
            req.out.put(_DONE)

    def _push_back(self, req: GenRequest) -> None:
        """A popped request leads the queue again (its order is kept)."""
        with self._admit.mutex:
            self._admit.queue.appendleft(req)

    def _admit_pending(self, riding: bool = False) -> bool:
        """Admit what the queue holds, each batch of whole prompts as an admit
        program of its own. `riding`: rows are decoding at a full batch and
        this configuration's admissions ride the decode rounds (_stage_ride),
        so a request that may ride stays queued for the next round's staging
        and only what may not is admitted here."""
        admitted = False
        if self._migrate_in is not None and not self._migrate_in.empty():
            # migrated-in snapshots re-enter first: their prefill was spent
            # on another engine and their consumers have been waiting since
            admitted = self._migrate_restore_pending() or admitted
        if not self._prefix_rpc_in.empty():
            # parked prefix_fetch work (export gathers / import uploads):
            # serviced here because only the engine thread may touch the
            # prefix cache and dispatch against the device pool
            self._drain_prefix_rpc()
        if self._pool is not None and self._pool.has_preempted():
            # offloaded snapshots re-enter ahead of the queue (subject to
            # the fairness/aging rule inside) — they already spent their
            # prefill and hold committed tokens
            admitted = self._restore_pending() or admitted
        while True:
            batch: list[tuple[int, GenRequest, list[int]]] = []
            # prefix-cache hits grouped by entry: one fused row-copy
            # dispatch serves the whole group
            hits: dict[int, tuple[dict, list]] = {}
            reserved: set[int] = set()
            held_by = "admit_batch"  # why the batch closed: it was full, or
            while len(batch) < self.admit_batch:
                slot = self._free_slot(reserved)
                if slot is None:
                    if not self._admit.empty():
                        # a request waits and no slot is free: where a pool
                        # with host offload would weigh a preemption
                        self._note_off("offload")
                    held_by = "no_slot"
                    break
                nxt = self._pop_request()
                if nxt is None:
                    held_by = "queue_empty"
                    break
                req, ids = nxt
                if riding and self._may_ride(req, ids):
                    self._push_back(req)  # the next round carries it
                    held_by = "rides"
                    break
                if batch and self._over_admit_budget(batch, ids):
                    # with this prompt the program would pad to more tokens
                    # than may stand between two decode rounds: it leads the
                    # next program instead (the queue's order is kept)
                    self._push_back(req)
                    held_by = "budget"
                    break
                admitted = True
                if not self._cn_attach(req):
                    continue  # bad constraint spec: request already errored
                ent = self._match_prefix(ids)
                if ent is not None:
                    # cached prefix: copy its KV rows, chunk-prefill only
                    # the suffix (works for any suffix length — the chunked
                    # machinery is ragged-safe)
                    reserved.add(slot)
                    hits.setdefault(id(ent), (ent, []))[1].append(
                        (slot, req, list(ids))
                    )
                    continue
                if self.sp == 1 and self.prefill_chunk and len(ids) > self.prefill_chunk:
                    # Long prompt: reserve the slot and prefill chunk-by-chunk
                    # under the token-budget scheduler, fused into decode
                    # rounds (no head-of-line blocking of in-flight streams).
                    # sp>1 keeps whole-prompt prefill: the sp axis bounds
                    # per-chip work.
                    # (a block configuration prefills whole blocks alone: the
                    # prompt's last P mod L tokens start its first block, `tail`)
                    cut = self._whole_blocks(len(ids))
                    self._prefills[slot] = _PrefillState(
                        req=req, ids=list(ids[:cut]), tail=list(ids[cut:]))
                    self._prefill_q.append(slot)
                    # ledger: reserve the prompt's blocks for the whole
                    # chunked prefill (the rows are written incrementally
                    # but the commitment is made now)
                    self._paging.admit_slot(slot, len(ids))
                    continue
                reserved.add(slot)
                batch.append((slot, req, list(ids)))
            for ent, group in hits.values():
                try:
                    self._start_cached(ent, group)
                except Exception as e:
                    log.exception("prefix-cache admission failed")
                    for slot, req, _ in group:
                        self._prefills.pop(slot, None)
                        self._paging.free_slot(slot)
                        self._phys_reset(slot)
                        try:
                            self._prefill_q.remove(slot)
                        except ValueError:
                            pass
                        self._count_error()
                        req.out.put({"type": "error", "error": str(e)})
                        req.out.put(_DONE)
                    if self._recover_cache():
                        self._abort_all("kv cache lost in failed prefix admission")
            if not batch:
                if hits:
                    continue  # hit slots consumed; more queue may admit
                break
            try:
                adm = self._start_batch(batch, held_by)
                if any(
                    req.cn is not None or self._exports_after_prefill(req)
                    for _, req, _ in batch
                ):
                    # the batch needs its first tokens' VALUE before the loop
                    # goes on: a constrained request's automaton cursor must
                    # stand on tok0 before _cn_round masks its next token,
                    # and a prefill-role engine hands over rows that no
                    # decode round may have touched
                    self._read_admit(adm, at_once=True)
                else:
                    self._inflight.append(adm)
            except Exception as e:  # malformed batch must not kill the loop
                log.exception("prefill failed")
                for slot, req, _ in batch:
                    # rows activated before the failure hold live slots whose
                    # consumers are about to get the error — free them so the
                    # continuous batch doesn't decode into dead queues
                    s = self._slots[slot]
                    if s is not None and s.req is req:
                        self._free_now(slot)
                    self._count_error()
                    req.out.put({"type": "error", "error": str(e)})
                    req.out.put(_DONE)
                if self._recover_cache():
                    self._abort_all("kv cache lost in failed prefill")
            if len(batch) < self.admit_batch:
                break  # admit queue drained (or its head rides the next round)
        return admitted

    # The packed-token sizes of a mixed round's prompt buffer, one executable
    # each: a batch takes the smallest that holds its prompts (_stage_ride: a
    # rung's first dispatch apart), and the largest is the most prompt tokens
    # one round carries.
    RIDE_RUNGS = (128, 256)

    def _ride_off(self) -> str:
        """Why this configuration's admissions never ride a decode round
        (`mixed_round_fn`), "" where they may: `other` (the decode step is
        neither `_decode_step_q8` nor `hybrid_decode_step` on one chip with the
        int8 cache: a mesh, a bf16 or latent cache, the XLA path, routed experts
        or sliding windows in the dense family, a cache shorter than a rung; a
        configuration that generates by diffusion over blocks, whose round is a
        block round with no step for a prompt to ride: memory.BLOCK_OFF)."""
        return self._ride_why

    def _round_carries(self, nact: int, group: _PrefillGroup | None) -> bool:
        """Whether the round about to be dispatched for `nact` rows may carry
        the queue's next whole prompts; notes what stands against it for the
        admit programs this iteration takes (_own_reason). A full batch
        (_dispatch_decode's compaction rule) with no chunk group in it and no
        snapshot waiting to re-enter ahead of the queue: _admit_pending knows
        their order, and a preempted one yields to the queue's head."""
        B = self.max_slots
        if not nact:
            self._ride_state = "no active rows"
        elif self.decode_compact and pow2_bucket(nact, B, floor=min(8, B)) != B:
            self._ride_state = "compact"
        else:
            self._ride_state = "other"
            return (
                group is None and not self._ride_off()
                and (self._migrate_in is None or self._migrate_in.empty())
                and not (self._pool is not None and self._pool.has_preempted())
            )
        return False

    def _may_ride(self, req: GenRequest, ids: list[int]) -> bool:
        """Whether this request's whole prompt may ride a decode round: its
        first token's value is not needed at once (no constraint, no hand-over
        after the prefill), it fits the largest rung whole, and no cached
        prefix serves it."""
        if self._constrain is not None and (req.constraint or req.logit_bias):
            return False
        if self._exports_after_prefill(req) or self._ride_len(ids) > self._ride_rungs[-1]:
            return False
        if self.prefill_chunk and len(ids) > self.prefill_chunk:
            return False
        return self._match_prefix(ids, count=False) is None

    def _ride_len(self, ids: list[int]) -> int:
        """The positions a riding prompt takes of a rung: its tokens, up to
        the next multiple of `_ride_align`."""
        return -(-len(ids) // self._ride_align) * self._ride_align

    def _stage_ride(self) -> _Ride | None:
        """Stage the queue's next whole prompts to ride the full-batch round
        about to be dispatched: up to `admit_batch` of them, as many as fit
        the largest rung (the rest lead the next round's batch; the queue's
        order is kept), each from a multiple of `_ride_align` on, the
        positions between them padding. None when nothing may ride now."""
        cap = self._ride_rungs[-1]
        batch: list[tuple[int, GenRequest, list[int]]] = []
        reserved: set[int] = set()
        total = 0
        held_by = "admit_batch"
        while len(batch) < self.admit_batch:
            slot = self._free_slot(reserved)
            if slot is None:
                held_by = "no_slot"
                break
            nxt = self._pop_request()
            if nxt is None:
                held_by = "queue_empty"
                break
            req, ids = nxt
            if not self._may_ride(req, ids):
                self._push_back(req)  # an admit program of its own (_admit_pending)
                held_by = "own"
                break
            if total + self._ride_len(ids) > cap:
                self._push_back(req)
                held_by = "budget"
                break
            self.prefix_cache_misses += bool(self._prefix_budget and self._prefix_cache)
            total += self._ride_len(ids)
            reserved.add(slot)
            batch.append((slot, req, ids))
        if not batch:
            return None
        # the smallest rung that holds the batch; but a rung never dispatched
        # yet takes the first batch it holds, the largest first, so the first
        # two rides first-dispatch both executables (seconds each) right away
        # and not whenever traffic first packs more than the small rung holds
        fits = [r for r in self._ride_rungs if r >= total]
        phys = self._phys is not None
        cold = [r for r in fits if ("mixed", r, phys) not in self._seen_exec_shapes]
        T = cold[-1] if cold else fits[0]
        R = self._ride_rows
        tokens = np.zeros((T,), dtype=np.int32)
        rowids = np.full((T,), R, dtype=np.int32)
        positions = np.full((T,), self.max_seq_len, dtype=np.int32)
        ipack = np.zeros((3 * R + 2,), dtype=np.int32)
        fpack = np.zeros((2 * R,), dtype=np.float32)
        fpack[R:] = 1.0  # top_p
        at = 0
        for i, (slot, req, ids) in enumerate(batch):
            n = len(ids)
            tokens[at : at + n] = ids
            rowids[at : at + n] = i
            positions[at : at + n] = np.arange(n)
            ipack[i] = slot
            ipack[R + i] = at + n - 1
            at += self._ride_len(ids)
            ipack[2 * R + i] = req.top_k
            fpack[i] = req.temperature
            fpack[R + i] = req.top_p
        # an unused descriptor row is empty (no token carries its id) and
        # writes nothing; it names the first prompt's slot to stay in bounds
        ipack[len(batch) : R] = batch[0][0]
        ipack[3 * R] = len(batch)
        return _Ride(batch=batch, rung=T, tokens=tokens, rowids=rowids,
                     positions=positions, ipack=ipack, fpack=fpack,
                     held_by=held_by)

    def _fail_ride(self, ride: _Ride, e: Exception) -> None:
        """The round that carried a staged batch failed at its dispatch: its
        requests are answered, and a slot seated before the failure is freed."""
        for slot, req, _ in ride.batch:
            s = self._slots[slot]
            if s is not None and s.req is req:
                self._free_now(slot)
            self._count_error()
            req.out.put({"type": "error", "error": str(e)})
            req.out.put(_DONE)

    def _own_reason(self, batch: list) -> str:
        """Why a batch of whole prompts takes an admit program of its own and
        does not ride a decode round (perf_stats()["admit"]["own"])."""
        off = self._ride_off()
        if off:
            return off
        if any(req.cn is not None or self._exports_after_prefill(req)
               for _, req, _ in batch):
            return "reads at once"
        if any(self._ride_len(ids) > self._ride_rungs[-1] for _, _, ids in batch):
            return "over the cap"
        return self._ride_state

    def _admit_tokens_max(self) -> int:
        """The most tokens (rows x bucket, padding included) one admit program
        of several whole prompts may hold: what a chunked prefill may put
        between two decode rounds, `prefill_chunk`. Every stream waits for the
        program, so its size is the gap's: unbounded, four prompts of the
        longest bucket stood in one gap (admit_batch x prefill_chunk tokens).
        0 = no bound (no chunking, or sp > 1: the sp axis bounds the work)."""
        if self.sp != 1 or not self.prefill_chunk:
            return 0
        return self._bucket(self.prefill_chunk)

    def _over_admit_budget(self, batch: list, ids: list[int]) -> bool:
        """Whether the whole prompt `ids`, joining `batch` (not empty), would
        pad the admit program past `_admit_tokens_max`. A prompt alone is
        always admitted; one longer than `prefill_chunk` is chunked and never
        joins a batch."""
        cap = self._admit_tokens_max()
        if not cap or len(ids) > self.prefill_chunk:
            return False
        rows = 1 << len(batch).bit_length()  # _start_batch's pow2 of len + 1
        longest = max(len(ids), *(len(i) for _, _, i in batch))
        return rows * self._bucket(longest) > cap

    # -- prompt-prefix KV cache --------------------------------------------

    PREFIX_MIN = 32  # shortest prefix worth caching (tokens)

    @staticmethod
    def _common_len(a: tuple, b: tuple) -> int:
        n = min(len(a), len(b))
        i = 0
        while i < n and a[i] == b[i]:
            i += 1
        return i

    def _match_prefix(self, ids: list[int], count: bool = True) -> dict | None:
        """Longest cached entry that is a STRICT prefix of `ids` (at least
        one suffix token must remain — the suffix chunk produces the
        first-sample logits). `count` False only asks: the hit and miss
        counters and the LRU order stay as they are (_may_ride)."""
        self._note_off("prefix_cache")
        if not self._prefix_budget or not self._prefix_cache:
            return None
        t = tuple(ids)
        best_key, best = None, None
        # Stored lengths are pow2-floored (_maybe_store_prefix), so the
        # by-length buckets number O(log S): probe longest-first with one
        # hash lookup each instead of scanning every entry and comparing
        # prefix_len tokens per entry (O(entries × prefix_len) at scale).
        for P in sorted(self._prefix_by_len, reverse=True):
            if P >= len(t):
                continue  # strict prefix: >= 1 suffix token must remain
            e = self._prefix_by_len[P].get(t[:P])
            if e is not None:
                best_key, best = t[:P], e
                break
        if not count:
            return best
        if best is not None:
            self._prefix_cache.move_to_end(best_key)  # LRU touch
            self.prefix_cache_hits += 1
        else:
            self.prefix_cache_misses += 1
        return best

    def _start_cached(self, ent: dict, group: list) -> None:
        """Admit a group of prefix-cache hits: ONE fused dispatch copies the
        entry's KV rows into every slot; the suffixes then ride the ordinary
        chunked-prefill queue (start=P0) and activate as usual."""
        maybe_fail("engine.prefill", f"prefix-hit slots={[s for s, _, _ in group]}")
        key = ent.get("key")
        if "k" in ent:
            # contiguous entries (physical paging off, or raw test pokes):
            # ONE fused dispatch duplicates the rows into every hit slot
            n = len(group)
            nb = 1 << (n - 1).bit_length()
            slots = np.zeros(nb, dtype=np.int32)
            for i, (slot, _, _) in enumerate(group):
                slots[i] = slot
            eid = ent.get("eid")
            if eid is None:
                # entry predates the dispatch plane (tests poke entries in
                # raw): register its device rows locally so the insert op
                # resolves them — never reachable under a live follower
                self._eid_ctr += 1
                eid = ent["eid"] = self._eid_ctr
                self._x_prefix[eid] = (ent["k"], ent["v"])
            self._dx("insert", eid, slots, n)
            self._note_admit("cached", [req for _, req, _ in group], nb, 0, 0)
        for slot, req, ids in group:
            self._prefills[slot] = _PrefillState(
                req=req, ids=list(ids), done=ent["P"],
                shared_entry=ent, shared_len=ent["P"],
            )
            self._prefill_q.append(slot)
            # ledger: pin the entry's blocks (refcount++, zero allocation
            # for the shared prefix), COW the boundary block if the stored
            # length isn't block-aligned, extend privately to the prompt
            if key is not None:
                ops = self._paging.admit_shared(slot, key, len(ids))
                if "k" not in ent:
                    # PHYSICAL hit admission is pin-only: no row copies at
                    # all — the slot's table row resolves the shared blocks
                    # straight into the prefix pool. Only an unaligned
                    # boundary block copies (once, whole-block, _phys_admit).
                    self._phys_admit(slot, ent, ops)
            else:  # entry predates the ledger (tests poke entries in raw)
                self._paging.admit_slot(slot, len(ids))

    def _maybe_store_prefix(self, slot: int, ids: list[int]) -> None:
        """At activation: if this prompt shares a long prefix with recent
        traffic, store that prefix's KV as a device SLICE of the slot's own
        cache rows (positions [0, P0) hold exactly the prompt KV a cold
        prefill computed — valid for any admission path, batch or chunked,
        and never touched again while the slot decodes at positions >= P)."""
        if not self._prefix_budget:
            return
        t = tuple(ids)
        best = 0
        for other in self._recent_prompts:
            if other is not t:
                best = max(best, self._common_len(t, other))
        # identical prompts cap at len-1: a hit must keep >= 1 suffix
        # token (PREFIX_MIN keeps trivial overlaps out)
        p0 = min(best, len(t) - 1)
        if p0 < self.PREFIX_MIN:
            return
        # pow2-FLOOR the stored length: insert_cached_fn compiles one
        # executable per (entry length, group size) — raw P0 would compile
        # per distinct prefix length on the serve loop (every other jit
        # input shape in this engine is bucketed for exactly this reason).
        # Rounding DOWN stays correct (a shorter prefix is still a prefix).
        p0 = 1 << (p0.bit_length() - 1)
        key = t[:p0]
        if key in self._prefix_cache:
            return
        # Single HBM ledger (paging.py): the entry claims blocks from the
        # manager's prefix partition BEFORE storing — evict LRU entries
        # until it fits; a partition too small for the entry ever skips the
        # store. (The byte counter below stays authoritative too: tests
        # shrink _prefix_budget at runtime and expect byte-LRU eviction.)
        while not self._paging.prefix_can_fit(p0) and self._prefix_cache:
            self._evict_lru_prefix()
        if self._paging.prefix_register(key, p0) is None:
            return
        if self._phys is not None:
            # PHYSICAL store: the entry owns pool rows, not row copies —
            # copy the slot's blocks [0, p0) into the pool (gathered through
            # the slot's own table: a sharer's shared blocks live in the
            # pool already, so those copy pool→pool), and record only the
            # byte ACCOUNTING the LRU budget needs. Every sharer then reads
            # the one pool copy through its block table.
            if not self._store_prefix_physical(slot, key, p0):
                self._paging.prefix_release(key)
                self._phys.sweep(self._paging.alive)
                return
            nbytes = sum(
                (x.size // (x.shape[1] * self.max_seq_len)) * p0 * x.dtype.itemsize
                for x in jax.tree.leaves((self._ck, self._cv))
            )
            ent = {"P": p0, "bytes": nbytes, "key": key}
            self._prefix_cache[key] = ent
            self._prefix_by_len.setdefault(p0, {})[key] = ent
            self._prefix_cache_bytes += nbytes
            with self._prefix_pub_lock:
                self._prefix_pub[key] = p0
            while self._prefix_cache_bytes > self._prefix_budget and self._prefix_cache:
                self._evict_lru_prefix()
            log.info(
                "prefix cache: stored %d-token prefix in pool (%.1f MB, %d entries)",
                p0, nbytes / 1e6, len(self._prefix_cache),
            )
            return
        self._eid_ctr += 1
        eid = self._eid_ctr
        pk, pv = self._dx("pfxput", eid, int(slot), p0)
        nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves((pk, pv)))
        ent = {"P": p0, "k": pk, "v": pv, "bytes": nbytes, "key": key, "eid": eid}
        self._prefix_cache[key] = ent
        self._prefix_by_len.setdefault(p0, {})[key] = ent
        self._prefix_cache_bytes += nbytes
        with self._prefix_pub_lock:
            self._prefix_pub[key] = p0
        while self._prefix_cache_bytes > self._prefix_budget and self._prefix_cache:
            self._evict_lru_prefix()
        log.info(
            "prefix cache: stored %d-token prefix (%.1f MB, %d entries)",
            p0, nbytes / 1e6, len(self._prefix_cache),
        )

    def _evict_lru_prefix(self) -> None:
        """Evict the least-recently-used prefix entry: byte counter, ledger
        registration (blocks stay alive while live tables still pin them),
        and the by-length index."""
        old_key, old = self._prefix_cache.popitem(last=False)
        self._prefix_cache_bytes -= old["bytes"]
        if old.get("eid") is not None:
            self._dx("pfxdrop", old["eid"])
        with self._prefix_pub_lock:
            self._prefix_pub.pop(old_key, None)
        self._paging.prefix_release(old.get("key", old_key))
        if self._phys is not None:
            # pool rows free only once the last sharer pin lets the ledger
            # id die — an evicted entry stays READABLE for its sharers
            self._phys.sweep(self._paging.alive)
        bucket_d = self._prefix_by_len.get(old["P"])
        if bucket_d is not None:
            bucket_d.pop(old_key, None)
            if not bucket_d:
                del self._prefix_by_len[old["P"]]

    # -- fleet prefix tier (prefix-locality routing, remote fetch) ---------

    def prefix_chains(self) -> list[tuple[tuple, int]]:
        """Resident prefix chains as ``(token_key, stored_tokens)`` pairs
        — the digest source. Reads the published mirror, safe from any
        thread."""
        with self._prefix_pub_lock:
            return list(self._prefix_pub.items())

    def prefix_digest(self, top_k: int = prefix_fp.DEFAULT_TOP_K) -> dict | None:
        """Compact digest of resident chains for the discovery tag channel
        (routing/prefix.py build_digest), or None when the prefix cache is
        off or empty — absent tag means "nothing to match", exactly like
        kv_headroom's opt-in semantics."""
        if not self._prefix_budget:
            return None
        chains = self.prefix_chains()
        if not chains:
            return None
        return prefix_fp.build_digest(
            chains, self._paging.block_tokens, top_k=top_k
        )

    def prefix_match_len(self, ids: list[int]) -> int:
        """Longest resident chain that is a STRICT prefix of `ids`
        (thread-safe; the fetch path compares this against a peer's claim
        before paying for the wire)."""
        t = tuple(ids)
        best = 0
        with self._prefix_pub_lock:
            for key, n in self._prefix_pub.items():
                if n > best and n < len(t) and key == t[:n]:
                    best = n
        return best

    def prefix_export(self, ids: list[int], timeout_s: float = 30.0) -> bytes | None:
        """Snapshot the longest resident chain prefixing `ids` as a wire
        payload (the `prefix_fetch` RPC's source side); a chain that only
        partially overlaps ships pow2-truncated to the shared prefix.
        Parks the request on the engine thread — only it may touch the
        prefix cache and the device pool — and blocks the caller until
        served. None on miss, disabled cache, or timeout."""
        if not self._prefix_budget:
            return None
        box: dict[str, Any] = {}
        ev = threading.Event()
        self._prefix_rpc_in.put(("export", (list(ids),), box, ev))
        self._wake.set()
        if not ev.wait(timeout_s):
            return None
        return box.get("payload")

    def prefix_export_by_hash(self, hash16: str, timeout_s: float = 30.0) -> bytes | None:
        """Resolve a digest head hash (routing/prefix.py chain_hashes) back
        to the resident chain's token ids and export it — the boot
        warm-fill path: a joining node learns the fleet's hottest chains
        only as digest hashes from discovery tags, never the ids behind
        them, so the ids must be recovered on the side that HAS them."""
        if not self._prefix_budget:
            return None
        want = str(hash16 or "").strip().lower()
        if not want:
            return None
        bt = self._paging.block_tokens
        with self._prefix_pub_lock:
            chains = list(self._prefix_pub.items())
        for key, n in sorted(chains, key=lambda kv: -kv[1]):
            bounds = prefix_fp.chain_hashes(list(key), bt)
            if bounds and bounds[-1][1] == want:
                return self.prefix_export(list(key), timeout_s=timeout_s)
        return None

    def prefix_import(self, payload: bytes, timeout_s: float = 30.0) -> bool:
        """Adopt a peer's exported prefix chain into the local cache (the
        fetch destination side). Decodes on the caller thread (pure host
        work), then parks the insert on the engine thread. After a
        successful import the next admission sees an ordinary prefix-cache
        hit and re-pins via admit_shared — pin-only, zero row copies on
        the physical path."""
        if not self._prefix_budget:
            return False
        try:
            header, trees = migration.decode_payload(payload)
        except Exception:
            with self.stats_lock:
                self.prefix_import_rejects_total += 1
            return False
        if header.get("kind") != "prefix":
            with self.stats_lock:
                self.prefix_import_rejects_total += 1
            return False
        box: dict[str, Any] = {}
        ev = threading.Event()
        self._prefix_rpc_in.put(("import", (header, trees, len(payload)), box, ev))
        self._wake.set()
        if not ev.wait(timeout_s):
            return False
        return bool(box.get("ok"))

    def _drain_prefix_rpc(self) -> None:
        """Engine thread: service parked prefix export/import requests
        (_admit_pending). Failures report through the box — the waiting
        RPC thread owns error semantics."""
        while True:
            try:
                kind, args, box, ev = self._prefix_rpc_in.get_nowait()
            except queue.Empty:
                return
            try:
                if kind == "export":
                    box["payload"] = self._prefix_export_now(*args)
                else:
                    box["ok"] = self._prefix_import_now(*args)
            except Exception as e:  # noqa: BLE001 — must release the waiter
                log.warning("prefix %s failed: %s", kind, e)
                box["error"] = str(e)
            finally:
                ev.set()

    def _prefix_export_now(self, ids: list[int]) -> bytes | None:
        """Gather the longest resident chain prefixing `ids` into a wire
        payload (engine thread). Non-strict match: exporting the whole
        prompt is fine — the REQUESTER enforces its own strict-prefix rule
        against its (longer) prompt. When no whole chain prefixes the
        request, the best chain ships TRUNCATED to the largest pow2
        prefix both sides share: the advertised digest claims matches at
        block granularity (routing/prefix.py chain hashes), so a peer may
        dial on a partial overlap — refusing it here would waste the RPC
        the router already paid for. Pow2 because import only admits pow2
        lengths (one compiled insert per entry length)."""
        if not self._prefix_cache:
            return None
        t = tuple(ids)
        key, ent, P0 = None, None, 0
        for P in sorted(self._prefix_by_len, reverse=True):
            if P > len(t):
                continue
            e = self._prefix_by_len[P].get(t[:P])
            if e is not None:
                key, ent, P0 = t[:P], e, P
                break
        if ent is None:
            for P, bucket in self._prefix_by_len.items():
                for k2, e in bucket.items():
                    c = self._common_len(k2, t)
                    trunc = 1 << (c.bit_length() - 1) if c else 0
                    if trunc >= self.PREFIX_MIN and trunc < P and trunc > P0:
                        key, ent, P0 = k2, e, trunc
        if ent is None:
            return None
        t0 = time.perf_counter()
        if "k" in ent:
            if ent.get("eid") is not None:
                hk, hv = self._dx("pfxexp", ent["eid"])
            else:  # entry predates the plane (tests poke entries in raw)
                hk, hv = self._host_tree(ent["k"]), self._host_tree(ent["v"])
        else:
            lids = self._paging.prefix_ids(key)
            if lids is None or self._phys is None:
                return None
            rows = []
            for lid in lids[: max(1, P0 // self._paging.block_tokens)]:
                prow = self._phys.phys_of(lid)
                if prow is None:
                    self._phys.missing_pins += 1
                    return None
                rows.append(prow - self._phys.pool_base)
            hk, hv = self._dx("poolexp", rows, P0)
        if P0 < int(ent["P"]) and "k" in ent:
            # contiguous entry: token axis is 3 ([L, 1, H, P, *rest]),
            # dict leaves are the fused-int8 live sentinel
            def _cut(x):
                if isinstance(x, dict):
                    return {k: _cut(v) for k, v in x.items()}
                return x[:, :, :, :P0]

            hk, hv = _cut(hk), _cut(hv)
        header = {
            "kind": "prefix",
            "P": P0,
            "ids": [int(x) for x in key[:P0]],
            "block_tokens": self._paging.block_tokens,
        }
        payload = migration.encode_payload(header, {"k": hk, "v": hv})
        self._prefix_cache.move_to_end(key)  # a fetched chain is hot fleet-wide
        with self.stats_lock:
            self.prefix_exports_total += 1
            self.prefix_export_bytes_total += len(payload)
        self._flight.event(
            "prefix_out", tokens=P0, wire_bytes=len(payload),
            wall_ms=round((time.perf_counter() - t0) * 1e3, 1),
        )
        log.info(
            "prefix export: %d tokens, %.1f KB in %.1f ms",
            P0, len(payload) / 1024, (time.perf_counter() - t0) * 1e3,
        )
        return payload

    def _prefix_import_now(self, header: dict, trees: dict, nbytes_wire: int) -> bool:
        """Insert a wire-decoded chain into the local prefix cache (engine
        thread): ledger registration first (evicting LRU entries to fit,
        exactly like a local store), then pool-row uploads on the physical
        path or a device-array entry on the contiguous path."""
        if not self._runs("prefix_cache"):
            # a peer's prefix holds KV rows and no recurrent state (or, for a
            # counted latent pair, bare rows of both members): never here
            self._note_off("prefix_cache")
            with self.stats_lock:
                self.prefix_import_rejects_total += 1
            return False
        P0 = int(header.get("P") or 0)
        ids = [int(x) for x in header.get("ids") or []]
        hk = trees.get("k")
        hv = trees.get("v")
        hv = {} if hv is None else hv
        # Only pow2 lengths insert: _match_prefix probes pow2 buckets and
        # insert_cached compiles per entry length — a peer's entries are
        # pow2 by construction (_maybe_store_prefix), so a violation means
        # a corrupt or foreign payload. Geometry must match the local
        # cache leaf-for-leaf (layers, heads, head dims): a peer running a
        # different model or cache layout never imports.
        if (
            P0 < self.PREFIX_MIN or P0 & (P0 - 1) or len(ids) != P0
            or hk is None
            or not self._prefix_wire_compat(hk, hv)
        ):
            with self.stats_lock:
                self.prefix_import_rejects_total += 1
            return False
        key = tuple(ids)
        if key in self._prefix_cache:
            self._prefix_cache.move_to_end(key)
            return True
        while not self._paging.prefix_can_fit(P0) and self._prefix_cache:
            self._evict_lru_prefix()
        if self._paging.prefix_register(key, P0) is None:
            with self.stats_lock:
                self.prefix_import_rejects_total += 1
            return False
        if self._phys is not None:
            if not self._import_prefix_physical(key, hk, hv):
                self._paging.prefix_release(key)
                self._phys.sweep(self._paging.alive)
                with self.stats_lock:
                    self.prefix_import_rejects_total += 1
                return False
            nbytes = sum(
                (x.size // (x.shape[1] * self.max_seq_len)) * P0 * x.dtype.itemsize
                for x in jax.tree.leaves((self._ck, self._cv))
            )
            ent = {"P": P0, "bytes": nbytes, "key": key}
        else:
            self._eid_ctr += 1
            eid = self._eid_ctr
            pk, pv = self._dx("pfximp", eid, hk, hv if hv is not None else {})
            nbytes = sum(
                x.size * x.dtype.itemsize for x in jax.tree.leaves((pk, pv))
            )
            ent = {"P": P0, "k": pk, "v": pv, "bytes": nbytes, "key": key,
                   "eid": eid}
        self._prefix_cache[key] = ent
        self._prefix_by_len.setdefault(P0, {})[key] = ent
        self._prefix_cache_bytes += nbytes
        with self._prefix_pub_lock:
            self._prefix_pub[key] = P0
        while self._prefix_cache_bytes > self._prefix_budget and self._prefix_cache:
            self._evict_lru_prefix()
        ok = key in self._prefix_cache  # budget smaller than the entry evicts it
        with self.stats_lock:
            if ok:
                self.prefix_imports_total += 1
                self.prefix_import_bytes_total += nbytes_wire
            else:
                self.prefix_import_rejects_total += 1
        if ok:
            self._flight.event("prefix_in", tokens=P0, wire_bytes=nbytes_wire)
            log.info(
                "prefix import: %d tokens, %.1f KB wire (%d entries)",
                P0, nbytes_wire / 1024, len(self._prefix_cache),
            )
        return ok

    def _prefix_wire_compat(self, hk, hv) -> bool:
        """Whether wire-decoded host KV trees match the local cache's
        geometry (same leaf set; same layer, head, and trailing dims) —
        everything except the slot and token axes, which import rewrites."""
        ref = (
            (self._pool_k, self._pool_v) if self._phys is not None
            else (self._ck, self._cv)
        )
        try:
            ref_leaves = jax.tree.leaves(ref)
            host_leaves = jax.tree.leaves((hk, hv))
        except Exception:
            return False
        if len(ref_leaves) != len(host_leaves):
            return False
        for p, h in zip(ref_leaves, host_leaves):
            if (
                h.ndim != p.ndim
                or h.shape[0] != p.shape[0]
                or h.shape[1] != 1
                or h.shape[2] != p.shape[2]
                or h.shape[4:] != p.shape[4:]
            ):
                return False
        return True

    def _import_prefix_physical(self, key: tuple, hk, hv) -> bool:
        """Upload a wire-decoded chain's blocks into fresh prefix-pool
        rows (one block-shaped dispatch per block — same executable for
        every chain length)."""
        lids = self._paging.prefix_ids(key)
        if lids is None:
            return False
        rows = self._phys.register_prefix(lids)
        if rows is None:
            return False
        bt = self._paging.block_tokens
        for j, prow in enumerate(rows):
            first = self._note_exec_shape("pool_put_host")
            t0 = time.perf_counter()
            self._dx(
                "pput", "host",
                _host_block(hk, j * bt, bt), _host_block(hv, j * bt, bt),
                int(prow),
            )
            if first:
                self._compile_obs("pool_put_host", (bt,),
                                  time.perf_counter() - t0)
        return True

    def prefix_tier_stats(self) -> dict[str, float]:
        """Fleet-prefix-tier observability block (engines_info, dashboard,
        /v1/debug/prefix)."""
        with self._prefix_pub_lock:
            chains = len(self._prefix_pub)
            longest = max(self._prefix_pub.values(), default=0)
        with self.stats_lock:
            return {
                "enabled": 1.0 if self._prefix_budget else 0.0,
                "chains": float(chains),
                "longest_tokens": float(longest),
                "exports_total": float(self.prefix_exports_total),
                "export_bytes_total": float(self.prefix_export_bytes_total),
                "imports_total": float(self.prefix_imports_total),
                "import_bytes_total": float(self.prefix_import_bytes_total),
                "import_rejects_total": float(self.prefix_import_rejects_total),
            }

    def _note_admit(
        self, kind: str, reqs: list[GenRequest], rows_padded: int, bucket: int,
        true_tokens: int, held_by: str = "",
    ) -> int:
        """One record an admission dispatched as a program of its own (the
        ring's `admit_prog`, the sums of perf_stats()["admit"]); returns its
        `aid`. `queued` is what the batch left behind in the queue, `held_by`
        why it closed (a batch alone has a reason), `after_rid` its place in
        the device's order: the newest round dispatched before it."""
        queued = self._admit.qsize()
        aid = self._adm.program(
            kind, len(reqs), rows_padded, bucket, true_tokens, queued, held_by
        )
        now = time.monotonic()
        self._flight.event(
            "admit_prog", aid=aid, kind=kind, rows=len(reqs),
            rows_padded=rows_padded, bucket=bucket, true_tokens=true_tokens,
            padded_tokens=rows_padded * bucket, queued=queued,
            held_by=held_by or None,
            wait_ms_max=round(
                max((now - r.arrived_t for r in reqs), default=0.0) * 1e3, 3
            ),
            after_rid=self._rid_dispatched, t=now,
        )
        return aid

    def _start_batch(
        self, batch: list[tuple[int, GenRequest, list[int]]], held_by: str = "",
    ) -> _DispatchedAdmit:
        """Admit up to admit_batch short prompts with ONE batched prefill
        dispatch. At 8B the prompt weight pass dominates admission cost;
        per-request prefill starves admissions badly enough to leave most
        slots idle (measured 102 tok/s at B=64 — vs the decode loop's ~1.9k).

        The dispatch half of an admission: nothing here reads the device.
        The rows are seated (_seat), so the very next decode dispatch carries
        them (admit_fn left their first tokens in the device's token ring);
        the host learns those tokens at _read_admit."""
        A = len(batch)
        Ab = 1 << (A - 1).bit_length()  # pow2 pad: bounded executable count
        bucket = self._bucket(max(len(ids) for _, _, ids in batch))
        tokens = np.zeros((Ab, bucket), dtype=np.int32)
        ipack = np.zeros((3 * Ab + 2,), dtype=np.int32)
        fpack = np.zeros((2 * Ab,), dtype=np.float32)
        ipack[Ab : 2 * Ab] = 1  # dummy rows: 1 harmless token
        fpack[Ab:] = 1.0  # top_p
        L = self._block
        if L:
            # whole blocks alone are prefilled, and nothing is sampled: each
            # slot's first block as it starts rides behind the packed ints
            bucket = self._bucket(max(1, max(self._whole_blocks(len(ids)) for _, _, ids in batch)))
            tokens = np.zeros((Ab, bucket), dtype=np.int32)
            ipack = np.concatenate([ipack, np.full((Ab * L,), self.cfg.mask_token_id, np.int32)])
        for i, (slot, req, ids) in enumerate(batch):
            whole = self._whole_blocks(len(ids))
            tokens[i, :whole] = ids[:whole]
            ipack[i] = slot
            ipack[Ab + i] = whole
            ipack[2 * Ab + i] = req.top_k
            fpack[i] = req.temperature
            fpack[Ab + i] = req.top_p
            if L:
                at = 3 * Ab + 2 + i * L
                ipack[at : at + L] = self._first_block(ids[whole:])
        ipack[3 * Ab] = A
        ipack[3 * Ab + 1] = self._next_counter()
        # constrained admissions: the first sampled token rides the same
        # fused dispatch, so its mask (start-state row) and bias must too
        cn_payload = self._cn_payload([req.cn for _, req, _ in batch], Ab)
        # ONE fused dispatch: prefill + cache inserts + device sampling-param
        # rows + first-token sample (see admit_fn)
        first = self._note_exec_shape("admit", Ab, bucket, cn_payload is not None)
        t0c = time.perf_counter()
        # the dispatch by its number: in a profiler trace each run of
        # jit_admit_fn has the annotation that caused it, inside engine.admit
        with TraceAnnotation("engine.admit.dispatch", aid=self._adm.programs + 1):
            toks0 = self._dx("admit", tokens, ipack, fpack, cn_payload)
        t_call = time.perf_counter()  # jit returned; device running
        aid = self._note_admit(
            "batch", [req for _, req, _ in batch], Ab, bucket,
            sum(len(ids) for _, _, ids in batch), held_by,
        )
        self._adm.own(self._own_reason(batch), A)
        self._note_off("mixed_round")
        if first:
            # jit traces and compiles inside the call: the wall up to its
            # return is the compile's, and the ledger's context closes here,
            # before another first dispatch can open its own
            self._compile_obs("admit", (Ab, bucket), t_call - t0c,
                              planned=cn_payload is None)
        return _DispatchedAdmit(
            toks0=toks0,
            entries=[
                (slot, self._seat(slot, req, ids, batched=True), len(ids))
                for slot, req, ids in batch
            ],
            t0=t0c, t_call=t_call, first=first,
            aid=aid, mark=self._adm.mark(),
        )

    def _read_admit(self, adm: _DispatchedAdmit, at_once: bool = False) -> None:
        """The read half of a batched admission: the one blocking read of its
        first tokens, then all that needs their value (_first_token). From
        the queue this runs when the admission is the oldest item in flight:
        every round dispatched before it has been fetched and emitted, none
        dispatched after it has been fetched, so a slot's first token is on
        its stream before the text of the first round that carried it."""
        # chaos site: a poisoned admission surfaces here, as a poisoned
        # round does at its fetch
        maybe_fail("engine.admit", f"slots={[b for b, _, _ in adm.entries]}")
        blocked = not adm.toks0.is_ready()
        t_wait = time.perf_counter()
        with TraceAnnotation("engine.admit.sync"):
            toks0 = np.asarray(adm.toks0)  # the admission's only host sync
        now = time.perf_counter()
        if adm.round is not None:
            self._round_ended(adm.round, now, t_wait, blocked, "admit")
        elif not at_once:  # read from the queue: a retirement of its own
            self._retired("admit", 0, now, t_wait, "admit")
        else:  # dispatched and read in one place: the device was busy, no stall
            self._perf.rounds.unchain()
        self._adm.read(blocked, at_once)
        # a ride names its round and carries no `aid`: the readers place the
        # runs of the admit PROGRAM by the aids of blocked reads
        self._flight.event(
            "admit_read", **({"rid": adm.rid} if adm.rid else {"aid": adm.aid}),
            rows=len(adm.entries), after_rid=self._rid_fetched,
            wait_ms=round((now - t_wait) * 1e3, 3), blocked=blocked,
            t=time.monotonic(),
        )
        tot_tok = sum(P for _, _, P in adm.entries)
        if not adm.first and not adm.rid:
            self._sample_prefill_phase(
                "admit", adm.t0, adm.t_call, tot_tok, len(adm.entries)
            )
        # latency waterfall: dispatch to read is wall every batched prompt
        # sat through — attribute it by token share
        wall_a_token = (now - adm.t0) / (tot_tok or 1)
        for i, (slot, s, P) in enumerate(adm.entries):
            if self._slots[slot] is not s:
                continue  # failed and freed since the dispatch
            if s.aborted:
                # the stall watchdog delivered this consumer's terminal
                # error while the admission was in flight (_complete_round)
                self._free_now(slot)
                continue
            s.prefill_compute_s += wall_a_token * P
            if not self._block:  # a block configuration's comes with its first round
                self._first_token(slot, s, int(toks0[i]), adm.mark)

    def _activate_state(
        self, slot: int, req: GenRequest, ids: list[int], tok0: int
    ) -> None:
        """Seat a slot and deliver its first token in one step: the chunked
        prefill's activations, which read their sample where it is made."""
        self._first_token(slot, self._seat(slot, req, ids), tok0)

    def _seat(
        self, slot: int, req: GenRequest, ids: list[int], batched: bool = False
    ) -> _Slot:
        """All of an activation that the next decode dispatch needs and that
        does not need the first token's value: the slot object, its length,
        the paging ledger, the sampling mirrors, the prefix store, the
        drafter. It closes the slot's vacancy; a `batched` admission (a whole
        prompt through an admit program) books it by owner, any other taker
        (a chunked prefill, which reserved the slot long before, a prefix
        hit) drops the stamp unbooked."""
        P = len(ids)
        vacant = self._vacant.pop(slot, None)
        if vacant is not None and batched:
            now = time.monotonic()
            t_free, t_cool = vacant
            self._adm.vacancy(
                t_free, now if t_cool is None else t_cool, req.arrived_t, now
            )
        # the slot's cache rows [0, P) now hold exactly this prompt's KV —
        # the moment to learn a shared prefix for future admissions
        self._maybe_store_prefix(slot, ids)
        self._recent_prompts.append(tuple(ids))
        s = _Slot(req=req, prompt_len=P)
        # the automaton cursor moves onto the slot BEFORE tok0 is emitted:
        # _process_token advances it for every token including the first
        s.cn = req.cn
        # prefix-hit provenance rides the _PrefillState onto the live slot
        # (still present here — _finish_prefill_group deletes it after);
        # preemption uses it to snapshot only the private rows
        st = self._prefills.get(slot)
        if st is not None and st.shared_len:
            s.shared_entry = st.shared_entry
            s.shared_len = st.shared_len
        if st is not None:
            # chunked-path prefill walls accumulated while mid-chunk carry
            # onto the live slot for the latency waterfall
            s.prefill_compute_s += st.prefill_s
        # ledger: batch-path admissions create their table here; the
        # chunked/prefix-hit paths already reserved one (ensure extends it)
        mgr = self._paging
        mgr.ensure_slot(slot, P)
        want = min(P + max(0, req.max_tokens) + self.decode_chunk, self.max_seq_len)
        shared_full = s.shared_len // mgr.block_tokens if s.shared_len else 0
        mgr.note_admit_cost(mgr.blocks_for(want) - shared_full)
        self._slots[slot] = s
        self._lengths[slot] = P
        if self._block:
            # the cache holds the prompt's whole blocks: the slot's length is
            # its first block's first position, and the prompt's last P mod L
            # tokens stand fixed at the block's front (the recovery mirror)
            self._lengths[slot] = self._whole_blocks(P)
            self._last_tok[slot] = self._first_block(ids[self._lengths[slot]:])
        if self._state_pool is not None:
            self._state_pool.admitted_total += 1  # the slot's state row is this prompt's
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        if self._verify_fn is not None:
            # seed the n-gram drafter with the prompt: prompt-lookup drafting
            # pays off exactly when completions quote the prompt (extraction,
            # code edits, RAG). _process_token appends every emitted token so
            # the index also covers generated history.
            s.spec = NGramDrafter(self.spec_min_ngram, self.spec_max_ngram)
            s.spec.extend(ids)
        return s

    def _whole_blocks(self, n: int) -> int:
        """Of a prompt of `n` tokens, those an admission prefills: all of them,
        or for a block configuration its whole blocks (the rest start the
        slot's first block: `_first_block`)."""
        return n - n % self._block if self._block else n

    def _first_block(self, tail: list[int]) -> Any:
        """A slot's first block as it starts: the prompt's last P mod L
        tokens, fixed, then masks."""
        row = np.full((self._block,), self.cfg.mask_token_id, dtype=np.int32)
        row[: len(tail)] = tail
        return row

    def _first_token(
        self, slot: int, s: _Slot, tok0: int, mark: tuple | None = None
    ) -> None:
        """The host has read a seated slot's first token: the TTFT stamp
        and its records, the recovery mirror, the token's emission (`mark`:
        _put_text), and the hand-over of a prefill-role engine."""
        self._last_tok[slot] = tok0
        self._first_stamp(slot, s)
        # tok0's KV will be written at position P in the first decode round.
        self._emit_token(slot, s, tok0, pos=s.prompt_len - 1, mark=mark)
        if self._exports_after_prefill(s.req) and not s.done and not s.aborted:
            # disaggregated mode: this engine spent the prefill and emitted
            # the first token; the decode-role peer continues from here
            self._migrate_export_slot(slot, s)

    def _first_stamp(self, slot: int, s: _Slot) -> None:
        """A slot's first token is in the host's hands (an admission's read,
        or for a block configuration its first block round's fetch): the TTFT
        stamp and its records."""
        req = s.req
        P = s.prompt_len
        s.first_token_at = time.time()
        ttft_ms = (s.first_token_at - req.created_at) * 1000.0
        with self.stats_lock:
            self.total_requests += 1
            self._ttft_window.append((s.first_token_at, ttft_ms))
        self._flight.event(
            "admit", trace_id=self._tid(req), request_id=req.request_id[:8],
            slot=slot, prompt_tokens=P, ttft_ms=round(ttft_ms, 1),
        )
        self._anomaly.signal("ttft_burn", ttft_ms=ttft_ms)
        if req.trace_ctx:
            # retroactive spans from timestamps already stamped: the caller's
            # trace gets engine.admit (submit→pop) and engine.prefill
            # (pop→first token, i.e. TTFT minus queue time)
            tracer = tracing.get_tracer()
            admitted = req.admitted_at or req.created_at
            tracer.record(
                "engine.admit", req.created_at, admitted,
                parent=req.trace_ctx, attrs={"request_id": req.request_id},
            )
            tracer.record(
                "engine.prefill", admitted, s.first_token_at,
                parent=req.trace_ctx,
                attrs={
                    "request_id": req.request_id,
                    "prompt_tokens": P,
                    "ttft_ms": round(ttft_ms, 1),
                    # scheduler decision context at activation: the budget
                    # this prompt's last chunk rode in under, and whether the
                    # backlog has been outrunning the TTFT deadline
                    "prefill_token_budget": self._sched.last_budget,
                    "sched_starved_rounds": self._sched.starved_rounds,
                },
            )

    def _prefill_backlog(self) -> int:
        """Prompt tokens not yet written for live mid-prefill slots."""
        return sum(
            len(st.ids) - st.done
            for st in self._prefills.values()
            if not st.aborted
        )

    def _chunk_shape(self, slot: int, cap: int = 0) -> tuple[int, int, int, int]:
        """(start, n, bucket, skey) for a mid-prefill slot's next chunk,
        with `cap` (>0) bounding n to the scheduler's remaining budget.

        bucket never runs past the cache row end — dynamic_update_slice would
        CLAMP the start index and silently overwrite earlier prompt KV
        (prompts are pre-truncated to max_seq_len - decode_chunk, so
        S - start > n always holds). skey statically bounds the PAST key
        range (bucketed for jit-cache reuse): early chunks of a long prompt
        don't pay an O(max_seq_len) score tensor."""
        st = self._prefills[slot]
        start = st.done
        n = min(self.prefill_chunk, len(st.ids) - start)
        if cap > 0:
            n = min(n, cap)
        bucket = min(pow2_bucket(n, self.prefill_chunk), self.max_seq_len - start)
        skey = (
            min(pow2_bucket(start, self.max_seq_len), self.max_seq_len)
            if start
            else min(128, self.max_seq_len)
        )
        return start, n, bucket, skey

    def _stage_prefill_group(
        self, n_active: int, reserved_tokens: int = 0
    ) -> _PrefillGroup | None:
        """Ask the scheduler for this round's prefill token budget and stage
        one batched chunk group under it: up to admit_batch mid-prefill slots
        whose next chunks share (bucket, skey) — the chunk weight pass is the
        cost, and batching amortizes it like _start_batch does for short
        prompts. Staging only; the group is dispatched fused with the decode
        round (_dispatch_decode) or standalone (_dispatch_prefill_group).
        `reserved_tokens` is chunk work this iteration already owes elsewhere
        (a speculative verify dispatch); it shrinks the budget so verify +
        prefill together stay inside the round's fair share."""
        # states the stall watchdog error-terminated while the loop was
        # wedged: reclaim silently (their consumers are gone)
        for slot in [
            s for s in self._prefill_q if self._prefills.get(s, None) is not None
            and self._prefills[s].aborted
        ]:
            self._prefill_q.remove(slot)
            del self._prefills[slot]
            self._paging.free_slot(slot)
            self._phys_reset(slot)
        if not self._prefill_q:
            self._sched.decide(0, n_active, 0.0)
            return None
        oldest = min(
            self._prefills[s].req.created_at for s in self._prefill_q
        )
        budget = self._sched.decide(
            self._prefill_backlog(), n_active, time.time() - oldest,
            reserved_tokens=reserved_tokens,
        )
        if budget <= 0:
            return None
        if self.ragged_prefill:
            return self._stage_ragged_group(budget)
        self._note_off("ragged_prefill")  # a bucketed chunk group instead
        group: list[int] = []
        metas: list[tuple[int, _PrefillState, int]] = []
        try:  # staging bugs must also fail over to waiters
            first = self._prefill_q[0]
            _, f_n, f_bucket, f_skey = self._chunk_shape(first, cap=budget)
            group.append(first)
            used = f_n
            for slot in list(self._prefill_q)[1:]:
                if len(group) >= self.admit_batch or used >= budget:
                    break
                start2, n2, _, s2 = self._chunk_shape(
                    slot, cap=min(budget - used, f_bucket)
                )
                # join only on identical (bucket, skey): one executable per
                # group shape. n2 rides row raggedness (nvalid) inside
                # f_bucket, so a budget-trimmed tail row still joins.
                if s2 == f_skey and n2 > 0 and start2 + f_bucket <= self.max_seq_len:
                    group.append(slot)
                    used += n2
            Ab = 1 << (len(group) - 1).bit_length()
            tokens = np.zeros((Ab, f_bucket), dtype=np.int32)
            slots_arr = np.zeros((Ab,), dtype=np.int32)
            starts_arr = np.zeros((Ab,), dtype=np.int32)
            nv_arr = np.ones((Ab,), dtype=np.int32)
            total = 0
            rem = budget
            for i, slot in enumerate(group):
                st = self._prefills[slot]
                start, n, _, _ = self._chunk_shape(
                    slot, cap=min(rem, f_bucket) if i else budget
                )
                tokens[i, :n] = st.ids[start : start + n]
                slots_arr[i] = slot
                starts_arr[i] = start
                nv_arr[i] = n
                metas.append((slot, st, n))
                total += n
                rem -= n
            for i in range(len(group), Ab):  # pad rows dup row 0: identical writes
                tokens[i] = tokens[0]
                slots_arr[i] = slots_arr[0]
                starts_arr[i] = starts_arr[0]
                nv_arr[i] = nv_arr[0]
            return _PrefillGroup(
                metas=metas, tokens=tokens, slots_arr=slots_arr,
                starts_arr=starts_arr, nv_arr=nv_arr,
                bucket=f_bucket, skey=f_skey, n_tokens=total,
            )
        except Exception as e:
            self._fail_prefill_group(
                _PrefillGroup(
                    metas=metas or [
                        (s, self._prefills[s], 0)
                        for s in group or self._prefill_q
                        if s in self._prefills
                    ],
                    tokens=None, slots_arr=None, starts_arr=None,
                    nv_arr=None, bucket=0, skey=0, n_tokens=0,
                ),
                e,
            )
            return None

    def _stage_ragged_group(self, budget: int) -> _PrefillGroup | None:
        """Ragged staging (the tentpole path): pack up to admit_batch slots'
        next chunks back-to-back into ONE [T] token buffer with per-token
        (rowid, position) and per-row (slot, start) descriptors — no
        (bucket, skey) join constraint, no pad rows, and each row is charged
        its TRUE token count against the budget (the bucketed path charges
        true tokens too but dispatches bucket-padded compute; here the pad
        tail is only T - total ≤ the pow2 rounding). T rides the pow2 ladder
        capped at _ragged_cap, so every fill mix reuses one executable per
        packed length."""
        R = max(1, self.admit_batch)
        S = self.max_seq_len
        picked: list[tuple[int, _PrefillState, int, int]] = []
        metas: list[tuple[int, _PrefillState, int]] = []
        try:
            used = 0
            max_start = 0
            cap = min(budget, self._ragged_cap)
            for slot in list(self._prefill_q):
                if len(picked) >= R or used >= cap:
                    break
                st = self._prefills[slot]
                start = st.done
                n = min(self.prefill_chunk, len(st.ids) - start, cap - used)
                if n <= 0:
                    continue
                picked.append((slot, st, start, n))
                used += n
                max_start = max(max_start, start)
            if not picked:
                return None
            T = pow2_bucket(used, self._ragged_cap, floor=min(32, self._ragged_cap))
            tokens = np.zeros((T,), dtype=np.int32)
            rowids = np.full((T,), R, dtype=np.int32)  # pads → dropped writes
            positions = np.full((T,), S, dtype=np.int32)
            slots_arr = np.zeros((R,), dtype=np.int32)
            starts_arr = np.zeros((R,), dtype=np.int32)
            nv_arr = np.zeros((R,), dtype=np.int32)
            last_idx = np.zeros((R,), dtype=np.int32)
            off = 0
            for i, (slot, st, start, n) in enumerate(picked):
                tokens[off : off + n] = st.ids[start : start + n]
                rowids[off : off + n] = i
                positions[off : off + n] = np.arange(start, start + n)
                slots_arr[i] = slot
                starts_arr[i] = start
                nv_arr[i] = n
                last_idx[i] = off + n - 1
                metas.append((slot, st, n))
                off += n
            # the kernel arm ignores skey entirely (data-dependent block
            # trips) — pass 0 so TPU mints ONE executable per T; the XLA arm
            # (CPU) keeps the bucketed-style static past bound for compile
            # cache reuse without whole-S gathers on short prefixes.
            if self._ragged_impl == "kernel":
                skey = 0
            else:
                skey = (
                    min(pow2_bucket(max_start, S), S)
                    if max_start
                    else min(128, S)
                )
            return _PrefillGroup(
                metas=metas, tokens=tokens, slots_arr=slots_arr,
                starts_arr=starts_arr, nv_arr=nv_arr,
                bucket=T, skey=skey, n_tokens=used, ragged=True,
                rowids_arr=rowids, positions_arr=positions,
                last_idx_arr=last_idx,
            )
        except Exception as e:
            self._fail_prefill_group(
                _PrefillGroup(
                    metas=metas or [
                        (s, self._prefills[s], 0)
                        for s in self._prefill_q
                        if s in self._prefills
                    ],
                    tokens=None, slots_arr=None, starts_arr=None,
                    nv_arr=None, bucket=0, skey=0, n_tokens=0,
                ),
                e,
            )
            return None

    def _dispatch_prefill_group(self, group: _PrefillGroup) -> None:
        """Standalone chunk dispatch for a pure-prefill window (no decode
        rows active — nothing to fuse with). Synchronous: the measured wall
        feeds the scheduler's per-token prefill cost EMA."""
        self._perf.rounds.unchain()  # device seconds that are no retirement's
        try:
            maybe_fail(
                "engine.prefill", f"slots={[s for s, _, _ in group.metas]}"
            )
            if group.ragged:
                # packed ragged dispatch: compiled shape is (T, skey, phys)
                # only — fill mix rides the descriptors, not the executable
                first = self._note_exec_shape("pf_rag", group.bucket,
                                              group.skey,
                                              self._phys is not None)
                t0 = time.perf_counter()
                self._gid_ctr += 1
                group.gid = self._gid_ctr
                self._dx(
                    "ragged", group.gid, group.tokens, group.rowids_arr,
                    group.positions_arr, group.slots_arr, group.starts_arr,
                    group.last_idx_arr, group.skey, self._paged_payload(),
                )
                t_call = time.perf_counter()  # jit returned; device running
                with TraceAnnotation("engine.prefill.sync"):
                    jax.block_until_ready(self._ck)
                wall = time.perf_counter() - t0
                if first:
                    self._compile_obs(
                        "pf_rag",
                        (group.bucket, group.skey, self._phys is not None),
                        wall,
                    )
                else:
                    self._sample_prefill_phase(
                        "pf_rag", t0, t_call, group.n_tokens,
                        len(group.metas),
                    )
                # a compile wall is not a prefill cost: count, teach nothing
                self._sched.observe_prefill(
                    group.n_tokens, 0.0 if first else wall,
                    padded_tokens=group.bucket,
                )
                self._credit_prefill_wall(group, wall)
                self._flight.event(
                    "pf_rag", rows=len(group.metas), tokens=group.n_tokens,
                    packed=group.bucket, wall_ms=round(wall * 1e3, 2),
                )
                # a packed buffer is one row of `bucket` tokens
                self._note_admit("chunk", [st.req for _, st, _ in group.metas],
                                 1, group.bucket, group.n_tokens)
                self._finish_prefill_group(group)
                return
            first = self._note_exec_shape("chunk", group.tokens.shape[0],
                                          group.bucket, group.skey,
                                          self._phys is not None)
            t0 = time.perf_counter()
            self._gid_ctr += 1
            group.gid = self._gid_ctr
            self._dx(
                "chunk", group.gid, group.tokens, group.slots_arr,
                group.starts_arr, group.nv_arr, group.skey,
                self._paged_payload(),
            )
            t_call = time.perf_counter()  # jit returned; device running
            with TraceAnnotation("engine.prefill.sync"):
                jax.block_until_ready(self._ck)
            wall = time.perf_counter() - t0
            if first:
                self._compile_obs(
                    "chunk",
                    (group.tokens.shape[0], group.bucket, group.skey,
                     self._phys is not None), wall,
                )
            else:
                self._sample_prefill_phase(
                    "chunk", t0, t_call, group.n_tokens, len(group.metas),
                )
            self._sched.observe_prefill(
                group.n_tokens, 0.0 if first else wall,
                padded_tokens=group.tokens.shape[0] * group.bucket,
            )
            self._credit_prefill_wall(group, wall)
            self._flight.event(
                "chunk", rows=len(group.metas), tokens=group.n_tokens,
                bucket=group.bucket, wall_ms=round(wall * 1e3, 2),
            )
            self._note_admit("chunk", [st.req for _, st, _ in group.metas],
                             group.tokens.shape[0], group.bucket, group.n_tokens)
        except Exception as e:
            self._fail_prefill_group(group, e)
            return
        self._finish_prefill_group(group)

    def _credit_prefill_wall(self, group: _PrefillGroup, wall: float) -> None:
        """Latency waterfall: attribute a synchronous chunk-dispatch wall to
        the mid-prefill prompts that rode it, by valid-token share. (The
        fused chunk path has no synchronous wall — its share surfaces as
        prefill_queue, which is honest: the prompt rode a decode round.)"""
        tot = group.n_tokens or 1
        for _, st, n in group.metas:
            st.prefill_s += wall * (n / tot)

    def _finish_prefill_group(self, group: _PrefillGroup) -> None:
        """Advance chunk progress for a dispatched group and activate the
        prompts whose last chunk just landed (first-token sample from the
        group's prefill logits)."""
        try:
            fin: list[tuple[int, int, _PrefillState]] = []
            for i, (slot, st, n) in enumerate(group.metas):
                st.done += n
                if st.done >= len(st.ids):
                    fin.append((i, slot, st))
            # BATCHED activation: one first-token sample + one update per
            # device sampling array for the whole finishing group (per-slot
            # activation cost ~5 host<->device round trips — with
            # prefix-cache hits riding this path, that tax would dominate
            # admission again). Dispatched even with nothing finishing: the
            # op pops the group's parked logits on every process.
            rows = np.asarray([i for i, _, _ in fin], dtype=np.int32)
            slots_fin = np.asarray([s for _, s, _ in fin], dtype=np.int32)
            temps = np.asarray([st.req.temperature for _, _, st in fin], np.float32)
            topks = np.asarray([st.req.top_k for _, _, st in fin], np.int32)
            topps = np.asarray([st.req.top_p for _, _, st in fin], np.float32)
            # constrained slots finishing their chunked prefill sample
            # tok0 here: their start-state masks ride the same dispatch
            cn_payload = self._cn_payload(
                [st.req.cn for _, _, st in fin], len(fin)
            )
            if self._block:
                # nothing is sampled: the finishing slots' first blocks as
                # they start go to the device's start buffer with their
                # sampling parameters, and their first tokens come with their
                # first block round (_emit_round)
                self._dx(
                    "bsample", group.gid, rows, slots_fin, temps, topks, topps,
                    0, None, np.stack([self._first_block(st.tail) for _, _, st in fin])
                    if fin else np.zeros((0, self._block), np.int32),
                )
                for _, slot, st in fin:
                    self._prefill_q.remove(slot)
                    self._seat(slot, st.req, st.ids + st.tail)
                    del self._prefills[slot]
                return
            toks0 = self._dx(
                "bsample", group.gid, rows, slots_fin, temps, topks, topps,
                self._next_counter(), cn_payload,
            )
            if fin:
                with TraceAnnotation("engine.prefill.sync"):
                    toks0 = np.asarray(toks0)
                for k, (_, slot, st) in enumerate(fin):
                    self._prefill_q.remove(slot)
                    # _prefills entry is dropped only AFTER activation
                    # succeeds: on a raise the except path below still finds
                    # the state and delivers error+_DONE to the waiter (it
                    # would hang forever otherwise)
                    self._activate_state(slot, st.req, st.ids, int(toks0[k]))
                    del self._prefills[slot]
        except Exception as e:
            self._fail_prefill_group(group, e)

    def _fail_prefill_group(self, group: _PrefillGroup, e: Exception) -> None:
        """Fail a chunk group's waiters and recover the cache if the failed
        dispatch consumed the donated buffers."""
        slots = [s for s, _, _ in group.metas]
        log.exception("chunked prefill failed (slots %s)", slots)
        for slot in slots:
            st = self._prefills.pop(slot, None)
            if st is not None:
                try:
                    self._prefill_q.remove(slot)
                except ValueError:
                    pass
                # free the slot if activation partially completed
                s = self._slots[slot]
                if s is not None and s.req is st.req:
                    self._free_now(slot)
                else:  # reserved-not-activated: release the ledger table
                    self._paging.free_slot(slot)
                    self._phys_reset(slot)
                if not st.aborted:  # watchdog may have terminated it already
                    self._count_error()
                    st.req.out.put({"type": "error", "error": str(e)})
                    st.req.out.put(_DONE)
        if self._recover_cache():
            self._abort_all("kv cache lost in failed prefill chunk")

    def _stage_spec(self, active: list[int]) -> list[tuple[int, list[int]]] | None:
        """Propose drafts for a speculative verify round, or None to keep the
        normal pipelined decode path.

        Every active slot joins the round (a slot with no n-gram match rides
        with zero drafts — its verify row degenerates to a single-token
        decode step, so nobody stalls), but the round only runs when a
        MAJORITY of slots actually have drafts: a verify dispatch costs a
        C-wide chunk pass and forces a pipeline drain, so it must beat the
        K-token decode round it displaces.

        Hard precondition: every row must satisfy len + C <= S, because
        dynamic_update_slice CLAMPS out-of-range starts — a clamped verify
        write would silently overwrite live KV. One near-cap slot falls the
        whole round back to normal decode (it will finish within a few
        rounds and unblock speculation)."""
        if not active:
            return None
        C = self.spec_k + 1
        S = self.max_seq_len
        entries: list[tuple[int, list[int]]] = []
        n_drafting = 0
        for b in active:
            s = self._slots[b]
            if s is None or s.spec is None:
                return None
            if int(self._lengths[b]) + C > S:
                return None
            d = s.spec.draft(self.spec_k)
            if d and s.cn is not None:
                # spec × constraint composition: truncate the draft to its
                # longest automaton-legal prefix, so every draft position
                # verify scores is constraint-legal BY CONSTRUCTION and a
                # masked target can never be asked to accept an illegal
                # token (it would always reject — wasted verify width)
                d = s.cn.filter_draft(d)
            if d:
                n_drafting += 1
            entries.append((b, d))
        if n_drafting == 0 or 2 * n_drafting < len(entries):
            return None
        return entries

    def _spec_round(self, entries: list[tuple[int, list[int]]]) -> None:
        """Dispatch one speculative verify round SYNCHRONOUSLY (the pipeline
        is already drained): one chunk pass over [token, draft_1..draft_nd]
        per slot, accept the longest agreeing prefix, emit accepted drafts +
        the device-sampled final token, and roll lengths forward to the
        accepted position. Rollback on rejection is pure arithmetic: cache
        rows past base+n_acc are dead (chunk attention masks key_pos >=
        start per row, decode attends < length, later writes land in place),
        so nothing is erased."""
        maybe_fail("engine.verify", f"slots={[b for b, _ in entries]}")
        self._perf.rounds.unchain()  # device seconds that are no retirement's
        t0 = time.perf_counter()
        B = self.max_slots
        Kd = self.spec_k
        C = Kd + 1
        n = len(entries)
        A = 1 << (n - 1).bit_length()
        tokens = np.zeros((A, C), dtype=np.int32)
        slots_arr = np.full((A,), B, dtype=np.int32)  # pads OOB: writes drop
        starts_arr = np.zeros((A,), dtype=np.int32)
        nv_arr = np.ones((A,), dtype=np.int32)
        drafts_arr = np.zeros((A, Kd), dtype=np.int32)
        nd_arr = np.zeros((A,), dtype=np.int32)
        total = 0
        for i, (b, d) in enumerate(entries):
            nd = len(d)
            tokens[i, 0] = self._last_tok[b]
            if nd:
                tokens[i, 1 : 1 + nd] = d
                drafts_arr[i, :nd] = d
            slots_arr[i] = b
            starts_arr[i] = self._lengths[b]
            nv_arr[i] = 1 + nd
            nd_arr[i] = nd
            total += 1 + nd
        skey = min(
            pow2_bucket(int(starts_arr[:n].max()), self.max_seq_len),
            self.max_seq_len,
        )
        # constrained verify rounds (reached only via _cn_round, so the
        # round is HOMOGENEOUS — every live row carries an automaton):
        # per-position packed masks + the per-request bias arrays ride the
        # payload; pad rows/positions stay all-ones (spec_verify never
        # reads past each row's valid draft span)
        cn_objs = [self._slots[b].cn for b, _ in entries]
        constrained = any(c is not None for c in cn_objs)
        cn_payload = None
        if constrained:
            t_m = time.perf_counter()
            W = constrain.mask_words(self.cfg.vocab_size)
            NB = self.cn_bias_max
            masks = np.full((A, C, W), 0xFFFFFFFF, dtype=np.uint32)
            bids = np.full((A, NB), -1, dtype=np.int32)
            bvals = np.zeros((A, NB), dtype=np.float32)
            for i, (b, d) in enumerate(entries):
                cn = cn_objs[i]
                if cn is None:
                    continue
                rows = cn.masks_for_draft(d)
                masks[i, : rows.shape[0]] = rows
                nb = min(len(cn.bias_ids), NB)
                if nb:
                    bids[i, :nb] = cn.bias_ids[:nb]
                    bvals[i, :nb] = cn.bias_vals[:nb]
            self.cn_mask_s += time.perf_counter() - t_m
            cn_payload = (masks, bids, bvals)
        first = self._note_exec_shape("verify", A, C, skey,
                                      self._phys is not None, constrained)
        n_acc, final = self._dx(
            "verify", tokens, slots_arr, starts_arr, nv_arr, drafts_arr,
            nd_arr, self._next_counter(), skey, self._paged_payload(),
            cn_payload,
        )
        t_call = time.perf_counter()  # jit returned (dispatch is async)
        n_acc = np.asarray(n_acc)  # the round's host sync point
        final = np.asarray(final)
        if first:
            self._compile_obs(
                "verify", (A, C, skey, self._phys is not None, constrained),
                time.perf_counter() - t0,
            )
        elif self._perf.should_sample("verify"):
            # verify is synchronous, so the asarray fetch IS the device wall
            t_done = time.perf_counter()
            wait_s = max(0.0, t0 - self._perf_mark)
            self._perf.observe_phase(
                "verify", t_call - t0, t_done - t_call, wait_s,
                tokens=total, rows=n,
                ctx_mean=float(starts_arr[:n].mean()) if n else 0.0,
            )
            self._flight.event(
                "perf", phase="verify",
                host_ms=round((t_call - t0) * 1e3, 3),
                device_ms=round((t_done - t_call) * 1e3, 3),
                wait_ms=round(wait_s * 1e3, 3),
                rows=n,
            )
        # a first dispatch counts its round and tokens and teaches the cost
        # EMA nothing (a wall of 0, as observe_prefill reads it)
        self._sched.observe_verify(
            total, 0.0 if first else time.perf_counter() - t0
        )
        before = self.total_tokens
        drafted_round = 0
        accepted_round = 0
        blk_wants: dict[int, int] = {}
        for i, (b, d) in enumerate(entries):
            s = self._slots[b]
            if s is None or s.done:
                continue
            if s.aborted:
                # watchdog delivered the terminal error mid-call
                self._free_now(b)
                continue
            na = min(int(n_acc[i]), len(d))
            base_b = int(starts_arr[i])
            drafted_round += len(d)
            accepted_round += na
            s.spec_drafted += len(d)
            s.spec_accepted += na
            toks = list(d[:na]) + [int(final[i])]
            parts: list[str] = []
            finish = None
            emitted = 0
            gen_before = s.generated
            for j, tok in enumerate(toks):
                emit, finish = self._process_token(s, int(tok), base_b + j)
                if int(tok) != self.tokenizer.eos_id:
                    emitted += 1  # mirrors _process_token's counting rule
                if emit:
                    parts.append(emit)
                if finish is not None:
                    break
            self.spec_emitted += emitted
            self._observe_itl(s, s.generated - gen_before)
            if parts:
                self._put_text(s, "".join(parts))
            if finish is not None:
                self._finish_slot(b, s, finish)
            else:
                # commit: KV valid through base+na (token + accepted
                # drafts); `final`'s KV is written by the next round
                self._lengths[b] = base_b + 1 + na
                self._last_tok[b] = int(final[i])
                blk_wants[b] = base_b + 1 + na
        if blk_wants:
            self._paging.extend_many(blk_wants)
        self.spec_calls += 1
        self.spec_drafted += drafted_round
        self.spec_accepted += accepted_round
        self._last_round_ts = time.time()  # verify rounds are cadence too
        self._flight.event(
            "verify", rows=n, drafted=drafted_round, accepted=accepted_round,
        )
        if constrained:
            # spec × constraint composition telemetry: how much of the
            # filtered draft stream survives the masked target
            self.cn_spec_drafted += drafted_round
            self.cn_spec_accepted += accepted_round
            self._flight.event(
                "cn_spec", rows=n, drafted=drafted_round,
                accepted=accepted_round,
            )
        self._anomaly.signal(
            "spec_collapse", drafted=drafted_round, accepted=accepted_round
        )
        if drafted_round and accepted_round * 4 < drafted_round:
            # drafts aren't landing (workload shifted away from its own
            # history): a verify round still emits >=1 token per slot, but a
            # decode round emits K — back off before re-probing
            self._spec_cooldown = 50
        with self.stats_lock:
            self._window.append((time.time(), self.total_tokens - before))

    def _cn_round(self, cn_active: list[int]) -> None:
        """One synchronous round for the constrained slots. Constrained
        traffic composes with speculation first: when the n-gram drafters
        have automaton-filtered drafts for a majority of constrained slots,
        the round IS a masked verify (_spec_round with the cn payload —
        per-position masks applied before accept/reject, so the committed
        tokens follow the renormalized masked target exactly). Otherwise
        one masked single decode step (op "cnstep"). Either way the round
        commits before returning: constrained slots are never pipelined,
        because the mask for token t+1 only exists after the host automaton
        consumed token t."""
        if self._verify_fn is not None and self._spec_cooldown <= 0:
            entries = self._stage_spec(cn_active)
            if entries is not None:
                self._spec_round(entries)
                return
        self._cn_step_round(cn_active)

    def _cn_step_round(self, cn_active: list[int]) -> None:
        """Masked single-step decode round: gather each slot automaton's
        current packed mask row + bias arrays, dispatch op "cnstep", and
        commit the sampled token through _process_token (which advances
        the automaton for the NEXT round's masks)."""
        maybe_fail("engine.cnstep", f"slots={cn_active}")
        self._perf.rounds.unchain()  # device seconds that are no retirement's
        t0 = time.perf_counter()
        B = self.max_slots
        S = self.max_seq_len
        n = len(cn_active)
        Ba = pow2_bucket(n, B, floor=min(8, B))
        act = np.asarray(cn_active, dtype=np.int32)
        if Ba > n:
            # pad rows must target an inactive cache row (the same append-
            # tile safety rule as _dispatch_decode's compact path)
            in_round = set(cn_active)
            free = next(
                (i for i in range(B)
                 if self._slots[i] is None and i not in self._prefills),
                next(
                    (i for i in range(B) if self._slots[i] is None),
                    next(i for i in range(B) if i not in in_round),
                ),
            )
        else:
            free = 0  # Ba == n: no pad rows exist
        ids = np.full(Ba, free, dtype=np.int32)
        ids[:n] = act
        lens_in = np.full(Ba, S, dtype=np.int32)
        lens_in[:n] = self._lengths[act]
        packed = np.concatenate(
            [lens_in, ids, [self._next_counter()]]
        ).astype(np.int32)
        # host mask gather: memoized per automaton state, so steady-state
        # cost is a dict hit + row copy per slot (cn_mask_s / cn_tokens is
        # the published mask_us_per_tok)
        masks, bids, bvals = self._cn_payload(
            [self._slots[b].cn for b in cn_active], Ba
        )
        first = self._note_exec_shape("cnstep", Ba, self._phys is not None)
        toks = self._dx(
            "cnstep", packed, masks, bids, bvals, self._paged_payload()
        )
        t_call = time.perf_counter()
        toks = np.asarray(toks)  # synchronous round: this is the device wall
        if first:
            self._compile_obs("cnstep", (Ba, self._phys is not None),
                              time.perf_counter() - t0)
        elif self._perf.should_sample("cnstep"):
            t_done = time.perf_counter()
            wait_s = max(0.0, t0 - self._perf_mark)
            self._perf.observe_phase(
                "cnstep", t_call - t0, t_done - t_call, wait_s,
                tokens=n, rows=n,
                ctx_mean=float(lens_in[:n].mean()) if n else 0.0,
            )
            self._flight.event(
                "perf", phase="cnstep",
                host_ms=round((t_call - t0) * 1e3, 3),
                device_ms=round((t_done - t_call) * 1e3, 3),
                wait_ms=round(wait_s * 1e3, 3),
                rows=n,
            )
        before = self.total_tokens
        blk_wants: dict[int, int] = {}
        for i, b in enumerate(cn_active):
            s = self._slots[b]
            if s is None or s.done:
                continue
            if s.aborted:
                self._free_now(b)
                continue
            pos = int(self._lengths[b])
            gen_before = s.generated
            emit, finish = self._process_token(s, int(toks[i]), pos)
            self._observe_itl(s, s.generated - gen_before)
            if emit:
                self._put_text(s, emit)
            if finish is not None:
                self._finish_slot(b, s, finish)
            else:
                self._lengths[b] = pos + 1
                self._last_tok[b] = int(toks[i])
                blk_wants[b] = pos + 1
        if blk_wants:
            self._paging.extend_many(blk_wants)
        self._last_round_ts = time.time()  # cn rounds are decode cadence too
        self._flight.event("cnstep", rows=n)
        with self.stats_lock:
            self._window.append((time.time(), self.total_tokens - before))

    def _dispatch_decode(
        self, active: list[int], group: _PrefillGroup | None = None,
        ride: _Ride | None = None,
    ) -> _DispatchedRound:
        """Phase 1: stage host inputs and dispatch one decode round (NO
        fetch — the returned round is in flight on device). Input tokens
        come from the device-resident ring (decode_chunk_fn), so this never
        waits on an earlier round's output; host lengths advance
        OPTIMISTICALLY here (+K per dispatched row — the device really does
        advance them), which is what lets the next dispatch stage correct
        write positions before this round is fetched.

        With a staged prefill chunk `group`, the round goes through
        fused_step_fn: the same dispatch also writes the group's prompt
        tokens (budget-bounded, slot-disjoint from the active rows) and
        parks its boundary logits un-fetched on the dispatch plane
        (_x_logits[group.gid]) for the activation sample.

        With a staged `ride` (a full batch and no group: _run) the round goes
        through mixed_round_fn: its first step carries the batch's whole
        prompts through its pass over the weights, and the batch is seated
        here, after the dispatch, as _start_batch seats one (`ride.adm` is its
        record for the in-flight queue, read by _read_admit)."""
        # chaos site: a failed round must fail active slots with error
        # events, not hang callers (the poisoned-round guard in _run)
        maybe_fail("engine.decode", f"active={len(active)}")
        self._note_off("speculation")  # a decode round, dispatched with no draft
        round_t0 = time.perf_counter()
        B = self.max_slots
        nact = len(active)
        self._last_active_n = nact
        # Slot compaction: dispatch a pow2 bucket of just the active rows.
        # Floor 8 bounds the executable count (8, 16, 32, ... B); at Ba == B
        # the full-batch trace (slot_ids=None) is reused instead — identical
        # math, no indirection.
        Ba = pow2_bucket(nact, B, floor=min(8, B)) if self.decode_compact else B
        compact = Ba < B
        if compact:
            act = np.asarray(active, dtype=np.int32)
            # Pad rows MUST target an INACTIVE cache row: pads are parked
            # (length = S ⇒ the append kernels write nothing live), but each
            # pallas grid cell still rewrites its target tile — aimed at an
            # active row, a pad cell ordered after that row's real cell could
            # write back a PRE-append tile and silently drop the append.
            # Prefer a row that is neither active nor mid-chunked-prefill —
            # those hold garbage by definition, so the no-op rewrite (and the
            # attend kernel's discarded read) is trivially harmless. A
            # mid-prefill row is still value-safe (parked pads write back
            # byte-identical tiles; fallbacks drop OOB scatters) but only a
            # last resort — as is an occupied-but-undispatchable row (at the
            # context cap awaiting its fetch; possible only under the
            # pipelined loop's dispatch filter): its pad cell reads the
            # post-append tile (device stream is in-order) and writes it
            # back unchanged. The one UNSAFE target is a row active in THIS
            # dispatch (its real cell and the pad cell race within one
            # kernel launch) — and compact (Ba < B ⇒ nact < B) guarantees a
            # non-active row exists.
            in_round = set(active)
            free = next(
                (i for i in range(B)
                 if self._slots[i] is None and i not in self._prefills),
                next(
                    (i for i in range(B) if self._slots[i] is None),
                    next(i for i in range(B) if i not in in_round),
                ),
            )
            ids = np.full(Ba, free, dtype=np.int32)
            ids[:nact] = act
            lens_in = np.full(Ba, self.max_seq_len, dtype=np.int32)
            lens_in[:nact] = self._lengths[act]
            # ONE packed transfer per round (see decode_chunk_fn docstring)
            packed = np.concatenate(
                [lens_in, ids, [self._next_counter()]]
            ).astype(np.int32)
        else:
            packed = np.concatenate(
                [self._lengths, [self._next_counter()]]
            ).astype(np.int32)
        base = self._lengths.copy()
        if self._attn_stream is not None:
            self._attn_stream.dispatched(packed[:Ba], self.decode_chunk)
        if self._win_stream is not None:
            self._win_stream.dispatched(packed[:Ba], self.decode_chunk)
        if group is not None:
            maybe_fail(
                "engine.prefill", f"slots={[s for s, _, _ in group.metas]}"
            )
            if group.ragged:
                first = self._note_exec_shape(
                    "fused_rag", Ba, compact, group.bucket, group.skey,
                    self._phys is not None,
                )
                t0c = time.perf_counter()
                self._gid_ctr += 1
                group.gid = self._gid_ctr
                out = self._dx(
                    "decode", "fusedrag", group.gid, packed,
                    (group.tokens, group.rowids_arr, group.positions_arr,
                     group.slots_arr, group.starts_arr, group.last_idx_arr),
                    compact, group.skey, self._paged_payload(),
                )
                if first:
                    self._compile_obs(
                        "fused_rag",
                        (Ba, compact, group.bucket, group.skey,
                         self._phys is not None),
                        time.perf_counter() - t0c,
                    )
            else:
                first = self._note_exec_shape(
                    "fused", Ba, compact, group.tokens.shape[0],
                    group.bucket, group.skey, self._phys is not None,
                )
                t0c = time.perf_counter()
                self._gid_ctr += 1
                group.gid = self._gid_ctr
                out = self._dx(
                    "decode", "fused", group.gid, packed,
                    (group.tokens, group.slots_arr, group.starts_arr,
                     group.nv_arr),
                    compact, group.skey, self._paged_payload(),
                )
                if first:
                    # dispatch is async but jit trace+compile is synchronous
                    # — the first call's wall time is dominated by the
                    # compile
                    self._compile_obs(
                        "fused",
                        (Ba, compact, group.tokens.shape[0], group.bucket,
                         group.skey, self._phys is not None),
                        time.perf_counter() - t0c,
                    )
        elif ride is not None:
            ride.ipack[-1] = self._next_counter()
            first = self._note_exec_shape("mixed", ride.rung,
                                          self._phys is not None)
            t0c = time.perf_counter()
            out, toks0 = self._dx(
                "decode", "mixed", 0, packed,
                (ride.tokens, ride.rowids, ride.positions, ride.ipack,
                 ride.fpack),
                False, 0, self._paged_payload(),
            )
            t_call = time.perf_counter()
            if first:
                self._compile_obs(
                    "mixed", (ride.rung, self._phys is not None),
                    t_call - t0c,
                )
            true_tokens = sum(len(ids) for _, _, ids in ride.batch)
            self._adm.ride(len(ride.batch), true_tokens, ride.rung)
            ride.adm = _DispatchedAdmit(
                toks0=toks0,
                entries=[
                    (slot, self._seat(slot, req, ids, batched=True), len(ids))
                    for slot, req, ids in ride.batch
                ],
                t0=t0c, t_call=t_call, first=first,
                rid=self._rid_dispatched + 1, mark=self._adm.mark(),
            )
        else:
            first = self._note_exec_shape("decode", Ba, compact,
                                          self._phys is not None)
            t0c = time.perf_counter()
            if self._block:  # the block round in the decode round's place
                with TraceAnnotation("engine.block", rid=self._rid_dispatched + 1):
                    out = self._dx("decode", "plain", 0, packed, (), compact, 0, None)
            else:
                out = self._dx(
                    "decode", "plain", 0, packed, (), compact, 0,
                    self._paged_payload(),
                )
            if first:
                self._compile_obs(
                    "decode", (Ba, compact, self._phys is not None),
                    time.perf_counter() - t0c,
                )
        entries = [
            (b, self._slots[b], (i if compact else b)) for i, b in enumerate(active)
        ]
        # optimistic advance: the device WILL move every dispatched row K
        # steps; later dispatches must stage post-round positions without
        # waiting for this round's fetch. Capped at S (parking invariant).
        for b in active:
            self._lengths[b] = min(int(base[b]) + self.decode_chunk,
                                   self.max_seq_len)
        # ledger: grow block tables to cover the advanced lengths (batched —
        # one lock acquisition per round; a no-op inside a block)
        self._paging.extend_many({b: int(self._lengths[b]) for b in active})
        self._rid_dispatched += 1
        if group is not None:
            padded = (
                group.bucket if group.ragged
                else group.tokens.shape[0] * group.bucket
            )
        else:
            padded = 0
        # a mixed round is the perf observatory's `fused`: a decode round
        # with prompt tokens in the same dispatch, its decode rows its tokens
        phase_name = (
            ("fused_rag" if group.ragged else "fused")
            if group is not None else "fused" if ride is not None else "decode"
        )
        if ride is not None:
            # a ring event of its own kind: `admit_prog` stays "a program of
            # its own stood between two rounds"
            self._flight.event(
                "mixed", rid=self._rid_dispatched, rows=len(active),
                prompts=len(ride.batch), prompt_tokens=true_tokens,
                padded_tokens=ride.rung, queued=self._admit.qsize(),
                held_by=ride.held_by, t=time.monotonic(),
            )
        elif self._block:
            self._flight.event(
                "block", rid=self._rid_dispatched, rows=len(active), t=time.monotonic())
        else:
            self._flight.event(
                phase_name,
                rid=self._rid_dispatched, rows=len(active),
                prefill_tokens=group.n_tokens if group is not None else 0,
                prefill_padded=padded, t=time.monotonic(),
            )
        # Sampled steady-state attribution (every Nth dispatch of this
        # phase; first dispatches belong to the CompileLedger): host = the
        # staging+dispatch wall up to the async jit return, wait = the
        # host-side gap since the previous round's fetch landed. The sample
        # is counted HERE, with this round's rows (decode_occupancy reads
        # them): rounds whose device time can be told are the full ones, a
        # free slot means an admission. Device seconds are taken where the
        # round ends (_round_ended): nothing here blocks the pipeline.
        t_disp = time.perf_counter()
        sample = None
        if self._perf.should_sample(phase_name) and not first:
            sample = (t_disp - round_t0, max(0.0, round_t0 - self._perf_mark))
            self._perf.observe_phase(
                phase_name, sample[0], 0.0, sample[1],
                tokens=nact * self.decode_chunk, rows=nact,
                ctx_mean=float(base[active].mean()) if nact else 0.0,
            )
        disp = _DispatchedRound(
            out=out, entries=entries, base=base, t0=round_t0,
            rid=self._rid_dispatched,
            prefill_tokens=group.n_tokens if group is not None else 0,
            prefill_padded=padded, phase=phase_name, dx=self._dx_n,
            t_disp=t_disp, sample=sample, mark=self._adm.mark(),
            prog=self._round_prog(group, ride),
        )
        if ride is not None:
            ride.adm.round = disp  # the read of its first tokens ends the round
        return disp

    def _round_prog(self, group: _PrefillGroup | None, ride: _Ride | None) -> str:
        """A round's step program as the account of rounds keys it
        (telemetry/perf.py:RoundAccount), known before the dispatch: the
        plain round, the mixed round by its rung, the round fused with a
        chunk group by its phase; `block` for a configuration that generates
        by diffusion over blocks, whose every round is a block round."""
        if self._block:
            return "block"
        if group is not None:
            return "fused_rag" if group.ragged else "fused"
        return "plain" if ride is None else f"mixed_{ride.rung}"

    def _complete_round(self, disp: _DispatchedRound) -> _PendingRound:
        """Phase 2 (the per-round sync point): fetch the round, fast-scan
        finishes so the NEXT dispatch excludes finishing slots, and advance
        the host mirrors. Token emission is deferred (_emit_round) so it
        overlaps the next round's device time.

        The fast-scan duplicates ONLY _emit_token's counter-based finish
        rules (eos, max_tokens, seq-len cap) — a strict SUBSET of emission's
        rules (which add stop sequences), so a fast-scan finish always
        implies an emission finish on the same tokens; emission stays
        authoritative for events, usage, and text."""
        # whether this read will block: a round that has already ended
        # comes back in about a millisecond, and says nothing about when
        blocked = not disp.out.is_ready()
        t_wait = time.perf_counter()
        with TraceAnnotation("engine.fetch.sync"):
            out = np.asarray(disp.out)  # [K, Ba] — the only host sync per round
        row_passes = None
        if self._block:  # the row behind the block's tokens: each row's denoising passes
            row_passes, out = out[self._block], np.delete(out, self._block, axis=0)
        if self._experts is not None:
            # rows past the K of tokens: the expert layer's counts [2, L, 5]
            K, L = self.decode_chunk, self._experts.n_layers
            self._experts.counts = out[K:].reshape(-1)[: 10 * L].reshape(2, L, 5).tolist()
            out = out[:K]
        now = time.perf_counter()
        self._last_round_ts = time.time()  # decode-cadence stall signal
        self._perf_mark = now  # sampled wait-gap anchor
        wait_s = now - t_wait
        self._flight.event(
            "fetch", rid=disp.rid, wait_ms=round(wait_s * 1e3, 3),
            t=time.monotonic(),
        )
        if not disp.ended:  # a round that carried prompts ended at their read
            self._round_ended(disp, now, t_wait, blocked, "fetch")
        rows = len(disp.entries)
        self._perf.rounds.fetched(disp.prog, rows, rows * self.decode_chunk)
        # feed the token-budget scheduler's cost model: prefill-free rounds
        # teach the decode-round EMA; fused rounds attribute their time over
        # that EMA to the chunk group's prompt tokens. A round whose life
        # holds a first dispatch (its own, or one the loop made before this
        # fetch) carries a compile wall and is left out.
        dt = now - disp.t0
        if disp.t0 < self._first_end:
            pass
        elif disp.prefill_tokens:
            self._sched.observe_fused(
                dt, disp.prefill_tokens, padded_tokens=disp.prefill_padded
            )
        else:
            self._sched.observe_decode(dt)
        K = out.shape[0]
        S = self.max_seq_len
        eos = self.tokenizer.eos_id
        # Device advanced every dispatched row K steps; mirror that for rows
        # still owned by the SAME request (identity check: a slot freed by a
        # stop-sequence finish and re-admitted between dispatch and fetch
        # owns its new lengths — never touch them). Parked rows stay pinned
        # at exactly max_seq_len (drifting past it would eventually wrap
        # int32 back into [0, S) and break the OOB-drop parking invariant —
        # see __init__).
        for b, s, col in disp.entries:
            if self._slots[b] is not s:
                continue  # freed (and possibly re-admitted) since dispatch
            if s.aborted:
                # stall watchdog already delivered this consumer's terminal
                # error while the loop was wedged — reclaim the slot now
                # instead of decoding garbage until the seq cap
                self._free_now(b)
                continue
            g = s.generated
            fin = False
            base_b = int(disp.base[b])
            # (a first block's leading positions are the prompt's own)
            for k in range(max(0, s.prompt_len - base_b) if self._block else 0, K):
                if int(out[k, col]) == eos:
                    fin = True
                    break
                g += 1
                if g >= s.req.max_tokens:
                    fin = True
                    break
                if self._at_cap(base_b + k):
                    fin = True
                    break
            if fin:
                # free NOW: the next dispatch must exclude this slot and
                # admission may reuse it (after the cooling fence — rounds
                # already in flight still reference the row); the deferred
                # emission delivers its events from the pinned slot object
                self._free_now(b)
            else:
                # lengths were advanced optimistically at dispatch (the
                # pipelined loop stages later rounds before this fetch) —
                # only the recovery mirror updates here
                self._last_tok[b] = self.cfg.mask_token_id if self._block else out[-1, col]
        if self._block_book is not None:
            fixed = [min(self._block, max(0, s.prompt_len - int(disp.base[b])))
                     for b, s, _ in disp.entries]
            self._block_book.fetched(
                [int(row_passes[col]) for _, _, col in disp.entries],
                rows * self._block - sum(fixed), sum(fixed),
                [int(disp.base[b]) for b, _, _ in disp.entries], out.shape[1])
        self._rid_fetched = max(self._rid_fetched, disp.rid)
        if self._cooling:
            # the fetch that ends a freed slot's fence stamps it: from here
            # the slot is empty for want of a request or of an admit program
            t_cool = time.monotonic()
            for b, fence in self._cooling.items():
                vacant = self._vacant.get(b)
                if (vacant is not None and vacant[1] is None
                        and fence <= self._rid_fetched):
                    vacant[1] = t_cool
        return _PendingRound(
            out=out, entries=disp.entries, base=disp.base, rid=disp.rid,
            mark=disp.mark, prog=disp.prog,
        )

    def _round_ended(
        self, disp: _DispatchedRound, now: float, t_wait: float, blocked: bool,
        kind: str,
    ) -> None:
        """The first read that waited for a round's program has returned at
        `now`, having started at `t_wait` inside the loop phase `kind`: its
        fetch ("fetch"), or for a round that carried prompts the read of
        their first tokens ("admit": the same program's output, queued before
        the round, so the host waits for a mixed round THERE and its fetch
        then finds it ended). That read's time and whether it blocked are the
        round's end, which the next round's device seconds start from;
        and a round whose interval since the retirement before was a stall
        tells nothing: those seconds are the stall's."""
        disp.ended = True
        prev, self._prev_end = self._prev_end, (disp.rid, disp.dx, now, blocked)
        stalled = self._retired(disp.prog, disp.rid, now, t_wait, kind)
        self._observe_round_device(disp, now, blocked and not stalled, prev)

    def _retired(
        self, prog: str, rid: int, now: float, t_wait: float, kind: str
    ) -> bool:
        """One retirement of the in-flight queue (a round's end, the read of
        an admit program of its own), for the account's stalls: with the
        loop's seconds by phase up to `now`, the blocked read in progress
        counted into its phase `kind` (`timed` adds it when the call
        returns). A stall is one `stall` event in the flight ring."""
        phase_s = dict(self._phase_s)
        phase_s[kind] += now - t_wait
        stall = self._perf.rounds.retired(
            prog, rid, now, now - t_wait, phase_s, self._firsts)
        if stall is not None:
            self._flight.event("stall", **stall)
        return stall is not None

    def _observe_round_device(
        self, disp: _DispatchedRound, now: float, blocked: bool, prev: tuple
    ) -> None:
        """A round's device seconds, told where it ends (_round_ended) where
        they can be (nothing blocks the pipeline for them): the device began
        this round when the round before it ended (that round's end, if this
        one was queued behind it: dispatched back to back with nothing
        between, the host waiting at that end) or when this one was
        dispatched (if that read had already returned), and ended it `now`,
        if the host was waiting here. Every round that can tell gives the
        perf observatory its seconds and its tokens together, under its step
        program's key; one that cannot (an admit program of its own sat
        between, or the read found its round long ended) gives neither, and a
        sampled one then journals no device time."""
        p_rid, p_dx, p_t, p_blocked = prev
        device_s = None
        if (blocked and p_rid == disp.rid - 1
                and (disp.t_disp >= p_t or (p_blocked and p_dx == disp.dx - 1))):
            device_s = now - max(disp.t_disp, p_t)
            rows = len(disp.entries)
            self._perf.observe_device(
                disp.phase, disp.prog, device_s, rows, rows * self.decode_chunk,
                sampled=disp.sample is not None,
            )
        if disp.sample is not None:
            self._flight.event(
                "perf", phase=disp.phase,
                host_ms=round(disp.sample[0] * 1e3, 3),
                device_ms=None if device_s is None else round(device_s * 1e3, 3),
                wait_ms=round(disp.sample[1] * 1e3, 3),
                rows=len(disp.entries),
            )

    def _free_now(self, b: int) -> None:
        """Park a slot and fence its reuse until every round currently in
        flight (which may still write the row's cache tiles / token-ring
        entry) has been fetched."""
        self._slots[b] = None
        self._lengths[b] = self.max_seq_len  # park
        # ledger: drop the slot's block table (idempotent no-op when the
        # table is already gone — e.g. preempt parked it under a snap_id);
        # physical: the device table row back to identity + pool-row sweep
        self._paging.free_slot(b)
        self._phys_reset(b)
        t_free = time.monotonic()  # the vacancy _seat closes
        if self._rid_dispatched > self._rid_fetched:
            self._cooling[b] = self._rid_dispatched
            self._vacant[b] = [t_free, None]
        else:
            self._vacant[b] = [t_free, t_free]  # nothing in flight: cool at once

    def _emit_round(self, p: _PendingRound) -> None:
        """Phase 3 (deferred, overlapped with the next round's device time):
        decode token text, deliver events, finalize usage/finishes."""
        K = p.out.shape[0]
        t_emit = time.perf_counter()
        before = self.total_tokens  # _process_token counts delivered tokens
        texts = held = 0
        for b, s, col in p.entries:
            if s.done or s.aborted:
                continue  # terminal event already delivered
            parts: list[str] = []
            finish = None
            base_b = int(p.base[b])
            gen_before = s.generated
            k0 = 0
            if self._block:
                # a block round's first tokens are the slot's first: the TTFT
                # stamp is this emission's; a first block's leading positions
                # are the prompt's own and are not delivered
                k0 = max(0, s.prompt_len - base_b)
                if not s.first_token_at:
                    self._first_stamp(b, s)
            for k in range(k0, K):
                emit, finish = self._process_token(s, int(p.out[k, col]), base_b + k)
                if emit:
                    parts.append(emit)
                if finish is not None:
                    break
            self._observe_itl(s, s.generated - gen_before)
            if parts:
                # ONE coalesced text event per slot per round: the K tokens
                # were all learned at the same fetch, so splitting them into
                # K queue events (and K SSE frames) adds overhead with zero
                # client-visible timing difference
                self._put_text(s, "".join(parts), p.mark)
                texts += 1
                if self._pool is not None:
                    # the "idle" preemption policy's victim signal; guarded
                    # so the pool-off hot path writes nothing
                    s.last_emit = time.time()
            elif s.generated > gen_before:
                held += 1  # tokens and no text: bytes the decoder holds back
            if finish is not None:
                self._finish_slot(b, s, finish)
        delivered = self.total_tokens - before
        self._perf.rounds.delivered(p.prog, delivered)
        if self._block_book is not None:
            self._block_book.delivered(delivered)
        self._flight.event(
            "emit", rid=p.rid, rows=len(p.entries), delivered=delivered,
            texts=texts, held=held,
            dur_ms=round((time.perf_counter() - t_emit) * 1e3, 3),
            t=time.monotonic(),
        )
        with self.stats_lock:
            self._window.append((time.time(), delivered))

    def _put_text(self, s: _Slot, text: str, mark: tuple | None = None) -> None:
        """One text event onto a stream's queue, stamped with the
        time.monotonic() of its put (the HTTP handler observes `stream_lag`
        against it after the socket write), and the gap since the stream's
        previous text event: what a reader of the stream would see if the
        handler added nothing, whole, not spread over the round's tokens.
        `mark` is where what brought the event stands in the device's order,
        (admissions dispatched before it, their padded tokens): a round's,
        an admission's own, or where nothing was in flight the engine's
        count as it stands. The gap's sample says how many admit programs,
        of how many padded tokens, the device ran between the two events."""
        now = time.monotonic()
        mark = mark or self._adm.mark()
        if s.last_text_t:
            self._perf.observe_sample(
                "event_gap", now - s.last_text_t,
                mark[0] - s.adm_mark[0], mark[1] - s.adm_mark[1],
            )
        s.last_text_t = now
        s.adm_mark = mark
        s.req.out.put({"type": "token", "text": text, "t": now})

    def _sample_prefill_phase(
        self, phase: str, t0: float, t_call: float, tokens: int, rows: int
    ) -> None:
        """Sampled attribution for the synchronous prefill-family
        dispatches, called right after their device sync: t0→t_call is host
        staging (the jit call returns as soon as the dispatch is queued),
        t_call→now is device compute. Every Nth dispatch per phase
        (TPU_PERF_SAMPLE); first dispatches never reach here (they are the
        CompileLedger's)."""
        if not self._perf.should_sample(phase):
            return
        t_done = time.perf_counter()
        wait_s = max(0.0, t0 - self._perf_mark)
        self._perf.observe_phase(
            phase, t_call - t0, t_done - t_call, wait_s,
            tokens=tokens, rows=rows,
        )
        self._flight.event(
            "perf", phase=phase,
            host_ms=round((t_call - t0) * 1e3, 3),
            device_ms=round((t_done - t_call) * 1e3, 3),
            wait_ms=round(wait_s * 1e3, 3),
            rows=rows,
        )

    def _observe_itl(self, s: _Slot, n_new: int) -> None:
        """Fold one emission round's tokens into the slot's token timeline:
        the wall gap since the slot's previous emission (first round: since
        its TTFT stamp) spread evenly over the round's tokens — the engine
        learns a round's tokens at ONE fetch, so a finer per-token split
        would be fiction. Feeds the observatory's ITL window/goodput and
        the itl_degradation anomaly detector."""
        if n_new <= 0:
            return
        now = time.time()
        anchor = s.perf_last_emit or s.first_token_at or now
        gap = max(0.0, now - anchor)
        itl = self._perf.observe_itl(gap, n_new)
        s.perf_last_emit = now
        s.itl_s_total += gap
        s.itl_samples += n_new
        # latency waterfall: the part of an emission gap beyond the stall
        # threshold is decode time the request did NOT spend computing its
        # own tokens (compile pause, preempt-adjacent churn, wedged link)
        thr = workload.stall_threshold_s()
        if gap > thr:
            s.stall_s += gap - thr
        self._anomaly.signal("itl_degradation", itl_ms=itl * 1e3)

    def _emit_token(
        self, slot_idx: int, s: _Slot, tok: int, pos: int,
        mark: tuple | None = None,
    ) -> bool:
        """Append one token to a slot; returns False when the slot finished.

        `pos` is the cache position this token's KV occupies (or will occupy,
        for the prefill's first sample). The slot must finish while the next
        decode chunk's K writes still fit: pos+1+K ≤ max_seq_len.

        `s` is the slot OBJECT captured at dispatch time: under the
        pipelined loop the table entry may already be freed (fast
        finish-scan) or re-owned by a newer request — table mutations are
        identity-guarded (_finish_slot)."""
        emit, finish = self._process_token(s, tok, pos)
        if emit:
            self._put_text(s, emit, mark)
        if finish is not None:
            self._finish_slot(slot_idx, s, finish)
            return False
        return True

    def _at_cap(self, pos: int) -> bool:
        """Whether the token at cache position `pos` is a sequence's last for
        want of room: the next round's writes would pass the cache's end. A
        block configuration's next round is the next BLOCK, so only a block's
        last position can be the cap."""
        if self._block and (pos + 1) % self._block:
            return False
        return pos + 1 + self.decode_chunk > self.max_seq_len

    def _process_token(self, s: _Slot, tok: int, pos: int) -> tuple[str, str | None]:
        """Advance one slot by one token WITHOUT delivering events: returns
        (text to emit, finish_reason | None). Event delivery is the caller's
        job so _emit_round can coalesce a whole round's text into ONE queue
        event per slot — the engine only learns tokens once per round, so
        per-token events add queue/SSE overhead with zero timing benefit."""
        req = s.req
        finish = None
        emit = ""
        cut = -1
        if s.cn is not None:
            # the single automaton hook for every emission path (admit tok0,
            # decode rounds, verify commits, cn steps): consume the token so
            # the next mask reflects it. The mask made an illegal token
            # impossible — cn_illegal is the live proof (must stay 0).
            self.cn_tokens += 1
            if not s.cn.advance(tok):
                self.cn_illegal += 1
        if tok == self.tokenizer.eos_id:
            finish = "stop"
        else:
            s.generated += 1
            # counted HERE (not per decode round) so a slot's finishing token
            # — and the prefill's first sample — aren't dropped from stats.
            # No lock: the engine thread is the ONLY writer (readers see a
            # plain int); taking stats_lock per token would mean ~B×K lock
            # round-trips per decode round.
            self.total_tokens += 1
            if s.spec is not None:
                s.spec.append(tok)
            text, s.pending = self.tokenizer.decode_stream(s.pending, [tok])
            # Stop sequences trim BEFORE emission (OpenAI/Ollama semantics:
            # the stop string itself is never delivered). Scan the window
            # where a stop could straddle the old/new text boundary.
            prev_len = len(s.text)
            total = s.text + text
            cut = -1
            for stop_s in req.stop:
                if not stop_s:
                    continue
                i = total.find(stop_s, max(0, prev_len - len(stop_s) + 1))
                if i != -1 and (cut == -1 or i < cut):
                    cut = i
            if cut != -1:
                emit = total[prev_len:cut]
                s.text = total[:cut]
                finish = "stop"
            else:
                emit = text
                s.text = total
            if finish is None and s.generated >= req.max_tokens:
                finish = "length"
            if finish is None and self._at_cap(pos):
                finish = "length"
        if finish is not None and s.pending:
            # End of stream: flush any buffered partial decode (unless we cut
            # at a stop sequence — the buffered tail is post-stop text).
            if cut == -1:
                emit += self.tokenizer.decode_flush(s.pending)
            s.pending = b""
        return emit, finish

    def _finish_slot(self, slot_idx: int, s: _Slot, finish: str) -> None:
        """Deliver a slot's terminal events and release its table entry."""
        req = s.req
        s.done = True
        # counters move BEFORE the done/_DONE events publish: a caller
        # unblocked by the queue must never observe stale counters
        with self.stats_lock:
            self.finished_requests += 1
            self.finished_tokens += s.generated
        if s.cn is not None and s.cn.constrained:
            # schema validity at the REQUEST level: a constrained stream
            # that ends anywhere but an accepting automaton state produced
            # a syntactically incomplete document (e.g. cut by max_tokens)
            self.cn_finished += 1
            if s.cn.accepting:
                self.cn_finished_accepting += 1
        ttft_ms = (s.first_token_at - req.created_at) * 1000.0
        itl_mean_ms = (
            s.itl_s_total / s.itl_samples * 1e3 if s.itl_samples else 0.0
        )
        # goodput ledger: classify against the joint TTFT+ITL SLO (the
        # tenant id lands the request in that tenant's ledger too)
        if s.first_token_at:
            self._perf.finish_request(
                ttft_ms, itl_mean_ms, s.generated, tenant=req.tenant
            )
        if req.tenant:
            # bill the tenant's token bucket: prompt + generated tokens
            # drain the quota the admission gate refills against
            self._sched.tenant_charge(req.tenant, s.prompt_len + s.generated)
        # record BEFORE the done/_DONE events publish: a caller unblocked by
        # the queue must be able to see the completed trace immediately
        if req.trace_ctx and s.first_token_at:
            now = time.time()
            dur = max(now - s.first_token_at, 1e-9)
            attrs = {
                "request_id": req.request_id,
                "completion_tokens": s.generated,
                "output_tokens": s.generated,
                "tok_per_s": round(s.generated / dur, 1),
                "itl_mean_ms": round(itl_mean_ms, 2),
                "finish_reason": finish,
            }
            if s.spec is not None:
                # speculation contribution to this stream: drafted vs
                # accepted counts explain the tok_per_s figure
                attrs["spec_drafted"] = s.spec_drafted
                attrs["spec_accepted"] = s.spec_accepted
            if s.cn is not None and s.cn.constrained:
                attrs["cn_accepting"] = bool(s.cn.accepting)
            tracing.get_tracer().record(
                "engine.decode", s.first_token_at, now,
                parent=req.trace_ctx, attrs=attrs,
            )
        # Latency waterfall (telemetry/workload.py): decompose this
        # request's wall into an EXACT partition — the accumulated stage
        # walls are clamped into their windows so the stages always sum to
        # the measured total (residuals land in prefill_queue / decode,
        # which is honest: unattributed time is queueing).
        fin_ts = time.time()
        admitted = req.admitted_at or req.created_at
        admit_wait = max(0.0, admitted - req.created_at)
        ft = s.first_token_at or admitted
        pf_window = max(0.0, ft - admitted)
        pf_compute = min(max(0.0, s.prefill_compute_s), pf_window)
        dec_window = max(0.0, fin_ts - ft)
        preempt = min(max(0.0, s.preempted_s), dec_window)
        stall = min(max(0.0, s.stall_s), dec_window - preempt)
        shed = max(0.0, req.shed_wait_s)
        stages = {
            "admit_wait": admit_wait,
            "shed": shed,
            "prefill_queue": pf_window - pf_compute,
            "prefill_compute": pf_compute,
            "decode": dec_window - preempt - stall,
            "stall": stall,
            "preempt": preempt,
        }
        total_s = admit_wait + shed + pf_window + dec_window
        tid = self._tid(req)
        self._waterfall.observe(
            stages, total_s, trace_id=tid, rid=req.request_id[:8],
            ts=req.created_at,
        )
        self._flight.event(
            "wf", trace_id=tid, request_id=req.request_id[:8],
            total_ms=round(total_s * 1e3, 2),
            **{f"{k}_ms": round(v * 1e3, 2) for k, v in stages.items()},
        )
        # Workload capture: one compact record per finished admitted
        # request — prefix-chain head hashes (routing/prefix.py digests),
        # never raw text; raw token ids only behind TPU_WORKLOAD_IDS=1.
        if self._workload.enabled():
            chain = prefix_fp.chain_hashes(
                req.prompt_ids, self._paging.block_tokens
            )[: workload.CHAIN_HEAD]
            self._workload.record(
                ts=req.created_at, rid=req.request_id, trace_id=tid,
                model=self.cfg.name, prompt_tokens=len(req.prompt_ids),
                chain=chain, max_tokens=req.max_tokens,
                temperature=req.temperature, top_k=req.top_k,
                top_p=req.top_p, output_tokens=s.generated, finish=finish,
                ids=req.prompt_ids, shed_s=shed,
            )
            self._flight.event(
                "wl", trace_id=tid, request_id=req.request_id[:8],
                prompt_tokens=len(req.prompt_ids),
                output_tokens=s.generated, finish=finish,
            )
        req.out.put(
            {
                "type": "done",
                "finish_reason": finish,
                "usage": {
                    "prompt_tokens": s.prompt_len,
                    "completion_tokens": s.generated,
                    "total_tokens": s.prompt_len + s.generated,
                },
                "ttft_ms": ttft_ms,
            }
        )
        req.out.put(_DONE)
        # identity-guarded: the fast-scan may have freed the entry
        # already, and admission may have re-filled it with a NEW
        # request whose slot state must not be clobbered
        if self._slots[slot_idx] is s:
            self._free_now(slot_idx)


# -- multi-host spelling ----------------------------------------------------
# (Folded in from the retired executor/slice_engine.py shim: one loop, one
# queue, one request dataclass — the multi-host behavior lives entirely in
# the GSPMDBackend dispatch seam, and SliceEngine is just the constructor
# that wires it.)

# The slice request type was always structurally identical to the engine's;
# now it IS the engine's.
SliceRequest = GenRequest


class SliceEngine(GenerationEngine):
    """`GenerationEngine` over a `GSPMDBackend` — the multi-host spelling of
    the one unified engine. Construct it in EVERY process of the cluster
    with identical arguments; `.start()` on the leader (process 0),
    `.run_follower()` everywhere else — both inherited. Keeps the old
    keyword surface (`cmd_addr`, `connect_timeout_s`, the strict
    quant-with-checkpoint error, the `max_slots % dp` check)."""

    def __init__(
        self,
        model: str | ModelConfig = "tiny-llm",
        *,
        mesh: Any,
        cmd_addr: str,
        max_slots: int = 8,
        max_seq_len: int = 256,
        dtype: Any = jnp.bfloat16,
        decode_chunk: int = 8,
        quant: str = "",
        weights_dir: str = "",
        tokenizer: Tokenizer | None = None,
        seed: int = 0,
        connect_timeout_s: float = 60.0,
        prefill_chunk: int = 0,
        target_ttft_ms: float = 2000.0,
        **engine_kw: Any,
    ):
        if quant not in ("", "int8") and weights_dir:
            # The unified engine downgrades unknown quant modes to a warning;
            # a multi-host boot must not silently serve different bytes than
            # the operator asked for across a whole slice.
            raise NotImplementedError(
                f"slice engine quant={quant!r} with a checkpoint "
                f"(only 'int8' is supported)"
            )
        if mesh is not None:
            dp = dict(mesh.shape).get("dp", 1)
            if max_slots % max(dp, 1) != 0:
                raise ValueError(
                    f"max_slots {max_slots} must divide over dp={dp}"
                )
        super().__init__(
            model,
            mesh=mesh,
            backend=GSPMDBackend(cmd_addr, connect_timeout_s=connect_timeout_s),
            max_slots=max_slots,
            max_seq_len=max_seq_len,
            dtype=dtype,
            decode_chunk=decode_chunk,
            quant=quant,
            weights_dir=weights_dir,
            tokenizer=tokenizer,
            seed=seed,
            prefill_chunk=prefill_chunk,
            target_ttft_ms=target_ttft_ms,
            **engine_kw,
        )
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        self.is_leader = self.process_index == 0
