"""HBM-resident embedding engine serving `/v1/embeddings`.

Replaces the reference's Ollama `/api/embed` proxy path
(`core/internal/api/handlers.go:1942-2015`): a call's inputs are packed into
rows and run as jitted forwards of one shape, entirely on TPU. Matryoshka
`dimensions` support is exact (truncate + renormalize) rather than the
reference's client-side truncation fallback (`handlers.go:2063-2078`).

A call dispatches AHEAD of its fetch (PR 53): under the engine's lock it
plans, stages and dispatches all its forwards and reads none of them; it
fetches and post-processes them with the lock free. JAX's dispatch returns
once a forward is enqueued, so the next caller's forwards queue on the device
behind this call's, in lock order (first come, first served), and the device
holds its next forward when one ends.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..models.configs import ModelConfig, resolve_config
from ..models.embedder import init_embedder_params, embed_forward
from ..parallel.sharding import (
    embedder_param_specs,
    llama_param_specs,
    shard_pytree,
)
from .common import pow2_bucket
from .tokenizer import Tokenizer, load_tokenizer

# Sequence packing (PR 31). A row of a forward holds up to PACK_TEXTS texts
# back to back where the model function computes each as if it were alone
# (`llama_encode_packed`); it is the width of the lengths operand [R, K] and
# of the output [R, K, D]. 16 texts of the shortest length bucket (32) fill a
# row of PACK_ROW, so packing never dispatches more positions than one text a
# row did.
PACK_TEXTS = 16
# A call whose texts need more than one row gets rows of at least this many
# tokens (and never more than its longest text needs): an engine built for
# 8,192-token inputs does not pack short texts into rows whose attention is
# quadratic in 8,192.
PACK_ROW = 512
# Token positions of one forward of the packed encoder, 2 rows of 512. A call
# that needs more runs EQUAL forwards of one shape, so a row count rounds up
# to a small bucket and a deployment meets one shape. From a chip sweep
# (PERF.md section 5, PR 31; one v5e, Qwen3-Embedding-8B int8, the cell
# embed_batch): `jit_fwd` takes 30.5 / 25.4 / 27.9 / 28.6 / 30.8 / 32.6 ms a
# row at 1 / 2 / 4 / 8 / 16 / 32 rows of 512, and the cell gives 65.4 / 75.5 /
# 66.3 / 63.8 / 60.1 embeddings/s at 512 / 1,024 / 2,048 / 4,096 / 8,192.
# Those embeddings/s were read WITH the hand-over between requests in them
# (each forward fetched under the lock, until PR 53); the ms a row are the
# device's own.
FORWARD_TOKENS = 2 * 512


def pack_rows(lens: list[int], row_len: int, per_row: int) -> list[list[int]]:
    """First fit, longest first: rows of indices into `lens`, each of at most
    `row_len` tokens and `per_row` texts. With one text a row it is the
    texts, longest first."""
    rows: list[list[int]] = []
    room: list[int] = []
    open_rows: list[int] = []  # rows that can still take the shortest text
    shortest = min(lens)
    for i in sorted(range(len(lens)), key=lambda i: -lens[i]):
        for r in open_rows:
            if lens[i] <= room[r]:
                break
        else:
            r = len(rows)
            rows.append([])
            room.append(row_len)
            open_rows.append(r)
        rows[r].append(i)
        room[r] -= lens[i]
        if len(rows[r]) == per_row or room[r] < shortest:
            open_rows.remove(r)
    return rows


class EmbeddingEngine:
    def __init__(
        self,
        model: str | ModelConfig = "tiny-embed",
        *,
        mesh=None,
        params: Any = None,
        tokenizer: Tokenizer | None = None,
        max_batch: int = 64,
        max_seq_len: int = 512,
        dtype: Any = jnp.bfloat16,
        seed: int = 0,
        weights_dir: str = "",
        quant: str = "",
    ):
        # a config.json beside the weights is authoritative, exactly as for
        # GenerationEngine. Two architectures serve embeddings:
        #   arch="encoder"  — bidirectional mean/cls pooling
        #                     (models/embedder.py; nomic-class)
        #   decoder configs — causal LM with last-token pooling
        #                     (models/llama.py:llama_encode; Qwen3-Embedding
        #                     checkpoints are Qwen3ForCausalLM, so their
        #                     config.json resolves here and real safetensors
        #                     load through the ordinary decoder mapping)
        self.cfg = resolve_config(model, weights_dir) if isinstance(model, str) else model
        self.decoder_arch = self.cfg.arch != "encoder"
        self.mesh = mesh
        self.max_batch = max_batch
        if self.cfg.arch == "encoder" and self.cfg.enc_pos == "learned":
            # a learned position table has exactly cfg.max_seq_len rows
            # (BERT: 512) — longer buckets would index past it
            max_seq_len = min(max_seq_len, self.cfg.max_seq_len)
        self.max_seq_len = max_seq_len
        self.tokenizer: Tokenizer = tokenizer or load_tokenizer(weights_dir)

        if self.decoder_arch:
            from ..models import init_llama_params
            from ..models.weights import load_llama_checkpoint
            from .engine import _has_safetensors

            if params is None and _has_safetensors(weights_dir):
                params = load_llama_checkpoint(
                    self.cfg, weights_dir, dtype=dtype, mesh=mesh
                )
            elif params is None:
                if quant == "int8":
                    from ..models.quant import init_llama_params_quantized

                    params = init_llama_params_quantized(
                        self.cfg, jax.random.PRNGKey(seed), scale_dtype=dtype
                    )
                else:
                    params = init_llama_params(
                        self.cfg, jax.random.PRNGKey(seed), dtype=dtype
                    )
            if quant == "int8":
                from ..models.quant import quantize_params

                params = quantize_params(params)  # no-op on int8 trees
        elif params is None:
            from .engine import _has_safetensors

            if _has_safetensors(weights_dir):
                # real encoder checkpoint (BERT/nomic naming) — quantize
                # after load when asked (encoder checkpoints are small
                # enough to materialize first, unlike the 8B decoder path)
                from ..models.weights import load_embedder_checkpoint

                params = load_embedder_checkpoint(
                    self.cfg, weights_dir, dtype=dtype, mesh=None
                )
                if quant == "int8":
                    from ..models.quant import quantize_params

                    params = quantize_params(params)
            elif quant == "int8":
                # direct int8 init: an 8B-class embedder's bf16 tree
                # (~15 GB) never fits beside activations on a 16 GB chip
                from ..models.embedder import init_embedder_params_quantized

                params = init_embedder_params_quantized(
                    self.cfg, jax.random.PRNGKey(seed), scale_dtype=dtype
                )
            else:
                params = init_embedder_params(
                    self.cfg, jax.random.PRNGKey(seed), dtype=dtype
                )
        elif quant == "int8":
            from ..models.quant import quantize_params

            params = quantize_params(params)
        if mesh is not None:
            specs = (
                llama_param_specs(self.cfg)
                if self.decoder_arch
                else embedder_param_specs(self.cfg)
            )
            if quant == "int8":
                # {"q","s"} leaves need the quantized spec shape (the same
                # step GenerationEngine takes before sharding int8 trees)
                from ..models.quant import quantized_specs

                specs = quantized_specs(specs)
            params = shard_pytree(params, specs, mesh)
        self.params = params

        cfg = self.cfg

        # one calling form for both: tokens [R, S], lengths [R, K] (a row's
        # texts in order, 0 = unused place) -> [R, K, D]. The model function
        # says how many texts a row may hold and how many token positions a
        # forward: the decoder computes a text in a shared row as it is
        # alone; the bidirectional encoders take one (mean/cls pooling and a
        # learned position table know no segments)
        if self.decoder_arch:
            from ..models.llama import llama_encode_packed

            self.texts_per_row = PACK_TEXTS
            self.forward_tokens = FORWARD_TOKENS

            @jax.jit
            def fwd(params, tokens, lengths):
                return llama_encode_packed(cfg, params, tokens, lengths)

        else:
            self.texts_per_row = 1
            # no forward was measured for these: `max_batch` rows, as before
            self.forward_tokens = self.max_batch * self.max_seq_len

            @jax.jit
            def fwd(params, tokens, lengths):
                return embed_forward(cfg, params, tokens, lengths[:, 0])[:, None]

        self._fwd = fwd
        # held over a call's planning, staging and dispatch ALONE: the order
        # callers take it in is the order their forwards run on the device
        self._lock = threading.Lock()
        self.total_inputs = 0
        self.total_tokens = 0
        # counters behind stats(). Written at dispatch, with `_lock` held:
        # forwards dispatched, of them those dispatched while the forward
        # before was not ready yet (`ahead`: the device had its next forward
        # before it ended the last), texts asked for (`rows`), the rows they
        # were packed into and the rows dispatched after batch padding,
        # tokens asked for and tokens after padding to (batch bucket x row
        # length), and seconds waiting for the lock. Written where a
        # forward's fetch ends, with `_recent_lock` held (never `_lock`
        # inside it): `host_locked_s`, the host seconds of that forward with
        # `_lock` held (staging and the dispatching call: what a waiting
        # caller still waits for), and `forward_s`, the seconds its caller
        # stood blocked in its fetch (for one caller the two cannot pass the
        # wall clock; over callers `forward_s` counts a device second once
        # for each caller that waited through it). `inflight_max` is the
        # most forwards dispatched and not yet fetched at one time, read
        # where a call has dispatched its last
        self._stats: dict[str, float] = {
            "forwards": 0, "ahead": 0, "rows": 0, "rows_packed": 0, "rows_padded": 0,
            "true_tokens": 0, "padded_tokens": 0, "lock_wait_s": 0.0,
            "forward_s": 0.0, "host_locked_s": 0.0, "inflight_max": 0,
        }
        self._last_out: Any = None  # the newest forward dispatched, for `ahead`
        self._inflight = 0  # dispatched and not yet fetched; under `_recent_lock`
        # (time.monotonic() at the forward's dispatch, forward_s,
        # host_locked_s) of each forward, so that a reader can cut by its own
        # window
        self._recent: deque = deque(maxlen=4096)
        # stats() copies while embed() appends; taken inside `_lock`, never
        # around it
        self._recent_lock = threading.Lock()

    def _bucket(self, n: int) -> int:
        return pow2_bucket(n, self.max_seq_len)

    def prepare_ids(self, text: str) -> list[int]:
        """Tokenize one input exactly as `embed` feeds the forward pass
        (truncation + the trailing [SEP] for encoder tokenizers). The single
        source of truth for anything that must replay the REAL executable
        (benchmark/correctness.py feeds its reference these ids)."""
        ids = self.tokenizer.encode(text)[: self.max_seq_len]
        eos = getattr(self.tokenizer, "eos_id", -1)
        if not self.decoder_arch and eos is not None and eos >= 0:
            # BERT-family encoders were trained on [CLS] … [SEP] frames; the
            # tokenizer wrapper adds [CLS] (bos) but not the trailing [SEP]
            if not ids or ids[-1] != eos:
                ids = ids[: self.max_seq_len - 1] + [eos]
        return ids

    def plan(self, lens: list[int]) -> tuple[int, int, list[list[list[int]]]]:
        """One call's forwards from its texts' token counts: (row length, rows
        of a forward, forwards), a forward being rows of indices into `lens`.
        Every forward of a call has ONE shape: a call that needs more rows
        than a forward carries runs equal forwards (5 rows over a cap of 4 =
        3 + 2, both in the 4-row bucket), never a full one and a remainder
        of another shape."""
        row_len = self._bucket(max(lens))
        if self.texts_per_row > 1 and sum(lens) > row_len:
            row_len = max(row_len, min(PACK_ROW, self._bucket(sum(lens))))
        rows = pack_rows(lens, row_len, self.texts_per_row)
        cap = max(1, min(self.max_batch, self.forward_tokens // row_len))
        n_fwd = -(-len(rows) // cap)
        per = -(-len(rows) // n_fwd)
        return (
            row_len,
            pow2_bucket(per, cap, floor=1),
            [rows[i : i + per] for i in range(0, len(rows), per)],
        )

    def embed(
        self, texts: list[str], dimensions: int | None = None
    ) -> tuple[list[list[float]], int]:
        """Encode texts → (vectors in the caller's order, total_tokens).
        The texts are packed into rows (`plan`) and run as forwards of one
        shape: all dispatched under the lock, then fetched and post-processed
        in order with the lock free."""
        if not texts:
            return [], 0
        all_ids = [self.prepare_ids(t) for t in texts]
        total_tokens = sum(len(i) for i in all_ids)
        vectors: list[Any] = [None] * len(texts)
        K = self.texts_per_row
        # this call's forwards dispatched and not yet fetched: (output,
        # time of dispatch, host seconds under the lock, its texts, each
        # one's row and its place in the row)
        pending: deque = deque()

        t_ask = time.monotonic()
        with TraceAnnotation("embed.lock_wait"):
            self._lock.acquire()
        try:
            t_prev = time.monotonic()  # host time under the lock counts from here
            st = self._stats
            st["lock_wait_s"] += t_prev - t_ask
            # an empty text takes one position (token 0): a place of length 0
            # is an unused one
            lens = [max(len(ids), 1) for ids in all_ids]
            row_len, Bb, forwards = self.plan(lens)
            for rows in forwards:
                with TraceAnnotation("embed.stage"):
                    # the batch axis pads to a pow2 bucket too: without it
                    # every distinct row count compiles a fresh executable;
                    # pad rows hold 1 dummy token and their vectors are dropped
                    tokens = np.zeros((Bb, row_len), dtype=np.int32)
                    lengths = np.zeros((Bb, K), dtype=np.int32)
                    lengths[len(rows) :, 0] = 1
                    texts_at: list[int] = []  # this forward's texts,
                    rows_at: list[int] = []  # each one's row
                    places_at: list[int] = []  # and its place in the row
                    for r, row in enumerate(rows):
                        at = 0
                        for k, i in enumerate(row):
                            tokens[r, at : at + len(all_ids[i])] = all_ids[i]
                            lengths[r, k] = lens[i]
                            at += lens[i]
                            texts_at.append(i)
                            rows_at.append(r)
                            places_at.append(k)
                t_fwd = time.monotonic()
                ahead = self._last_out is not None and not self._last_out.is_ready()
                with TraceAnnotation("embed.forward"):
                    # enqueued, not awaited: the call returns once the device
                    # has the forward in its queue
                    out = self._fwd(self.params, tokens, lengths)
                    out.copy_to_host_async()
                t_done = time.monotonic()
                self._last_out = out
                pending.append((out, t_fwd, t_done - t_prev, texts_at, rows_at, places_at))
                t_prev = t_done
                st["forwards"] += 1
                st["ahead"] += ahead
                st["rows"] += len(texts_at)
                st["rows_packed"] += len(rows)
                st["rows_padded"] += Bb
                st["true_tokens"] += sum(lens[i] for i in texts_at)
                st["padded_tokens"] += Bb * row_len
            self.total_inputs += len(texts)
            self.total_tokens += total_tokens
            with self._recent_lock:  # counted where the call has dispatched its last
                self._inflight += len(pending)
                st["inflight_max"] = max(st["inflight_max"], self._inflight)
        finally:
            self._lock.release()

        try:
            while pending:
                out, t_fwd, host_s, texts_at, rows_at, places_at = pending[0]
                t_wait = time.monotonic()
                with TraceAnnotation("embed.fetch"):
                    out = np.asarray(out, dtype=np.float32)
                fwd_s = time.monotonic() - t_wait
                pending.popleft()
                with self._recent_lock:
                    self._inflight -= 1
                    st["forward_s"] += fwd_s
                    st["host_locked_s"] += host_s
                    self._recent.append((t_fwd, fwd_s, host_s))
                with TraceAnnotation("embed.post"):
                    out = out[rows_at, places_at]  # [texts of this forward, D]
                    if dimensions and 0 < dimensions < out.shape[1]:
                        out = out[:, :dimensions]
                        norms = np.maximum(np.linalg.norm(out, axis=1, keepdims=True), 1e-9)
                        out = out / norms
                    for i, vec in zip(texts_at, out.tolist()):
                        vectors[i] = vec
        finally:
            # a fetch that raised leaves forwards behind that nobody will
            # read: they leave the count with their call
            if pending:
                with self._recent_lock:
                    self._inflight -= len(pending)
        return vectors, total_tokens

    def stats(self, recent: bool = True) -> dict[str, Any]:
        """Counters of the forwards dispatched so far (see __init__), which
        engines_info shows at /v1/debug/health and /v1/dashboard: beside
        `forwards`, `ahead` of them were dispatched while the forward before
        was not ready yet, and `inflight_max` were at most dispatched and not
        yet fetched. With `recent`, one (time.monotonic() at its dispatch,
        forward_s, host_locked_s) a FETCHED forward: `host_locked_s` its host
        seconds with the lock held (staging and the dispatching call),
        `forward_s` the seconds its caller stood blocked in its fetch."""
        with self._recent_lock:
            if not recent:
                return dict(self._stats)
            return {**self._stats, "recent": list(self._recent)}
