"""The step programs of the benchmark's configurations, stated ONCE outside the
engine: for each configuration of BENCHMARK.json, by name, the programs its
cell dispatches (`decode`, `admit`, `chunk`, `mixed`, `ragged`, `block`) with
the operand shapes the cell's traffic gives them.

The engine builds these programs as closures of `GenerationEngine.__init__` and
`_build_decode` (executor/engine.py), which cannot be had without an engine that
holds real arrays; so what compiles for a described chip
(tests/test_tpu_compile.py) and what is hashed to hold two trees equal
(scripts/hybrid_hlo_digest.py) is a statement of them from shapes alone, without
the sampler. This file is that statement, and the only one: a test that
compiles for the described chip adds a ROW here, not a helper of its own
(ROADMAP working rules; debt C14 closes when the engine's builders are functions
this table can call).

Nothing is built at import: `shapes` and `traced` are called from a fixture, a
test or a script's `main`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

BF, I32 = jnp.bfloat16, jnp.int32


@dataclasses.dataclass(frozen=True)
class Cell:
    """A configuration as its cell boots it, and the programs that cell runs."""

    config: str  # models/configs.py
    slots: int
    seq: int
    rows: tuple  # (program, operand shapes [int32], what traffic gives this shape)
    int8: bool = False  # the weights quantised and fused (models/quant.py), not bfloat16
    int8_kv: bool = True
    n_layers: int = 0  # a cut of the depth (0: the configuration's own)
    attn: str = "pallas"


def mixed(slots: int, rung: int, prompts: int = 4) -> list[tuple]:
    """`mixed_round_fn`'s operands: the rows' tokens and lengths, the packed
    buffer of one rung (tokens, row ids, positions), the prompts' slots and last
    positions."""
    return [(slots,), (slots,), (rung,), (rung,), (rung,), (prompts,), (prompts,)]


_DECODE = ("decode", [(64,), (64,), (64,)], "every slot a row")
_ADMIT = ("admit", [(4, 128), (4,), (4,)], "the cell's largest admit program, 512 padded tokens")
_ADMIT_1 = ("admit", [(1, 64), (1,), (1,)], "and its smallest")
_CHUNK = ("chunk", [(2, 512), (2,), (2,), (2,)], "two prompts' second chunks of 512")
_HYBRID = (_DECODE, _ADMIT, _ADMIT_1, _CHUNK)
_MIXED = (("mixed", mixed(64, 128), "the round that carries prompts, at the rung of 128"),
          ("mixed", mixed(64, 256), "and of 256"))

CELLS = {
    # decode_closed: Qwen3-8B int8, 32 slots x 2048 (no slot ids: the whole batch in order)
    "qwen3": Cell("qwen3-8b", 32, 2048, int8=True, n_layers=36, rows=(
        ("decode", [(32,), (32,)], "every slot a row"),
        ("mixed", mixed(32, 128), "the round that carries prompts, at the rung of 128"),
        ("mixed", mixed(32, 256), "and of 256"))),
    # the same round with bfloat16 weights and KV (16 layers and 8 slots, so that it
    # fits the chip), and through the XLA attention that windows, softcaps and meshes take
    "qwen3_bf16": Cell("qwen3-8b", 8, 2048, int8_kv=False, n_layers=16, rows=(
        ("decode", [(8,), (8,)], "every slot a row"),)),
    "qwen3_xla_attention": Cell("qwen3-8b", 32, 2048, int8=True, n_layers=36, attn="xla", rows=(
        ("decode", [(32,), (32,)], "every slot a row"),)),
    "solar": Cell("solar-open2-250b-ep8", 64, 1024, rows=(
        _DECODE,
        ("admit", [(4, 256), (4,), (4,)], "four prompts in the 256 bucket"),
        _CHUNK,
        ("admit", [(1, 64), (1,), (1,)], "the smallest prompts: few rows, as a decode round"),
        ("admit", [(2, 256), (2,), (2,)], "the cell's largest admit program, 512 padded tokens"),
        *_MIXED)),
    "olmo": Cell("olmo-hybrid-7b-d20", 64, 1024, rows=(_DECODE, _ADMIT, _CHUNK, *_MIXED)),
    "granite": Cell("granite-4.0-h-micro", 64, 1024, rows=(*_HYBRID, *_MIXED)),
    "kexaone": Cell("k-exaone-236b-ep8", 64, 4096, rows=(
        _DECODE,
        ("admit", [(1, 1024), (1,), (1,)], "the cell's admit shapes: a prompt of 769-1024 tokens,"),
        ("admit", [(1, 768), (1,), (1,)], "and one of 640-768"),
        ("admit", [(2, 512), (2,), (2,)], "two shorter prompts: 1024 rows through the expert layer too"),
        ("chunk", [(1, 1024), (1,), (1,), (1,)], "a prompt over 1024 tokens: its second chunk"))),
    "lfm2": Cell("lfm2-8b-a1b-d14", 64, 1024, rows=(*_HYBRID, *_MIXED)),
    "joyai": Cell("joyai-llm-flash-ep16", 64, 1024, rows=(
        *_HYBRID,
        ("ragged", [(512,), (512,), (512,), (4,), (4,), (4,)], "a packed buffer of 512 tokens, four rows"))),
    "sdar": Cell("sdar-30b-a3b-ep8", 64, 1024, rows=(
        ("block", [(64, 4), (64,), (1,)], "every slot a row: 256 rows a pass"),
        ("block", [(32, 4), (32,), (1,), (32,)], "half the slots seated: rows by slot id"),
        _ADMIT, _ADMIT_1, _CHUNK)),
}


def row_id(cell: str, which: str, operands) -> str:
    """`solar-admit-4x256`, `lfm2-mixed-256`, `sdar-block-32x4`: the cell, the
    program and the shape that tells its rows apart (a mixed round's is its rung)."""
    lead = operands[2] if which == "mixed" else operands[0]
    return f"{cell}-{which}-{'x'.join(map(str, lead))}"


ROWS = [(cell, which, operands) for cell, spec in CELLS.items() for which, operands, _ in spec.rows]


@functools.lru_cache(maxsize=None)
def shapes(cell: str, sharding=None):
    """(cfg, params, cache) of a cell as shapes (on `sharding`: a described
    chip): the weights, the KV cache of its slots and length, and beside it
    whatever the family keeps a slot (state pool, rings, counts)."""
    from llm_mcp_tpu.models import llama, quant
    from llm_mcp_tpu.models.configs import get_config

    spec = CELLS[cell]
    cfg = get_config(spec.config)
    if spec.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=spec.n_layers)

    def init():
        if not spec.int8:
            return llama.init_llama_params(cfg, jax.random.PRNGKey(0), dtype=BF)
        p = quant.init_llama_params_quantized(cfg, jax.random.PRNGKey(0), scale_dtype=BF)
        return quant.fuse_layer_weights(quant.quantize_params(p))

    params = jax.eval_shape(init)
    cache = jax.eval_shape(functools.partial(
        llama.init_kv_cache, cfg, spec.slots, spec.seq, dtype=BF, quantized=spec.int8_kv))
    return (cfg, *jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), (params, cache)))


def step_program(which: str, cfg, attn: str = "pallas"):
    """One program as the engine builds it, `fn(params, ck, cv, *operands)`:
    `decode_body`'s scan of 4 steps; `admit_fn`'s prefill and row inserts;
    the bucketed and the packed chunk; `mixed_round_fn` (the first step carries
    the packed prompts, three plain ones follow); `block_round_fn` (a while loop
    of denoising passes over the batch, the unmask rule with the sampler, the
    commit pass)."""
    from llm_mcp_tpu.executor.engine import _put_rows
    from llm_mcp_tpu.models import hybrid, llama

    def steps(params, ck, cv, tokens, lengths, ids, n):
        def step(carry, _):
            ck, cv, toks, lens = carry
            logits, ck, cv = llama.llama_decode_step(
                cfg, params, ck, cv, toks, lens, attn_impl=attn, slot_ids=ids)
            new = jnp.argmax(logits, axis=-1).astype(I32)
            return (ck, cv, new, lens + 1), new

        (ck, cv, _, _), out = jax.lax.scan(step, (ck, cv, tokens, lengths), None, length=n)
        return out, ck, cv

    def decode(params, ck, cv, tokens, lengths, ids=None):
        return steps(params, ck, cv, tokens, lengths, ids, 4)

    def admit(params, ck, cv, tokens, lengths, slots):
        logits, ks, vs = llama.llama_prefill(
            cfg, params, tokens, lengths, attn_impl="pallas", quant_kv=True)

        def put(c, rows, i):  # `engine._insert_kv`'s
            return _put_rows(c, jax.lax.dynamic_slice_in_dim(rows, i, 1, 1), slots[i], 0)

        def body(i, cc):
            ck, cv = cc
            if cfg.kv_lora_rank:  # a counted latent pair: rows of BOTH members (the rope keys lie apart)
                return (jax.tree.map(lambda c, r: put(c, r, i), ck, ks),
                        dict(cv, v=jax.tree.map(lambda c, r: put(c, r, i), cv["v"], vs["v"])))
            if cfg.block_len:  # a counted dense pair: the fused cache alone
                return jax.tree.map(lambda c, r: put(c, r, i), ck, ks), cv
            ck = {"q": jax.lax.dynamic_update_slice(
                      ck["q"], jax.lax.dynamic_slice_in_dim(ks["q"], i, 1, 1), (0, slots[i], 0, 0, 0)),
                  "s": jax.lax.dynamic_update_slice(
                      ck["s"], jax.lax.dynamic_slice_in_dim(ks["s"], i, 1, 1), (0, slots[i], 0, 0))}
            return ck, dict(cv, **hybrid.insert_state_row(cv, vs, i, slots[i]))

        ck, cv = jax.lax.fori_loop(0, tokens.shape[0], body, (ck, cv))
        return logits, ck, hybrid.add_counts(cv, vs)

    def chunk(params, ck, cv, tokens, slots, starts, nvalid):
        return llama.llama_prefill_chunk_batch(
            cfg, params, ck, cv, tokens, slots, starts, nvalid, skey=min(512, tokens.shape[1]))

    def ragged(params, ck, cv, tokens, rowids, positions, slots, starts, last_idx):
        return llama.llama_prefill_chunk_ragged(
            cfg, params, ck, cv, tokens, rowids, positions, slots, starts, last_idx, impl="kernel")

    def mixed_round(params, ck, cv, tokens, lengths, p_tokens, p_rowids, p_positions, p_slots, p_last):
        first = hybrid.hybrid_mixed_step if cfg.recurrent else llama.mixed_step_q8
        logits, ck, cv = first(
            cfg, params, ck, cv, tokens, lengths, p_tokens, p_rowids, p_positions, p_slots, p_last)
        new = jnp.argmax(logits, axis=-1).astype(I32)
        n = tokens.shape[0]
        out, ck, cv = steps(params, ck, cv, new[:n], lengths + 1, None, 3)
        return jnp.concatenate([new[None, :n], out]), new[n:], ck, cv

    def block(params, ck, cv, first, starts, counter, slots=None):
        live = starts < ck["q"].shape[3]
        temp = jnp.full(starts.shape, 0.7, jnp.float32)
        topk, topp = jnp.zeros(starts.shape, I32), jnp.ones(starts.shape, jnp.float32)

        def denoise(carry):
            tokens, passes, rng, moe, n = carry
            rng, sub = jax.random.split(rng)
            new, cv_p, _ = llama.block_denoise(
                cfg, params, ck, dict(cv, moe=moe), tokens, slots, starts, live, sub, temp, topk, topp,
                attn_impl="pallas")
            return new, passes + jnp.any(tokens == cfg.mask_token_id, axis=1), rng, cv_p["moe"], n + 1

        tokens, passes, _, moe, _ = jax.lax.while_loop(
            lambda c: jnp.any((c[0] == cfg.mask_token_id) & live[:, None]) & (c[4] < cfg.denoise_steps),
            denoise,
            (first, jnp.zeros(starts.shape, I32), jax.random.fold_in(jax.random.PRNGKey(1), counter[0]),
             cv["moe"], jnp.int32(0)))
        _, ck, cv = llama.block_pass(
            cfg, params, ck, dict(cv, moe=moe), tokens, slots, starts, live, commit=True,
            attn_impl="pallas")
        return jnp.concatenate([tokens.T, passes[None]]), ck, cv

    return {"decode": decode, "admit": admit, "chunk": chunk, "ragged": ragged,
            "mixed": mixed_round, "block": block}[which]


def traced(cell: str, which: str, operands, sharding=None):
    """The row's program traced over the cell's shapes, the cache donated as the
    engine donates it: `.lower(...)` for a platform, or for a described chip's
    sharding and `.compile()`."""
    spec = CELLS[cell]
    cfg, params, cache = shapes(cell, sharding)
    args = [jax.ShapeDtypeStruct(tuple(shape), I32, sharding=sharding) for shape in operands]
    return jax.jit(step_program(which, cfg, spec.attn), donate_argnums=(1, 2)).trace(
        params, cache["k"], cache["v"], *args)


def preset_steps(cfg, slots: int, tokens: int, rows: int) -> dict:
    """ONE step of each kind for a tiny preset, `{tag: (fn, operands)}`: what
    scripts/hybrid_hlo_digest.py hashes on the CPU and tests/test_sdar.py pins
    (lambdas, as they were where the pinned digests were read: a module is
    named after its function)."""
    from llm_mcp_tpu.models import hybrid, llama

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32)

    B, T, R = slots, tokens, rows
    programs = {
        "decode": (lambda p, ck, cv, *a: llama.llama_decode_step(
            cfg, p, ck, cv, *a, attn_impl="pallas"), (i32(B), i32(B))),
        "mixed": (lambda p, ck, cv, *a: hybrid.hybrid_mixed_step(cfg, p, ck, cv, *a),
                  (i32(B), i32(B), i32(T), i32(T), i32(T), i32(R), i32(R))),
        "chunk": (lambda p, ck, cv, *a: llama.llama_prefill_chunk_batch(
            cfg, p, ck, cv, *a, skey=64), (i32(2, 32), i32(2), i32(2), i32(2))),
        "prefill": (lambda p, ck, cv, *a: llama.llama_prefill(
            cfg, p, *a, attn_impl="pallas", quant_kv=True), (i32(2, 64), i32(2))),
    }
    if not llama.mixed_step_supported(cfg):  # a stack with rings, a block configuration: admit programs alone
        del programs["mixed"]
    elif not cfg.recurrent:  # the dense family's mixed step
        programs["mixed"] = (lambda p, ck, cv, *a: llama.mixed_step_q8(cfg, p, ck, cv, *a),
                             programs["mixed"][1])
    if cfg.kv_lora_rank:  # the latent family packs its chunks (ragged prefill stays on)
        programs["ragged"] = (lambda p, ck, cv, *a: llama.llama_prefill_chunk_ragged(
            cfg, p, ck, cv, *a, impl="kernel"), (i32(T), i32(T), i32(T), i32(R), i32(R), i32(R)))
    return programs
