"""Parameter and KV-cache sharding specs (tensor parallelism).

Megatron-style TP mapping expressed as PartitionSpecs; XLA GSPMD inserts the
collectives:

  wq/wk/wv [L, D, H·hd]: shard output (head) dim on tp → per-chip heads
  wo       [L, H·hd, D]: shard input dim on tp → psum after projection
  w1/w3    [L, D, F]:    shard F on tp
  w2       [L, F, D]:    shard F on tp → psum after down-projection
  embed    [V, D]:       shard vocab on tp (vocab-parallel logits; top-k/argmax
                         over the sharded vocab axis gathers only [B, k])
  KV cache [L, B, Hkv, S, hd]: layers on pp, heads on tp, batch slots on dp

The stacked layer axis L shards on pp everywhere (params and cache): each
pipeline stage then holds only its own layers' weights and KV rows in HBM —
the capacity unlock pipeline_prefill's stage scan relies on. At pp=1 the
axis is a no-op and the specs reduce to the pure-TP mapping above.

GQA note: Llama-3.1-8B has 8 KV heads — exactly one per chip on a v5e-8 TP
mesh; Q heads (32) shard 4-per-chip. No KV replication needed up to tp=8.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.configs import ModelConfig


def llama_param_specs(cfg: ModelConfig) -> dict[str, Any]:
    if cfg.kv_lora_rank:
        # MLA (models/mla.py): heads live inside flat [.., D, H*(dn+dr)]
        # projections — tp shards the head-packed output axes; the shared
        # latent down-projection and its norm replicate (the latent is
        # per-token global state every head reads).
        attn: dict[str, Any] = {
            "attn_norm": P("pp", None),
            "wq_mla": P("pp", None, "tp"),
            "w_dkv": P("pp", None, None),
            "kv_norm": P("pp", None),
            "w_ukv": P("pp", None, "tp"),
            "wo_mla": P("pp", "tp", None),
            "ffn_norm": P("pp", None),
        }
        if cfg.q_lora_rank:  # the query through a latent of its own: its down-
            # projection and norm replicate as the key/value latent's do
            del attn["wq_mla"]
            attn.update(w_dq=P("pp", None, None), q_a_norm=P("pp", None),
                        w_uq=P("pp", None, "tp"))
        dense_ffn = {
            "w1": P("pp", None, "tp"),
            "w3": P("pp", None, "tp"),
            "w2": P("pp", "tp", None),
        }
        if cfg.n_experts:
            ffn: dict[str, Any] = {
                "router": P("pp", None, None),
                "w1e": P("pp", "ep", None, "tp"),
                "w3e": P("pp", "ep", None, "tp"),
                "w2e": P("pp", "ep", "tp", None),
            }
            if cfg.n_shared_experts:
                ffn.update(
                    {
                        "w1s": P("pp", None, "tp"),
                        "w3s": P("pp", None, "tp"),
                        "w2s": P("pp", "tp", None),
                    }
                )
            if cfg.router_score == "sigmoid":
                ffn["router_bias"] = P("pp", None)
        else:
            ffn = dense_ffn
        specs: dict[str, Any] = {
            "embed": P("tp", None),
            "layers": {**attn, **ffn},
            "final_norm": P(None),
        }
        if cfg.n_experts and cfg.first_dense_layers:
            specs["dense_layers"] = {**attn, **dense_ffn}
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(None, "tp")
        return specs
    layers: dict[str, Any] = {
        "attn_norm": P("pp", None),
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
        "ffn_norm": P("pp", None),
    }
    if cfg.qkv_bias:
        # biases follow their projection's output sharding
        layers.update({"bq": P("pp", "tp"), "bk": P("pp", "tp"), "bv": P("pp", "tp")})
    if cfg.qk_norm:
        # per-head norm weights are [L, hd] — every tp shard applies the
        # same head-local norm, so they replicate over tp
        layers.update({"q_norm": P("pp", None), "k_norm": P("pp", None)})
    if cfg.post_norms:
        layers.update(
            {"post_attn_norm": P("pp", None), "post_ffn_norm": P("pp", None)}
        )
    if cfg.n_experts:
        # Experts on ep, expert FFN hidden on tp: the dispatch einsums in
        # models/moe.py become the token all-to-all over ep under GSPMD.
        layers.update(
            {
                "router": P("pp", None, None),
                "w1e": P("pp", "ep", None, "tp"),
                "w3e": P("pp", "ep", None, "tp"),
                "w2e": P("pp", "ep", "tp", None),
            }
        )
        if cfg.n_shared_experts:
            layers.update(
                {
                    "w1s": P("pp", None, "tp"),
                    "w3s": P("pp", None, "tp"),
                    "w2s": P("pp", "tp", None),
                }
            )
    else:
        layers.update(
            {
                "w1": P("pp", None, "tp"),
                "w3": P("pp", None, "tp"),
                "w2": P("pp", "tp", None),
            }
        )
    specs: dict[str, Any] = {
        "embed": P("tp", None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


# Spec per encoder leaf name (full table, unconditional). Biases shard with
# their projection's output axis; norms and position/type tables replicate.
_ENCODER_LAYER_SPECS: dict[str, Any] = {
    "attn_norm": P(None, None),
    "attn_norm_b": P(None, None),
    "wq": P(None, None, "tp"),
    "bq": P(None, "tp"),
    "wk": P(None, None, "tp"),
    "bk": P(None, "tp"),
    "wv": P(None, None, "tp"),
    "bv": P(None, "tp"),
    "wo": P(None, "tp", None),
    "bo": P(None, None),
    "ffn_norm": P(None, None),
    "ffn_norm_b": P(None, None),
    "w1": P(None, None, "tp"),
    "b1": P(None, "tp"),
    "w3": P(None, None, "tp"),
    "b3": P(None, "tp"),
    "w2": P(None, "tp", None),
    "b2": P(None, None),
}
_ENCODER_TOP_SPECS: dict[str, Any] = {
    "embed": P("tp", None),
    "pos_embed": P(None, None),
    "type_embed": P(None, None),
    "embed_norm": P(None),
    "embed_norm_b": P(None),
    "final_norm": P(None),
}


def embedder_param_specs(cfg: ModelConfig) -> dict[str, Any]:
    """Specs for models/embedder.py:init_embedder_params, derived from the
    init tree's OWN structure via eval_shape — the conditional leaf set
    (gated w3, norm/linear biases, pos/type tables, embed vs final norm)
    lives in exactly one place, so specs can never drift from params
    (place_params zips flattened specs against flattened params and a
    mismatch would silently shard the wrong leaves)."""
    import jax

    from ..models.embedder import init_embedder_params

    shapes = jax.eval_shape(
        lambda: init_embedder_params(cfg, jax.random.PRNGKey(0))
    )
    specs: dict[str, Any] = {}
    for key, sub in shapes.items():
        if key == "layers":
            specs["layers"] = {k: _ENCODER_LAYER_SPECS[k] for k in sub}
        else:
            specs[key] = _ENCODER_TOP_SPECS[key]
    return specs


def kv_cache_specs(quantized: bool = False, latent: bool = False) -> dict[str, Any]:
    # [L, B, Hkv, S, hd] — layers on pp, batch slots on dp, KV heads on tp.
    # The int8 cache ({"q", "s"} pytrees) shards the payload identically;
    # scales [L,B,Hkv,S] drop the trailing head_dim axis.
    if latent:
        # MLA latent cache [L, B, 1, S, R]: the fake one-head axis cannot
        # shard — every tp shard's heads read the SAME latent row, so it
        # replicates over tp and shards batch on dp only (models/mla.py).
        row = P("pp", "dp", None, None, None)
        if quantized:
            entry = {"q": row, "s": P("pp", "dp", None, None)}
            return {"k": entry, "v": entry}
        return {"k": row, "v": row}
    row = P("pp", "dp", "tp", None, None)
    if quantized:
        # Fused GQA layout: one payload block [L, B, 2*Hkv + p, S, hd] holding
        # K rows, V rows, and (when p == 1) a bit-packed scale pseudo-head.
        # The head axis is no longer a clean Hkv multiple, so it replicates
        # over tp and shards batch on dp only (int8 + mesh decodes via the
        # XLA path, which reads whole heads anyway).
        return {
            "k": {
                "q": P("pp", "dp", None, None, None),
                "s": P("pp", "dp", None, None),
            },
            "v": {},
        }
    return {"k": row, "v": row}


def kv_pool_specs(quantized: bool = False, latent: bool = False) -> dict[str, Any]:
    """Specs for the physical prefix pool (executor/physical.py pool_like):
    pool leaves are the arena leaves with batch→pool-row and S→block_tokens
    `[L, PXB, Hx, bt, ...]`. Axis-for-axis the cache specs apply, EXCEPT the
    pool-row axis replicates instead of sharding on dp — pool rows hold
    shared prefix blocks any slot on any dp shard may gather through its
    block table, so they are a global resource, not slot-partitioned."""
    def drop_dp(spec: Any) -> Any:
        if not isinstance(spec, P):
            return spec
        return P(*(None if ax == "dp" else ax for ax in spec))

    return jax.tree.map(
        drop_dp, kv_cache_specs(quantized=quantized, latent=latent),
        is_leaf=lambda x: isinstance(x, P),
    )


def supports_ragged_prefill(mesh: Mesh | None) -> bool:
    """Whether the ragged packed-prefill path (kernels/attention.py
    ragged_* family) may run under `mesh`.

    The ragged kernels take the packed [T] token buffer and the per-row
    (slot, start, len) descriptors as whole-array operands and stream cache
    blocks by absolute physical index. Rows bound for different dp shards
    interleave inside one packed buffer, and sp would split the per-row DMA
    descriptors mid-stream — any mesh with dp/sp/ep > 1 keeps the bucketed
    chunk path, which shards per kv_cache_specs. Pure pp×tp meshes are fine:
    the packed buffer replicates, heads/layers shard cleanly, and the engine
    forces the XLA ragged impl (no Pallas DMA descriptors) whenever
    mesh.size > 1."""
    if mesh is None or mesh.size == 1:
        return True
    shape = dict(mesh.shape)
    return all(shape.get(ax, 1) == 1 for ax in ("dp", "sp", "ep"))


def named_shardings(mesh: Mesh, specs: Any) -> Any:
    """PartitionSpec tree → NamedSharding tree on `mesh`."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


def shard_pytree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """Place a pytree on the mesh according to matching PartitionSpecs."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )
