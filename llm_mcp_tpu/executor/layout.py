"""The KV cache's layout, decided once.

`GenerationEngine.__init__` builds ONE `CacheLayout` from what it knows before
anything is allocated; the value says what the cache pair IS, how it is made,
how it is read and what it rules out, and nothing else in the engine decides
any of that again:

    preset, kv_quant             latent int8   fused slot_member name
    tiny-llm ""                  False  False  False ""          gqa_bf16
    tiny-llm "int8"              False  True   True  ""          gqa_int8
    tiny-mla ""                  True   False  False ""          mla_bf16
    tiny-mla "int8"              True   True   False ""          mla_int8
    tiny-solar "int8"            False  True   True  "state"     gqa_int8
    tiny-olmo-hybrid "int8"      False  True   True  "state"     gqa_int8
    tiny-granite-hybrid "int8"   False  True   True  "state"     gqa_int8
    tiny-kexaone "int8"          False  True   True  "win"       gqa_int8
    tiny-lfm2 "int8"             False  True   True  "state"     gqa_int8
    tiny-joyai "int8"            True   True   False ""          mla_int8   (counted)
    tiny-sdar "int8"             False  True   True  ""          gqa_int8   (counted)

`latent`: MLA's two asymmetric members (models/mla.py). `counted`: a latent or a
dense pair whose expert layer counts its work (`moe.share_form`): the counts
[2, Le, 5] ride the second member beside the rope keys (the V rows of a dense
pair: none in the fused form), {"v": those rows, "moe": the counts}, as they ride
a hybrid pair's; `without` is then `memory.COUNTED_OFF`, what takes the second
member for bare rows, or `memory.BLOCK_OFF` where the configuration generates by
diffusion over blocks (`cfg.block_len`). `wrapped`: the second member is such a dict
(a slot member, or the counts), and the full-length rows are its "v". `fused`: int8 GQA, V
rides `cache["k"]`'s head axis and `cache["v"]` is the empty dict
(models/llama.py:init_kv_cache). `slot_member`: the member of `cache["v"]` that
holds one row a slot beside the full-length rows (`hybrid.SLOT_MEMBERS`; a
"state" is a matrix state and a convolution's tail a layer, or for a kind
without a matrix state, `tiny-lfm2`'s, the tail alone); the
full-length rows' second member is then `cache["v"]["v"]`. `without`: what
such a configuration runs without, feature to reason (`memory.RECURRENT_OFF`,
the one list), empty where every layer keeps full-length rows.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Mapping

import jax
from jax.sharding import Mesh, PartitionSpec

from ..models.configs import ModelConfig
from ..models.llama import fuse_prompt_kv, init_kv_cache, quantize_kv
from ..models.moe import share_form
from ..parallel.sharding import kv_cache_specs, kv_pool_specs, named_shardings
from ..telemetry.perf import layout_name
from .memory import BLOCK_OFF, COUNTED_OFF, RECURRENT_OFF
from .physical import pool_like


@dataclass(frozen=True)
class CacheLayout:
    cfg: ModelConfig
    max_slots: int
    max_seq_len: int
    dtype: Any
    int8: bool  # the validated kv_quant
    mesh: Mesh | None = None

    @property
    def latent(self) -> bool:
        return bool(self.cfg.kv_lora_rank)

    @property
    def fused(self) -> bool:
        return self.int8 and not self.latent

    @property
    def slot_member(self) -> str:
        if not self.cfg.recurrent:
            return ""
        return "win" if self.cfg.recurrent_kind == "win" else "state"

    @property
    def counted(self) -> bool:
        return not self.cfg.recurrent and share_form(self.cfg)

    @property
    def wrapped(self) -> bool:
        return bool(self.slot_member) or self.counted

    @property
    def name(self) -> str:
        return layout_name(self.latent, self.int8)

    @property
    def without(self) -> Mapping[str, str]:
        if self.slot_member:
            return RECURRENT_OFF
        if self.cfg.block_len:
            return BLOCK_OFF
        return COUNTED_OFF if self.counted else {}

    # -- how it is made ------------------------------------------------------

    def _init(self) -> dict[str, Any]:
        return init_kv_cache(self.cfg, self.max_slots, self.max_seq_len,
                             dtype=self.dtype, quantized=self.int8)

    def specs(self) -> dict[str, Any]:
        """PartitionSpecs of the pair, in the tree `allocate` returns. The
        members beside the full-length rows (a slot member, the expert counts)
        replicate: such a configuration runs on one chip."""
        specs = kv_cache_specs(quantized=self.int8, latent=self.latent)
        if self.wrapped:
            beside = jax.eval_shape(self._init)["v"]
            specs["v"] = {m: specs["v"] if m == "v" else jax.tree.map(lambda _: PartitionSpec(), sub)
                          for m, sub in beside.items()}
        return specs

    def pool_specs(self) -> dict[str, Any]:
        return kv_pool_specs(quantized=self.int8, latent=self.latent)

    def _born(self, make, specs) -> Any:
        """What `make` returns: plain without a mesh; under one, born sharded
        as ONE program with explicit out_shardings, so that no device (and,
        multi-controller, no process) ever holds the whole tree. Made on the
        default device and sharded afterwards it is the whole cache on chip 0:
        an OOM on the four-chip host the mesh exists for."""
        if self.mesh is None:
            return make()
        with self.mesh:
            return jax.jit(make, out_shardings=named_shardings(self.mesh, specs))()

    def allocate(self) -> dict[str, Any]:
        """The cache pair {"k", "v"}, zeroed."""
        return self._born(self._init, self.specs())

    def allocate_pools(self, rows: int, block_tokens: int) -> dict[str, Any]:
        """The physical prefix pools {"k", "v"} (executor/physical.py): the
        pair's leaves with the slot axis `rows` long and S `block_tokens`."""
        return self._born(
            partial(pool_like, jax.eval_shape(self._init), rows, block_tokens, self.max_seq_len),
            self.pool_specs())

    def entries(self, ks, vs) -> tuple[Any, Any]:
        """A prompt's float K/V rows in the form the pair stores them; inside
        the prefill's jit, so that the float KV of a batched admission (A x
        bucket rows x L layers) never lands in HBM outside the fused program."""
        if self.fused:
            return fuse_prompt_kv(ks, vs, scale_dtype=self.dtype), {}
        if self.int8:
            return quantize_kv(ks, scale_dtype=self.dtype), quantize_kv(vs, scale_dtype=self.dtype)
        return ks, vs

    # -- how it is read ------------------------------------------------------

    def kv_rows(self, ck: Any, cv: Any) -> dict[str, Any]:
        """The members of a pair that hold full-length KV rows."""
        return {"k": ck, "v": cv["v"] if self.wrapped else cv}
