"""executor/layout.py: the one value that says which cache an engine holds.

The table of the module's docstring, held row by row without booting an
engine, and the pair `_recover_cache` leaves held to the pair at boot."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec

from llm_mcp_tpu.executor.layout import CacheLayout
from llm_mcp_tpu.executor.memory import RECURRENT_OFF
from llm_mcp_tpu.models.configs import get_config
from llm_mcp_tpu.models.hybrid import SLOT_MEMBERS

#  preset, kv_quant -> latent, int8, fused, slot_member, name
TABLE = [
    ("tiny-llm", "", (False, False, False, "", "gqa_bf16")),
    ("tiny-llm", "int8", (False, True, True, "", "gqa_int8")),
    ("tiny-mla", "", (True, False, False, "", "mla_bf16")),
    ("tiny-mla", "int8", (True, True, False, "", "mla_int8")),
    ("tiny-solar", "int8", (False, True, True, "state", "gqa_int8")),
    ("tiny-olmo-hybrid", "int8", (False, True, True, "state", "gqa_int8")),
    ("tiny-granite-hybrid", "int8", (False, True, True, "state", "gqa_int8")),
    ("tiny-kexaone", "int8", (False, True, True, "win", "gqa_int8")),
    ("tiny-lfm2", "int8", (False, True, True, "state", "gqa_int8")),  # a state of tails alone
]


def _described(tree):
    return jax.tree.map(lambda x: (x.shape, str(x.dtype), str(x.sharding)), tree)


@pytest.mark.parametrize("preset,kv_quant,row", TABLE, ids=[f"{p}-{q or 'float'}" for p, q, _ in TABLE])
def test_the_layout_is_what_its_table_says(preset, kv_quant, row):
    slots, seq = 2, 256
    layout = CacheLayout(get_config(preset), slots, seq, jnp.float32, kv_quant == "int8")
    assert (layout.latent, layout.int8, layout.fused, layout.slot_member, layout.name) == row
    assert layout.slot_member in ("", *SLOT_MEMBERS)
    cache = layout.allocate()
    assert set(cache) == {"k", "v"}
    is_spec = lambda x: isinstance(x, PartitionSpec)
    assert jax.tree.structure(cache) == jax.tree.structure(layout.specs(), is_leaf=is_spec)
    if layout.fused:  # V rides the first member's head axis
        assert layout.kv_rows(cache["k"], cache["v"])["v"] == {} and cache["k"]["q"].dtype == jnp.int8
    if layout.slot_member:
        assert dict(layout.without) == RECURRENT_OFF and layout.slot_member in cache["v"]
        assert layout.kv_rows(cache["k"], cache["v"])["v"] is cache["v"]["v"]
    else:
        assert not layout.without and layout.kv_rows(cache["k"], cache["v"])["v"] is cache["v"]
        # the prefix pools mirror the pair: pool rows for slots, a block for S
        pools = layout.allocate_pools(3, 32)
        assert jax.tree.structure(pools) == jax.tree.structure(layout.pool_specs(), is_leaf=is_spec)
        # (a leaf of P positions abreast is pooled apart, a P-th as wide)
        assert jax.tree.map(
            lambda p, c: p.shape == (c.shape[0], 3, c.shape[2], 32) + tuple(
                n * c.shape[3] // seq for n in c.shape[4:]) and p.dtype == c.dtype,
            pools, cache) == jax.tree.map(lambda _: True, cache)
    # every full-length leaf is [layers, slots, heads, S, ...], but the latent pair's
    # int8 rope keys: P = 128 // 16 positions abreast in rows of whole lanes
    rows = layout.kv_rows(cache["k"], cache["v"])
    abreast = rows["v"].pop("q") if layout.latent and layout.int8 else None
    assert all(x.shape[1] == slots and x.shape[3] == seq for x in jax.tree.leaves(rows))
    assert abreast is None or abreast.shape == (2, slots, 1, seq // 8, 128)


@pytest.mark.parametrize("meshed", [False, True], ids=["no-mesh", "one-device-mesh"])
def test_a_recovered_cache_is_the_cache_at_boot(meshed):
    """A failed donated dispatch deletes the pair and the pools; what
    `_recover_cache` leaves is allocated as at boot: under a mesh born sharded,
    not made on the default device and moved."""
    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.parallel.mesh import make_mesh

    mesh = make_mesh("tp=1", devices=jax.devices()[:1]) if meshed else None
    eng = GenerationEngine("tiny-llm", mesh=mesh, max_slots=2, max_seq_len=128,
                           dtype=jnp.float32, kv_quant="int8")
    pair = lambda: {"k": eng._ck, "v": eng._cv, "pk": eng._pool_k, "pv": eng._pool_v}
    assert eng._pool_k is not None and not eng._recover_cache()  # nothing lost, nothing done
    boot = _described(pair())
    for leaf in jax.tree.leaves(pair()):
        leaf.delete()
    assert eng._recover_cache()
    assert _described(pair()) == boot
    assert all(not x.is_deleted() and not x.any() for x in jax.tree.leaves(pair()))
