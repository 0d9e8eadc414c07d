"""Multi-host (DCN) data plane: `parallel/distributed.py` executed for real.

Two OS processes form a `jax.distributed` cluster over a localhost
coordinator (the standard env triplet the k8s manifests set from the
StatefulSet ordinal), build ONE global mesh spanning both processes'
devices, and run a sharded tiny-llm decode step whose dp axis crosses the
process boundary — the same program a 2-host TPU pod runs, shrunk to
4 CPU devices per process. Reference scale-out analog: SURVEY.md §2.2
(NCCL-free HTTP/gRPC cluster plane + per-host workers); here the model's
data plane is one GSPMD program instead.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as P

from llm_mcp_tpu.parallel import distributed
from llm_mcp_tpu.parallel.sharding import llama_param_specs, kv_cache_specs
from llm_mcp_tpu.models import (
    get_config, init_llama_params, init_kv_cache, llama_decode_step,
)

# env triplet (JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/JAX_PROCESS_ID)
# is set by the parent test
assert distributed.env_process_info() is not None
assert distributed.initialize() is True, "multi-process runtime expected"
assert jax.process_count() == 2, jax.process_count()
assert len(jax.local_devices()) == 4
assert len(jax.devices()) == 8

mesh = distributed.make_global_mesh("dp=4,tp=2")
assert mesh.devices.size == 8
assert distributed.dcn_axis({"dp": 4, "tp": 2}) == "dp"

cfg = get_config("tiny-llm")
B_global, S = 8, 32
B_local = distributed.host_local_batch(B_global)
assert B_local == 4

# identical host data on every process (deterministic PRNG) -> global arrays:
# params replicate, the KV cache and token rows shard over dp ACROSS the
# process boundary (each process owns 2 of the 4 dp shards).
params_h = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
cache_h = init_kv_cache(cfg, B_local, S, dtype=jnp.float32)

def to_global(tree, specs):
    return jax.tree.map(
        lambda x, s: multihost_utils.host_local_array_to_global_array(
            np.asarray(x), mesh, s
        ),
        tree, specs,
    )

params = to_global(params_h, jax.tree.map(lambda _: P(), params_h))
cache = to_global(cache_h, kv_cache_specs())
tokens = multihost_utils.host_local_array_to_global_array(
    np.ones((B_local,), np.int32), mesh, P("dp")
)
lengths = multihost_utils.host_local_array_to_global_array(
    np.full((B_local,), 5, np.int32), mesh, P("dp")
)

@jax.jit
def step(params, ck, cv, tokens, lengths):
    return llama_decode_step(cfg, params, ck, cv, tokens, lengths)

with mesh:
    logits, ck, cv = step(params, cache["k"], cache["v"], tokens, lengths)

assert logits.shape == (B_global, cfg.vocab_size), logits.shape
local = np.asarray(logits.addressable_shards[0].data)
assert np.isfinite(local).all()
# cross-process agreement: every slot got identical inputs (same tokens,
# lengths, zero cache, replicated params), so each process's first local
# row must match the other's bit-for-bit — a real check that the two
# processes ran one coherent GSPMD program, not two divergent ones.
gathered = np.asarray(
    multihost_utils.process_allgather(local[0], tiled=False)
)
assert gathered.shape == (2, cfg.vocab_size), gathered.shape
np.testing.assert_allclose(gathered[0], gathered[1], rtol=1e-5, atol=1e-5)
print(f"DIST OK p{jax.process_index()} logits={logits.shape}", flush=True)
"""


def test_two_process_jax_distributed_decode():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(pid)
        env["JAX_PLATFORMS"] = "cpu"
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _CHILD],
                env=env,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-3000:]}"
        assert f"DIST OK p{pid}" in out, out[-1500:]
