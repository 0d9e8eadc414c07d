#!/usr/bin/env python3
"""Digests of the step programs of the tiny hybrid presets, to hold two trees
equal before a chip call: for each preset the decode step, the mixed step, a
bucketed chunk and a whole-prompt prefill are lowered on the CPU (kernels in
interpret mode, so their bodies are in the text), the StableHLO is run through
`cse, canonicalize, cse` (a duplicated index computation or a reshape in two
steps is no other program) and hashed. Two trees whose digests agree give the
compiler the same programs at these presets; a change to shared code of
models/hybrid.py, llama.py, kda.py, ssm.py, moe.py or the kernels that is
meant to leave a configuration alone shows here in seconds.

    JAX_PLATFORMS=cpu python scripts/hybrid_hlo_digest.py [--tree _clean] [--out DIR]
        [--lin-value-dim 128]

`--tree` is a checkout of another commit (`git archive <commit> | tar -x -C
_clean`); `--out` keeps the texts, to diff where a digest differs. PR 43 read
all twelve equal between 40d3bf3 and its own tree; PR 52 added `tiny-kexaone`
(no mixed step: rings) and `tiny-lfm2`, and a preset the tree lacks is skipped. `--lin-value-dim 128` gives
the delta-rule presets Solar-Open2's own value heads, ONE head a tile of the
pool (`kernels/kda.py:heads_abreast`; `tiny-solar`'s 32 lie four abreast, which
Solar's never do): a change to where states are packed into the pool's layout
moves `tiny-solar`'s text and leaves Solar's alone, and shows so here (PR 50).
The whole-prompt prefill is lowered on the Pallas arm since PR 51, as the cells
run it (before, on the XLA arm, a change to `flash_prefill_attention` moved no
digest: PR 51 read the three prefills alone differ from dd59802's).
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import os
import sys
from functools import partial

PRESETS = ("tiny-solar", "tiny-olmo-hybrid", "tiny-granite-hybrid", "tiny-kexaone", "tiny-lfm2")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--out", default="")
    ap.add_argument("--lin-value-dim", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    import jax.numpy as jnp
    from jax._src.lib.mlir import passmanager

    from llm_mcp_tpu.models import hybrid, llama
    from llm_mcp_tpu.models.configs import MODEL_CONFIGS, get_config

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    B, T, R = 4, 128, 4
    for name in PRESETS:
        if name not in MODEL_CONFIGS:  # a tree from before the preset
            continue
        cfg = get_config(name)
        if args.lin_value_dim and cfg.lin_heads:
            cfg = dataclasses.replace(cfg, lin_value_dim=args.lin_value_dim)
        params = jax.eval_shape(
            partial(llama.init_llama_params, cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
        cache = jax.eval_shape(
            partial(llama.init_kv_cache, cfg, B, 128, dtype=jnp.float32, quantized=True))
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
        if not llama.mixed_step_supported(cfg):  # a stack with rings takes admit programs alone
            del programs["mixed"]
        for tag, (fn, operands) in programs.items():
            module = jax.jit(fn).lower(params, cache["k"], cache["v"], *operands).compiler_ir(
                "stablehlo")
            with module.context:
                passmanager.PassManager.parse("builtin.module(cse,canonicalize,cse)").run(
                    module.operation)
            text = str(module)
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                with open(os.path.join(args.out, f"{name}.{tag}.mlir"), "w") as f:
                    f.write(text)
            print(name, tag, hashlib.sha1(text.encode()).hexdigest()[:12], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
