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
        [--lin-value-dim 128] [--chip]

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

`--chip` (PR 55) lowers the CELLS' programs instead: every row of
tests/cell_programs.py (PR 61: the table tests/test_tpu_compile.py compiles for
the described chip, so what is hashed here is what is compiled there), which is
each benchmark configuration at its published widths with its cell's slots and
length, and each program the cell dispatches (the decode round of 4 steps, the
admit programs, the bucketed and the packed chunk, the mixed round at both
rungs, SDAR's block round whole and compact) at the operand shapes its traffic
gives it, 44 programs. They are lowered FOR the TPU (`lowering_platforms`, no chip
and no libtpu; the platform question answered "tpu", so the dispatchers take
the kernels with interpret mode off and the arms a chip would compile): a
Mosaic kernel is then a `tpu_custom_call` whose body is serialised bytecode
WITH the source lines of the kernel's Python, which move with every edit above
them, so each body is read back and printed without locations before the text
is hashed. `--tree` names the tree whose `llm_mcp_tpu` is lowered; the table is
this tree's. PR 55 read the fifteen programs of the four cells of heads of 128
equal between a600a99 and its own tree (the tiny presets cannot show that: all
but `tiny-lfm2` have heads no row of 128 lanes holds whole), and Granite's and
LFM2's, heads of 64, all differ; PR 58 added the latent family's cell. Until PR
61 this mode hashed ONE step of each kind at shapes of its own (64 x 1024, 256
packed tokens), from a second hand copy of the programs that lacked SDAR.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import os
import sys
from functools import partial

PRESETS = ("tiny-solar", "tiny-olmo-hybrid", "tiny-granite-hybrid", "tiny-kexaone", "tiny-lfm2")


def without_locations(text: str) -> str:
    """`text` with every Mosaic kernel's serialised body replaced by the body
    printed without its source locations."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True  # the serialised dialect, `stable_mosaic`

    def body(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation.get_asm(
                enable_debug_info=False)
        return f'body\\22: \\22<{hashlib.sha1(asm.encode()).hexdigest()}>\\22'

    return re.sub(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--out", default="")
    ap.add_argument("--lin-value-dim", type=int, default=0)
    ap.add_argument("--chip", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))

    import cell_programs
    import jax
    import jax.numpy as jnp
    from jax._src.lib.mlir import passmanager

    from llm_mcp_tpu.models import llama
    from llm_mcp_tpu.models.configs import MODEL_CONFIGS, get_config
    from llm_mcp_tpu.utils import platform

    def digest(name, tag, lowered):
        module = lowered.compiler_ir("stablehlo")
        with module.context:
            passmanager.PassManager.parse("builtin.module(cse,canonicalize,cse)").run(
                module.operation)
        text = without_locations(str(module)) if args.chip else str(module)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{name}.{tag}.mlir"), "w") as f:
                f.write(text)
        print(name, tag, hashlib.sha1(text.encode()).hexdigest()[:12], flush=True)

    if args.chip:
        platform.device_platform = lambda: "tpu"
        for cell, which, operands in cell_programs.ROWS:
            spec = cell_programs.CELLS[cell]
            if spec.config not in MODEL_CONFIGS:  # a tree from before the configuration
                continue
            digest(spec.config, cell_programs.row_id(cell, which, operands),
                   cell_programs.traced(cell, which, operands).lower(lowering_platforms=("tpu",)))
        return 0
    for name in PRESETS:
        if name not in MODEL_CONFIGS:  # a tree from before the preset
            continue
        cfg = get_config(name)
        if args.lin_value_dim and cfg.lin_heads:
            cfg = dataclasses.replace(cfg, lin_value_dim=args.lin_value_dim)
        params = jax.eval_shape(
            partial(llama.init_llama_params, cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
        cache = jax.eval_shape(
            partial(llama.init_kv_cache, cfg, 4, 128, dtype=jnp.float32, quantized=True))
        for tag, (fn, operands) in cell_programs.preset_steps(cfg, 4, 128, 4).items():
            digest(name, tag, jax.jit(fn).trace(params, cache["k"], cache["v"], *operands).lower(
                lowering_platforms=("cpu",)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
