#!/usr/bin/env python3
"""Where the two forms of `models/moe.py:moe_share_ffn` cross, on the chip.

    chiprun -- python3 scripts/moe_crossing.py [--rows 64 512 1024]

The expert layer of `solar-open2-250b-ep8` at its published widths (40 held
experts of 320 scored, 8 a row, banks [4, 40, 4096, 1280] bfloat16 stacked over
the four layers and handed over whole with a traced layer index, as the layer
scan does), seeded weights and rows, one jitted call a layer. For each row
count both forms are timed (host clock around `reps` calls that end in
`block_until_ready`, after three that warm up) and the line says which won:
`EXPERT_MAJOR_MAX_ROWS` stands on these readings (PERF.md section 6). An even
router: a row lands 1.0 pairs on the held experts in expectation, where the
cell's seeded hidden states land 0.73 (PERF.md section 5). Refuses to run
without a TPU: a CPU time says nothing about the crossing."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, nargs="+", default=[64, 128, 256, 512, 768, 1024, 2048])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.models import moe
    from llm_mcp_tpu.models.configs import get_config

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): the crossing is a chip reading", file=sys.stderr)
        return 2
    cfg = get_config("solar-open2-250b-ep8")
    key = jax.random.PRNGKey(args.seed)
    params = jax.jit(lambda k: moe.init_moe_layer_params(cfg, k, jnp.bfloat16))(key)
    params["router_bias"] = 0.01 * jax.random.normal(key, (cfg.n_layers, cfg.router_width))
    banks = {n: params.pop(n) for n in ("w1e", "w3e", "w2e")}

    for rows in args.rows:
        x = jax.random.normal(jax.random.fold_in(key, rows), (rows, cfg.dim), jnp.bfloat16)
        line = {"rows": rows, "device": dev.device_kind, "layers": cfg.n_layers}
        for form, cap in (("expert_major", 1 << 30), ("grouped", 0)):
            moe.EXPERT_MAJOR_MAX_ROWS = cap  # forces the form of what is traced from here on

            def layer_call(x, params, banks, li):  # a function of its own a form: jit caches by function
                lp = jax.tree.map(lambda a: a[li], params)
                return moe.moe_share_ffn(cfg, lp, x, banks=banks, layer=li)

            fn = jax.jit(layer_call)
            text = str(jax.make_jaxpr(layer_call)(x, params, banks, jnp.int32(0)))
            assert ("ragged_dot" in text) == (form == "grouped"), form
            layers = [jnp.int32(i) for i in range(cfg.n_layers)]
            counts = [fn(x, params, banks, li)[1] for _ in range(3) for li in layers][-cfg.n_layers:]
            jax.block_until_ready(counts)
            t0 = time.perf_counter()
            outs = [fn(x, params, banks, li)[0] for _ in range(args.reps) for li in layers]
            jax.block_until_ready(outs)
            line[f"{form}_ms_a_layer"] = (time.perf_counter() - t0) * 1e3 / (args.reps * cfg.n_layers)
            line["touched_a_layer"] = sum(int(c[2]) for c in counts) / cfg.n_layers
            line["pairs_a_layer"] = sum(int(c[1]) for c in counts) / cfg.n_layers
        line["wins"] = min(("expert_major", "grouped"), key=lambda f: line[f"{f}_ms_a_layer"])
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
