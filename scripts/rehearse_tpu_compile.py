#!/usr/bin/env python3
"""Rehearsal 3 for the whole step programs: compile the engine's jitted
decode, fused decode+ragged-chunk, admit and ragged-prefill programs at the
real size for a TPU v5e that is described, not attached.

    JAX_PLATFORMS=cpu python scripts/rehearse_tpu_compile.py            # int8 8B, one chip
    JAX_PLATFORMS=cpu python scripts/rehearse_tpu_compile.py --tp 4     # bf16 8B, tp=4

Costs no chip time and finds what interpret mode cannot: a kernel Mosaic
refuses, a program that does not fit HBM, a kernel the compiler chews on
for minutes. Nothing runs, so it says nothing about results or times; a
compile that passes here is not a chip run.

How: the engine is built here on the CPU through its normal constructor
(real arrays in host RAM: about 15 GB for the int8 8B engine, 25 GB for
bf16), with the one platform question (utils/platform.py) answered "tpu"
so every resolver takes the kernel path with interpret mode off. Each step
program is then lowered with the operands `engine.warmup_operands` gives
(shapes, dtypes and shardings, no data), every sharding replaced by the
described chip's, and compiled. The persistent
compile cache stays off: a compile for a described chip must neither read
the CPU tests' entries nor leave entries a real chip cannot load.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# opcodes that hand an array on without making one
_PASS_THROUGH = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
                 "conditional", "call", "opt-barrier"}


def stacked_weight_dims(layers) -> set[tuple[int, int]]:
    """Trailing two dims of every stacked weight ([L, in, out] and deeper)
    of a `params["layers"]` tree of arrays or shapes."""
    import jax

    return {tuple(x.shape[-2:]) for x in jax.tree.leaves(layers) if len(x.shape) >= 3}


def stacked_weight_producers(hlo: str, dims: set[tuple[int, int]]) -> list[tuple[str, str, str]]:
    """Instructions of a compiled module that MAKE an array in HBM with the
    trailing two dims of a stacked layer weight: (computation or "ENTRY",
    opcode, shape). A step program should have none: its matmuls read each
    layer's weights where they lie in the stacked tree. `lax.scan(unroll=4)`
    over the stacked tree gave one `dynamic-slice_bitcast_fusion s8[4, ...]`
    per weight and layer group, every weight byte read, written and read again.

    Not counted: instructions inside fused computations (a fusion's inner
    `dynamic-slice` is the read in place), the pass-through opcodes, and
    arrays the compiler places in on-chip memory (`S(n)` in the layout: a
    prefetch reads its bytes from HBM once)."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    found, comp = [], None
    for line in hlo.splitlines():
        if line.startswith(("%", "ENTRY %")) and line.endswith("{"):
            name = line.split("%", 1)[1].split(" ", 1)[0]
            comp = None if name in fused else "ENTRY" if line.startswith("ENTRY") else name
        elif line.startswith("}"):
            comp = None
        elif comp and (m := re.match(r"\s+(?:ROOT )?%\S+ = (.*?) ([\w\-]+)\(", line)):
            result, opcode = m.groups()
            if opcode in _PASS_THROUGH:
                continue
            for dtype, shape, layout in re.findall(r"(\w+)\[([\d,]+)\](\{[^}]*\})?", result):
                d = tuple(int(x) for x in shape.split(","))
                if d[-2:] in dims and not re.search(r"S\([1-9]", layout):
                    found.append((comp, opcode, f"{dtype}[{shape}]"))
    return found


def main() -> int:
    if "TPU_LOG_DIR" not in os.environ:  # libtpu's own variable: keep its logs out of /tmp
        os.environ["TPU_LOG_DIR"] = "disabled"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["TPU_WARMUP"] = "0"
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="llama-3.1-8b")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--tp", type=int, default=1,
                    help="1: int8 weights + int8 KV on one chip (the smoke's "
                         "default path); 4: bf16 on a tp=4 mesh of the 2x2 host")
    ap.add_argument("--only", default="",
                    help="comma-separated phase names to keep (decode, "
                         "fused_rag, pf_rag, admit)")
    args = ap.parse_args()

    if args.tp > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.tp}".strip()
        )

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.utils import platform

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    platform.device_platform = lambda: "tpu"  # the one place the program asks

    t0 = time.time()
    if args.tp == 1:
        eng = GenerationEngine(
            args.model, max_slots=args.slots, max_seq_len=args.seq,
            dtype=jnp.bfloat16, quant="int8", kv_quant="int8",
        )
        target = SingleDeviceSharding(topo.devices[0])
        reshard = lambda sh: target  # noqa: E731
    else:
        from llm_mcp_tpu.parallel import distributed

        mesh = distributed.make_global_mesh(f"tp={args.tp}")
        eng = GenerationEngine(
            args.model, mesh=mesh, max_slots=args.slots, max_seq_len=args.seq,
            dtype=jnp.bfloat16,
        )
        import numpy as np

        tmesh = Mesh(
            np.array(topo.devices[: args.tp]).reshape(mesh.devices.shape),
            mesh.axis_names,
        )
        rep = NamedSharding(tmesh, PartitionSpec())

        def reshard(sh):
            # the same PartitionSpec, on the described chips
            return NamedSharding(tmesh, sh.spec) if isinstance(sh, NamedSharding) else rep

    print(f"engine built on the CPU in {time.time() - t0:.0f} s: "
          f"attn_impl={eng.attn_impl} decode_impl={eng.decode_impl} "
          f"ragged={eng._ragged_impl or 'off'} cap={eng._ragged_cap} "
          f"paged={eng._phys is not None}", flush=True)

    phys = eng._phys is not None
    B = eng.max_slots
    zoo: list[tuple[str, tuple]] = [
        (ph, key) for ph, key in eng.warmup_shape_zoo() if ph in ("decode", "mixed")
    ]
    if eng._ragged_cap:
        skey = 0 if eng._ragged_impl == "kernel" else min(128, eng.max_seq_len)
        ts = sorted({min(32, eng._ragged_cap), min(512, eng._ragged_cap), eng._ragged_cap})
        zoo += [("pf_rag", (t, skey, phys)) for t in ts]
        compact = eng.decode_compact
        zoo += [("fused_rag", (min(8, B) if compact else B, compact, t, skey, phys))
                for t in (ts[0], ts[-1])]
    zoo += [("admit", (1, 32)), ("admit", (4, 512))]
    only = {p for p in args.only.split(",") if p}
    if only:
        zoo = [z for z in zoo if z[0] in only]

    def on_described(x):
        # an operand's shape and dtype, on the described chips; the static
        # arguments (compact, skey) pass through
        if not isinstance(x, jax.ShapeDtypeStruct):
            return x
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=reshard(x.sharding))

    hbm = 16 * (1 << 30)
    wdims = stacked_weight_dims(eng.params["layers"])
    failed = 0
    for phase, key in zoo:
        t1 = time.time()
        try:
            fn, a, kw = eng.warmup_operands(phase, key)
            a, kw = jax.tree.map(on_described, (a, kw))
            compiled = fn.lower(*a, **kw).compile()
        except Exception as e:  # noqa: BLE001 — report every refusal, then fail
            failed += 1
            print(f"REFUSED {phase} {key}: {type(e).__name__}: {str(e)[:1500]}",
                  flush=True)
            continue
        ma = compiled.memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        txt = compiled.as_text()
        kernels = sorted({re.sub(r"(\.\d+)+$", "", m) for m in re.findall(
            r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", txt)})
        copies = stacked_weight_producers(txt, wdims)
        print(f"ok {phase} {key}: {time.time() - t1:.1f} s  "
              f"tpu_custom_call={txt.count('tpu_custom_call')} {kernels}  "
              f"per-device bytes={need} = {need / 2**30:.2f} GiB "
              f"(args {ma.argument_size_in_bytes / 2**30:.2f}, "
              f"temp {ma.temp_size_in_bytes / 2**30:.2f}, "
              f"aliased {ma.alias_size_in_bytes / 2**30:.2f})"
              f"  stacked-weight copies={len(copies)} {sorted({c[2] for c in copies})}"
              f"{'  OVER 16 GiB' if need > hbm else ''}", flush=True)
        if need > hbm:
            failed += 1
    print(f"{len(zoo) - failed}/{len(zoo)} step programs compiled for "
          f"{topo.devices[0].device_kind} (described)")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
