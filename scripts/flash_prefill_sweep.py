#!/usr/bin/env python3
"""`kernels/attention.py:flash_prefill_attention` alone, on the chip.

    chiprun -- python3 scripts/flash_prefill_sweep.py [--blocks 0,0 128,128 128,256] [--tree _clean]

The prompt attention at the shapes the benchmark's admit programs run it at
(`[B, H, S, hd]` with `Hkv` KV heads: kexaone_reason_closed's two buckets,
`[1, 64, 1024, 128]` and `[1, 64, 768, 128]` over 8 KV heads, with its window
of 128 and as its global layer; Qwen3-8B's, Granite's heads of 64 and
Olmo-Hybrid's 30 heads of a group of one at a bucket of 1,024), the prompt's
length the middle of what fills the bucket, the window a TRACED scalar as a
scanned layer stack hands it over. For each pair of block sizes one jitted
function (a function of its own a pair: `jax.jit` caches by function) runs the
kernel `--calls` times, each call's output the next one's queries; the line
gives microseconds a call on the host's clock around `--reps` such functions
that end in `block_until_ready`, the largest distance of one call's rows under
the length to dense float32 math on the same operands, and what the products
the mask leaves (2 x 2 x hd a pair of a query and a key it attends) come to in
TFLOP/s. Pair 0,0 is the tree's own rule (`prefill_block`); with `--tree` at
a checkout of the parent, whose wrapper has its blocks fixed, the other pairs
are skipped. `prefill_block` stands on these readings (`PERF.md` section 6,
PR 51). Refuses to run without a TPU: a CPU time says nothing about a block."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SHAPES = {  # name: H, Hkv, S, hd, window, prompt length
    "kexaone_1024_win": (64, 8, 1024, 128, 128, 893),
    "kexaone_1024_global": (64, 8, 1024, 128, 0, 893),
    "kexaone_768_win": (64, 8, 768, 128, 128, 700),
    "kexaone_768_global": (64, 8, 768, 128, 0, 700),
    "qwen3_1024": (32, 8, 1024, 128, 0, 893),
    "granite_1024": (32, 8, 1024, 64, 0, 893),
    "olmo_hybrid_1024": (30, 30, 1024, 128, 0, 893),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", nargs="+", default=["0,0"], help="block_q,block_k pairs; 0,0 = the rule")
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES), choices=list(SHAPES))
    ap.add_argument("--calls", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    help="the checkout whose llm_mcp_tpu is measured")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.kernels import attention as A

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): a block size is a chip reading", file=sys.stderr)
        return 2
    has_rule = hasattr(A, "prefill_block")

    for name in args.shapes:
        H, Hkv, S, hd, window, n = SHAPES[name]
        key = jax.random.PRNGKey(args.seed)
        q = jax.random.normal(key, (1, H, S, hd), jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, Hkv, S, hd), jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, Hkv, S, hd), jnp.bfloat16)
        lens = jnp.asarray([n], jnp.int32)
        win = jnp.int32(window)

        pos = jnp.arange(S)
        seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n)
        if window:
            seen &= pos[:, None] - pos[None, :] < window
        pairs = int(jnp.sum(seen[:n]))  # a query under the length and a key it attends
        scores = jnp.einsum("hgqd,hkd->hgqk", q[0].reshape(Hkv, H // Hkv, S, hd).astype(jnp.float32),
                            k[0].astype(jnp.float32)) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, A.NEG_INF), axis=-1)
        ref = jnp.einsum("hgqk,hkd->hgqd", probs, v[0].astype(jnp.float32)).reshape(H, S, hd)[:, :n]
        del scores, probs

        for pair in args.blocks:
            bq, bk = (int(x) for x in pair.split(","))
            if (bq or bk) and not has_rule:
                continue
            if S % (bq or S) or S % (bk or S):
                continue  # a pair that does not tile this bucket
            kw = {"block_q": bq, "block_k": bk} if bq or bk else {}

            def many(q, k, v, lens, win, kw=kw):  # a function of its own a pair
                def call(_, x):
                    return A.flash_prefill_attention(x, k, v, lens, window=win, interpret=False, **kw)
                return jax.lax.fori_loop(0, args.calls, call, q)

            fn = jax.jit(many)
            try:
                jax.block_until_ready(fn(q, k, v, lens, win))
            except Exception as e:  # noqa: BLE001 — a pair the compiler refuses is a reading too
                print(json.dumps({"shape": name, "blocks": pair, "refused": str(e)[:300]}), flush=True)
                continue
            one = A.flash_prefill_attention(q, k, v, lens, window=win, interpret=False, **kw)
            err = float(jnp.max(jnp.abs(one[0, :, :n].astype(jnp.float32) - ref)))
            t0 = time.perf_counter()
            jax.block_until_ready([fn(q, k, v, lens, win) for _ in range(args.reps)])
            us = (time.perf_counter() - t0) * 1e6 / (args.reps * args.calls)
            rule = A.prefill_block(H // Hkv, S) if has_rule else 128  # the parent's: 128 x 128
            eff = (bq or rule, bk or rule)
            print(json.dumps({
                "shape": name, "qkv": [H, Hkv, S, hd], "window": window, "length": n,
                "device": dev.device_kind, "blocks": "rule" if not (bq or bk) else pair,
                "block_q": eff[0], "block_k": eff[1], "us_a_call": round(us, 1),
                "max_err_to_f32": round(err, 4),
                "masked_tflop_s": round(4 * hd * pairs * H / us / 1e6, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
