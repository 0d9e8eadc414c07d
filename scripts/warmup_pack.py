#!/usr/bin/env python3
"""Warmup pack: ship a fleet's compile cache + measured warmup plan.

A cold process pays 1-2 minutes of XLA compiles before its first token.
Two artifacts make that cost portable (doc/performance.md "Cold start &
warmup"): the persistent XLA compile cache (the executables
themselves) and the compile ledger's per-shape aggregates
(which shapes a real serve window actually dispatched, and what each
cost). This tool bundles both into a directory you can rsync/objstore to
a joining host, so its warmup planner (executor/warmup.py) deserializes
the exporting fleet's executables in measured-cost × hit-priority order
instead of compiling its config-derived zoo blind.

    # on a warm host (core running, cache populated):
    python scripts/warmup_pack.py export PACK_DIR --core http://localhost:8080

    # on the joining host (before boot):
    python scripts/warmup_pack.py import PACK_DIR

Pack layout: PACK_DIR/cache/* (verbatim XLA cache entries — content-keyed
files, safe to merge), PACK_DIR/warmup_plan.json (compile-ledger table
rows), PACK_DIR/manifest.json. Import copies cache entries into the
resolved cache dir and drops warmup_plan.json beside them, where
CoreServer.boot_warmup auto-loads it as plan priors. Both directions
resolve the cache dir through the one rule
(utils/config.compile_cache_path: JAX_COMPILATION_CACHE_DIR where set,
else <checkout>/.jax_cache) unless --cache-dir overrides it.

Export plan sources, first available wins: --plan FILE (a saved
/v1/debug/compiles response or bare table list), --core URL (live fetch).
A pack without a plan is still useful (cache hits in config-zoo order);
a plan without cache entries still orders the compiles correctly.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llm_mcp_tpu.utils.config import compile_cache_path  # noqa: E402


def _resolve_cache_dir(arg: str | None) -> str:
    return arg or compile_cache_path()


def _plan_rows(doc: object) -> list[dict]:
    """Ledger table rows from a /v1/debug/compiles response or a bare list."""
    if isinstance(doc, dict):
        doc = doc.get("table", [])
    rows = [r for r in (doc or []) if isinstance(r, dict) and "phase" in r and "key" in r]
    return rows


def cmd_export(args: argparse.Namespace) -> int:
    cache_dir = _resolve_cache_dir(args.cache_dir)
    plan: list[dict] = []
    if args.plan:
        with open(args.plan, encoding="utf-8") as fh:
            plan = _plan_rows(json.load(fh))
    elif args.core:
        url = f"{args.core.rstrip('/')}/v1/debug/compiles?limit=0"
        with urllib.request.urlopen(url, timeout=10.0) as r:  # noqa: S310
            plan = _plan_rows(json.loads(r.read()))

    out_cache = os.path.join(args.pack_dir, "cache")
    os.makedirs(out_cache, exist_ok=True)
    copied = 0
    if os.path.isdir(cache_dir):
        for name in sorted(os.listdir(cache_dir)):
            src = os.path.join(cache_dir, name)
            if not os.path.isfile(src) or name == "warmup_plan.json":
                continue
            shutil.copy2(src, os.path.join(out_cache, name))
            copied += 1
    with open(os.path.join(args.pack_dir, "warmup_plan.json"), "w", encoding="utf-8") as fh:
        json.dump(plan, fh, indent=1)
    manifest = {
        "kind": "warmup_pack",
        "version": 1,
        "created_at": time.time(),
        "cache_files": copied,
        "plan_rows": len(plan),
        "source_cache_dir": cache_dir,
    }
    with open(os.path.join(args.pack_dir, "manifest.json"), "w", encoding="utf-8") as fh:
        json.dump(manifest, fh, indent=1)
    print(f"exported {copied} cache file(s), {len(plan)} plan row(s) -> {args.pack_dir}")
    if not copied and not plan:
        print("warning: empty pack (no cache files, no plan rows)", file=sys.stderr)
    return 0


def cmd_import(args: argparse.Namespace) -> int:
    cache_dir = _resolve_cache_dir(args.cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    in_cache = os.path.join(args.pack_dir, "cache")
    copied = skipped = 0
    if os.path.isdir(in_cache):
        for name in sorted(os.listdir(in_cache)):
            src = os.path.join(in_cache, name)
            dst = os.path.join(cache_dir, name)
            if not os.path.isfile(src):
                continue
            # XLA cache entries are content-keyed: an existing same-named
            # entry IS the same executable — never clobber a warm cache
            if os.path.exists(dst):
                skipped += 1
                continue
            shutil.copy2(src, dst)
            copied += 1
    plan_src = os.path.join(args.pack_dir, "warmup_plan.json")
    plan_rows = 0
    if os.path.isfile(plan_src):
        with open(plan_src, encoding="utf-8") as fh:
            rows = _plan_rows(json.load(fh))
        plan_rows = len(rows)
        # lands where CoreServer.boot_warmup looks for priors
        with open(os.path.join(cache_dir, "warmup_plan.json"), "w", encoding="utf-8") as fh:
            json.dump(rows, fh, indent=1)
    print(
        f"imported {copied} cache file(s) ({skipped} already present), "
        f"{plan_rows} plan row(s) -> {cache_dir}"
    )
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    ex = sub.add_parser("export", help="bundle cache dir + ledger plan into PACK_DIR")
    ex.add_argument("pack_dir")
    ex.add_argument("--cache-dir", default=None, help="override resolved cache dir")
    ex.add_argument("--core", default=None, help="core URL to fetch the live ledger from")
    ex.add_argument("--plan", default=None, help="saved /v1/debug/compiles JSON (or bare table)")
    ex.set_defaults(fn=cmd_export)
    im = sub.add_parser("import", help="unpack PACK_DIR into the local cache dir")
    im.add_argument("pack_dir")
    im.add_argument("--cache-dir", default=None, help="override resolved cache dir")
    im.set_defaults(fn=cmd_import)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
