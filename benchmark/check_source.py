#!/usr/bin/env python3
"""Compare a configuration's file with its row of the `model-configs` catalog,
as the driver will before any run.

    python3 benchmark/check_source.py <configuration file> <architectures.jsonl>

The row is the one whose `source_url` is the file's `source`. Every key of the
row's `config`, at any depth, must be in the file under the same key with the
same JSON value: null is null and 0 is 0, a group is a group, a list is equal
element by element. A path may differ only if it, or the group that holds it,
is in the file's `reduced`, and then `published.<path>` must hold the row's
value; a path that names a width may not differ at all. Each difference is
printed, and the exit code is then 1. A file whose `source` is in no row prints
"not in the catalog" and exits 0. Pure standard library; run.py never calls it,
because the catalog is not on the measuring machine.
"""

from __future__ import annotations

import json
import re
import sys

# A width: a hidden, intermediate, latent, state or projection size, a key that
# ends in _dim or _rank, a head size, an expansion factor, the experts a token
# takes. Matched against the END of a dotted path, so a width inside a group is
# one too (`linear_attn_config.head_dim`).
WIDTH = re.compile(
    r"(_dim|_rank|hidden_size|intermediate_size|head_size|state_size|latent_size|proj_size"
    r"|projection_size|d_state|d_head|d_inner|expand|expansion_factor|experts_per_tok"
    r"|experts_per_token|active_primary_experts)$")
ABSENT = object()


def is_width(path: str) -> bool:
    return bool(WIDTH.search(path))


def _number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _said(v) -> str:
    return "nothing" if v is ABSENT else json.dumps(v)


def same_json(a, b) -> bool:
    """Equal as JSON values: a bool is no number and null is not 0; 1 is 1.0."""
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(same_json(a[k], b[k]) for k in a)
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(same_json(x, y) for x, y in zip(a, b))
    if _number(a) and _number(b):
        return a == b
    return type(a) is type(b) and a == b


def lookup(group, path: str):
    """The value at a dotted path of a nested group, or ABSENT."""
    for key in path.split("."):
        if not isinstance(group, dict) or key not in group:
            return ABSENT
        group = group[key]
    return group


def changed(have, want, path: str) -> list[tuple[str, object, object]]:
    """(path, the file's value, the source's) of every leaf that differs; a
    group on one side only differs by every leaf of the source's group, and a
    key that only the file's group has differs from ABSENT."""
    if isinstance(want, dict) and isinstance(have, dict):
        out = []
        for key in want:
            out += changed(have.get(key, ABSENT), want[key], f"{path}.{key}")
        return out + [(f"{path}.{key}", have[key], ABSENT) for key in have if key not in want]
    if isinstance(want, dict) and want:
        return [leaf for key in want for leaf in changed(ABSENT, want[key], f"{path}.{key}")]
    return [] if have is not ABSENT and same_json(have, want) else [(path, have, want)]


def differs(config: dict, entry: dict) -> list[str]:
    """What the driver would refuse in a configuration's file against its row
    of the catalog, one line a difference; empty where the file stands."""
    reduced = list(config.get("reduced", []))
    published = config.get("published", {})
    out = []
    for key, want in entry["config"].items():
        for path, have, source in changed(config.get(key, ABSENT), want, key):
            gives = f"gives {path} as {_said(have)} and its source gives {_said(source)}"
            if have is ABSENT:
                gives = f"leaves out {path}, which its source gives as {_said(source)}"
            if not any(path == r or path.startswith(r + ".") for r in reduced):
                out.append(f"{gives}: reduced does not list it")
            elif is_width(path):
                out.append(f"{gives}: a width may not change, whether or not reduced lists it")
            elif source is not ABSENT and not same_json(lookup(published, path), source):
                out.append(f"{gives}: reduced lists it, and published.{path} is "
                           f"{_said(lookup(published, path))} where it has to be the source's value")
    return out


def main(argv: list[str]) -> int:
    if len(argv) != 2:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        config = json.load(f)
    with open(argv[1]) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    entry = next((r for r in rows if r.get("source_url") == config["source"]), None)
    if entry is None:
        print(f"{argv[0]}: {config['source']} is not in the catalog: nothing to compare")
        return 0
    found = differs(config, entry)
    for line in found:
        print(f"{argv[0]} {line}")
    if not found:
        print(f"{argv[0]}: holds every key of {entry['name']}'s entry"
              + (f", less {config['reduced']} as published states" if config.get("reduced") else ""))
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
