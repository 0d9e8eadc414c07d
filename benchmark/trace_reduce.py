"""From the profiler's trace (`.xplane.pb`) to device busy time, time by
operation name and idle gaps. Reads with `jax.profiler.ProfileData` alone.

What a TPU v5e trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, with the lines `XLA Modules` (one event per executed step
program, named `jit_<fn>(<fingerprint>)`), `XLA Ops` (one event per HLO
operation inside a module, named by its whole HLO text: `%fusion.12 = bf16[32,
4096]{...} fusion(...), kind=kLoop, ...`) and `Async XLA Ops` (copy-start to
copy-done spans, which overlap compute and are not read here); and
`/host:CPU` with one line per host thread (`python3` holds `PjitFunction(<fn>)`
and `np.asarray(jax.Array)`). Control-flow operations (`while`, `conditional`,
`call`) span the operations of their bodies, so device busy time is the UNION
of operation intervals, never their sum, and time by name counts leaf
operations only. A Pallas kernel is a `custom-call` whose text holds
`custom_call_target="tpu_custom_call"`; it is named after the traced function
that made it (`append_kv_q8.7`, `flash_prefill_attention.8`, and
`branch_1_fun.5` for the decode attention, which sits in a `lax.cond` branch):
no `name=` is set on any `pallas_call` yet.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# operations that contain other operations of the same line
CONTAINERS = re.compile(r"^(while|conditional|call)[.\d]*$")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
HLO_TEXT = re.compile(r"^%(?P<name>\S+) = (?P<type>\(|[a-z]+\d*\[[\d,]*\])")


def find_xplane(trace_dir: str) -> str | None:
    """The newest .xplane.pb under a jax.profiler log directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def union_s(intervals: list[tuple[float, float]]) -> float:
    """Seconds covered by the union of [start, end) intervals in nanoseconds."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        elif b > cur_b:
            cur_b = b
    if cur_b is not None:
        total += cur_b - cur_a
    return total / 1e9


def gaps(intervals: list[tuple[float, float]], w0: float, w1: float):
    """Idle intervals inside [w0, w1] left by the union of `intervals`."""
    out, edge = [], w0
    for a, b in sorted(intervals):
        if a > edge:
            out.append((edge, min(a, w1)))
        edge = max(edge, b)
        if edge >= w1:
            break
    if edge < w1:
        out.append((edge, w1))
    return [(a, b) for a, b in out if b > a]


def short_name(text: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`; a plain name stays."""
    m = HLO_TEXT.match(text)
    return m.group("name") if m else text.lstrip("%").split(" ", 1)[0]


def base_name(name: str) -> str:
    """`fusion.123` -> `fusion`: HLO numbers its operations anew on every
    compile, the kind stays."""
    return re.sub(r"[.\d]+$", "", name) or name


def is_mosaic(text: str) -> bool:
    """A Pallas (Mosaic) kernel, by its custom-call target."""
    return MOSAIC_TARGET in text


def label(text: str) -> str:
    """What an operation is called in the breakdown: its kind and what it
    produces (`dynamic-slice_bitcast_fusion s8[4,4096,24576]`), both stable
    across compiles; a kernel also says that it is one."""
    m = HLO_TEXT.match(text)
    if not m:
        return base_name(text)
    kind = base_name(m.group("name"))
    out = f"{kind} {m.group('type')}" if m.group("type") != "(" else kind
    return f"{out} [pallas]" if is_mosaic(text) else out


def program_name(module: str) -> str:
    """`jit_decode_chunk_fn(3519799609298949828)` -> `jit_decode_chunk_fn`."""
    return re.sub(r"\(\d+\)$", "", module)


def whole_runs(mods: list[tuple[str, float, float]]) -> list[tuple[str, float, float]]:
    """One chip's `XLA Modules` events less the first and the last by time: the
    two the traced slice's edges cut. The profiler clips a program that was
    running when the trace began, or still is when it ends, to the part it saw
    (a round of 105 ms read 83 and 89 at the two ends of one slice; my chip
    run, PR 47), and a device that serves is never idle at an edge. A mean A
    RUN counts whole runs alone: where a slice holds two runs of a program, one
    cut run in the mean reads four fifths of a round and 106% of a roofline.
    A run that happened to lie whole at an edge is one sample lost."""
    return sorted(mods, key=lambda m: m[1])[1:-1]


def read_planes(path: str):
    """[(chip index, [(name, start_ns, end_ns)] ops, [..] modules)] and
    the host plane's lines {thread: [(name, start_ns, end_ns)]}."""
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    chips, host = [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines[OPS_LINE].events] if OPS_LINE in lines else []
            mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in lines[MODULES_LINE].events] if MODULES_LINE in lines else []
            chips.append((int(m.group(1)), ops, mods))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host[ln.name] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                 for e in ln.events if e.duration_ns > 0]
    return sorted(chips), host


def _host_doing(host: dict, a: float, b: float) -> str:
    """The host event that covers most of [a, b], as `thread: event`."""
    best, best_cover = "", 0.0
    for thread, events in host.items():
        for name, s, e in events:
            cover = min(e, b) - max(s, a)
            if cover > best_cover:
                best, best_cover = f"{thread.split('/')[0]}: {name}"[:80], cover
    return best


def reduce_trace(path: str, top: int = 10) -> dict | None:
    """Busy and idle seconds averaged over the chips in the trace, seconds by
    operation, seconds inside Mosaic kernels, and the longest idle gaps named
    by the step programs around them and what the host was doing. None where
    the trace holds no device operation."""
    chips, host = read_planes(path)
    chips = [c for c in chips if c[1]]
    if not chips:
        return None
    busy, window, mosaic = [], [], []
    by_name: dict[str, float] = {}
    by_module: dict[str, float] = {}
    module_calls: dict[str, int] = {}
    whole: dict[str, list[float]] = {}
    gap_s: dict[str, float] = {}
    for _idx, ops, mods in chips:
        spans = [(a, b) for _n, a, b in ops]
        w0, w1 = min(a for a, _ in spans), max(b for _, b in spans)
        busy.append(union_s(spans))
        window.append((w1 - w0) / 1e9)
        mos = 0.0
        kinds: dict[str, tuple[str | None, bool]] = {}  # an HLO text recurs every step
        for text, a, b in ops:
            if text not in kinds:
                leaf = not CONTAINERS.match(short_name(text))
                kinds[text] = (label(text) if leaf else None, is_mosaic(text))
            name, kernel = kinds[text]
            if name is None:
                continue
            by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
            if kernel:
                mos += (b - a) / 1e9
        mosaic.append(mos)
        for name, a, b in mods:
            key = program_name(name)
            by_module[key] = by_module.get(key, 0.0) + (b - a) / 1e9
            module_calls[key] = module_calls.get(key, 0) + 1
        for name, a, b in whole_runs(mods):
            whole.setdefault(program_name(name), []).append((b - a) / 1e9)
        mods_sorted = sorted(mods, key=lambda m: m[1])
        for a, b in sorted(gaps(spans, w0, w1), key=lambda g: g[0] - g[1])[:200]:
            before = [m for m in mods_sorted if m[1] <= a]  # a program may end a little after its last op
            after = [m for m in mods_sorted if m[2] >= b]
            name = (f"{program_name(before[-1][0]) if before else 'start'} -> "
                    f"{program_name(after[0][0]) if after else 'end'}")
            doing = _host_doing(host, a, b) if (b - a) > 2e5 else ""
            key = f"{name} [{doing}]" if doing else name
            gap_s[key[:160]] = gap_s.get(key[:160], 0.0) + (b - a) / 1e9
    n = len(chips)

    def ranked(d: dict[str, float]):
        return [[k, v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "chips": n,
        "busy_s": sum(busy) / n,
        "window_s": sum(window) / n,
        "mosaic_s": sum(mosaic) / n,
        "device_ops": ranked(by_name),
        "modules": ranked(by_module),
        # step programs: {name: [times run, mean seconds a run]} over all chips,
        # the runs the slice's edges cut among them: for sums and counts
        "module_runs": {k: [module_calls[k], by_module[k] / module_calls[k]] for k in by_module},
        # the same over whole runs alone (`whole_runs`): for a time A RUN
        "whole_runs": {k: [len(v), sum(v) / len(v)] for k, v in whole.items()},
        "idle_gaps": ranked(gap_s),
    }
