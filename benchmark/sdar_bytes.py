"""What the readers of the block-diffusion cell (`sdar_block_closed`: a stack of
GQA layers whose every feed-forward is a share of routed experts, generating by
diffusion over blocks of L positions) share: the block rounds' own counters
(`perf_stats()["blocks"]`: rounds, rows, denoising passes, commits, positions
filled), the bytes a PASS and a ROUND must move, computed from shapes, from the
program's expert counters and from the live positions of the window's records,
and the device time of the round's program and of its attention in the trace.
LOGICAL bytes: what ANY implementation must read and write, whatever the XLA
chunk attention streams beyond a row's fill; with every held expert touched a
denoising pass's weights are the parameter count less the embedding table times
the item size (tests/benchmark/test_bench_sdar.py holds it to that). A program
without the counters or the program's name, or a configuration that yields one
token a step (the parent commit, any other cell), gives None everywhere."""

from __future__ import annotations

from benchmark import counters, peaks, solar_bytes, spans, trace_reduce
from benchmark.lfm2_bytes import bank_bytes, one_expert_bytes, touched_a_step  # the banks' bytes: any held experts'

# The engine's block round, as the trace names it: the PLAIN round's one name
# (the program's `telemetry/perf.py:PLAIN_ROUND_TRACE_NAME`), so `decode_round_ms`,
# which every generation cell reports, is this cell's round time and no reader
# here repeats it
PROGRAM = counters.DECODE_PROGRAM
SUMS = ("rounds", "rows", "passes", "commits", "unmasked", "remainder_tokens", "delivered")
# a block pass's attention, by the scopes its operations carry in a trace's
# `tf_op` / `name` stats: the round's (`block.denoise`, `block.commit`) and the
# attention half's inside it (`attn`)
ROUND_SCOPES, ATTN_SCOPE = ("block.denoise", "block.commit"), "/attn/"


def is_ours(gen) -> bool:
    return bool(gen is not None and getattr(gen.cfg, "block_len", 0))


def blocks(run: dict) -> dict | None:
    """The block rounds' sums between the run's edges (the traced slice's, of a
    run `counters.slice_of` cut to it); None without the counters or a round."""
    a = (run.get("start") or {}).get("perf", {}).get("blocks")
    b = (run.get("end") or {}).get("perf", {}).get("blocks")
    if not a or not b or b["rounds"] <= a["rounds"]:
        return None
    return {k: float(b[k]) - float(a[k]) for k in SUMS}


def passes_a_round(run: dict) -> tuple[float, float] | None:
    """(denoising passes, commit passes) of a mean round of the run."""
    got = blocks(run)
    return (got["passes"] / got["rounds"], got["commits"] / got["rounds"]) if got else None


def head_bytes(gen) -> int:
    return peaks.tree_bytes(gen.params["lm_head"])


def pass_bytes(run: dict) -> float | None:
    """The least ONE denoising pass reads: every weight outside the expert banks
    once (the embedding table left out, a row a position: peaks.decode_weight_bytes),
    the banks of the held experts the pass's rows touched (by the program's
    counter, layer by layer, a call a pass), and the live int8 KV rows at the
    mean fill of the run's window. A commit pass reads the same less the head
    (`head_bytes`) and writes its block's rows."""
    gen = run["sut"]["gen"]
    got = solar_bytes.decode_counts(run)
    if not got or not is_ours(gen):
        return None
    return (peaks.decode_weight_bytes(gen.params) - bank_bytes(gen)
            + touched_a_step(got) * one_expert_bytes(gen)
            + solar_bytes.kv_row_bytes(gen.cfg, gen.kv_quant) * counters.mean_live_tokens(run))


def round_bytes(run: dict) -> float | None:
    """The least a mean block round of the run moves: its denoising passes and
    its commit, by the counter of the passes that ran; the commit without the
    head and with its rows' new positions written."""
    gen = run["sut"]["gen"]
    need, n, got = pass_bytes(run), passes_a_round(run), blocks(run)
    if need is None or n is None:
        return None
    rows = got["rows"] / got["rounds"]
    written = rows * gen.cfg.block_len * solar_bytes.kv_row_bytes(gen.cfg, gen.kv_quant)
    return n[0] * need + n[1] * (need - head_bytes(gen) + written)


def round_s(run: dict) -> float | None:
    """Mean device seconds of one WHOLE run of the block round's program in the
    trace (`counters.decode_round_s`: the round's program under that name);
    None for a configuration whose round under that name is a decode round."""
    return counters.decode_round_s(run) if is_ours(run["sut"]["gen"]) else None


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: a varint's
    value, or the bytes of a length-delimited field; fixed fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            byte = buf[i]
            i += 1
            key |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        field, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                byte = buf[i]
                i += 1
                value |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            yield field, wire, value
        elif wire == 2:
            size = shift = 0
            while True:
                byte = buf[i]
                i += 1
                size |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            yield field, wire, buf[i : i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def operation_scopes(path: str) -> dict[str, str]:
    """{operation's HLO text: the strings of its metadata's stats} of the first
    TPU plane of an `.xplane.pb`: where JAX's `op_name` lies, with the
    `jax.named_scope`s an operation was traced under
    (`jit(..)/while/body/block.denoise/while/body/attn/dot_general`).
    `jax.profiler.ProfileData` hands an event's own stats over and not its
    metadata's, so the plane's `event_metadata` is read off the wire here
    (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.str_value = 5, .ref_value = 7):
    the lines with their events are skipped whole, a few thousand entries are
    read."""
    with open(path, "rb") as f:
        space = f.read()
    for field, _w, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((v for f_, w, v in parts if f_ == 2 and w == 2), b"").decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        names = {}  # stat_metadata: id -> name (a stat's value may be a reference to one)
        for f_, w, entry in parts:
            if f_ == 5 and w == 2:
                for g, _w2, meta in _fields(entry):
                    if g == 2:
                        got = dict((h, v) for h, _w3, v in _fields(meta))
                        names[got.get(1, 0)] = got.get(2, b"").decode(errors="replace")
        out = {}
        for f_, w, entry in parts:
            if f_ != 4 or w != 2:
                continue
            for g, _w2, meta in _fields(entry):
                if g != 2:
                    continue
                text, said = "", []
                for h, w3, v in _fields(meta):
                    if h == 2 and w3 == 2:
                        text = v.decode(errors="replace")
                    elif h == 5 and w3 == 2:
                        for k, w4, x in _fields(v):
                            if k == 5 and w4 == 2:
                                said.append(x.decode(errors="replace"))
                            elif k == 7 and w4 == 0:
                                said.append(names.get(x, ""))
                out[text] = " ".join(said)
        return out
    return {}


def attn_round_s(run: dict) -> float | None:
    """Device seconds a whole run of the block round's program spends in its
    passes' attention: the leaf operations traced under `block.denoise` or
    `block.commit` and `attn` (the attention half of a layer: q/k/v products,
    norms, rope, the past rows' read, scores, softmax, context, the output
    product), inside whole runs of the program. None where the trace's
    operations carry no scope."""
    if not is_ours(run["sut"]["gen"]):
        return None
    got = spans.planes(run)
    path = run.get("trace_path") or (
        trace_reduce.find_xplane(run["trace"]["dir"]) if run.get("trace", {}).get("dir") else None)
    if got is None or not path or path.endswith(".txt"):
        return None
    runs = spans.program_runs(got[0], PROGRAM, whole=True)
    scopes = operation_scopes(path)
    mine = {text for text, said in scopes.items()
            if ATTN_SCOPE in said and any(s in said for s in ROUND_SCOPES)}
    if not runs or not mine:
        return None
    import bisect

    starts = [a for a, _ in runs]
    total = 0.0
    for _i, ops, _mods in got[0][:1]:
        for text, a, b in ops:
            if text not in mine or trace_reduce.CONTAINERS.match(trace_reduce.short_name(text)):
                continue
            k = bisect.bisect_right(starts, a) - 1
            if k >= 0 and b <= runs[k][1] + 1e3:
                total += (b - a) / 1e9
    return total / len(runs) if total else None
