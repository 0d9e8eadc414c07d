"""Bytes of the window layers' rings over the bytes of the whole KV cache
(rings and the global layers' full-length rows), from `perf_stats()["kv_kinds"]`
at the window's end: what the second kind costs the pool. About a tenth in
`kexaone_reason_closed` (four rings of 128 beside one layer of 4096); four
fifths if window layers ever got full-length caches back."""
from benchmark import kexaone_bytes

NAME, UNIT, BETTER, SOURCE = "kv_window_bytes_share", "%", "lower", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    got = kexaone_bytes.kinds(run)
    if not got:
        return None
    ring, full = got["window"]["bytes"], got["full"]["bytes"]
    return 100.0 * ring / (ring + full) if ring + full else None
