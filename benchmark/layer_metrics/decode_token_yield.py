"""Tokens delivered to streams over row-steps dispatched, in the window: the
flight ring's `emit` events (one a round: `rows` dispatched, `delivered` =
tokens that reached a stream) give sum(delivered) / (sum(rows) x
decode_chunk). What is lost: steps computed past a row's EOS or `max_tokens`
inside a round, and rows whose request ended before the round was fetched."""

NAME, UNIT, BETTER, SOURCE = "decode_token_yield", "%", "higher", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    gen = run["sut"]["gen"]
    w0, w1 = run["window_abs"]
    rows = delivered = 0
    for ev in gen._flight.snapshot(etype="emit"):
        f = ev["fields"] or {}
        if "t" in f and w0 <= f["t"] < w1:
            rows += f["rows"]
            delivered += f["delivered"]
    return 100.0 * delivered / (rows * gen.decode_chunk) if rows else None
