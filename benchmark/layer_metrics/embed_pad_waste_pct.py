"""Share of the tokens the encoder computed inside the window that were
padding: 1 - true tokens over batch bucket x length bucket, summed over the
forwards."""

NAME, UNIT, BETTER, SOURCE = "embed_pad_waste_pct", "%", "lower", "program_counter"
LAYER, MOVES = "step programs", "embeddings_per_s"


def read(run: dict):
    tap = run.get("embed_tap")
    w0, w1 = run["window_abs"]
    calls = [(p, t) for a, _b, p, t in tap.calls if w0 <= a < w1] if tap else []
    padded = sum(p for p, _t in calls)
    return 100.0 * (1.0 - sum(t for _p, t in calls) / padded) if padded else None
