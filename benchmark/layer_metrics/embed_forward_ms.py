"""Mean host-clock time of one encoder forward and its fetch inside the
window, wrapped around EmbeddingEngine._fwd from the benchmark's side."""

NAME, UNIT, BETTER, SOURCE = "embed_forward_ms", "ms", "lower", "program_span"
LAYER, MOVES = "step programs", "embeddings_per_s"


def read(run: dict):
    tap = run.get("embed_tap")
    w0, w1 = run["window_abs"]
    v = [b - a for a, b, _p, _t in tap.calls if w0 <= a < w1] if tap else []
    return 1e3 * sum(v) / len(v) if v else None
