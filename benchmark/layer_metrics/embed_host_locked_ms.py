"""Mean host milliseconds a forward of the window spent holding the
embedding engine's lock outside `embed.forward`: staging the padded batch
before it, slicing, normalising and `tolist` after it. The chip waits for all
of it, because the next request cannot start its forward."""

NAME, UNIT, BETTER, SOURCE = "embed_host_locked_ms", "ms", "lower", "program_span"
LAYER, MOVES = "step programs", "embeddings_per_s"


def read(run: dict):
    stats = getattr(run["sut"]["emb"], "stats", None)
    w0, w1 = run["window_abs"]
    v = [host_s for t, _fwd_s, host_s in stats()["recent"] if w0 <= t < w1] if stats else []
    return 1e3 * sum(v) / len(v) if v else None
