"""Seconds of first dispatches on the engine's thread before the window: the
CompileLedger's walls with source `serve` at the window's start. Set-up that only a
warm-up which dispatches (and not just compiles) can take off the serve path."""

NAME, UNIT, BETTER, SOURCE = "setup_first_dispatch_s.serve", "s", "lower", "program_span"
LAYER, MOVES = "step programs", "setup_s"


def read(run: dict):
    p = run["start"].get("ledger", {}).get("parts", {}).get("serve")
    return p["wall_s"] if p else None
