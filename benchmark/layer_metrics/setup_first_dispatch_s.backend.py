"""Of `setup_first_dispatch_s.serve`, the seconds in the backend (JAX's
`backend_compile_duration`): an XLA compile, or with a warm persistent cache
the load of the executable."""

NAME, UNIT, BETTER, SOURCE = "setup_first_dispatch_s.backend", "s", "lower", "program_span"
LAYER, MOVES = "step programs", "setup_s"


def read(run: dict):
    p = run["start"].get("ledger", {}).get("parts", {}).get("serve")
    return p["backend_s"] if p else None
