"""Of `setup_first_dispatch_s.serve`, the seconds JAX spent tracing and lowering
(its own `jaxpr_trace_duration` and `jaxpr_to_mlir_module_duration`): what no
compile ahead of time into the persistent cache can remove."""

NAME, UNIT, BETTER, SOURCE = "setup_first_dispatch_s.trace_lower", "s", "lower", "program_span"
LAYER, MOVES = "step programs", "setup_s"


def read(run: dict):
    p = run["start"].get("ledger", {}).get("parts", {}).get("serve")
    return p["trace_s"] + p["lower_s"] if p else None
