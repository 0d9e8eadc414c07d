"""Device seconds of the runs of the admit program (`jit_admit_fn`, one batch
of whole prompts) on the trace's `XLA Modules` line over the slice's busy
seconds: the share of the device that admission takes from the decode rounds.
Read only where the runs and the `engine.admit.dispatch` annotations in the
slice agree in number to within the two at its edges, so that the program
counted is the one the engine says it dispatched. Logs, a shape, the runs and
their mean device ms (`admit_spans.runs_by_shape`)."""
from benchmark import admit_spans

NAME, UNIT, BETTER, SOURCE = "admit_program_share", "%", "lower", "device_trace"
LAYER, MOVES = "step programs", "out_tokens_per_s"


def read(run: dict):
    tr = run.get("trace_reduced")
    got = admit_spans.admit_runs(run) if tr and tr.get("busy_s") else None
    if got is None:
        return None
    runs, disp = got
    by_shape = admit_spans.runs_by_shape(run, runs, disp)
    ms = [(b - a) / 1e6 for a, b in runs]
    print(f"admit programs in the slice: {len(runs)} runs, {len(disp)} dispatches annotated, "
          f"{sum(ms) / len(ms):.2f} ms a run; rows_padded:bucket -> runs x mean ms: "
          + ", ".join(f"{k} -> {len(v)} x {sum(v) / len(v):.2f}" for k, v in sorted(by_shape.items())),
          flush=True)
    return 100.0 * sum(ms) / 1e3 / tr["busy_s"]
