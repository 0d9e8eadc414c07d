"""Mean device time of one MIXED round (`jit_mixed_round_fn`: a decode round
whose first step carries queued prompts through its pass over the weights),
from the trace: the `XLA Modules` events of that step program, whole runs
alone, `decode_round_ms`'s twin for the other round. The p95 gap between a
stream's chunks is a mixed round, and `decode_round_ms` times the plain one:
this less that is the surcharge a riding prompt costs. Where the program keeps
the account of rounds (`perf_stats()["rounds"]`), logs beside it the WHOLE
window's rounds, told rounds and ms by program and rung on the host's clock
(the trace is an 8 s slice and does not know a rung): a cross-check, not the
metric. None where the slice holds no whole mixed round."""
from benchmark import round_account

NAME, UNIT, BETTER, SOURCE = "mixed_round_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "step programs", "itl_p95_ms"


def read(run: dict):
    rows = round_account.by_program(run)
    if rows is not None:
        print("rounds of the window by program (host clock): " + round_account.log_rows(rows), flush=True)
    tr = run.get("trace_reduced")
    runs = tr["whole_runs"].get(round_account.MIXED_PROGRAM) if tr else None
    return 1e3 * runs[1] if runs else None
