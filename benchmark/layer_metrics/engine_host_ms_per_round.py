"""Milliseconds of the engine thread's own work a decode round, on the
trace's clock: seconds inside the `engine.dispatch`, `engine.emit`,
`engine.admit` and `engine.prefill` annotations less their `.sync` children
(the blocking device reads), over the runs of the engine's round programs in
the same slice, `counters.ROUND_PROGRAMS`: the host serves a round that carries
prompts (`jit_mixed_round_fn`) as it serves a plain one, and the phases' seconds
are those of all rounds. Once the device's round shrinks, this is what sets
the pace."""
from benchmark import counters, spans

NAME, UNIT, BETTER, SOURCE = "engine_host_ms_per_round", "ms", "lower", "program_span"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"
PHASES = ("dispatch", "emit", "admit", "prefill")


def read(run: dict):
    got = spans.planes(run)
    if got is None:
        return None
    chips, host = got
    names = {f"engine.{p}" for p in PHASES} | {f"engine.{p}.sync" for p in PHASES}
    s = spans.host_seconds(host, names)
    rounds = sum(len(spans.program_runs(chips, p)) for p in counters.ROUND_PROGRAMS)
    if not rounds or not any(f"engine.{p}" in s for p in PHASES):
        return None
    busy = sum(s.get(f"engine.{p}", 0.0) - s.get(f"engine.{p}.sync", 0.0) for p in PHASES)
    return 1e3 * busy / rounds
