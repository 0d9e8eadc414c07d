"""Seconds of the window lost to stalls of the engine's in-flight queue over
the window's seconds: `perf_stats()["rounds"]["stalls"]["excess_s"]`, end
minus start. A stall is an interval between two successive retirements of the
queue (a round's end, the read of an admit program), the loop neither idle
nor running another program to its end between, longer than 0.2 s and than twice the retiring program's mean device
seconds; its excess is the interval less that mean. 0.0 is a reading: no
stall in the window. Logs the window's count, longest, `by_phase` (the loop
phase that held most of each stall's host seconds, `first_dispatch` where a
shape was first dispatched inside it), the stalls' seconds inside Python's
collector and the newest rows. None from a program without the account."""
from benchmark import round_account

NAME, UNIT, BETTER, SOURCE = "round_stall_share", "%", "lower", "program_counter"
LAYER, MOVES = "admission and scheduler", "out_tokens_per_s"


def read(run: dict):
    st = round_account.stalls(run)
    if st is None or not st["window_s"]:
        return None
    print(f"stalls of the window: {int(st['count'])} of {st['seconds']:.3f} s, excess {st['excess_s']:.3f} s, "
          f"longest {st['longest_s']:.3f} s, by_phase {st['by_phase']}, gc_s {st['gc_s']:.3f} "
          f"(the window's collections {st['gc_window_s']:.3f} s); rows {st['recent']}", flush=True)
    return 100.0 * st["excess_s"] / st["window_s"]
