"""The benchmark: one command runs one cell once (benchmark/run.py).

Everything that decides a number lives here, where a PR that claims a gain may
not change it: traffic generation (trafficgen.py, loadgen.py), the reduction
from client records, counters and the profiler's trace to metrics (reduce.py,
trace_reduce.py, layer_metrics/), the table of peaks and the byte counts
(peaks.py), the plain float32 references (reference.py for the dense family,
references/<name>.py for a family a configuration's file names) and the
comparison that decides `correct` (correctness.py). From the program it
takes only the system under test and its counters.
"""
