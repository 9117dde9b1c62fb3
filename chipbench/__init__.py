"""The chip benchmark: one command, cells and metrics as data.

Everything that decides a number lives here, where a later PR cannot edit
it: traffic generation (``traffic/``), the loops that drive the program
(``loops/``), the plain references (``reference/``), the reduction from
the profiler's trace to busy time, program time and kernel time
(``xplane.py``), the operation and byte counts of the kernels
(``kernels/``), the table of peaks (``peaks.json``) and one small reader
for each metric (``e2e_metrics/``, ``layer_metrics/``). ``run.py`` names no
cell, configuration or metric: it finds each by the name BENCHMARK.json
gives it. From the program it takes the system under test, its spans and
its counters.
"""
