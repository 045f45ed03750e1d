"""chipbench: the repo's on-chip benchmark (see BENCHMARK.json, PERF.md).

Everything that decides a number lives here, where later PRs cannot edit
it: traffic generation, window arithmetic, the trace reduction, the table
of peaks, the operation and byte counts, the plain reference and the
comparison that decides ``correct``. From the program it takes only the
system under test and its spans, counters and kernel names.
"""
