"""The benchmark of paddle_tpu: the yardstick later PRs are measured with.

Everything here is the benchmark's own (BENCHMARK.json ``paths``): traffic
generation, the reduction from spans, counters and device traces to
metrics, the table of peaks, operation and byte counts, each
configuration's plain reference, and the comparison that decides
``correct``.  From the program it takes only the system under test and
its spans, counters and kernel names.  ``run.py`` is the entry point.
"""
