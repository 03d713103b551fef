"""Shared arithmetic of the benchmark: peaks, counts, statistics, the
compile clock, the comparison with the reference, the trace reduction."""
