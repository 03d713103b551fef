"""engine_host: 95th percentile, over the requests due in the window, of
first-token time minus DUE time (open loop: a stall charges the requests
behind it); a request that fails or never finishes counts as the worst.
Taken by the benchmark's own token callbacks.  Only requests due a
second or more before the traced slice began count: writing the trace
out stalls the loop for seconds, and that wait is the profiler's.

A per-layer metric, not an end-to-end one, in cells that serve too few
requests in a window for its spread to admit a bound (PERF.md section 2).
"""

from benchmarks.lib import stats


def read(run):
    pairs = run.get("ttft_by_due")
    if not pairs:
        return None
    clock = run.get("trace_clock")
    if clock and clock[0] is not None:
        cut = clock[0] - run["window"][0] - 1.0
        pairs = [p for p in pairs if p[0] < cut]
    return 1e3 * stats.percentile([t for _, t in pairs], 0.95) \
        if pairs else None
