"""engine_host: 95th percentile of the time a request waited between
``submit()`` and admission, from the engine's ``queued`` spans (exact
``perf_counter`` pairs) of the requests that arrived inside the window and
before the traced slice began: writing the trace out stalls the loop for
seconds, and the requests due meanwhile wait for the profiler, not for
the engine."""

from benchmarks.lib import stats


def read(run):
    if not run.get("spans"):
        return None
    t0, t1 = run["window"]
    clock = run.get("trace_clock")
    if clock and clock[0] is not None:
        t1 = min(t1, clock[0])
    waits = [end - start for name, start, end, _ in run["spans"]
             if name == "queued" and t0 <= start < t1]
    return 1e3 * stats.percentile(waits, 0.95) if waits else None
