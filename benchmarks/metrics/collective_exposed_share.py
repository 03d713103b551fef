"""collectives: time in collective operations on device 0 during which no
other operation runs there, over the traced window."""

from benchmarks.lib import xplane

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv")


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def read(run):
    trace = run.get("trace")
    if not trace or 0 not in trace["devices"]:
        return None
    t0, t1 = run["trace_window_ns"]
    ops = xplane.clip(trace["devices"][0]["ops"], t0, t1)
    if not ops:
        return None
    return 100.0 * xplane.exposed_ns(ops, is_collective) / (t1 - t0)
