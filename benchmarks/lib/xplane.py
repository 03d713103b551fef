"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics need.  ``jax.profiler.ProfileData`` reads the file with
nothing but jax; everything after the load works on plain
``(name, start_ns, duration_ns)`` tuples, so the arithmetic is tested on
a three-event trace and on a small recorded one
(``benchmarks/fixtures/``).

What a TPU trace holds (looked at by hand in PR 24, PERF.md section 5):
one plane per chip named ``/device:TPU:<n>``; on it the line ``XLA
Modules`` has one event per executed program, named after the jitted
function (``jit_decode(...)``), and the line ``XLA Ops`` one event per
HLO operation or fusion that ran, named by its whole HLO line
(``Async XLA Ops`` holds copies in flight, which overlap the rest and are
not counted as busy time).  Host threads are lines of the plane
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear there under
their own names, on the device planes' clock.
"""

import glob
import os
import time

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


WINDOW_MARK = "bench.window"


class TraceSlice:
    """The traced slice of a window: the profiler runs from ``start()``
    to ``stop()``, with one ``TraceAnnotation`` named ``WINDOW_MARK``
    over the whole slice, which puts the slice's bounds on the trace's
    own clock.  ``clock`` is the same pair on ``perf_counter``.

    The Python call tracer is off: it records every Python call on the
    host, which slows the loop being measured and buries the
    annotations.  ``TraceAnnotation`` spans and the runtime's own host
    spans stay."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.clock = [None, None]
        self._mark = None

    @property
    def running(self) -> bool:
        return self.clock[0] is not None and self.clock[1] is None

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()
        self.clock[0] = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.clock[1] = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` trace left under
    ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """``{"devices": {n: {"ops": [...], "modules": [...]}}, "host":
    [...]}`` with every event a ``(name, start_ns, duration_ns)`` tuple.
    ``host`` holds the host plane's events of every thread."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            tail = plane.name[len(DEVICE_PREFIX):].split()[0]
            if not tail.isdigit():
                continue
            dev = out["devices"].setdefault(int(tail),
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"} \
                    .get(line.name)
                if key is not None:
                    dev[key].extend((e.name, e.start_ns, e.duration_ns)
                                    for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend((e.name, e.start_ns, e.duration_ns)
                                   for e in line.events)
    return out


def describe(path: str, limit: int = 3) -> list:
    """Every plane and line of a trace with its event count and first
    event names: what to look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            rows.append({"plane": plane.name, "line": line.name,
                         "events": len(events),
                         "first": [(e.name[:60], e.start_ns, e.duration_ns)
                                   for e in events[:limit]]})
    return rows


def merged(events) -> list:
    """The union of the events' intervals as sorted, disjoint
    ``[start, end]`` pairs (nested and overlapping events count once)."""
    out = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def clip(events, t0: float, t1: float) -> list:
    """Events cut to the window ``[t0, t1]``; those outside are dropped."""
    out = []
    for name, start, dur in events:
        lo, hi = max(start, t0), min(start + dur, t1)
        if hi > lo:
            out.append((name, lo, hi - lo))
    return out


def busy_ns(events) -> float:
    return sum(end - start for start, end in merged(events))


def idle_share(events, t0: float, t1: float) -> float:
    """1 - (union of the events' intervals inside the window) / window."""
    return 1.0 - busy_ns(clip(events, t0, t1)) / (t1 - t0)


def module_durations(modules, prefix: str, t0: float = float("-inf"),
                     t1: float = float("inf")) -> list:
    """Durations (ns) of the executed programs whose name starts with
    ``prefix`` (``jit_decode`` matches ``jit_decode(1234...)``) and that
    ran wholly inside ``[t0, t1]``."""
    return [dur for name, start, dur in modules
            if name.startswith(prefix) and t0 <= start and start + dur <= t1]


def top_ops(events, n: int = 10, width: int = 120) -> list:
    """``[[name, seconds], ...]``: the operations that took most device
    time, summed over their runs.  The trace names an operation by its
    whole HLO line; the first ``width`` characters (result name, shape,
    opcode, first operand) tell which it is."""
    total = {}
    for name, _, dur in events:
        total[name] = total.get(name, 0.0) + dur
    return [[name[:width], ns / 1e9] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def device0_idle_percent(run: dict):
    """What both ``device_idle.*`` metrics read: the idle share of
    device 0 over the traced window of ``run``, in percent (nothing
    where the run has no device trace)."""
    trace = run.get("trace")
    if not trace or not trace["devices"].get(0, {}).get("ops"):
        return None
    return 100.0 * idle_share(trace["devices"][0]["ops"],
                              *run["trace_window_ns"])


def idle_gaps(events, t0: float, t1: float, host_spans, n: int = 10,
              min_gap_ns: float = 5e3, outside: str = "no_host_span") -> list:
    """``[[label, seconds], ...]``: the device's idle time inside the
    window, summed by what the host was doing at the middle of each gap:
    the innermost (shortest) of ``host_spans`` that covers it.  Gaps
    under ``min_gap_ns`` (the seams between operations of one program)
    are summed under one label of their own."""
    import numpy as np
    total = {}
    edges = [[t0, t0]] + merged(clip(events, t0, t1)) + [[t1, t1]]
    gaps = [(a, b) for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]
    small = sum(b - a for a, b in gaps if b - a < min_gap_ns)
    if small:
        total[f"gaps_under_{min_gap_ns / 1e3:g}us"] = small
    names = [s[0] for s in host_spans]
    start = np.array([s[1] for s in host_spans], float)
    dur = np.array([s[2] for s in host_spans], float)
    for a, b in gaps:
        if b - a < min_gap_ns:
            continue
        mid = (a + b) / 2
        covers = np.flatnonzero((start <= mid) & (mid <= start + dur))
        label = names[covers[np.argmin(dur[covers])]] if covers.size \
            else outside
        total[label] = total.get(label, 0.0) + (b - a)
    return [[name, ns / 1e9] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def exposed_ns(events, is_target) -> float:
    """Time in events for which ``is_target(name)`` holds during which no
    other event runs: the collectives' part that compute does not hide."""
    target = merged([e for e in events if is_target(e[0])])
    other = merged([e for e in events if not is_target(e[0])])
    hidden = 0.0
    j = 0
    for start, end in target:
        while j < len(other) and other[j][1] <= start:
            j += 1
        k = j
        while k < len(other) and other[k][0] < end:
            hidden += min(end, other[k][1]) - max(start, other[k][0])
            k += 1
    return sum(e - s for s, e in target) - hidden
