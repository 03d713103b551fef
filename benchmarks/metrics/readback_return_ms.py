"""device: how long after the chip finished a decode step the host held
its tokens.  In the traced slice, for every ``serving.step`` annotation
on the host plane that contains a ``step.readback`` annotation: the end
of ``step.readback`` minus the end of that step's decode program, the
median.  The step's program is the one on device 0's ``XLA Modules``
line whose name starts with the traffic file's ``decode_module_prefix``
and which overlaps the ``step.readback`` annotation longest (the
readback waits for it from start to end).  It exists only because the
program's spans are annotations on the device's clock.

Not "the program that starts inside the step": the two planes' clocks
agree only to about a millisecond (PERF.md section 6, PR 26: in one
trace every program starts 0.5-0.8 ms BEFORE its step's dispatch
begins), which would pair a step with the next step's program.  The
value carries that offset; an earlier line gives what bounds it: the
program's start less the dispatch's start (negative: the device plane
runs ahead by at least that much), and dispatch-to-readback less the
program's device time, which is free of the offset.

Nothing where the trace has no device plane or the program writes no
``step.readback`` annotation."""

import bisect

from benchmarks.lib import stats


def _first_inside(events, lo: int, hi: int):
    """The first of ``events`` (sorted ``(start, end)`` pairs) that
    starts in ``[lo, hi]``, or None."""
    i = bisect.bisect_left(events, (lo,))
    return events[i] if i < len(events) and events[i][0] <= hi else None


def _longest_overlap(events, lo: int, hi: int):
    """The one of ``events`` (sorted, disjoint ``(start, end)`` pairs)
    that shares most time with ``[lo, hi]``, or None if none does."""
    best, shared = None, 0
    i = max(bisect.bisect_left(events, (lo,)) - 1, 0)
    while i < len(events) and events[i][0] < hi:
        both = min(events[i][1], hi) - max(events[i][0], lo)
        if both > shared:
            best, shared = events[i], both
        i += 1
    return best


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    t0, t1 = run["trace_window_ns"]
    prefix = run["decode_module_prefix"]
    programs = sorted((start, start + dur) for name, start, dur
                      in trace["devices"].get(0, {}).get("modules", ())
                      if name.startswith(prefix))
    spans = {name: sorted((start, start + dur) for n, start, dur
                          in trace["host"] if n == name)
             for name in ("step.readback", "step.decode_dispatch")}
    if not programs or not spans["step.readback"]:
        return None
    late, launch, around = [], [], []
    for name, start, dur in trace["host"]:
        if name != "serving.step" or start < t0 or start + dur > t1:
            continue
        readback = _first_inside(spans["step.readback"], start, start + dur)
        if readback is None:
            continue
        program = _longest_overlap(programs, *readback)
        if program is None:
            continue
        late.append(readback[1] - program[1])
        dispatch = _first_inside(spans["step.decode_dispatch"],
                                 start, readback[0])
        if dispatch is not None:
            launch.append(program[0] - dispatch[0])
            around.append(readback[1] - dispatch[0]
                          - (program[1] - program[0]))
    if not late:
        return None
    said = (f"readback_return_ms: {len(late)} decode steps in the slice, "
            f"p95 {stats.percentile(late, 0.95) / 1e6:.3f} ms")
    if launch:
        said += (f"; program start less dispatch start median "
                 f"{stats.median(launch) / 1e6:.3f} ms, dispatch to "
                 f"readback less the program's device time median "
                 f"{stats.median(around) / 1e6:.3f} ms")
    run["log"](said)
    return stats.median(late) / 1e6
