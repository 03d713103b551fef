"""kernels: the least time a decode step could take over the device
time it took, in the traced slice of the window.

Least time: the larger of bytes / peak HBM bandwidth and FLOPs / peak
FLOP/s (``lib/flops_bytes.py``: every weight once, plus the live K and V
rows of the slots that were decoding, counted per step from the
benchmark's own token callbacks).  Device time: the mean duration of the
decode program's events on the trace's ``XLA Modules`` line of device 0.
"""

from benchmarks.lib import flops_bytes, xplane


def read(run):
    trace, clock = run.get("trace"), run.get("trace_clock")
    if not trace or not clock or clock[1] is None or 0 not in trace["devices"]:
        return None
    durs = xplane.module_durations(trace["devices"][0]["modules"],
                                   run["decode_module_prefix"],
                                   *run["trace_window_ns"])
    steps = [s for s in run["steps"]
             if clock[0] <= s[0] and s[1] <= clock[1] and s[2] > 0]
    if not durs or not steps:
        return None
    facts, peaks = run["facts"], run["peaks"]
    least, bounds = [], {}
    for _, _, active, rows, _ in steps:
        t, bound = flops_bytes.least_time_s(
            flops_bytes.decode_step_flops(facts, active, rows - active),
            flops_bytes.decode_step_bytes(facts, active, rows - active),
            peaks)
        least.append(t)
        bounds[bound] = bounds.get(bound, 0) + 1
    mean_least = sum(least) / len(least)
    mean_dur = sum(durs) / len(durs) / 1e9
    run["log"](f"decode_roofline_share: {len(steps)} steps, bound by "
               f"{bounds}, least {1e3 * mean_least:.3f} ms, device "
               f"{1e3 * mean_dur:.3f} ms over {len(durs)} programs")
    return 100.0 * mean_least / mean_dur
