"""kernels: the least time a decode step of a model with sparse experts
could take over the device time it took, in the traced slice.

Least time: the larger of bytes / peak HBM bandwidth and FLOPs / peak
FLOP/s (``lib/moe_flops_bytes.py``: the dense weights once, the experts
the step TOUCHED, the live latent rows), per ``serving.step`` span of
the slice that decoded, from the span's own counts (``active_slots``,
``live_kv_rows``, ``experts_touched``: the program counts the experts
that got a live row and the count rides the step's token readback).
Device time: the mean duration of the decode program's events on device
0's ``XLA Modules`` line.  An earlier line prints the mean experts
touched against what the live slots could touch at most.  Nothing where
the steps carry no ``experts_touched`` (a model without expert layers,
or a program older than the counter).

The slice is not the window: starting the profiler holds the host for a
moment, the open-loop queue backs up behind it, and the slice's steps
run fuller than the window's (30.7 active slots of 32 against a median
of 13.5 in this cell's first traced runs, PERF.md section 5, PR 34).  A
second earlier line therefore gives the same least time over the WHOLE
window's decode-only steps at their own counts, against the median host
time of their dispatch and readback (``decode_step_ms``'s quantity): a
share on the host's clock, which carries the readback's return and is
no device metric, at the occupancy the untraced run has."""

from benchmarks.lib import flops_bytes, moe_flops_bytes, stats, xplane


def decode_steps(run) -> list:
    """``(active, live rows, experts touched)`` of the slice's
    ``serving.step`` spans that decoded and counted experts."""
    clock, spans = run.get("trace_clock"), run.get("spans")
    if not spans or not clock or clock[1] is None:
        return []
    return [(a["active_slots"], a.get("live_kv_rows", 0),
             a["experts_touched"])
            for name, start, end, a in spans
            if name == "serving.step" and clock[0] <= start
            and end <= clock[1] and a.get("active_slots", 0) > 0
            and a.get("experts_touched", 0) > 0]


def decode_durations_ns(run) -> list:
    trace = run.get("trace")
    if not trace or 0 not in trace["devices"]:
        return []
    return xplane.module_durations(trace["devices"][0]["modules"],
                                   run["decode_module_prefix"],
                                   *run["trace_window_ns"])


def window_line(run, facts) -> None:
    """Log the least time of the window's decode-only steps at their
    own counts over the median host time of such a step."""
    spans, steps = run.get("spans"), run.get("steps")
    if not spans or not steps:
        return
    roots = {a["step"]: a for name, _, _, a in spans
             if name == "serving.step" and "step" in a}
    if not roots:
        return
    first, cost = min(roots), {}
    for name, start, end, a in spans:
        if name in ("step.decode_dispatch", "step.readback"):
            cost[a["step"]] = cost.get(a["step"], 0.0) + end - start
    pure = [(roots[i], cost[i]) for i in sorted(cost)
            if i in roots and 0 <= i - first < len(steps)
            and steps[i - first][4] == 0
            and roots[i].get("experts_touched", 0) > 0]
    if not pure:
        return
    least = [flops_bytes.least_time_s(
        moe_flops_bytes.decode_step_flops(
            facts, a["active_slots"], a.get("live_kv_rows", 0)),
        moe_flops_bytes.decode_step_bytes(
            facts, a["active_slots"], a.get("live_kv_rows", 0),
            a["experts_touched"]), run["peaks"])[0] for a, _ in pure]
    took = stats.median([c for _, c in pure])
    n = len(pure)
    run["log"](
        f"moe_decode_roofline_share, the WINDOW's {n} decode-only steps "
        f"(host clock, no device metric): median active "
        f"{stats.median([a['active_slots'] for a, _ in pure]):.1f}, mean "
        f"experts touched "
        f"{sum(a['experts_touched'] for a, _ in pure) / n:.1f}, mean "
        f"least {1e3 * sum(least) / n:.3f} ms over the median dispatch + "
        f"readback {1e3 * took:.3f} ms = "
        f"{100 * sum(least) / n / took:.1f}%")


def read(run):
    facts = run.get("facts") or {}
    steps, durs = decode_steps(run), decode_durations_ns(run)
    if "expert_params" not in facts or not steps or not durs:
        return None
    least, bounds = [], {}
    for active, rows, touched in steps:
        cap = moe_flops_bytes.experts_cap(facts, active)
        if touched > cap:
            raise ValueError(f"a step with {active} active slots counted "
                             f"{touched} experts touched, over the {cap} "
                             f"they can reach: parked rows were routed")
        t, bound = flops_bytes.least_time_s(
            moe_flops_bytes.decode_step_flops(facts, active, rows),
            moe_flops_bytes.decode_step_bytes(facts, active, rows, touched),
            run["peaks"])
        least.append(t)
        bounds[bound] = bounds.get(bound, 0) + 1
    n = len(steps)
    mean_least = sum(least) / n
    mean_dur = sum(durs) / len(durs) / 1e9
    run["log"](
        f"moe_decode_roofline_share: {n} steps, mean active "
        f"{sum(s[0] for s in steps) / n:.2f}, mean experts touched "
        f"{sum(s[2] for s in steps) / n:.1f} of at most "
        f"{sum(moe_flops_bytes.experts_cap(facts, s[0]) for s in steps) / n:.1f}"
        f", bound by {bounds}, least {1e3 * mean_least:.3f} ms, device "
        f"{1e3 * mean_dur:.3f} ms over {len(durs)} programs")
    window_line(run, facts)
    return moe_flops_bytes.share(mean_least, mean_dur,
                                 "moe_decode_roofline_share")
