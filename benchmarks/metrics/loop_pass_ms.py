"""engine_programs: what ONE pass through the layer stack costs on the
device, for a model whose decode program passes its stack several times
a step.  The mean device time of the decode program in the traced slice
(the ``XLA Modules`` line of device 0, as ``decode_roofline_share`` reads
it) over the median ``loop_passes`` of the slice's decoding
``serving.step`` spans.  The figure to hold against an unlooped model of
the same stack, and the one that must NOT move when a later PR lowers
the number of passes.  Nothing where the program's step spans carry no
``loop_passes``."""

from benchmarks.lib import stats, xplane


def read(run):
    trace, clock = run.get("trace"), run.get("trace_clock")
    spans = run.get("spans")
    if not trace or not spans or not clock or clock[1] is None \
            or 0 not in trace["devices"]:
        return None
    passes = [a["loop_passes"] for name, start, _, a in spans
              if name == "serving.step" and clock[0] <= start <= clock[1]
              and (a.get("active_slots") or 0) > 0
              and a.get("loop_passes")]
    durs = xplane.module_durations(trace["devices"][0]["modules"],
                                   run["decode_module_prefix"],
                                   *run["trace_window_ns"])
    if not passes or not durs:
        return None
    per_step = stats.median(passes)
    mean_dur = sum(durs) / len(durs) / 1e9
    run["log"](f"loop_pass_ms: {len(passes)} decoding steps of "
               f"{per_step:g} passes, device {1e3 * mean_dur:.3f} ms a "
               f"step over {len(durs)} programs")
    return 1e3 * mean_dur / per_step
