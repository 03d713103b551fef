"""kernels: the least time the chips could take for one training step
over the device time the step program took.

Least time: forward + backward FLOPs of the step's tokens
(``lib/flops_bytes.train_flops_per_token``, ``required``: recomputed
operations do not count) over chips x peak FLOP/s.  Device time: the
median duration of the step program's events on the trace's ``XLA
Modules`` line of device 0."""

from benchmarks.lib import flops_bytes, stats, xplane


def read(run):
    trace = run.get("trace")
    if not trace or 0 not in trace["devices"]:
        return None
    durs = xplane.module_durations(trace["devices"][0]["modules"],
                                   run["step_module_prefix"],
                                   *run["trace_window_ns"])
    if not durs:
        return None
    flops = run["tokens_per_step"] * flops_bytes.train_flops_per_token(
        run["facts"], run["seq"], "required")
    least = flops / (run["chips"] * run["peaks"]["bf16_flops"])
    return 100.0 * least / (stats.median(durs) / 1e9)
