"""kernels: the grouped matmul over the experts against ITS roofline, in
the decode programs of the traced slice.

The kernel's device time a decode program: the summed durations of the
``XLA Ops`` events on device 0 that fall inside a decode program of the
slice and whose name holds one of ``facts["expert_matmul_ops"]`` (how
the trace names the grouped matmul, which the builder states), over the
number of decode programs.  Its least time: the bytes of the experts the
steps TOUCHED (``experts_touched`` of the slice's decoding
``serving.step`` spans, mean) / peak HBM bandwidth: a decode step's
grouped matmul multiplies a row or two by each expert's matrices, so
reading them is all it must do.  Nothing where the trace holds no such
operation or the steps no count."""

from benchmarks.lib import moe_flops_bytes
from benchmarks.metrics import moe_decode_roofline_share as decode


def kernel_ns_per_program(run, names) -> tuple:
    """``(summed ns of the named operations inside the slice's decode
    programs, number of those programs)``."""
    trace = run.get("trace")
    if not trace or 0 not in trace["devices"]:
        return 0.0, 0
    dev = trace["devices"][0]
    t0, t1 = run["trace_window_ns"]
    spans = sorted((start, start + dur) for name, start, dur
                   in dev["modules"]
                   if name.startswith(run["decode_module_prefix"])
                   and t0 <= start and start + dur <= t1)
    total, i = 0.0, 0
    for name, start, dur in sorted(dev["ops"], key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if i < len(spans) and spans[i][0] <= start \
                and start + dur <= spans[i][1] \
                and any(n in name for n in names):
            total += dur
    return total, len(spans)


def read(run):
    facts = run.get("facts") or {}
    names = facts.get("expert_matmul_ops")
    steps = decode.decode_steps(run)
    if not names or not steps:
        return None
    total, programs = kernel_ns_per_program(run, names)
    if not total:
        return None
    touched = sum(s[2] for s in steps) / len(steps)
    least = moe_flops_bytes.touched_expert_bytes(facts, touched) \
        / run["peaks"]["hbm_bytes_per_s"]
    took = total / programs / 1e9
    run["log"](f"expert_matmul_roofline_share: operations named {names} "
               f"took {1e3 * took:.3f} ms a decode program over {programs} "
               f"programs; mean experts touched {touched:.1f} = "
               f"{1e3 * least:.3f} ms of HBM")
    return moe_flops_bytes.share(least, took,
                                 "expert_matmul_roofline_share")
