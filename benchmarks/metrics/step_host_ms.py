"""engine_host: what the host does in a pure decode step besides wait
for the device.  Over the window's ``serving.step`` spans whose counts
say ``active_slots > 0`` and ``prefill_tokens == 0``: the median of the
step's duration minus its ``step.readback`` child's (the readback is the
one place such a step waits for the chip).  Nothing where the program
puts no counts on its step spans."""

from benchmarks.lib import stats


def read(run):
    spans = run.get("spans")
    if not spans:
        return None
    t0, t1 = run["window"]
    readback = {a.get("step"): end - start for name, start, end, a in spans
                if name == "step.readback"}
    host = [end - start - readback[a["step"]]
            for name, start, end, a in spans
            if name == "serving.step" and t0 <= start < t1
            and (a.get("active_slots") or 0) > 0
            and a.get("prefill_tokens") == 0 and a["step"] in readback]
    if not host:
        return None
    run["log"](f"step_host_ms: {len(host)} pure decode steps, p95 "
               f"{1e3 * stats.percentile(host, 0.95):.3f} ms")
    return 1e3 * stats.median(host)
