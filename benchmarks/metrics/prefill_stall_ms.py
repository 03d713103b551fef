"""engine_programs: what a prefilling step adds to the gap of every
request that is decoding.  Over the window's ``serving.step`` spans whose
counts say ``prefill_tokens > 0`` and ``active_slots >
prefills_completed`` (someone else was decoding): the median of the time
from the step's start to the start of its ``step.decode_dispatch`` child:
admission, the prefill programs' dispatch and the first-token readback,
all ahead of the decode program.  Nothing where the program puts no
counts on its step spans."""

from benchmarks.lib import stats


def read(run):
    spans = run.get("spans")
    if not spans:
        return None
    t0, t1 = run["window"]
    dispatch = {a.get("step"): start for name, start, _, a in spans
                if name == "step.decode_dispatch"}
    decoding = [(start, a) for name, start, _, a in spans
                if name == "serving.step" and t0 <= start < t1
                and (a.get("active_slots") or 0) > 0
                and a["step"] in dispatch]
    stall = [dispatch[a["step"]] - start for start, a in decoding
             if (a.get("prefill_tokens") or 0) > 0
             and a["active_slots"] > a.get("prefills_completed", 0)]
    if not stall:
        return None
    run["log"](f"prefill_stall_ms: {len(stall)} of {len(decoding)} "
               f"decoding steps also prefilled "
               f"({100.0 * len(stall) / len(decoding):.1f}%), p95 "
               f"{1e3 * stats.percentile(stall, 0.95):.3f} ms")
    return 1e3 * stats.median(stall)
