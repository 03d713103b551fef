"""engine_programs: median host time of ``step.decode_dispatch`` +
``step.readback`` (the readback closes the device work) over the
window's steps in which no prompt token was prefilled: the engine's
``prefill_tokens`` and ``prefill_chunk_tokens`` counts did not advance
across that ``step()``."""

from benchmarks.lib import stats


def read(run):
    spans, steps = run.get("spans"), run.get("steps")
    if not spans or not steps:
        return None
    first = min((a["step"] for name, _, _, a in spans
                 if name == "serving.step"), default=None)
    if first is None:
        return None
    cost = {}
    for name, start, end, attrs in spans:
        if name in ("step.decode_dispatch", "step.readback"):
            cost[attrs["step"]] = cost.get(attrs["step"], 0.0) + end - start
    pure = [ms for idx, ms in cost.items()
            if 0 <= idx - first < len(steps) and steps[idx - first][4] == 0]
    return 1e3 * stats.median(pure) if pure else None
