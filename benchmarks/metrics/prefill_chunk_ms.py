"""engine_programs: the device time of one prefill chunk.  The median
duration of the ``jit_prefill`` program's events on device 0's ``XLA
Modules`` line in the traced slice (as ``decode_roofline_share`` reads
``jit_decode``).  With chunked prefill most chunks run at the full width
(an earlier line gives the count by ``width`` from the request lanes'
``prefill_chunk`` spans, and the share that started from a carried
recurrent state), so the median is a full chunk: what a prefilling step
puts ahead of every decoding request's next token.  Nothing where the
slice holds no such program."""

from benchmarks.lib import stats, xplane

PREFILL_MODULE_PREFIX = "jit_prefill"


def chunk_spans(run) -> list:
    """Attributes of the ``prefill_chunk`` spans that began inside the
    traced slice."""
    clock, spans = run.get("trace_clock"), run.get("spans")
    if not spans or not clock or clock[1] is None:
        return []
    return [a for name, start, _, a in spans
            if name == "prefill_chunk" and clock[0] <= start <= clock[1]]


def durations_ns(run) -> list:
    trace = run.get("trace")
    if not trace or 0 not in trace["devices"]:
        return []
    return xplane.module_durations(trace["devices"][0]["modules"],
                                   PREFILL_MODULE_PREFIX,
                                   *run["trace_window_ns"])


def read(run):
    durs = durations_ns(run)
    if not durs:
        return None
    chunks = chunk_spans(run)
    widths = {}
    for a in chunks:
        widths[a.get("width")] = widths.get(a.get("width"), 0) + 1
    carried = sum(1 for a in chunks if a.get("state_carried"))
    run["log"](f"prefill_chunk_ms: {len(durs)} programs, chunks by width "
               f"{dict(sorted(widths.items(), key=str))}, "
               f"{carried} of {len(chunks)} from a carried state, p95 "
               f"{stats.percentile(durs, 0.95) / 1e6:.3f} ms")
    return stats.median(durs) / 1e6
