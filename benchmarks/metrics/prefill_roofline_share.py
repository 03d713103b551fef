"""kernels: the least time ONE full-width prefill chunk could take over
the device time a chunk took (``prefill_chunk_ms``), in the traced slice.

Least time: the larger of FLOPs / peak FLOP/s and bytes / peak HBM
bandwidth (``lib/prefill_flops_bytes.py``: every matmul parameter but
the head twice per token, ONE row of logits, causal attention against
the rows the request holds; every weight once, the recurrent state read
and written, the request's KV rows), averaged over the slice's chunks of
the widest width, each at the offset its span gives.  Nothing where the
slice holds no ``jit_prefill`` program or no ``prefill_chunk`` span."""

from benchmarks.lib import flops_bytes, prefill_flops_bytes, stats
from benchmarks.metrics import prefill_chunk_ms


def read(run):
    durs = prefill_chunk_ms.durations_ns(run)
    chunks = [a for a in prefill_chunk_ms.chunk_spans(run)
              if a.get("width")]
    if not durs or not chunks:
        return None
    width = max(a["width"] for a in chunks)
    full = [a for a in chunks if a["width"] == width]
    facts, peaks = run["facts"], run["peaks"]
    least, bounds = [], {}
    for a in full:
        # every chunk before a full-width one is full-width too
        rows_before = a.get("chunk", 0) * width
        t, bound = flops_bytes.least_time_s(
            prefill_flops_bytes.chunk_flops(facts, width, rows_before),
            prefill_flops_bytes.chunk_bytes(facts, width, rows_before),
            peaks)
        least.append(t)
        bounds[bound] = bounds.get(bound, 0) + 1
    mean_least = sum(least) / len(least)
    dur = stats.median(durs) / 1e9
    run["log"](f"prefill_roofline_share: {len(full)} of {len(chunks)} "
               f"chunks at width {width}, bound by {bounds}, least "
               f"{1e3 * mean_least:.3f} ms, device {1e3 * dur:.3f} ms "
               f"(median of {len(durs)} programs)")
    return 100.0 * mean_least / dur
