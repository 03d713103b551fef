"""kernels: the least time the slice's prefill chunks of a model with
sparse experts could take over the device time they took.

Least time: the larger of FLOPs / peak FLOP/s and bytes / peak HBM
bandwidth (``lib/moe_flops_bytes.py``: the dense weights once, the
experts the chunk TOUCHED, the request's latent rows; 2 FLOPs per
parameter a token passes, ONE row of logits, expanded attention against
the rows held), for every ``prefill_chunk`` span of the slice at its own
``width``, offset and ``experts_touched`` (the program's count, read
back with the request's first token), averaged; device time: the mean
duration of the ``jit_prefill`` programs in the slice
(``prefill_chunk_ms``'s events).  Mean over mean, as
``decode_roofline_share`` is: this traffic's requests end in a chunk
narrower than the full one about as often as they hold a full one, so a
median program is not a full chunk.  An earlier line gives the chunks by
width.  Nothing where the chunks carry no ``experts_touched``."""

from benchmarks.lib import flops_bytes, moe_flops_bytes
from benchmarks.metrics import prefill_chunk_ms


def read(run):
    facts = run.get("facts") or {}
    durs = prefill_chunk_ms.durations_ns(run)
    chunks = [a for a in prefill_chunk_ms.chunk_spans(run)
              if a.get("width") and a.get("experts_touched")]
    if "expert_params" not in facts or not durs or not chunks:
        return None
    stride = max(a["width"] for a in chunks)
    least, bounds, widths = [], {}, {}
    for a in chunks:
        width, touched = a["width"], a["experts_touched"]
        cap = moe_flops_bytes.experts_cap(facts, a.get("tokens", width))
        if touched > cap:
            raise ValueError(f"a chunk of {a.get('tokens')} tokens counted "
                             f"{touched} experts touched, over the {cap} "
                             f"they can reach: padding was routed")
        rows_before = a.get("offset", a.get("chunk", 0) * stride)
        t, bound = flops_bytes.least_time_s(
            moe_flops_bytes.chunk_flops(facts, width, rows_before),
            moe_flops_bytes.chunk_bytes(facts, width, rows_before, touched),
            run["peaks"])
        least.append(t)
        bounds[bound] = bounds.get(bound, 0) + 1
        widths[width] = widths.get(width, 0) + 1
    mean_least = sum(least) / len(least)
    mean_dur = sum(durs) / len(durs) / 1e9
    run["log"](
        f"moe_prefill_roofline_share: {len(chunks)} chunks by width "
        f"{dict(sorted(widths.items()))}, mean experts touched "
        f"{sum(a['experts_touched'] for a in chunks) / len(chunks):.1f}, "
        f"bound by {bounds}, least {1e3 * mean_least:.3f} ms, device "
        f"{1e3 * mean_dur:.3f} ms over {len(durs)} programs")
    return moe_flops_bytes.share(mean_least, mean_dur,
                                 "moe_prefill_roofline_share")
