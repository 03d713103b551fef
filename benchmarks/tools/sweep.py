"""Find a serving cell's knee: one engine, one window per offered rate.

    python3 benchmarks/tools/sweep.py --workload <cell> --seed 1 --seconds 25 --rates 3,4,5,6,7,8

The knee is the highest rate at which at least 95% of the requests due in
the window finished (window plus the bounded drain) and the queue at the
window's end is no deeper than at its middle (mean depth over the last
tenth of the window against the tenth around its middle).  The cell's traffic file
then fixes 0.8 of it.  Needs a TPU, like ``run.py``.
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    from benchmarks import run as R
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    files = R.Files(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell = files.entry("workloads", args.workload)
    devices, peaks = R.demand_tpu(cell["chips"])
    from benchmarks.drivers import open_loop
    from benchmarks.lib import stats, traffic
    ctx, _ = R.make_context(files, args.workload, args.seed, args.seconds,
                            False, devices, peaks, os.path.join(R.HERE, "out"))
    mix = ctx.traffic
    model, mcfg, eng, _ = open_loop.build(ctx)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        at = dataclasses.replace(ctx, traffic={**mix, "rate_per_s": rate})
        w = open_loop.window(at, eng, traffic.open_loop_schedule(
            at.traffic, args.seed, args.seconds, mcfg.vocab_size),
            args.seconds)
        due = len(w["outs"])
        row = {
            "rate_per_s": rate, "due": due,
            "finished_share": sum(w["finished"]) / due,
            "finished_in_window_share": sum(
                1 for r in w["live"] if r.times
                and len(r.times) == r.arrival.max_new_tokens
                and r.times[-1] < w["t_end"]) / due,
            "queue_mid": w["queue_mid"], "queue_end": w["queue_end"],
            "serve_tokens_per_s": w["window_tokens"]
            / (w["t_end"] - w["t0"]),
            "ttft_p50_ms": 1e3 * stats.median(w["ttft"]),
            "ttft_p95_ms": 1e3 * stats.percentile(w["ttft"], 0.95),
            "itl_p50_ms": 1e3 * stats.median(w["gaps"]),
            "itl_p95_ms": 1e3 * stats.percentile(w["gaps"], 0.95),
            "occupancy": w["occupancy"], "drain_s": w["t_drained"]
            - w["t_end"], "programs_in_window": w["clock_all"]["programs"],
            "memory_peak_bytes": w["memory_peak"],
        }
        rows.append(row)
        R.log(f"sweep: {json.dumps(row)}")
    print(json.dumps({"sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
