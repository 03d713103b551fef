#!/usr/bin/env python
"""obs_dump — run a short CPU-smoke serving workload and emit the two
telemetry artifacts production tooling scrapes:

  * ``metrics.prom``  — Prometheus text exposition of the engine's
    metrics registry (TTFT/TPOT/step-time histograms, counters, gauges);
  * ``trace.json``    — Chrome trace (chrome://tracing / Perfetto) with
    the engine lane (``serving.step`` and its ``step.*`` phases) and
    per-request lifecycle lanes merged alongside the profiler's own
    host events.

Usage:
    python scripts/obs_dump.py --out /tmp/obs [--requests 6] [--slots 2]

tests/test_observability.py runs this as a tier-1-adjacent smoke test so
the exporters cannot rot: both artifacts must parse (the .prom through a
line-format check, the trace through json.load) every CI round.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def build_workload(n_requests: int, vocab: int, seed: int = 0):
    """Mixed-arrival smoke traffic: varied lengths, a shared prefix pair
    (exercises the radix cache), varied budgets."""
    import numpy as np
    rs = np.random.RandomState(seed)
    lens = [3 + (i * 5) % 12 for i in range(n_requests)]
    prompts = [rs.randint(0, vocab, (L,)) for L in lens]
    if n_requests >= 2:
        # two requests share a prefix so the trace shows a prefix_match
        prompts[-1] = np.concatenate(
            [prompts[0], rs.randint(0, vocab, (2,))])
    return prompts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="obs_dump", description=__doc__)
    ap.add_argument("--out", default="obs_artifacts",
                    help="output directory (created if missing)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new-tokens", type=int, default=6)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.profiler import Profiler
    from paddle_tpu.serving import ServingEngine

    with jax.default_prng_impl("rbg"):
        model = GPTForCausalLM(gpt_tiny())
    eng = ServingEngine(model, num_slots=args.slots, min_bucket=8,
                        record_events=True)
    prompts = build_workload(args.requests, model.cfg.vocab_size)

    os.makedirs(args.out, exist_ok=True)
    prof = Profiler(timer_only=True, trace_dir=args.out)
    tracer = eng.tracer
    tracer.enable()
    try:
        prof.start()
        try:
            # staggered submission: half up front, half mid-flight —
            # the queue_wait/TTFT histograms see real waiting
            half = max(len(prompts) // 2, 1)
            ids = [eng.submit(p, max_new_tokens=args.max_new_tokens)
                   for p in prompts[:half]]
            eng.step()
            ids += [eng.submit(p, max_new_tokens=args.max_new_tokens)
                    for p in prompts[half:]]
            eng.run_until_complete(max_steps=10000)
            for i in ids:
                eng.purge(i)
        finally:
            prof.stop()
        prom_path = os.path.join(args.out, "metrics.prom")
        with open(prom_path, "w") as f:
            f.write(eng.registry.prometheus())
        trace_path = os.path.join(args.out, "trace.json")
        # prof.export merges the profiler's host events with the engine
        # tracer's lanes (record_events=True registered it)
        prof.export(trace_path)
    finally:
        tracer.disable()
        tracer.remove_profiler_source()

    with open(trace_path) as f:
        n_events = len(json.load(f)["traceEvents"])
    summary = {
        "metrics_prom": prom_path,
        "trace_json": trace_path,
        "trace_events": n_events,
        "requests": len(prompts),
        "ttft_p50_ms": eng.metrics_dict()["ttft_p50_ms"],
    }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
