"""Device time of one prefill chunk by the form that runs the model's
recurrence, and of one decode step, on the chip, at a cell's full size.

    python3 scripts/scan_form_cost.py --config benchmarks/configs/jamba2-3b.json --seed 1

Builds the cell's engine as the benchmark does, then for each form of
``kernels.selective_scan`` (``--forms``) builds the ENGINE'S OWN prefill
program with that form forced (``model.cfg.scan_form``), runs chunks of
``prefill_chunk`` tokens through a request's staging (the first from a
zero state, the rest carried, the last right-padded), and reads the
``jit_prefill`` program's device time and its top operations from a
trace of its own.  Then the one decode program over every slot (its
recurrence is ``one_step`` whatever the form).  One JSON row per form
and one for decode.  This is where PR 32 chose the form ``scan_route``
defaults to (PERF.md section 6).  Needs a TPU, like ``benchmarks/run.py``.
"""

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _traced(trace_dir, run, prefix, top):
    """Run ``run()`` under a profiler trace of its own; the durations
    (ms) of the programs named ``prefix`` on device 0 and the top
    operations of the slice."""
    import jax
    from benchmarks.lib import xplane
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        jax.block_until_ready(run())
    finally:
        jax.profiler.stop_trace()
    dev = xplane.load(xplane.find_xplane(trace_dir))["devices"][0]
    durs = sorted(d / 1e6 for d in
                  xplane.module_durations(dev["modules"], prefix))
    return durs, [[n, round(1e3 * s, 3)]
                  for n, s in xplane.top_ops(dev["ops"], top, 100)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--forms", default="pallas_chunk,sequential")
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--top", type=int, default=8)
    args = ap.parse_args(argv)

    from benchmarks import run as R
    devices, _ = R.demand_tpu(1)
    import jax
    import jax.numpy as jnp
    from benchmarks.lib.build import build_model
    from paddle_tpu.serving import ServingEngine

    with open(args.config) as f:
        cfg = json.load(f)
    builder = R.Files(os.path.join(ROOT, "BENCHMARK.json")).module(
        f"builders/{cfg['builder']}.py")
    model, mcfg = build_model(builder, cfg, args.seed)
    eng = ServingEngine(model, **cfg["engine"])
    core = eng.core
    width = core.prefill_chunk
    out_dir = os.path.join(ROOT, "benchmarks", "out", "trace", "scan_form")
    rs = np.random.default_rng(args.seed)
    rows = []

    def chunks(fn):
        """One request's prefill: ``--chunks`` chunks, the last padded."""
        ks, vs, state = core._staging_init_fn()
        last = None
        for c in range(args.chunks):
            valid = width if c < args.chunks - 1 else width - 13
            ids = np.zeros((1, width), np.int32)
            ids[0, :valid] = rs.integers(0, mcfg.vocab_size, valid)
            last, ks, vs, state = fn(
                ks, vs, jnp.asarray(ids), jnp.asarray(c * width, jnp.int32),
                jnp.asarray(valid, jnp.int32), state)
        return last, state

    core._staging_init_fn = core._build_staging_init_fn()
    for form in args.forms.split(","):
        mcfg.scan_form = form
        fn = core._build_prefill_fn()
        t0 = time.perf_counter()
        last, _ = jax.block_until_ready(chunks(fn))
        first_s = time.perf_counter() - t0
        durs, ops = _traced(os.path.join(out_dir, form),
                            lambda: chunks(fn), "jit_prefill", args.top)
        rows.append({"program": "prefill", "width": width, "form": form,
                     "first_call_s": round(first_s, 2),
                     "device_ms": [round(d, 3) for d in durs],
                     "finite": bool(np.isfinite(np.asarray(last)).all()),
                     "top_ops_ms": ops})
        R.log(json.dumps(rows[-1]))
    mcfg.scan_form = None

    def steps():
        for _ in range(args.steps):
            tok = core._decode_dispatch()
        return tok

    t0 = time.perf_counter()
    jax.block_until_ready(steps())
    first_s = time.perf_counter() - t0
    durs, ops = _traced(os.path.join(out_dir, "decode"), steps,
                        "jit_decode", args.top)
    rows.append({"program": "decode", "slots": core.num_slots,
                 "scan_route": core.scan_route()[0],
                 "attention_route": core.attention_route()[0],
                 "first_call_s": round(first_s, 2),
                 "device_ms": [round(d, 3) for d in durs],
                 "top_ops_ms": ops})
    R.log(json.dumps(rows[-1]))
    print(json.dumps({"config": cfg["name"], "seed": args.seed,
                      "device": devices[0].device_kind, "rows": rows,
                      "memory_peak_bytes": int(
                          (devices[0].memory_stats() or {})
                          .get("peak_bytes_in_use", 0))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
