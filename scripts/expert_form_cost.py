"""Device time of one decode program and of one prefill chunk of a model
with expert layers, on the chip, at a cell's full size, by the form of
the grouped matmul over its experts.

    python3 scripts/expert_form_cost.py --config benchmarks/configs/joyai-llm-flash-d5.json --seed 1

Builds the cell's engine as the benchmark does.  Decode: for each
candidate (``--decode-forms``: ``gmm:<row tile>`` or ``ragged_dot``)
the ENGINE'S OWN decode program is rebuilt with
``distributed.moe_dropless.grouped_matmul`` replaced by that form, and
timed with 1..``num_slots`` slots live (``--live``; the others parked at
row 0 as a free slot is) from a trace of its own, with the experts the
program touched (the step's own counter).  Prefill: for each width
(``--widths``) and grouped-matmul form (``--chunk-forms``), the
engine's own prefill program over ``--chunks`` chunks of one request.
One JSON row each, with the top operations.  This is where PR 34 chose
``grouped_matmul_route`` (PERF.md section 6; the expanded form of the
attention was timed here too, lost at every width, and went).  Needs a
TPU.
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
    import jax
    from benchmarks.lib import xplane
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        jax.block_until_ready(run())
    finally:
        jax.profiler.stop_trace()
    dev = xplane.load(xplane.find_xplane(trace_dir))["devices"].get(0)
    if dev is None:         # no device plane: not a chip
        return [], []
    durs = sorted(d / 1e6 for d in
                  xplane.module_durations(dev["modules"], prefix))
    return durs, [[n[:90], round(1e3 * s, 3)]
                  for n, s in xplane.top_ops(dev["ops"], top, 100)]


def _form(name):
    """``gmm:<tile>`` / ``ragged_dot`` -> a ``grouped_matmul``."""
    from paddle_tpu.distributed import moe_dropless as M
    if name == "ragged_dot":
        return M.ragged_form
    tile = int(name.split(":")[1])
    return lambda lhs, rhs, sizes: M.gmm_form(lhs, rhs, sizes, row_tile=tile)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--decode-forms", default="gmm:16,gmm:32,ragged_dot")
    ap.add_argument("--live", default="1,4,8,16,32")
    ap.add_argument("--rows", type=int, default=1024,
                    help="cached rows a live slot holds")
    ap.add_argument("--widths", default="512,1024,2048")
    ap.add_argument("--chunk-forms", default="gmm:128,ragged_dot")
    ap.add_argument("--chunks", type=int, default=3)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--top", type=int, default=8)
    args = ap.parse_args(argv)

    from benchmarks import run as R
    devices, _ = R.demand_tpu(1)
    import jax
    import jax.numpy as jnp
    from benchmarks.lib.build import build_model
    from paddle_tpu.distributed import moe_dropless as M
    from paddle_tpu.serving import ServingEngine

    with open(args.config) as f:
        cfg = json.load(f)
    builder = R.Files(os.path.join(ROOT, "BENCHMARK.json")).module(
        f"builders/{cfg['builder']}.py")
    t0 = time.perf_counter()
    model, mcfg = build_model(builder, cfg, args.seed)
    jax.block_until_ready(model.lm_head.weight)
    R.log(f"weights made in {time.perf_counter() - t0:.1f}s; memory "
          f"{(devices[0].memory_stats() or {}).get('bytes_in_use')}")
    eng = ServingEngine(model, **cfg["engine"])
    core = eng.core
    out_dir = os.path.join(ROOT, "benchmarks", "out", "trace", "expert_form")
    rs = np.random.default_rng(args.seed)
    rows = []
    routed = M.grouped_matmul

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)

    def emit(row):
        rows.append(row)
        R.log(json.dumps(row))
        with open(os.path.join(ROOT, "chiprun_out", "expert_form.jsonl"),
                  "a") as f:
            f.write(json.dumps(row) + "\n")

    # ------------------------------------------------------------ decode
    def steps():
        for _ in range(args.steps):
            tok = core._decode_dispatch()
        return tok

    for form in args.decode_forms.split(","):
        M.grouped_matmul = _form(form)
        core._decode_fn = None
        for live in (int(n) for n in args.live.split(",")):
            pos = np.zeros((core.num_slots,), np.int32)
            pos[:live] = args.rows
            core.pool.seq_pos = jnp.asarray(pos)
            core._last_tok = jnp.asarray(rs.integers(
                0, mcfg.vocab_size, core.num_slots), jnp.int32)
            t0 = time.perf_counter()
            try:
                back = np.asarray(steps())
            except Exception as e:          # a form the chip refuses
                emit({"program": "decode", "form": form, "live": live,
                      "error": repr(e)[:300]})
                break
            first_s = time.perf_counter() - t0
            core.pool.seq_pos = jnp.asarray(pos)
            durs, ops = _traced(os.path.join(out_dir, "decode"), steps,
                                "jit_decode", args.top)
            back = np.asarray(core._last_tok)
            emit({"program": "decode", "form": form, "live": live,
                  "first_call_s": round(first_s, 2),
                  "device_ms": [round(d, 3) for d in durs],
                  "experts_touched_last": int(np.asarray(
                      core._decode_dispatch())[core.num_slots]),
                  "finite": bool((back >= 0).all()),
                  "top_ops_ms": ops if live in (1, core.num_slots) else []})
    M.grouped_matmul = routed

    # ----------------------------------------------------------- prefill
    core._staging_init_fn = core._build_staging_init_fn()

    def chunks(fn, width):
        ks, vs, touched = core._staging_init_fn()
        last = None
        for c in range(args.chunks):
            valid = width if c < args.chunks - 1 else width - 13
            ids = np.zeros((1, width), np.int32)
            ids[0, :valid] = rs.integers(0, mcfg.vocab_size, valid)
            last, ks, vs, core._expert_load, touched = fn(
                ks, vs, jnp.asarray(ids), jnp.asarray(c * width, jnp.int32),
                jnp.asarray(valid, jnp.int32), None, core._expert_load,
                touched)
        return last, touched

    for width in (int(w) for w in args.widths.split(",")):
        core.prefill_chunk = width
        for form in args.chunk_forms.split(","):
            M.grouped_matmul = _form(form)
            fn = core._build_prefill_fn()
            t0 = time.perf_counter()
            try:
                last, touched = jax.block_until_ready(chunks(fn, width))
            except Exception as e:      # a form the chip refuses
                emit({"program": "prefill", "width": width,
                      "form": form, "error": repr(e)[:300]})
                continue
            first_s = time.perf_counter() - t0
            durs, ops = _traced(os.path.join(out_dir, "prefill"),
                                lambda: chunks(fn, width),
                                "jit_prefill", args.top)
            emit({"program": "prefill", "width": width, "form": form,
                  "first_call_s": round(first_s, 2),
                  "device_ms": [round(d, 3) for d in durs],
                  "experts_touched": np.asarray(touched).tolist(),
                  "finite": bool(np.isfinite(np.asarray(last)).all()),
                  "top_ops_ms": ops})
    M.grouped_matmul = routed
    print(json.dumps({"config": cfg["name"], "seed": args.seed,
                      "device": devices[0].device_kind, "rows": rows,
                      "memory_peak_bytes": int(
                          (devices[0].memory_stats() or {})
                          .get("peak_bytes_in_use", 0))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
