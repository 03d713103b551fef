"""Device time of the three flash-attention kernels alone, on the chip, at
a training cell's attention shape, beside what ``flash_attention_plan``
chose for them.

    python3 scripts/flash_attention_cost.py [--shapes 64x4096x128,16x2048x128] [--dtypes bfloat16,float32] [--sweep 256,512,1024]

For each shape (``<batch x heads>x<seq>x<head_dim>``) and dtype it prints
the plan (every kernel's tile, operand dtype, grid and computing steps)
and the FLOPs the kernel's own matmuls need on the causal half (forward
2 matmuls of ``2 * bh * seq^2 / 2 * head_dim``, ``bwd_dq`` 3, ``bwd_dkv``
4: the backward pair runs 7 where 5 are required, because each kernel
rebuilds the scores and dP).  On a TPU it then runs forward and gradients
of ``flash_attention(causal=True)`` in one program from a trace of its
own and reads each kernel's device time off the trace by the kernel's
name: ms a call and the FLOPs' share of the chip's peak
(``benchmarks/lib/peaks.py``).  ``--sweep`` repeats that with every
``block_q x block_k`` of the given sizes forced on all three kernels:
where ``_MIN_TILES_A_SIDE`` comes from (PERF.md section 6, PR 35).  ``--tree
DIR`` times another checkout's kernels (the parent's, unpacked under
``.checkout/``) in the same call.  Without a TPU it prints the plan and
the FLOPs only, never a time.
"""

import argparse
import importlib
import itertools
import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KERNELS = {"fwd": ("flash_attention_fwd", 2),
           "bwd_dq": ("flash_attention_bwd_dq", 3),
           "bwd_dkv": ("flash_attention_bwd_dkv", 4)}


def causal_flops(bh: int, seq: int, head_dim: int, matmuls: int) -> float:
    """FLOPs of ``matmuls`` score-sized matmuls over the causal half."""
    return matmuls * 2.0 * bh * seq * seq * head_dim / 2


def kernel_ms(trace_dir: str, run, reps: int) -> dict:
    """Median device ms a call of each kernel over ``reps`` traced runs."""
    import jax
    from benchmarks.lib import xplane
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracing = xplane.TraceSlice(trace_dir)
    tracing.start()
    try:
        for _ in range(reps):
            jax.block_until_ready(run())
    finally:
        tracing.stop()
    dev = xplane.load(xplane.find_xplane(trace_dir))["devices"][0]
    out = {}
    for kernel, (name, _) in KERNELS.items():
        # the trace names an operation by its HLO line; autodiff wraps the
        # kernel's name (``%transpose_jvp_flash_attention_bwd_dq__.1 = ...``)
        durs = [dur / 1e6 for op, _, dur in dev["ops"]
                if name in op.split(" = ")[0]]
        if durs:
            out[kernel] = {"ms": round(statistics.median(durs), 4),
                           "calls": len(durs)}
    if not out:
        out["unmatched_top_ops"] = xplane.top_ops(dev["ops"], 5, 60)
    return out


def fwd_and_grads(F, block_q, block_k):
    """One jitted program holding the three kernels: forward and the
    gradients of ``flash_attention(causal=True)``, the tile forced on all
    three where given."""
    import jax

    def run(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k), q, k, v)
        return out, vjp(g)
    return jax.jit(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="64x4096x128,16x2048x128")
    ap.add_argument("--dtypes", default="bfloat16,float32")
    ap.add_argument("--sweep", default="",
                    help="block sizes to force, every pair, on all three "
                         "kernels (e.g. 256,512,1024); empty: the plan's")
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout whose kernels are timed")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)            # benchmarks/ (xplane, peaks)
    sys.path.insert(0, os.path.abspath(args.tree))
    import jax
    import jax.numpy as jnp
    F = importlib.import_module("paddle_tpu.kernels.flash_attention")
    plan_of = getattr(F, "flash_attention_plan", None)  # the parent has none
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    peak = None
    if on_chip:
        from benchmarks.lib.peaks import chip_peaks
        peak = chip_peaks(dev.device_kind)["bf16_flops"]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"tree={os.path.abspath(args.tree)}", flush=True)
    sizes = [int(s) for s in args.sweep.split(",") if s]
    tiles = list(itertools.product(sizes, sizes)) or [(None, None)]
    out_path = os.path.join(ROOT, "chiprun_out", "flash_attention_cost.jsonl")

    for shape, dtype in itertools.product(args.shapes.split(","),
                                          args.dtypes.split(",")):
        bh, seq, d = (int(n) for n in shape.split("x"))
        row = {"shape": [bh, seq, d], "dtype": dtype,
               "plan": plan_of(seq, seq, d, dtype, True) if plan_of else None,
               "causal_gflop": {k: round(causal_flops(bh, seq, d, m) / 1e9, 2)
                                for k, (_, m) in KERNELS.items()}}
        print(json.dumps(row), flush=True)
        if not on_chip:
            continue
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q, k, v, g = (jax.random.normal(kk, (1, seq, bh, d), jnp.float32)
                      .astype(dtype) for kk in keys)
        for bq, bk in tiles:
            timed = {"shape": [bh, seq, d], "dtype": dtype,
                     "forced_tile": [bq, bk] if bq else None}
            try:
                run = fwd_and_grads(F, bq, bk)
                jax.block_until_ready(run(q, k, v, g))
                ms = kernel_ms(os.path.join(ROOT, "benchmarks", "out",
                                            "trace", "flash_attention_cost"),
                               lambda: run(q, k, v, g), args.reps)
            except Exception as e:          # a tile the chip refuses
                timed["error"] = repr(e)[:300]
            else:
                for kernel, (_, matmuls) in KERNELS.items():
                    if kernel in ms:
                        flops = causal_flops(bh, seq, d, matmuls)
                        ms[kernel]["share_of_peak_pct"] = round(
                            100 * flops / peak / (ms[kernel]["ms"] / 1e3), 2)
                timed["kernels"] = ms
            print(json.dumps(timed), flush=True)
            os.makedirs(os.path.dirname(out_path), exist_ok=True)
            with open(out_path, "a") as f:
                f.write(json.dumps({"tree": args.tree, **timed}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
