"""Device time of the decode program by what its rows' sampling asks for.

    python3 scripts/sampling_tail_cost.py --workload gpt3-6.7b.serve-chat --seed 1

Builds a cell's engine exactly as the benchmark does (its configuration,
its warm-up), fills every slot with requests of one sampling setting,
traces a run of pure decode steps and prints the mean device time of the
``jit_decode`` program on the trace's ``XLA Modules`` line and the step's
``sampling_slots`` count, one JSON row per setting.  The benchmark has no
cell whose requests sample (its ``correct`` check holds every token to
the reference's argmax), so this is where the cost that remains for
sampling users is measured, and where the sampling branch's VALUES are
checked on the chip: before the engine is built, ``sample_rows`` and the
ungated pipeline (the plain reference ``tests/test_sampling_tail.py``
holds) are jitted on the same ``[16, 50304]`` operands and keys, and
tokens and carried keys must be bit-equal in every case.  Needs a TPU,
like ``benchmarks/run.py``.
"""

import argparse
import json
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> (rows that sample, their SamplingParams fields)
SETTINGS = (
    ("greedy", 0, {}),
    ("temperature_only_16", 16, dict(temperature=0.8)),
    ("top_k40_16", 16, dict(top_k=40)),
    ("top_p0.9_1", 1, dict(top_p=0.9)),
    ("top_p0.9_16", 16, dict(top_p=0.9)),
    ("top_k40_top_p0.9_16", 16, dict(top_k=40, top_p=0.9)),
)


def check_tail_equality(log) -> dict:
    """``sample_rows`` against the ungated pipeline as two jitted
    programs on this backend: rows all greedy / all sampling / mixed,
    ``top_k`` and ``top_p`` off and on, with and without a mask, float32
    and bfloat16 logits with ties.  Raises on the first difference."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_sampling_tail as T
    cases = drew = 0
    for with_mask in (False, True):
        for dtype in (jnp.float32, jnp.bfloat16):
            for rows in T.ROWS:
                for top_k in (0, 1, 5, 40):
                    for top_p in (1.0, 0.9):
                        ops = list(T._operands("16x50304", rows, top_k,
                                               top_p, with_mask, seed=cases))
                        ops[1] = ops[1].astype(dtype)
                        got = T._jitted("gated")(*ops)
                        want = T._jitted("reference")(*ops)
                        for g, w in zip(got, want):
                            assert g.dtype == w.dtype, (g.dtype, w.dtype)
                            np.testing.assert_array_equal(
                                np.asarray(g), np.asarray(w),
                                err_msg=f"{rows} top_k={top_k} "
                                f"top_p={top_p} mask={with_mask} "
                                f"{jnp.dtype(dtype).name}")
                        # the sampling rows really drew: count the
                        # tokens that are not their row's argmax
                        masked = ops[1].astype(jnp.float32)
                        if with_mask:
                            masked = jnp.where(ops[6], masked, -jnp.inf)
                        drew += int(np.sum(
                            np.asarray(got[0])
                            != np.asarray(jnp.argmax(masked, -1))))
                        cases += 1
    row = {"backend": jax.default_backend(), "cases": cases,
           "all_bit_equal": True, "tokens_off_the_argmax": drew}
    log(f"tail_equality: {json.dumps(row)}")
    return row


def main(argv=None) -> int:
    from benchmarks import run as R
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt3-6.7b.serve-chat")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--prompt-len", type=int, default=192)
    args = ap.parse_args(argv)
    files = R.Files(os.path.join(R.ROOT, "BENCHMARK.json"))
    devices, peaks = R.demand_tpu(files.entry("workloads",
                                              args.workload)["chips"])
    from benchmarks.drivers import open_loop
    from benchmarks.lib import xplane
    from paddle_tpu.serving import SamplingParams
    equality = check_tail_equality(R.log)
    out = os.path.join(R.HERE, "out")
    ctx, _ = R.make_context(files, args.workload, args.seed, 0.0, False,
                            devices, peaks, out)
    _, mcfg, eng, _ = open_loop.build(ctx)
    core = eng.core
    rs = np.random.default_rng(args.seed)
    rows = []
    for name, sampling, fields in SETTINGS:
        for slot in range(core.num_slots):
            sp = SamplingParams(do_sample=True, seed=slot, **fields) \
                if slot < sampling else SamplingParams()
            eng.submit(rs.integers(0, mcfg.vocab_size, args.prompt_len,
                                   dtype=np.int32),
                       max_new_tokens=args.steps + 3 * core.num_slots,
                       sampling=sp)
        while len(core._slots) < core.num_slots:
            eng.step()
        eng.step()                      # one settled step before tracing
        trace_dir = os.path.join(out, "trace", "sampling_tail_cost", name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing = xplane.TraceSlice(trace_dir)
        tracing.start()
        for _ in range(args.steps):
            eng.step()
        tracing.stop()
        counts = {s.attrs.get("sampling_slots")
                  for s in eng.tracer.spans(lane=0, name="serving.step")
                  [-args.steps:]}
        while eng.step():
            pass
        trace = xplane.load(xplane.find_xplane(trace_dir))
        device = trace["devices"].get(0, {"modules": [], "ops": []})
        durs = xplane.module_durations(device["modules"], "jit_decode")
        row = {"setting": name, "sampling_rows": sampling,
               "sampling_slots_read": sorted(counts, key=str),
               "jit_decode_programs": len(durs),
               "top_ops": xplane.top_ops(device["ops"], 4, 70)}
        if durs:                # a CPU rehearsal has no device plane
            row.update(
                jit_decode_device_ms_mean=sum(durs) / len(durs) / 1e6,
                jit_decode_device_ms_min=min(durs) / 1e6,
                jit_decode_device_ms_max=max(durs) / 1e6)
        rows.append(row)
        R.log(f"sampling_tail_cost: {json.dumps(row)}")
    print(json.dumps({"tail_equality": equality,
                      "sampling_tail_cost": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
