#!/usr/bin/env python
"""multichip_serving_smoke — drive the tensor-parallel serving engine
over a virtual-device mesh end-to-end and emit the evidence as
artifacts (the TP sibling of ``scripts/chaos_smoke.py``):

  * one identically-initialized GPT behind engines at every requested
    tp degree, in BOTH modes: ``composed`` (the default engine — the
    compute-collective ``tp_fused`` decode at tp > 1) and ``fused``
    (``fused_decode=True`` — the Pallas decode-block pair at tp=1 and
    the SHARDED Pallas block ``tp_fused_block`` at tp > 1, ISSUE 12); a
    mixed-length workload runs to completion per (mode, degree);
  * ``serving_tp.json`` — per-run verdict: decode path (asserted
    ``tp_fused`` composed / ``tp_fused_block`` fused at tp > 1 — the
    fused-TP leg cannot silently fall back), token PARITY against the
    composed tp=1 engine ACROSS modes, tokens/sec, TTFT p50/p99,
    the decode phases' ``serving.phase.decode_dispatch_s`` /
    ``readback_s`` stats (at tp > 1 they carry the fused collectives),
    and the sharded-plane check (slab PartitionSpec on the kv-head
    axis);
  * ``metrics.prom``  — Prometheus text of the last degree's run, so the
    ``serving_tp_degree`` gauge and ``serving_phase_*_s`` histograms
    documented in docs/observability.md can be eyeballed as scraped.

Usage:
    python scripts/multichip_serving_smoke.py --out /tmp/tp_smoke
        [--degrees 1,2,4] [--modes composed,fused] [--requests 6]
        [--slots 4] [--new 6]

The script FAILS (exit 1) on any parity break, undrained request, or a
degree whose plane is not actually sharded —
tests/test_zz_tp_serving_smoke runs it as a tier-1 artifact smoke (CI),
so the multi-chip serving path cannot rot.  On hardware, point
``--degrees`` at the pod slice's chip count; on CPU the XLA_FLAGS
virtual-device mesh (set below when unset) stands in, exactly like the
MULTICHIP dryruns.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _ensure_devices(n: int) -> None:
    """Force an n-device CPU mesh BEFORE jax initializes (same
    discipline as __graft_entry__.dryrun_multichip: never probe a
    backend that may hang, replace any inherited device-count flag)."""
    if os.environ.get("MULTICHIP_SMOKE_REAL_CHIPS") == "1":
        return                      # run on whatever hardware is there
    if "jax" in sys.modules:
        # the host process (pytest's 8-device mesh, a notebook) already
        # initialized a backend: re-forcing the count would clear it
        # under the host's feet — require it to be big enough instead
        import jax
        if len(jax.devices()) >= n:
            return
        raise RuntimeError(
            f"jax already initialized with {len(jax.devices())} "
            f"devices; need {n} (set XLA_FLAGS before importing jax)")
    import re
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = \
        (flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except AttributeError:
        pass                        # jax<0.5: XLA_FLAGS already did it
    try:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    except AttributeError:
        pass
    import jax.extend.backend as _jeb
    _jeb.clear_backends()


def run_degree(model_seed, tp, prompts, slots, new_tokens,
               fused=False):
    import numpy as np  # noqa: F401  (parity compare below)
    import paddle_tpu
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import ServingEngine

    paddle_tpu.seed(model_seed)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    eng = ServingEngine(model, num_slots=slots, tensor_parallel=tp,
                        fused_decode=fused)
    outs = eng.serve_batch(prompts, max_new_tokens=new_tokens,
                           max_steps=20000)
    md = eng.metrics_dict()
    snap = eng.registry.snapshot()
    slab_spec = tuple(eng.core.pool.ks[0].sharding.spec) \
        if tp > 1 else None
    return {
        "tp": tp,
        "mode": "fused" if fused else "composed",
        "decode_path": eng.decode_path,
        "decode_fallback_reason": eng.decode_fallback_reason,
        "tp_fusion_reason": eng.tp_fusion_reason,
        "finished": sum(o.finished for o in outs),
        "tokens": [list(map(int, o.tokens)) for o in outs],
        "tokens_per_sec": md["tokens_per_sec"],
        "ttft_p50_ms": md["ttft_p50_ms"],
        "ttft_p99_ms": md["ttft_p99_ms"],
        "decode_dispatch_s": snap["serving.phase.decode_dispatch_s"],
        "readback_s": snap["serving.phase.readback_s"],
        "tp_degree_gauge": snap["serving.tp_degree"],
        "slab_spec": slab_spec,
    }, eng


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--degrees", default="1,2,4")
    ap.add_argument("--modes", default="composed,fused")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new", type=int, default=6)
    args = ap.parse_args(argv)
    degrees = [int(d) for d in args.degrees.split(",")]
    _ensure_devices(max(degrees))

    import numpy as np
    rs = np.random.RandomState(5)
    lens = [3 + (i * 7) % 16 for i in range(args.requests)]
    prompts = [rs.randint(0, 256, (L,)) for L in lens]

    os.makedirs(args.out, exist_ok=True)
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    bad = [m for m in modes if m not in ("composed", "fused")]
    if bad:
        ap.error(f"--modes entries must be 'composed' or 'fused', "
                 f"got {bad}")
    rows, ok = [], True
    base_tokens, eng = None, None
    for mode in modes:
        fused = mode == "fused"
        for tp in degrees:
            row, eng = run_degree(0, tp, prompts, args.slots,
                                  args.new, fused=fused)
            if base_tokens is None:
                base_tokens = row["tokens"]
                row["parity_vs_tp1"] = True
            else:
                # cross-mode parity: every (mode, degree) run must match
                # the FIRST run's transcript — same model, same prompts
                row["parity_vs_tp1"] = row["tokens"] == base_tokens
            row["drained"] = row.pop("finished") == args.requests
            ok = ok and row["drained"] and row["parity_vs_tp1"]
            # the fused-TP leg must actually engage: a silent fallback
            # is a verdict failure, not a quieter row
            want = {("composed", False): "unfused",
                    ("composed", True): "tp_fused",
                    ("fused", False): "fused",
                    ("fused", True): "tp_fused_block"}[(mode, tp > 1)]
            row["path_ok"] = row["decode_path"] == want
            ok = ok and row["path_ok"]
            if tp > 1:
                sharded = row["slab_spec"] is not None \
                    and "mp" in row["slab_spec"]
                row["plane_sharded"] = sharded
                ok = ok and sharded
            del row["tokens"]       # the verdict, not the transcript
            rows.append(row)
    verdict = {"ok": ok, "rows": rows,
               "config": f"slots{args.slots}-reqs{args.requests}"
                         f"-new{args.new}"}
    with open(os.path.join(args.out, "serving_tp.json"), "w") as f:
        json.dump(verdict, f, indent=1)
    with open(os.path.join(args.out, "metrics.prom"), "w") as f:
        f.write(eng.registry.prometheus())
    print(json.dumps({"ok": ok,
                      "degrees": [r["tp"] for r in rows],
                      "parity": [r["parity_vs_tp1"] for r in rows]}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
