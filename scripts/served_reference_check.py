"""On the chip: a served model's logits, prefill then decode through the
KV cache, against its family's plain float32 ``highest`` reference, at
the configuration's full size on seeded weights.

    python3 scripts/served_reference_check.py --config benchmarks/configs/ouro-2.6b.json --seed 1
    python3 scripts/served_reference_check.py --config benchmarks/configs/jamba2-3b.json --seed 1 \
        --prompts 1500,700 --new 64 --max-seq 4096 --width 512

The benchmark's ``correct`` judges sampled TOKENS of the engine
(``argmax_gap``); this reads the LOGITS the same programs' model code
produces, which the engine never hands out: ``max|system - reference| /
max|reference|`` over every compared position, beside ``argmax_gap`` of
the system's own argmax.  Written for PR 28's first chip experiment (a
model that passes 192 layer applications where the 0.03 bar of
``benchmarks/lib/reference.py`` was set at 4-8), and since PR 32 also
the check of a model that carries a RECURRENT state (``model.
recurrent_state_spec``): its state rides beside the caches, chunk to
chunk and step to step, and every chunk is given its ``valid`` count, as
the engine's prefill program gives it.  Two requests of different
lengths: each prefilled alone into a one-row cache in chunks of
``--width`` tokens (the last one right-padded), as the engine prefills,
then decoded together as one ragged batch with per-row positions, fed
the sequence's own next tokens so that system and reference see the same
inputs.  Needs a TPU, like ``benchmarks/run.py``; prints one JSON line.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--prompts", default="96,40")
    ap.add_argument("--new", type=int, default=24)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--width", type=int, default=128,
                    help="prefill chunk width the prompts are padded to")
    args = ap.parse_args(argv)

    from benchmarks import run as R
    devices, _ = R.demand_tpu(1)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import reference
    from benchmarks.lib.build import build_model
    from paddle_tpu.nn.functional_call import bind_state, state

    with open(args.config) as f:
        cfg = json.load(f)
    builder = R.Files(os.path.join(ROOT, "BENCHMARK.json")).module(
        f"builders/{cfg['builder']}.py")
    t0 = time.perf_counter()
    model, mcfg = build_model(builder, cfg, args.seed)
    params, buffers = state(model)
    jax.block_until_ready(params)
    R.log(f"model built in {time.perf_counter() - t0:.1f}s")

    stateful = hasattr(model, "recurrent_state_spec")

    def step(params, caches, ids, pos, valid, state):
        with bind_state(model, params, buffers):
            if stateful:
                logits, caches, state = model.decode_step(
                    ids, caches, pos, state=state, valid=valid)
            else:
                logits, caches = model.decode_step(ids, caches, pos)
        return logits.astype(jnp.float32), caches, state

    step = functools.partial(jax.jit(step, donate_argnums=(1, 5)), params)
    lens = [int(n) for n in args.prompts.split(",")]
    rs = np.random.default_rng(np.random.SeedSequence([args.seed, 9]))
    seqs = [rs.integers(0, mcfg.vocab_size, n + args.new, dtype=np.int32)
            for n in lens]

    system, rows = [[] for _ in lens], []
    for r, (n, seq) in enumerate(zip(lens, seqs)):
        cache = model.init_cache(1, args.max_seq)
        state = model.init_state(1) if stateful else ()
        t0 = time.perf_counter()
        for off in range(0, n, args.width):
            valid = min(args.width, n - off)
            ids = np.zeros((1, args.width), np.int32)
            ids[0, :valid] = seq[off:off + valid]
            logits, cache, state = step(
                [(c[0], c[1], jnp.asarray(off, jnp.int32)) for c in cache],
                jnp.asarray(ids), jnp.asarray(off, jnp.int32),
                jnp.asarray(valid, jnp.int32), state)
            system[r].append(np.asarray(logits)[0, :valid])
        R.log(f"prefill of {n} tokens in chunks of {args.width}: "
              f"{time.perf_counter() - t0:.2f}s (the first compiles)")
        rows.append((cache, state))
    # the ragged batch: every slab's rows (and every state leaf's) side
    # by side, per-row positions
    caches = [tuple(jnp.concatenate([c[i][j] for c, _ in rows], 0)
                    for j in (0, 1)) + (None,)
              for i in range(len(rows[0][0]))]
    state = jax.tree_util.tree_map(lambda *leaves: jnp.concatenate(leaves, 0),
                                   *[st for _, st in rows])
    del rows
    pos = np.asarray(lens, np.int32)
    walls = []
    for k in range(args.new):
        ids = np.stack([seq[n + k] for n, seq in zip(lens, seqs)])[:, None]
        t0 = time.perf_counter()
        # the caches are donated: their position is an array of its own
        logits, caches, state = step(
            [(c[0], c[1], jnp.asarray(pos + k)) for c in caches],
            jnp.asarray(ids), jnp.asarray(pos + k), None, state)
        logits = np.asarray(logits)
        walls.append(time.perf_counter() - t0)
        for r in range(len(lens)):
            system[r].append(logits[r])
    del caches, state
    system = [np.concatenate(s, 0) for s in system]

    refs = reference.reference_logits(builder, cfg, params, seqs)
    worst_rel, worst_gap, scale = 0.0, 0.0, 0.0
    for sys_l, ref in zip(system, refs):
        worst_rel = max(worst_rel, float(np.max(np.abs(sys_l - ref))
                                         / np.max(np.abs(ref))))
        worst_gap = max(worst_gap, reference.argmax_gap(
            ref, np.argmax(sys_l, -1)))
        scale = max(scale, float(np.max(np.abs(ref))))
    walls = sorted(walls[1:])
    print(json.dumps({
        "config": cfg["name"], "seed": args.seed, "prompts": lens,
        "decoded": args.new, "positions": sum(len(s) for s in system),
        "rel_err": worst_rel, "argmax_gap": worst_gap,
        "max_abs_reference_logit": scale,
        "tol": reference.logit_tol(mcfg.dtype),
        "within_tol": worst_rel <= reference.logit_tol(mcfg.dtype),
        "decode_step_wall_ms_median": 1e3 * walls[len(walls) // 2],
        "device": devices[0].device_kind,
        "memory_peak_bytes": int((devices[0].memory_stats() or {})
                                 .get("peak_bytes_in_use", 0))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
