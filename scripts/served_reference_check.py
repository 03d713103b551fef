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
the engine's prefill program gives it; and since PR 34 of a model with
EXPERT layers (``model.expert_routing_spec``): ``valid`` keeps padding
and ``--parked`` extra rows (parked at row 0, as a free serving slot
rides along) away from the experts, the experts each (token, expert
layer) chose are read out of the program and compared with the
reference's choice (``routing_disagreements``: pairs whose chosen sets
differ: a near-tie between the last chosen and the first left out flips
on rounding), and the error is given apart for the positions that
agree in every layer; ``--initializer`` draws the weights at other stds
than the configuration file's, and ``--faults`` serves the same prompts
again on wrong builds of the program (``scripts/wrong_builds.py``)
against the same reference: one JSON line each, which is how PR 34
chose an initializer under which the 0.03 bar separates the sound
program from the wrong ones.  Two requests of different
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
    ap.add_argument("--parked", type=int, default=0,
                    help="rows parked at position 0 beside the requests "
                         "in the decode batch (a model with expert "
                         "layers: they must reach no expert)")
    ap.add_argument("--initializer", default="",
                    help="name=std,... over the configuration file's "
                         "``initializer`` group (a routing model)")
    ap.add_argument("--faults", default="",
                    help="wrong builds of scripts/wrong_builds.py, "
                         "comma-separated, each served after the sound "
                         "program against the same reference")
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
    if args.initializer:
        cfg["initializer"] = {
            **cfg.get("initializer", {}),
            **{k: float(v) for k, v in (
                kv.split("=") for kv in args.initializer.split(","))}}
    builder = R.Files(os.path.join(ROOT, "BENCHMARK.json")).module(
        f"builders/{cfg['builder']}.py")
    t0 = time.perf_counter()
    model, mcfg = build_model(builder, cfg, args.seed)
    params, buffers = state(model)
    jax.block_until_ready(params)
    R.log(f"model built in {time.perf_counter() - t0:.1f}s")

    stateful = hasattr(model, "recurrent_state_spec")
    routed = hasattr(model, "expert_routing_spec")
    chosen = []     # a routing model's choices, as the trace meets them
    if routed:
        from paddle_tpu.distributed import moe_dropless
        route = moe_dropless.sigmoid_topk_route

        def recorded(*a, **kw):
            idx, w = route(*a, **kw)
            chosen.append(idx)
            return idx, w
        moe_dropless.sigmoid_topk_route = recorded

    lens = [int(n) for n in args.prompts.split(",")]
    rs = np.random.default_rng(np.random.SeedSequence([args.seed, 9]))
    seqs = [rs.integers(0, mcfg.vocab_size, n + args.new, dtype=np.int32)
            for n in lens]


    def serve():
        """The prompts through the program as it stands: ``(logits per
        sequence, experts chosen per sequence, experts touched by chunk
        and by decode step, decode steps' wall seconds)``."""
        def step(params, caches, ids, pos, valid, state):
            del chosen[:]
            with bind_state(model, params, buffers):
                if stateful:
                    logits, caches, state = model.decode_step(
                        ids, caches, pos, state=state, valid=valid)
                elif routed:
                    # ``state`` carries the choices out: [layers,
                    # tokens, k]
                    logits, caches, rows = model.decode_step(
                        ids, caches, pos, valid=valid)
                    state = (jnp.stack(chosen), rows)
                else:
                    logits, caches = model.decode_step(ids, caches, pos)
            return logits.astype(jnp.float32), caches, state

        step = functools.partial(jax.jit(step, donate_argnums=(1, 5)),
                                 params)
        system, rows = [[] for _ in lens], []
        picks = [[] for _ in lens]      # [layers, k] per compared position
        touched = []
        for r, (n, seq) in enumerate(zip(lens, seqs)):
            cache = model.init_cache(1, args.max_seq)
            state = model.init_state(1) if stateful else ()
            t0 = time.perf_counter()
            for off in range(0, n, args.width):
                valid = min(args.width, n - off)
                ids = np.zeros((1, args.width), np.int32)
                ids[0, :valid] = seq[off:off + valid]
                logits, cache, state = step(
                    [(c[0], c[1], jnp.asarray(off, jnp.int32))
                     for c in cache],
                    jnp.asarray(ids), jnp.asarray(off, jnp.int32),
                    jnp.asarray(valid, jnp.int32), state)
                system[r].append(np.asarray(logits)[0, :valid])
                if routed:
                    picks[r].append(np.asarray(state[0])[:, :valid])
                    touched.append(int(np.count_nonzero(
                        np.asarray(state[1]))))
                    state = ()
            R.log(f"prefill of {n} tokens in chunks of {args.width}: "
                  f"{time.perf_counter() - t0:.2f}s (the first compiles)")
            rows.append((cache, state))
        # the ragged batch: every slab's rows (and every state leaf's) side
        # by side, per-row positions
        parked = model.init_cache(args.parked, args.max_seq) \
            if args.parked else None

        def side_by_side(i, j):
            if rows[0][0][i][j] is None:        # a cache of one row kind
                return None
            parts = [c[i][j] for c, _ in rows]
            return jnp.concatenate(
                parts + ([parked[i][j]] if parked else []), 0)

        caches = [(side_by_side(i, 0), side_by_side(i, 1), None)
                  for i in range(len(rows[0][0]))]
        state = jax.tree_util.tree_map(
            lambda *leaves: jnp.concatenate(leaves, 0),
            *[st for _, st in rows])
        del rows, parked
        live = np.asarray([1] * len(lens) + [0] * args.parked, np.int32)
        pos = np.asarray(lens, np.int32)
        walls, step_touched = [], []
        for k in range(args.new):
            ids = np.stack([seq[n + k] for n, seq in zip(lens, seqs)]
                           + [np.int32(0)] * args.parked)[:, None]
            at = np.concatenate([pos + k, np.zeros(args.parked, np.int32)])
            t0 = time.perf_counter()
            # the caches are donated: their position is an array of its own
            logits, caches, state = step(
                [(c[0], c[1], jnp.asarray(at)) for c in caches],
                jnp.asarray(ids), jnp.asarray(at),
                jnp.asarray(live) if routed else None, state)
            logits = np.asarray(logits)
            walls.append(time.perf_counter() - t0)
            for r in range(len(lens)):
                system[r].append(logits[r])
            if routed:
                idx = np.asarray(state[0])          # [layers, rows, k]
                for r in range(len(lens)):
                    picks[r].append(idx[:, r:r + 1])
                step_touched.append(int(np.count_nonzero(
                    np.asarray(state[1]))))
                state = ()
        del caches, state
        system = [np.concatenate(s, 0) for s in system]
        return system, picks, touched, step_touched, walls

    def judge(build, served):
        """One JSON line: the program as ``build`` against ``refs``."""
        system, picks, touched, step_touched, walls = served
        routing = {}
        if routed:
            agree = [_agreement(t, p) for t, p in zip(theirs, picks)]
            pairs = sum(a.size for a in agree)
            flipped = sum(int((~a).sum()) for a in agree)
            clean = [a.all(axis=0) for a in agree]      # per position
            rel = [np.max(np.abs(s - r), -1) / np.max(np.abs(r))
                   for s, r in zip(system, refs)]

            def worst(mask):
                vals = [float(e[m].max()) for e, m in zip(rel, mask)
                        if m.any()]
                return max(vals) if vals else None
            routing = {
                "routing_pairs": pairs, "routing_disagreements": flipped,
                "routing_disagreement_share": flipped / pairs,
                "positions_disagreeing_somewhere": sum(
                    int((~c).sum()) for c in clean),
                "rel_err_where_all_layers_agree": worst(clean),
                "rel_err_where_a_layer_disagrees": worst(
                    [~c for c in clean]),
                "experts_touched_by_chunk": touched,
                "experts_touched_by_decode_step_max": max(step_touched),
                "parked_rows": args.parked,
                "initializer": cfg.get("initializer")}
        worst_rel, worst_gap, scale = 0.0, 0.0, 0.0
        for sys_l, ref in zip(system, refs):
            worst_rel = max(worst_rel, float(np.max(np.abs(sys_l - ref))
                                             / np.max(np.abs(ref))))
            worst_gap = max(worst_gap, reference.argmax_gap(
                ref, np.argmax(sys_l, -1)))
            scale = max(scale, float(np.max(np.abs(ref))))
        walls = sorted(walls[1:])
        tol = reference.logit_tol(mcfg.dtype)
        print(json.dumps({
            "config": cfg["name"], "seed": args.seed, "build": build,
            "prompts": lens, "decoded": args.new,
            "positions": sum(len(s) for s in system),
            "rel_err": worst_rel, "argmax_gap": worst_gap,
            "max_abs_reference_logit": scale, "tol": tol,
            "within_tol": worst_rel <= tol,
            "tokens_near_reference_argmax": worst_gap <= tol,
            "decode_step_wall_ms_median": 1e3 * walls[len(walls) // 2],
            **routing,
            "device": devices[0].device_kind,
            "memory_peak_bytes": int((devices[0].memory_stats() or {})
                                     .get("peak_bytes_in_use", 0))}),
            flush=True)

    served = serve()
    if routed:
        refs, theirs = _routed_reference(builder, cfg, params, seqs)
    else:
        refs = reference.reference_logits(builder, cfg, params, seqs)
    judge("sound", served)
    for name in filter(None, args.faults.split(",")):
        from scripts.wrong_builds import FAULTS
        undo = []

        def patch(obj, attr, value):
            undo.append((obj, attr, getattr(obj, attr)))
            setattr(obj, attr, value)
        FAULTS[name](model, patch)
        try:
            served = serve()
        finally:
            for obj, attr, value in reversed(undo):
                setattr(obj, attr, value)
        judge(name, served)
    return 0


def _routed_reference(builder, cfg, params, seqs):
    """The reference's logits and, per sequence, ``[expert layers,
    positions, experts]`` bool: the experts it chose (its weights are 0
    off the chosen)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import reference

    @jax.jit
    def forward(p, ids):
        with jax.default_matmul_precision("highest"):
            return builder.reference_forward(cfg, reference._f32(p), ids,
                                             with_routing=True)

    refs, theirs = [], []
    for s in seqs:
        width = -(-len(s) // 128) * 128
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(s)] = s
        logits, w = forward(params, jnp.asarray(ids))
        refs.append(np.asarray(logits)[0, :len(s)])
        theirs.append(np.asarray(w)[:, :len(s)] > 0)
    return refs, theirs


def _agreement(theirs, pick):
    """``[expert layers, positions]`` bool: whether the system's chosen
    experts (``pick``: ``[layers, n_i, k]`` pieces) are the
    reference's."""
    import numpy as np
    ours = np.concatenate(pick, axis=1)                 # [layers, n, k]
    hit = np.take_along_axis(theirs, ours, axis=-1).all(-1)
    return hit & (theirs.sum(-1) == ours.shape[-1])


if __name__ == "__main__":
    sys.exit(main())
