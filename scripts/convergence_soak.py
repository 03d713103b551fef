"""Real-corpus convergence soak (round-5 VERDICT item 6).

Every prior loss series was memorization of one repeated random batch.
This drives END-TO-END TRAINING HEALTH on a real corpus with the full
stack — bf16 AMP with f32 masters, global-norm clip, warmup+cosine LR,
periodic validation on a held-out split, a mid-run checkpoint
save/kill/restore/resume cycle (fault injection), and a resume-
equivalence assertion — for >= 2000 steps.

Corpus: the Python standard library's own source files (megabytes of
real text with genuine token statistics; this box is zero-egress, so
the reference's downloadable corpora are unavailable by design —
SURVEY §2.2 text datasets are local-file parsers for the same reason).
Byte-level LM; val split is a disjoint 5% tail of files.

PRE-REGISTERED TARGET (written before the first run): final val CE
< 1.75 nats/byte (~2.52 bits) — far below uniform (5.55 nats) and
unigram (~2.9 nats) entropy — AND the val series must be monotonically
decreasing across its thirds.  Resume equivalence: after the kill at
step 1000, training restarted from the checkpoint must reproduce the
SAME next-step training loss (bitwise state restore) before continuing.

Writes CONVERGENCE_SOAK.json; ~20-40 min on the 1-core CPU host (the
model is sized for that budget: ~4M params, b8/s128).
Run: env JAX_PLATFORMS=cpu \
       python scripts/convergence_soak.py
"""

import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import jax
import jax.numpy as jnp

OUT = os.path.join(ROOT, "CONVERGENCE_SOAK.json")
CKPT_DIR = "/tmp/soak_ckpt"
TOTAL_STEPS = int(os.environ.get("SOAK_STEPS", "2000"))
KILL_AT = TOTAL_STEPS // 2
VAL_EVERY = min(100, max(1, TOTAL_STEPS // 6))
TARGET_VAL_CE = 1.75          # nats/byte, pre-registered above
B = int(os.environ.get("SOAK_BATCH", "8"))
S = 128
LR_PEAK = float(os.environ.get("SOAK_LR", "3e-3"))
WARMUP = 100


def build_corpus():
    import sysconfig
    stdlib = sysconfig.get_paths()["stdlib"]
    files = sorted(glob.glob(os.path.join(stdlib, "*.py")))
    assert len(files) > 100, f"stdlib too small? {len(files)}"
    split = int(len(files) * 0.95)
    def read(fs):
        out = []
        for f in fs:
            try:
                out.append(open(f, "rb").read())
            except OSError:
                pass
        return np.frombuffer(b"\n".join(out), dtype=np.uint8)
    train, val = read(files[:split]), read(files[split:])
    return train, val


def batches(data, rng, n):
    for _ in range(n):
        idx = rng.randint(0, len(data) - S - 1, size=B)
        x = np.stack([data[i:i + S] for i in idx])
        y = np.stack([data[i + 1:i + S + 1] for i in idx])
        yield jnp.asarray(x, jnp.int32), jnp.asarray(y, jnp.int32)


def main():
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import GPTForCausalLM, GPTConfig
    from paddle_tpu.nn.functional_call import functional_call, state

    t_start = time.time()
    train_data, val_data = build_corpus()
    res = {"corpus_bytes": {"train": int(len(train_data)),
                            "val": int(len(val_data))},
           "target_val_ce_nats": TARGET_VAL_CE,
           "config": f"h256-L4-heads4-b{B}-s{S}-bf16-amp-"
                     f"clip1.0-warmup{WARMUP}-cosine{TOTAL_STEPS}",
           "steps": TOTAL_STEPS, "kill_at": KILL_AT}

    paddle_tpu.seed(1234)
    cfg = GPTConfig(vocab_size=256, hidden_size=256, num_layers=4,
                    num_heads=4, max_seq_len=S, dtype="bfloat16",
                    remat=False)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    res["n_params"] = cfg.num_params()
    params, buffers = state(model)
    sched = opt.lr.CosineAnnealingDecay(
        learning_rate=LR_PEAK, T_max=TOTAL_STEPS)
    sched = opt.lr.LinearWarmup(sched, warmup_steps=WARMUP,
                                start_lr=1e-6, end_lr=LR_PEAK)
    o = opt.AdamW(learning_rate=sched, multi_precision=True,
                  grad_clip=opt.ClipGradByGlobalNorm(1.0))
    ostate = o.init(params)

    def loss_fn(p, x, y):
        logits, _ = functional_call(model, p, buffers, (x,), train=True)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))

    @jax.jit
    def step(p, os_, x, y, lr):
        l, g = jax.value_and_grad(loss_fn)(p, x, y)
        newp, nos = o.update(g, os_, p, lr=lr)
        return newp, nos, l

    @jax.jit
    def val_loss(p, x, y):
        return loss_fn(p, x, y)

    def run_val(p):
        rng = np.random.RandomState(9)
        tot = 0.0
        for x, y in batches(val_data, rng, 8):
            tot += float(val_loss(p, x, y))
        return tot / 8

    def save(step_i, p, os_):
        os.makedirs(CKPT_DIR, exist_ok=True)
        paddle_tpu.save({"params": p, "opt": os_, "step": step_i},
                        os.path.join(CKPT_DIR, "soak.pdparams"))

    rng = np.random.RandomState(77)
    train_iter = batches(train_data, rng, TOTAL_STEPS + 10)
    losses, vals = [], []
    t0 = time.time()
    killed_loss_next = None
    i = 0
    while i < TOTAL_STEPS:
        x, y = next(train_iter)
        sched.step()
        lr = jnp.asarray(sched.get_lr(), jnp.float32)
        params, ostate, l = step(params, ostate, x, y, lr)
        i += 1
        if i % 50 == 0:
            losses.append({"step": i, "loss": round(float(l), 4),
                           "lr": round(float(lr), 6)})
        if i % VAL_EVERY == 0:
            v = run_val(params)
            vals.append({"step": i, "val_ce": round(v, 4)})
            print(f"step {i} train {float(l):.4f} val {v:.4f}",
                  flush=True)
            # incremental flush: a killed/timed-out run still leaves an
            # inspectable partial artifact (status: running)
            res["status"] = "running"
            res["train_series"] = losses
            res["val_series"] = vals
            with open(OUT + ".tmp", "w") as f:
                json.dump(res, f, indent=1)
            os.replace(OUT + ".tmp", OUT)   # atomic: a kill mid-dump
                                            # can't truncate the artifact
        if i == KILL_AT:
            # fault injection: persist, THROW AWAY the live state, and
            # restore from disk — the resume must reproduce the next
            # training loss exactly (bitwise state roundtrip)
            x2, y2 = next(train_iter)
            sched.step()
            lr2 = jnp.asarray(sched.get_lr(), jnp.float32)
            p_ref, os_ref, l_ref = step(params, ostate, x2, y2, lr2)
            killed_loss_next = float(l_ref)
            save(i, params, ostate)
            del params, ostate, p_ref, os_ref
            blob = paddle_tpu.load(os.path.join(CKPT_DIR,
                                                "soak.pdparams"))
            params, ostate = blob["params"], blob["opt"]
            assert blob["step"] == i
            params, ostate, l_resume = step(params, ostate, x2, y2, lr2)
            res["resume_equivalence"] = {
                "loss_before_kill": killed_loss_next,
                "loss_after_restore": float(l_resume),
                "equal": bool(np.isclose(killed_loss_next,
                                         float(l_resume),
                                         rtol=0, atol=0)),
            }
            i += 1
            print(f"fault-injection at {KILL_AT}: resume loss "
                  f"{float(l_resume):.6f} vs {killed_loss_next:.6f}",
                  flush=True)

    res["status"] = "done"
    res["train_series"] = losses
    res["val_series"] = vals
    res["wall_s"] = round(time.time() - t0, 1)
    final = vals[-1]["val_ce"]
    thirds = [vals[len(vals) // 3 - 1]["val_ce"],
              vals[2 * len(vals) // 3 - 1]["val_ce"], final]
    res["verdict"] = {
        "final_val_ce": final,
        "target": TARGET_VAL_CE,
        "target_met": bool(final < TARGET_VAL_CE),
        "val_thirds_decreasing": bool(
            thirds[0] > thirds[1] > thirds[2]),
        "resume_exact": res.get("resume_equivalence", {}).get("equal"),
    }
    res["finished_unix"] = time.time()
    with open(OUT + ".tmp", "w") as f:
        json.dump(res, f, indent=1)
    os.replace(OUT + ".tmp", OUT)
    print(json.dumps(res["verdict"]), flush=True)
    assert res["verdict"]["target_met"], final
    assert res["verdict"]["val_thirds_decreasing"], thirds
    assert res["verdict"]["resume_exact"], res.get("resume_equivalence")


if __name__ == "__main__":
    main()
