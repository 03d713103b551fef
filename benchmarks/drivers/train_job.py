"""Single-chip training: the program ``chip_smoke.train_phase`` proved,
one donated ``jax.jit`` step of ``functional_call`` + cross-entropy +
``AdamW(multi_precision=True)``, over a seeded corpus cycled in a fixed
order, each batch put on the device inside the loop."""

import functools

from benchmarks.lib import reference, traffic
from benchmarks.lib.build import build_model
from benchmarks.lib.train_loop import report, run_window


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.meta_parallel.mp_layers import \
        parallel_cross_entropy
    from paddle_tpu.nn.functional_call import functional_call, state

    cfg, mix, log, builder = ctx.config, ctx.traffic, ctx.log, ctx.builder
    batch, seq = mix["batch"], mix["seq"]
    model, mcfg = build_model(builder, cfg, ctx.seed, max_seq_len=seq)
    facts = builder.facts(cfg)
    params, buffers = state(model)
    data = traffic.corpus(mix, ctx.seed, mcfg.vocab_size)

    def loss_of(p, x, y):
        out, _ = functional_call(model, p, buffers, (x,), train=True)
        return jnp.mean(parallel_cross_entropy(out, y))

    # correct, part 1: before the optimizer state exists, the system's
    # loss on batch 0 against the plain float32 reference's on the same
    # seeded weights (forward only)
    x0, y0 = data[0][:, :-1], data[0][:, 1:]
    sys_loss = float(jax.jit(loss_of)(params, jnp.asarray(x0),
                                      jnp.asarray(y0)))
    ref_loss = reference.reference_loss(builder, cfg, params, x0, y0)
    tol = reference.loss_tol(mcfg.dtype)
    log(f"batch-0 loss: system {sys_loss} reference {ref_loss} tol {tol}")

    o = opt.AdamW(learning_rate=mix["learning_rate"],
                  multi_precision=mcfg.dtype != "float32")
    ostate = o.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, os_, x, y):
        loss, g = jax.value_and_grad(loss_of)(p, x, y)
        newp, nos = o.update(g, os_, p)
        return newp, nos, loss

    def one(st, x, y):
        p, os_, loss = step(*st, x, y)
        return (p, os_), loss

    def put(k):
        return jnp.asarray(data[k][:, :-1]), jnp.asarray(data[k][:, 1:])

    res = run_window(ctx, one, (params, ostate), put, len(data),
                     batch * seq, mix["sync_every"], mix["trace_steps"])
    return report(
        ctx, res, facts, seq, batch * seq,
        {"loss_matches_reference": abs(sys_loss - ref_loss) <= tol,
         **res["checks"]},
        mix["step_module_prefix"],
        loss_batch0_system=sys_loss, loss_batch0_reference=ref_loss)
