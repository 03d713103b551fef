"""Hybrid-parallel training across chips: ``GPTHybridTrainer`` over
``fleet.init`` with the degrees the traffic file names (tensor-parallel
layers, the pipeline schedule, their collectives), as
``chip_smoke.hybrid_train_phase`` builds it, over the same cycled seeded
corpus as the single-chip job."""

import numpy as np

from benchmarks.lib import reference, traffic
from benchmarks.lib.build import seed32
from benchmarks.lib.train_loop import report, run_window


def _placement(x) -> dict:
    return {"devices": sorted(d.id for d in x.sharding.device_set),
            "shape": list(x.shape),
            "shard": list(x.addressable_shards[0].data.shape)}


def run(ctx) -> dict:
    import jax
    import paddle_tpu
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.sharding_utils import put_global
    from paddle_tpu.models import GPTHybridTrainer
    from paddle_tpu.nn.functional_call import state

    cfg, mix, log, builder = ctx.config, ctx.traffic, ctx.log, ctx.builder
    batch, seq, degrees = mix["batch"], mix["seq"], dict(mix["degrees"])
    n = int(np.prod(list(degrees.values())))
    if n != ctx.chips:
        raise ValueError(f"degrees {degrees} need {n} chips, the cell "
                         f"has {ctx.chips}")
    mcfg = builder.model_config(cfg, max_seq_len=seq)
    facts = builder.facts(cfg)
    dist.topology.set_hybrid_communicate_group(None)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = degrees
    dist.fleet.init(is_collective=True, strategy=strategy,
                    devices=jax.devices()[:n])
    # the trainer builds its own model from the process generator
    paddle_tpu.seed(seed32(ctx.seed))
    trainer = GPTHybridTrainer(
        mcfg, dist.get_hybrid_communicate_group(),
        opt.AdamW(learning_rate=mix["learning_rate"],
                  multi_precision=mcfg.dtype != "float32"),
        microbatches=mix["microbatches"])
    data = traffic.corpus(mix, ctx.seed, mcfg.vocab_size)
    sharding = NamedSharding(trainer.mesh, P(trainer.batch_spec()[0], None))

    def put(k):
        return (put_global(np.ascontiguousarray(data[k][:, :-1]), sharding),
                put_global(np.ascontiguousarray(data[k][:, 1:]), sharding))

    # correct, part 1: the trainer's initial weights, before they are
    # stacked and sharded, are its model's own: hand those to the plain
    # reference, and compare the trainer's forward loss on batch 0
    params, _ = state(trainer.model)
    ref_loss = reference.reference_loss(
        builder, cfg, params, data[0][:, :-1], data[0][:, 1:])
    st = trainer.init_state()
    where = _placement(jax.tree_util.tree_leaves(st[1])[0])
    with jax.set_mesh(trainer.mesh):
        sys_loss = float(jax.jit(trainer.loss_fn)(st[0], st[1], *put(0)))
    tol = reference.loss_tol(mcfg.dtype)
    log(f"batch-0 loss: system {sys_loss} reference {ref_loss} tol {tol}")

    res = run_window(ctx, trainer.train_step, st, put, len(data),
                     batch * seq, mix["sync_every"], mix["trace_steps"])
    log(f"block weights {where}")
    return report(
        ctx, res, facts, seq, batch * seq,
        {"loss_matches_reference": abs(sys_loss - ref_loss) <= tol,
         "block_weights_split_over_all_chips":
             len(where["devices"]) == n and where["shard"] != where["shape"],
         **res["checks"]},
        mix["step_module_prefix"],
        loss_batch0_system=sys_loss, loss_batch0_reference=ref_loss,
        degrees=degrees, microbatches=mix["microbatches"],
        block_weights=where)
