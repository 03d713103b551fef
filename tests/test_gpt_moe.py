"""GPT-MoE model family (BASELINE config #5: expert-parallel MoE).
Oracles follow the reference pattern: EP-parallel == serial loss, aux loss
flows, training learns."""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu
import paddle_tpu.optimizer as opt
import paddle_tpu.distributed as dist
from paddle_tpu.models import GPTMoEForCausalLM, gpt_moe_tiny
from paddle_tpu.nn.functional_call import functional_call, state


def _data(batch=4, seq=16, seed=0):
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, 256, (batch, seq + 1))
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def test_gpt_moe_forward_and_aux_loss():
    paddle_tpu.seed(0)
    cfg = gpt_moe_tiny(gate="gshard")
    model = GPTMoEForCausalLM(cfg)
    model.train()
    params, buffers = state(model)
    x, y = _data()
    key = jax.random.PRNGKey(0)

    @jax.jit
    def fwd(p, b):
        out, nb = functional_call(model, p, b, (x,), rng=key, train=True)
        aux = sum(v for k, v in nb.items() if k.endswith("aux_loss"))
        return out, aux

    logits, aux = fwd(params, buffers)
    assert logits.shape == (4, 16, 256)
    assert float(aux) > 0.0          # gshard aux loss engaged


def test_gpt_moe_trains():
    paddle_tpu.seed(1)
    cfg = gpt_moe_tiny(gate="naive")   # deterministic routing
    model = GPTMoEForCausalLM(cfg)
    model.train()
    params, buffers = state(model)
    o = opt.AdamW(learning_rate=3e-3)
    ostate = o.init(params)
    x, y = _data(seed=2)

    @jax.jit
    def step(p, os_, b):
        def loss_fn(p):
            out, nb = functional_call(model, p, b, (x,), train=True)
            logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
            tok = jnp.take_along_axis(logp, y[..., None], -1)[..., 0]
            aux = sum(v for k, v in nb.items() if k.endswith("aux_loss"))
            return -jnp.mean(tok) + cfg.aux_weight * aux
        loss, g = jax.value_and_grad(loss_fn)(p)
        newp, nos = o.update(g, os_, p)
        return newp, nos, loss

    losses = []
    for _ in range(15):
        params, ostate, loss = step(params, ostate, buffers)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, (losses[0], losses[-1])


def test_gpt_moe_expert_parallel_matches_serial():
    """Same seed, EP over 4 devices == serial (the reference's EP oracle
    pattern at the model level)."""
    paddle_tpu.seed(7)
    cfg_s = gpt_moe_tiny(gate="naive")
    serial = GPTMoEForCausalLM(cfg_s)
    serial.eval()
    x, y = _data(seed=3)
    ps, bs = state(serial)
    out_s, _ = functional_call(serial, ps, bs, (x,), train=False)

    g = dist.collective.new_group(list(range(4)))
    paddle_tpu.seed(7)
    cfg_p = gpt_moe_tiny(gate="naive")
    cfg_p.moe_group = g
    par = GPTMoEForCausalLM(cfg_p)
    par.eval()
    pp, bp = state(par)
    out_p, _ = functional_call(par, pp, bp, (x,), train=False)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_p),
                               rtol=2e-4, atol=2e-4)


def test_gpt_moe_loss_single_forward_with_aux():
    """model.loss = lm + aux from ONE forward: the gates' aux buffers are
    read right after self() inside the same bind (code-review r2: the old
    signature forced a second forward or stale aux)."""
    paddle_tpu.seed(5)
    cfg = gpt_moe_tiny(gate="gshard")
    model = GPTMoEForCausalLM(cfg)
    model.train()
    params, buffers = state(model)
    x, y = _data(seed=6)
    key = jax.random.PRNGKey(1)

    from paddle_tpu.nn.functional_call import bind_state
    from paddle_tpu.framework.random import rng_context

    @jax.jit
    def run(p, b):
        with bind_state(model, p, b):
            with rng_context(key):
                return model.loss(x, y)

    total = float(run(params, buffers))
    # oracle: the two-output route with the SAME rng -> lm + w*aux
    @jax.jit
    def parts(p, b):
        out, nb = functional_call(model, p, b, (x,), rng=key, train=True)
        return GPTMoEForCausalLM.loss_from_logits(out, y, nb,
                                                  cfg.aux_weight)

    np.testing.assert_allclose(total, float(parts(params, buffers)),
                               rtol=1e-5)


def _mk_moe_trainer(hybrid, gate="naive", microbatches=1, seed=11,
                    zero=1, gate_kwargs=None):
    from paddle_tpu.models import GPTMoEHybridTrainer
    s = dist.DistributedStrategy()
    s.hybrid_configs = hybrid
    dist.fleet.init(is_collective=True, strategy=s)
    hcg = dist.get_hybrid_communicate_group()
    paddle_tpu.seed(seed)
    cfg = gpt_moe_tiny(gate=gate, moe_every=1, gate_kwargs=gate_kwargs)
    tr = GPTMoEHybridTrainer(cfg, hcg, opt.SGD(learning_rate=0.1),
                             microbatches=microbatches, zero_stage=zero)
    return tr


def _teardown_hcg():
    dist.topology.set_hybrid_communicate_group(None)


def test_moe_hybrid_ep_pp_zero1_matches_serial():
    """EP x pp x ZeRO-1 GPT-MoE == serial (round-2 VERDICT item 5: the
    expert axis composed with the rest of the fleet topology).

    microbatches=1 so the expert capacity (a function of the routed token
    count) sees the same token set on both paths — with M>1 the
    per-microbatch capacity legitimately differs from whole-batch serial
    (the estimator is nonlinear in the token set; GPT dense covers M>1
    schedule parity)."""
    tr1 = _mk_moe_trainer({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                           "sharding_degree": 1, "ep_degree": 1},
                          microbatches=1)
    st1 = tr1.init_state()
    x, y = tr1.make_batch(batch=4, seq=16, seed=5)
    st1, loss1 = tr1.train_step(st1, x, y)
    st1, loss1b = tr1.train_step(st1, x, y)
    _teardown_hcg()

    tr2 = _mk_moe_trainer({"dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
                           "sharding_degree": 2, "ep_degree": 2},
                          microbatches=1, zero=1)
    # experts must ride the first-class ep axis
    assert tr2.hcg.get_expert_parallel_world_size() == 2
    st2 = tr2.init_state()
    x2, y2 = tr2.make_batch(batch=4, seq=16, seed=5)
    st2, loss2 = tr2.train_step(st2, x2, y2)
    st2, loss2b = tr2.train_step(st2, x2, y2)
    _teardown_hcg()

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=2e-4)
    np.testing.assert_allclose(float(loss1b), float(loss2b), rtol=2e-3)


def test_moe_hybrid_expert_params_shard_over_ep():
    """Per-device expert bytes shrink by the ep degree: the stacked expert
    leaves carry P('pp', 'ep', ...) so no device holds the full expert
    bank (the memory point of expert parallelism)."""
    tr = _mk_moe_trainer({"dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
                          "sharding_degree": 1, "ep_degree": 4},
                         microbatches=1)
    _, pblk, _, _ = tr.init_state()
    key = next(k for k in pblk if "stacked__" in k)
    arr = pblk[key]
    total = arr.size * arr.dtype.itemsize
    shard = arr.addressable_shards[0].data
    per_dev = shard.size * shard.dtype.itemsize
    # blocks over pp(2) x experts over ep(4) -> each device holds 1/8
    assert per_dev * 8 == total, (key, per_dev, total)
    _teardown_hcg()


def test_moe_hybrid_aux_loss_rides_pipeline():
    """Deterministic gshard (random_routing=False): the nonzero balance
    aux accumulated across pipeline stages matches the serial value at
    M=1 (exact: same token set)."""
    tr1 = _mk_moe_trainer({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                           "sharding_degree": 1, "ep_degree": 1},
                          gate="gshard", microbatches=1, seed=13,
                          gate_kwargs={"random_routing": False})
    st1 = tr1.init_state()
    x, y = tr1.make_batch(batch=2, seq=16, seed=9)
    st1, loss1 = tr1.train_step(st1, x, y)
    # aux engaged: loss with aux_weight=0 would differ
    _teardown_hcg()

    tr2 = _mk_moe_trainer({"dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
                           "sharding_degree": 1, "ep_degree": 2},
                          gate="gshard", microbatches=1, seed=13,
                          gate_kwargs={"random_routing": False})
    st2 = tr2.init_state()
    x2, y2 = tr2.make_batch(batch=2, seq=16, seed=9)
    st2, loss2 = tr2.train_step(st2, x2, y2)
    _teardown_hcg()

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=2e-4)


def test_moe_trainer_requires_uniform_blocks():
    from paddle_tpu.models import GPTMoEHybridTrainer
    s = dist.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "pp_degree": 2, "ep_degree": 2}
    dist.fleet.init(is_collective=True, strategy=s)
    hcg = dist.get_hybrid_communicate_group()
    cfg = gpt_moe_tiny(gate="naive", moe_every=2)
    try:
        import pytest
        with pytest.raises(ValueError, match="moe_every"):
            GPTMoEHybridTrainer(cfg, hcg, opt.SGD(learning_rate=0.1))
    finally:
        _teardown_hcg()

def test_ep_mp_parity():
    """ep x mp in ONE mesh (round-3 VERDICT item 5): experts shard over ep
    with weights additionally split over mp (expert-internal tensor
    parallelism — reference: MoELayer(mp_group) alongside the moe group);
    dp x ep x mp == serial loss over two steps."""
    tr1 = _mk_moe_trainer({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                           "sharding_degree": 1, "ep_degree": 1},
                          microbatches=1)
    st1 = tr1.init_state()
    x, y = tr1.make_batch(batch=4, seq=16, seed=21)
    st1, loss1 = tr1.train_step(st1, x, y)
    st1, loss1b = tr1.train_step(st1, x, y)
    _teardown_hcg()

    tr2 = _mk_moe_trainer({"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                           "sharding_degree": 1, "ep_degree": 2},
                          microbatches=1)
    assert tr2.cfg.mp_group == "mp"      # trainer wired the mp group in
    st2 = tr2.init_state()
    x2, y2 = tr2.make_batch(batch=4, seq=16, seed=21)
    st2, loss2 = tr2.train_step(st2, x2, y2)
    st2, loss2b = tr2.train_step(st2, x2, y2)
    _teardown_hcg()

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=2e-4)
    np.testing.assert_allclose(float(loss1b), float(loss2b), rtol=2e-3)


def test_ep_mp_expert_params_shard_over_both_axes():
    """Stacked expert weight bytes per device shrink by ep x mp: the
    stacked w0 leaf carries P('ep', None, 'mp') — no device holds a full
    expert bank NOR a full expert's weight."""
    tr = _mk_moe_trainer({"dp_degree": 1, "mp_degree": 2, "pp_degree": 1,
                          "sharding_degree": 1, "ep_degree": 4},
                         microbatches=1)
    _, pblk, _, _ = tr.init_state()
    key = next(k for k in pblk if k.endswith("stacked__w0"))
    arr = pblk[key]
    total = arr.size * arr.dtype.itemsize
    shard = arr.addressable_shards[0].data
    per_dev = shard.size * shard.dtype.itemsize
    # experts over ep(4) x inner columns over mp(2) -> each device holds 1/8
    assert per_dev * 8 == total, (key, per_dev, total)
    _teardown_hcg()


def test_expert_stack_inherits_template_specs():
    """ExpertStack prepends the ep axis to each expert param's OWN spec —
    the composition seam that makes any internally-sharded expert
    (not just ExpertFFN) ride ep x mp."""
    from paddle_tpu.distributed.moe import ExpertFFN, ExpertStack
    from paddle_tpu.distributed.sharding_utils import get_param_specs
    paddle_tpu.seed(0)
    experts = [ExpertFFN(8, 16, mp_group="mp") for _ in range(2)]
    stack = ExpertStack(experts, moe_group="ep")
    specs = get_param_specs(stack)
    assert tuple(specs["stacked__w0"]) == ("ep", None, "mp")
    assert tuple(specs["stacked__w1"]) == ("ep", "mp", None)
    assert tuple(specs["stacked__b0"]) == ("ep", "mp")
    assert tuple(specs["stacked__b1"]) == ("ep", None)
