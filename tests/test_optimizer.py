"""Optimizer tests: update rules vs hand-rolled numpy (model: reference
test/legacy_test/test_adamw_op.py, test_sgd_op.py...)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.nn import functional_call, state


def _simple_params():
    return {"w": jnp.asarray(np.array([1.0, 2.0, 3.0], np.float32)),
            "b": jnp.asarray(np.array([0.5], np.float32))}


def _grads():
    return {"w": jnp.asarray(np.array([0.1, -0.2, 0.3], np.float32)),
            "b": jnp.asarray(np.array([1.0], np.float32))}


def test_sgd():
    o = opt.SGD(learning_rate=0.1)
    p = _simple_params()
    s = o.init(p)
    newp, s = o.update(_grads(), s, p)
    np.testing.assert_allclose(np.asarray(newp["w"]),
                               [1.0 - 0.01, 2.0 + 0.02, 3.0 - 0.03], rtol=1e-6)
    assert int(s["step"]) == 1


def test_momentum():
    o = opt.Momentum(learning_rate=0.1, momentum=0.9)
    p = _simple_params()
    s = o.init(p)
    g = _grads()
    p1, s = o.update(g, s, p)
    p2, s = o.update(g, s, p1)
    # velocity after 2 steps: v2 = 0.9*g + g = 1.9g
    expect = np.asarray(p["w"]) - 0.1 * 0.1 - 0.1 * (0.9 * 0.1 + 0.1)
    np.testing.assert_allclose(float(p2["w"][0]), expect[()] if np.ndim(expect) == 0 else expect[0], rtol=1e-5)


def test_adam_first_step_matches_formula():
    o = opt.Adam(learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8)
    p = _simple_params()
    s = o.init(p)
    g = _grads()
    newp, s = o.update(g, s, p)
    gw = np.asarray(g["w"])
    m = 0.1 * gw
    v = 0.001 * gw**2
    mh = m / 0.1
    vh = v / 0.001
    ref = np.asarray(p["w"]) - 0.001 * mh / (np.sqrt(vh) + 1e-8)
    np.testing.assert_allclose(np.asarray(newp["w"]), ref, rtol=1e-5)


def test_adamw_decoupled_decay():
    o = opt.AdamW(learning_rate=0.01, weight_decay=0.1)
    o2 = opt.Adam(learning_rate=0.01)
    p = _simple_params()
    g = _grads()
    pw, _ = o.update(g, o.init(p), p)
    pa, _ = o2.update(g, o2.init(p), p)
    # AdamW result = Adam result - lr*coef*p
    ref = np.asarray(pa["w"]) - 0.01 * 0.1 * np.asarray(p["w"])
    np.testing.assert_allclose(np.asarray(pw["w"]), ref, rtol=1e-5)


def test_adamw_apply_decay_param_fun():
    o = opt.AdamW(learning_rate=0.01, weight_decay=0.5,
                  apply_decay_param_fun=lambda n: n == "w")
    p = _simple_params()
    g = _grads()
    newp, _ = o.update(g, o.init(p), p)
    o_ref = opt.Adam(learning_rate=0.01)
    pa, _ = o_ref.update(g, o_ref.init(p), p)
    # b has no decay
    np.testing.assert_allclose(np.asarray(newp["b"]), np.asarray(pa["b"]), rtol=1e-6)
    assert not np.allclose(np.asarray(newp["w"]), np.asarray(pa["w"]))


def test_multi_precision_master_weights():
    o = opt.AdamW(learning_rate=0.01, multi_precision=True)
    p = {"w": jnp.asarray(np.random.randn(4).astype(np.float32)).astype(jnp.bfloat16)}
    s = o.init(p)
    assert s["master"]["w"].dtype == jnp.float32
    g = {"w": jnp.asarray(np.full(4, 1e-3, np.float32)).astype(jnp.bfloat16)}
    newp, s = o.update(g, s, p)
    assert newp["w"].dtype == jnp.bfloat16
    assert s["master"]["w"].dtype == jnp.float32


def test_grad_clip_global_norm():
    clip = opt.ClipGradByGlobalNorm(1.0)
    g = {"a": jnp.full((10,), 10.0), "b": jnp.full((10,), 10.0)}
    clipped = clip(g)
    total = np.sqrt(sum(float(jnp.sum(jnp.square(v))) for v in clipped.values()))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


def test_grad_clip_value():
    clip = opt.ClipGradByValue(0.5)
    g = {"a": jnp.asarray([-2.0, 0.1, 3.0])}
    out = clip(g)
    np.testing.assert_allclose(np.asarray(out["a"]), [-0.5, 0.1, 0.5])


def test_lr_schedulers():
    from paddle_tpu.optimizer import lr
    s = lr.StepDecay(0.1, step_size=10, gamma=0.1)
    assert abs(float(s.lr_at(0)) - 0.1) < 1e-7
    assert abs(float(s.lr_at(10)) - 0.01) < 1e-7
    n = lr.NoamDecay(d_model=512, warmup_steps=100, learning_rate=1.0)
    assert float(n.lr_at(50)) < float(n.lr_at(100))
    c = lr.CosineAnnealingDecay(0.1, T_max=100)
    np.testing.assert_allclose(float(c.lr_at(100)), 0.0, atol=1e-7)
    w = lr.LinearWarmup(lr.CosineAnnealingDecay(0.1, 100), 10, 0.0, 0.1)
    assert float(w.lr_at(0)) == 0.0
    np.testing.assert_allclose(float(w.lr_at(10)), 0.1, rtol=1e-5)


def test_optimizer_in_jit_train_loop():
    """End-to-end: jitted train step drives loss down."""
    model = nn.Sequential(nn.Linear(2, 16), nn.Tanh(), nn.Linear(16, 1))
    params, buffers = state(model)
    o = opt.Adam(learning_rate=0.05)
    ostate = o.init(params)

    xs = np.random.randn(64, 2).astype(np.float32)
    ys = (xs[:, :1] * 2 - xs[:, 1:] * 3 + 0.5).astype(np.float32)

    @jax.jit
    def step(p, os_, x, y):
        def loss_fn(p):
            out, _ = functional_call(model, p, buffers, (x,))
            return jnp.mean((out - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(p)
        newp, newos = o.update(g, os_, p)
        return newp, newos, loss

    losses = []
    for _ in range(60):
        params, ostate, loss = step(params, ostate, jnp.asarray(xs), jnp.asarray(ys))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1


def test_eager_step_binding():
    model = nn.Linear(3, 1)
    o = opt.SGD(learning_rate=0.1).bind(model)
    params, buffers = state(model)
    g = {k: jnp.ones_like(v) for k, v in params.items()}
    w_before = np.asarray(model.weight)
    o.step(g)
    np.testing.assert_allclose(np.asarray(model.weight), w_before - 0.1,
                               rtol=1e-6)


def test_constant_linear_cyclic_lr():
    from paddle_tpu.optimizer.lr import ConstantLR, LinearLR, CyclicLR
    c = ConstantLR(0.3, factor=1 / 3, total_steps=4)
    np.testing.assert_allclose(float(c.lr_at(0)), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(c.lr_at(4)), 0.3, rtol=1e-6)
    l = LinearLR(0.4, total_steps=4, start_factor=0.5, end_factor=1.0)
    np.testing.assert_allclose(float(l.lr_at(0)), 0.2, rtol=1e-6)
    np.testing.assert_allclose(float(l.lr_at(2)), 0.3, rtol=1e-6)
    np.testing.assert_allclose(float(l.lr_at(10)), 0.4, rtol=1e-6)
    cy = CyclicLR(0.1, 0.5, step_size_up=4)
    np.testing.assert_allclose(float(cy.lr_at(0)), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(cy.lr_at(4)), 0.5, rtol=1e-6)
    np.testing.assert_allclose(float(cy.lr_at(8)), 0.1, rtol=1e-6)
    cy2 = CyclicLR(0.1, 0.5, step_size_up=4, mode="triangular2")
    np.testing.assert_allclose(float(cy2.lr_at(12)), 0.3, rtol=1e-6)


def test_update_preserves_param_dtype_all_optimizers():
    """bf16 params stay bf16 through update WITHOUT multi_precision: the
    f32 lr scalar silently promoted params to f32 (p - lr*g), the jitted
    step recompiled for the new dtypes, and every later step ran the
    whole model in f32 — measured 13x slower on the v5e for the Llama
    secondary bench (r4)."""
    import jax.numpy as jnp
    params = {"w": jnp.ones((8, 8), jnp.bfloat16),
              "b": jnp.ones((8,), jnp.float32)}
    grads = {"w": jnp.ones((8, 8), jnp.bfloat16) * 0.1,
             "b": jnp.ones((8,), jnp.float32) * 0.1}
    for o in (opt.SGD(learning_rate=0.1), opt.Momentum(learning_rate=0.1),
              opt.Adam(learning_rate=0.1), opt.AdamW(learning_rate=0.1),
              opt.Adamax(learning_rate=0.1),
              opt.Adagrad(learning_rate=0.1),
              opt.Adadelta(learning_rate=0.1),
              opt.RMSProp(learning_rate=0.1),
              opt.Lamb(learning_rate=0.1)):
        st = o.init(params)
        p2, st = o.update(grads, st, params)
        assert p2["w"].dtype == jnp.bfloat16, type(o).__name__
        assert p2["b"].dtype == jnp.float32, type(o).__name__
        p3, _ = o.update(grads, st, p2)
        assert p3["w"].dtype == jnp.bfloat16, (type(o).__name__, "step 2")


# ---------------------------------------------------------------------
# the per-leaf gradient barrier of Optimizer.update (PR 33)
# ---------------------------------------------------------------------

def _barrier_params(dtype):
    rs = np.random.default_rng(0)
    return {"w": jnp.asarray(rs.standard_normal((16, 24)), dtype),
            "norm": jnp.asarray(rs.standard_normal((24,)), dtype),
            "b": jnp.asarray(rs.standard_normal((24,)), jnp.float32)}


_BARRIER_CASES = [
    (cls, mp, kw)
    for cls, kw in (("SGD", {}), ("Momentum", {"momentum": 0.9}),
                    ("Adam", {}), ("AdamW", {"weight_decay": 0.05}))
    for mp in (False, True)
] + [("AdamW", True, {"weight_decay": 0.05,
                      "apply_decay_param_fun": lambda n: n != "norm"})]


@pytest.mark.parametrize(
    "cls,mp,kw", _BARRIER_CASES,
    ids=[f"{c}-{'mp' if mp else 'plain'}"
         + ("-decay_fun" if "apply_decay_param_fun" in kw else "")
         for c, mp, kw in _BARRIER_CASES])
def test_update_barrier_is_the_identity_on_values(cls, mp, kw, monkeypatch):
    """Two jitted steps with the gradient barrier and two with it
    replaced by the identity give bit-equal parameters and state."""
    params = _barrier_params(jnp.bfloat16)
    grads = jax.tree.map(lambda p: (p * 0.37 + 0.01).astype(p.dtype), params)

    def two_steps():
        o = getattr(opt, cls)(learning_rate=0.01, multi_precision=mp, **kw)
        upd = jax.jit(o.update)       # a fresh cache: traced anew
        p, s = upd(grads, o.init(params), params)
        return upd(grads, s, p)

    with_barrier = two_steps()
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    without = two_steps()
    a, b = jax.tree.leaves(with_barrier), jax.tree.leaves(without)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


@pytest.mark.parametrize("frozen", [(), ("b",), ("w", "b")],
                         ids=["none_frozen", "one_frozen", "two_frozen"])
def test_update_holds_one_barrier_per_gradient_leaf(frozen):
    """The jaxpr of a jitted value_and_grad + update step holds exactly
    one optimization_barrier per gradient leaf, and none for a leaf
    whose gradient is None."""
    from scripts.train_step_fusions import count_primitive
    params = _barrier_params(jnp.float32)
    o = opt.AdamW(learning_rate=0.01)

    @jax.jit
    def step(p, s, x):
        loss, g = jax.value_and_grad(
            lambda p: jnp.sum((x @ p["w"] * p["norm"] + p["b"]) ** 2))(p)
        g = {k: None if k in frozen else v for k, v in g.items()}
        return o.update(g, s, p), loss

    x = jnp.ones((4, 16), jnp.float32)
    jaxpr = step.trace(params, o.init(params), x).jaxpr.jaxpr
    assert count_primitive(jaxpr, "optimization_barrier") \
        == len(params) - len(frozen)
    (newp, _), _ = step(params, o.init(params), x)
    for k in params:
        same = bool(jnp.all(newp[k] == params[k]))
        assert same == (k in frozen), k


@pytest.mark.parametrize("how", ["vmap", "shard_map"])
def test_update_traces_under_vmap_and_shard_map(how):
    """The barrier has a batching rule and a shard_map rule: a batch of
    independent updates, and an update inside shard_map on a one-device
    mesh, equal the plain update."""
    params = _barrier_params(jnp.float32)
    grads = jax.tree.map(lambda p: p * 0.37 + 0.01, params)
    o = opt.AdamW(learning_rate=0.01)
    want, _ = jax.jit(o.update)(grads, o.init(params), params)
    if how == "vmap":
        stack = lambda t: jax.tree.map(lambda v: jnp.stack([v, 2 * v]), t)
        state0 = o.init(params)
        got, _ = jax.jit(jax.vmap(o.update, in_axes=(0, None, 0)))(
            stack(grads), state0, stack(params))
        got = jax.tree.map(lambda v: v[0], got)
    else:
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
        got, _ = jax.jit(jax.shard_map(
            o.update, mesh=mesh, in_specs=(P(), P(), P()),
            out_specs=P()))(grads, o.init(params), params)
    for k in params:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-7)
