"""Optimizer base + the classic suite.

Reference: python/paddle/optimizer/optimizer.py — Optimizer (regularization,
grad clip, multi_precision master weights, _apply_optimize), sgd.py,
momentum.py, adagrad.py, rmsprop.py; fused in-place device kernels
(_C_ops.adamw_) — SURVEY.md §2.2 "Optimizers".

TPU-native: optimizers are pure update rules (init/update over pytrees) the
way optax shapes them, so the whole update fuses into the jitted train step
(the reference needs hand-fused CUDA multi-tensor kernels for that).  A
stateful ``step()`` convenience mirrors the reference's eager API for
single-device scripts.

The ``multi_precision`` master-weight scheme is kept: when a param is
bf16/fp16, state carries an fp32 master copy; updates run in fp32 and cast
back (reference: Optimizer._multi_precision / master_weights).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .clip import GradClipBase, clip_grads
from .lr import LRScheduler, make_scheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adagrad", "RMSProp", "Adadelta",
           "Adamax", "ASGD"]


def _tree_cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


class Optimizer:
    """Base class. Subclasses implement ``_init_slot(p)`` and
    ``_update_param(g, p, slots, lr, step)`` returning (new_p, new_slots).
    """

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip: Optional[GradClipBase] = None,
                 multi_precision: bool = False, name=None):
        self._lr_sched: LRScheduler = make_scheduler(learning_rate)
        self._parameters = parameters  # optional binding for eager step()
        self.weight_decay = weight_decay if not isinstance(weight_decay, (int, float)) \
            else float(weight_decay)
        self.grad_clip = grad_clip
        self.multi_precision = multi_precision
        self._bound_layer = None
        self._state = None
        self._jit_update = None

    # ------------------------------------------------------------------
    # functional API
    # ------------------------------------------------------------------
    def init(self, params) -> Dict[str, Any]:
        def make_master(p):
            if self.multi_precision and p.dtype in (jnp.float16, jnp.bfloat16):
                return p.astype(jnp.float32)
            return None
        state = {
            "step": jnp.zeros((), jnp.int32),
            "slots": jax.tree.map(self._init_slot, params),
            "master": jax.tree.map(make_master, params),
        }
        return state

    def _decay_coef(self) -> float:
        """L2-style decay coefficient; 0 for L1Decay (see _l1_coef) so no
        subclass/fused path double-applies an L1 regularizer as L2."""
        wd = self.weight_decay
        if wd is None or type(wd).__name__ == "L1Decay":
            return 0.0
        if isinstance(wd, float):
            return wd
        # L2Decay-like object with a coeff attribute
        return float(getattr(wd, "_coeff", getattr(wd, "coeff", 0.0)))

    def _l1_coef(self) -> float:
        wd = self.weight_decay
        if wd is not None and type(wd).__name__ == "L1Decay":
            return float(getattr(wd, "coeff", 0.0))
        return 0.0

    def update(self, grads, state, params, lr=None):
        """Returns (new_params, new_state).  Pure; jit/pjit-safe.

        lr: optional override (traced scalar).  Default derives the schedule
        from the internal step counter — the jit-native convention.  Eager
        scripts that drive ``scheduler.step()`` per epoch (reference
        convention) go through :meth:`step`, which passes the scheduler's
        host-side lr here so both semantics hold.
        """
        with jax.named_scope("optimizer"):
            return self._update(grads, state, params, lr)

    def _update(self, grads, state, params, lr):
        grads = clip_grads(grads, self.grad_clip)
        step = state["step"]
        if lr is None:
            lr = self._lr_sched.lr_at(step)
        l2 = self._decay_coef()
        # L1Decay regularizer: coeff * sign(param) added to the gradient
        # (reference: paddle.regularizer.L1Decay)
        l1 = self._l1_coef()

        def upd(g, p, slots, master):
            if g is None:
                return p, slots, master
            # The identity on values, and NOT dead code: it ends the
            # fusion that computes g at g.  Without it XLA:TPU makes this
            # whole update the epilogue of the weight-gradient matmul,
            # which then drags master, both moments and their new values
            # through its output window, takes smaller tiles and runs at
            # half the speed of either part alone.  One barrier per leaf:
            # one over the whole tree would hold every update back until
            # the LAST gradient exists.  Every leaf, the small ones too:
            # sparing those under 16.8M elements read 284.9 ms a step
            # against 285.7, not worth a constant that knows one model's
            # shapes, and a spared leaf can fuse badly again.  Measured on
            # a v5e, PR 33 (docs/performance.md, "Why Optimizer.update
            # holds a barrier"; scripts/train_step_fusions.py counts them).
            g = jax.lax.optimization_barrier(g)
            compute_p = master if master is not None else p
            g32 = g.astype(jnp.float32) if master is not None else g
            if l1:
                g32 = g32 + l1 * jnp.sign(compute_p)
            if l2 and self._l2_mode == "l2":
                g32 = g32 + l2 * compute_p
            new_p, new_slots = self._update_param(g32, compute_p, slots, lr, step)
            if l2 and self._l2_mode == "decoupled" and self._should_decay(p):
                new_p = new_p - lr * l2 * compute_p
            if master is not None:
                return new_p.astype(p.dtype), new_slots, new_p
            # dtype contract: updated params keep the parameter dtype.
            # Without this cast a bf16 model without multi_precision is
            # silently promoted to f32 by the f32 lr scalar (p - lr*g),
            # the step recompiles for the new dtypes, and every later
            # step runs the WHOLE model in f32 — measured 13x slower on
            # the v5e for the Llama secondary bench (r4).
            return new_p.astype(p.dtype), new_slots, None

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(state["slots"])
        flat_m = treedef.flatten_up_to(state["master"])
        out = [upd(g, p, s, m) for g, p, s, m in zip(flat_g, flat_p, flat_s, flat_m)]
        new_params = treedef.unflatten([o[0] for o in out])
        new_slots = treedef.unflatten([o[1] for o in out])
        new_master = treedef.unflatten([o[2] for o in out])
        return new_params, {"step": step + 1, "slots": new_slots,
                            "master": new_master}

    # L2 handling mode: classic optimizers treat weight_decay as L2 reg on the
    # gradient; AdamW overrides to "decoupled".
    _l2_mode = "l2"

    def _should_decay(self, p) -> bool:
        return True

    def _init_slot(self, p):
        return ()

    def _update_param(self, g, p, slots, lr, step):
        raise NotImplementedError

    # ------------------------------------------------------------------
    # stateful eager convenience (parity with reference scripts)
    # ------------------------------------------------------------------
    def bind(self, layer) -> "Optimizer":
        """Bind to an nn.Layer for eager .step(grads) usage."""
        self._bound_layer = layer
        return self

    def step(self, grads: Optional[dict] = None):
        """Eager: apply ``grads`` (dict keyed like state_dict) to the bound
        layer's parameters in place.  Requires bind() or parameters= at ctor
        being a Layer."""
        layer = self._bound_layer
        if layer is None:
            raise ValueError("Optimizer.step() needs bind(layer) first; "
                             "for functional training use update()")
        from ..nn.functional_call import parameters_dict
        params = parameters_dict(layer)
        if self._state is None:
            self._state = self.init(params)
        if self._jit_update is None:
            self._jit_update = jax.jit(
                lambda g, s, p, lr: self.update(g, s, p, lr=lr))
        # lr passed as a traced arg: scheduler.step()/set_lr() between calls
        # take effect without recompilation
        new_params, self._state = self._jit_update(
            grads, self._state, params, jnp.asarray(self.get_lr(), jnp.float32))
        # write back
        index = {}
        for lname, sub in layer.named_sublayers(include_self=True):
            for pname in sub._parameters:
                key = f"{lname}.{pname}" if lname else pname
                index[key] = (sub._parameters, pname)
        for k, v in new_params.items():
            store, name = index[k]
            store[name] = v

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """Static-graph entry (reference: Optimizer.minimize).  Marks the
        loss Variable's Program as a training program; Executor.run then
        replays forward + AD + this optimizer's pure update as one jitted
        step.  Returns the reference's (ops, params_grads) tuple shape."""
        from ..static.program import Variable
        if isinstance(loss, Variable):
            loss.program._set_train(loss, self)
            return None, []
        raise ValueError(
            "minimize() takes a static-graph loss Variable; in eager mode "
            "compute grads functionally and call update()/step()")

    def clear_grad(self):
        pass  # grads are values here, nothing to zero (parity no-op)

    clear_gradients = clear_grad

    def get_lr(self) -> float:
        return self._lr_sched.get_lr()

    def set_lr(self, value: float):
        self._lr_sched = make_scheduler(float(value))

    def state_dict(self):
        return {"state": self._state, "lr": self._lr_sched.state_dict()}

    def set_state_dict(self, sd):
        self._state = sd.get("state")
        if "lr" in sd:
            self._lr_sched.set_state_dict(sd["lr"])

    @property
    def _learning_rate(self):
        return self._lr_sched


class SGD(Optimizer):
    def _update_param(self, g, p, slots, lr, step):
        return p - lr * g, slots


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _init_slot(self, p):
        return {"velocity": jnp.zeros(p.shape, jnp.float32)}

    def _update_param(self, g, p, slots, lr, step):
        v = self.momentum * slots["velocity"] + g.astype(jnp.float32)
        if self.use_nesterov:
            upd = g.astype(jnp.float32) + self.momentum * v
        else:
            upd = v
        return (p - lr * upd.astype(p.dtype)).astype(p.dtype), {"velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.epsilon = epsilon
        self.initial_accumulator_value = initial_accumulator_value

    def _init_slot(self, p):
        return {"moment": jnp.full(p.shape, self.initial_accumulator_value,
                                   jnp.float32)}

    def _update_param(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        m = slots["moment"] + jnp.square(g32)
        upd = g32 / (jnp.sqrt(m) + self.epsilon)
        return (p - lr * upd.astype(p.dtype)).astype(p.dtype), {"moment": m}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.rho = rho
        self.epsilon = epsilon
        self.momentum = momentum
        self.centered = centered

    def _init_slot(self, p):
        s = {"mean_square": jnp.zeros(p.shape, jnp.float32),
             "momentum": jnp.zeros(p.shape, jnp.float32)}
        if self.centered:
            s["mean_grad"] = jnp.zeros(p.shape, jnp.float32)
        return s

    def _update_param(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        ms = self.rho * slots["mean_square"] + (1 - self.rho) * jnp.square(g32)
        new = {"mean_square": ms}
        if self.centered:
            mg = self.rho * slots["mean_grad"] + (1 - self.rho) * g32
            denom = jnp.sqrt(ms - jnp.square(mg) + self.epsilon)
            new["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self.epsilon)
        mom = self.momentum * slots["momentum"] + lr * g32 / denom
        new["momentum"] = mom
        return (p - mom.astype(p.dtype)).astype(p.dtype), new


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.epsilon = epsilon
        self.rho = rho

    def _init_slot(self, p):
        return {"avg_sq_grad": jnp.zeros(p.shape, jnp.float32),
                "avg_sq_update": jnp.zeros(p.shape, jnp.float32)}

    def _update_param(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        asg = self.rho * slots["avg_sq_grad"] + (1 - self.rho) * jnp.square(g32)
        upd = g32 * jnp.sqrt(slots["avg_sq_update"] + self.epsilon) / \
            jnp.sqrt(asg + self.epsilon)
        asu = self.rho * slots["avg_sq_update"] + (1 - self.rho) * jnp.square(upd)
        return (p - lr * upd.astype(p.dtype)).astype(p.dtype), \
            {"avg_sq_grad": asg, "avg_sq_update": asu}


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _init_slot(self, p):
        return {"moment": jnp.zeros(p.shape, jnp.float32),
                "inf_norm": jnp.zeros(p.shape, jnp.float32)}

    def _update_param(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        t = step.astype(jnp.float32) + 1.0
        m = self.beta1 * slots["moment"] + (1 - self.beta1) * g32
        u = jnp.maximum(self.beta2 * slots["inf_norm"], jnp.abs(g32))
        lr_t = lr / (1 - jnp.power(self.beta1, t))
        upd = lr_t * m / (u + self.epsilon)
        return (p - upd.astype(p.dtype)).astype(p.dtype), \
            {"moment": m, "inf_norm": u}


class ASGD(Optimizer):
    """Stochastic Average Gradient (reference: paddle.optimizer.ASGD —
    asgd op; Schmidt et al., "Minimizing Finite Sums with the Stochastic
    Average Gradient").  Keeps the running gradient sum ``d`` and the
    last seen gradient per batch slot ``y`` (``batch_num`` slots, rotated
    by step):

        d       <- d - y[slot] + g
        y[slot] <- g
        param   <- param - lr * d / min(seen, batch_num)

    With batch_num=1 this reduces to plain SGD.  Slot memory is
    ``batch_num`` gradient copies per parameter, faithful to the
    reference's accumulator layout.
    """

    def __init__(self, learning_rate=0.001, batch_num: int = 1,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        if batch_num <= 0:
            raise ValueError(f"batch_num must be positive, got {batch_num}")
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.batch_num = int(batch_num)

    def _init_slot(self, p):
        return {"d": jnp.zeros(p.shape, jnp.float32),
                "y": jnp.zeros((self.batch_num,) + tuple(p.shape),
                               jnp.float32)}

    def _update_param(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        slot = (step % self.batch_num).astype(jnp.int32)
        d = slots["d"] - slots["y"][slot] + g32
        y = slots["y"].at[slot].set(g32)
        # average over gradients actually SEEN, not the slot capacity —
        # otherwise the first batch_num-1 steps are up to batch_num x too
        # small (reference: n = min(step, m) in the asgd kernel)
        n = jnp.minimum(step + 1, self.batch_num).astype(jnp.float32)
        new_p = p - lr * (d / n).astype(p.dtype)
        return new_p.astype(p.dtype), {"d": d, "y": y}
