"""Configuration file -> the program's model, weights made on the device
from the seed in ONE jitted call, in the type they are served in."""

import jax
import numpy as np


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for ``stream`` of run seed ``seed`` (any whole
    number: the driver's are larger than 32 signed bits hold)."""
    return int(np.random.SeedSequence([int(seed), stream])
               .generate_state(1)[0]) >> 1


def build_model(builder, cfg: dict, seed: int, max_seq_len=None):
    """The program's model for ``cfg`` with random weights from ``seed``.
    The constructor is traced inside one ``jax.jit`` (its initializers
    draw from the key the ``rng_context`` carries), so every weight is
    made on the device in its serving dtype by one program; the concrete
    arrays are then loaded into the traced module.  Returns
    ``(model, model_config)``, the model in eval mode."""
    from paddle_tpu.framework.random import rng_context
    from paddle_tpu.nn.functional_call import state
    mcfg = builder.model_config(cfg, max_seq_len)
    cls = builder.model_class()
    made = []

    def make(key):
        with rng_context(key):
            model = cls(mcfg)
        if mcfg.dtype != "float32":
            model.to(dtype=mcfg.dtype)
        made.append(model)
        return state(model)

    params, buffers = jax.jit(make)(jax.random.key(seed32(seed)))
    model = made[0]
    model.set_state_dict({**params, **buffers})
    leaked = [k for tree in state(model) for k, v in tree.items()
              if isinstance(v, jax.core.Tracer)]
    if leaked:
        raise RuntimeError(f"build_model: traced values left in {leaked}")
    model.eval()
    return model, mcfg
