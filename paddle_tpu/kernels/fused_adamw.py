"""Fused AdamW update as one Pallas kernel.

Reference: paddle/phi/kernels/gpu/adamw_kernel.cu — the in-place fused
`_C_ops.adamw_` op every optimizer.step() dispatches to (SURVEY.md §3.2).

TPU-native: one VPU pass reads (p, g, m, v) tiles from VMEM and writes
(p', m', v') — no intermediate HBM round trips between the moment updates
and the parameter write.  XLA usually fuses the unfused lax ops nearly as
well; this kernel exists to (a) guarantee the fusion at any size, (b) halve
peak residency via input/output aliasing.  Scalars ride in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adamw_update"]


def _adamw_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref,
                  po_ref, mo_ref, vo_ref):
    lr = sc_ref[0]
    beta1 = sc_ref[1]
    beta2 = sc_ref[2]
    eps = sc_ref[3]
    wd = sc_ref[4]
    bc1 = sc_ref[5]          # 1 - beta1^t
    bc2 = sc_ref[6]          # 1 - beta2^t
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    new_p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    po_ref[:] = new_p.astype(po_ref.dtype)
    mo_ref[:] = m
    vo_ref[:] = v


def fused_adamw_update(p, g, m, v, step, lr, beta1=0.9, beta2=0.999,
                       epsilon=1e-8, weight_decay=0.0, interpret=None,
                       block_rows=None, alias=True):
    """One fused AdamW step on a single tensor.  m/v must be float32.
    Returns (new_p, new_m, new_v).  ``step`` is the 1-based step index
    (traced ok); scalars may be traced values.

    ``block_rows`` overrides the per-program tile height (tuning knob for
    the on-chip sweep); ``alias`` requests input/output buffer aliasing so
    XLA may update p/m/v in place when the inputs are dead after the call.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    orig_shape = p.shape
    n = int(p.size)
    lane = 128
    rows = max((n + lane - 1) // lane, 1)
    pad = rows * lane - n

    def flat(x, dt):
        x = x.reshape(-1).astype(dt)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(rows, lane)

    t = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(epsilon, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        1.0 - jnp.asarray(beta1, jnp.float32) ** t,
        1.0 - jnp.asarray(beta2, jnp.float32) ** t,
    ])

    p2 = flat(p, p.dtype)
    g2 = flat(g, p.dtype)
    m2 = flat(m, jnp.float32)
    v2 = flat(v, jnp.float32)

    # default tile: 8192 rows x 128 lanes = 1M elements per grid program.
    # The r4 on-chip sweep measured per-program overhead dominating this
    # bandwidth-bound kernel: 8M params at 512-row blocks (128 programs)
    # ran 3.23 ms vs 1.52 ms at 8192-row blocks (8 programs), closing the
    # round-3 0.75x loss to an exact tie with the XLA fused loop.  Very
    # large tensors shrink the tile: at 64M params the 8192-row tile blew
    # Mosaic's scoped-vmem budget (grid-pipelining reserves scale with
    # grid depth), so cap total tile footprint at ~2M elements of f32
    # working set per buffer set.
    if block_rows is None:
        # VMEM-safe default: 7 f32 buffers x block x 128 lanes x double
        # buffering must stay under the 16 MiB scoped budget -> 1024 rows
        # (3.7 MiB working set).  Larger tiles (8192) measured faster
        # in-scan on chip (r4 sweep: 1.52 ms vs 3.23 ms at 8M params)
        # but exceed scoped vmem when compiled standalone — callers who
        # know their compilation context can pass block_rows explicitly.
        block_rows = 1024
    block_rows = min(rows, block_rows)
    while rows % block_rows:
        block_rows -= 1
    grid = (rows // block_rows,)
    bs = lambda: pl.BlockSpec((block_rows, lane), lambda i: (i, 0))
    # p/m/v tiles are read once and written once: aliasing their HBM
    # buffers (input k -> output k-1; input 0 is the SMEM scalar vector)
    # lets XLA drop the three output allocations when the inputs die at
    # this call, matching the reference op's in-place update semantics
    aliases = {1: 0, 3: 1, 4: 2} if alias else {}
    new_p, new_m, new_v = pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  bs(), bs(), bs(), bs()],
        out_specs=[bs(), bs(), bs()],
        out_shape=[
            jax.ShapeDtypeStruct((rows, lane), p.dtype),
            jax.ShapeDtypeStruct((rows, lane), jnp.float32),
            jax.ShapeDtypeStruct((rows, lane), jnp.float32),
        ],
        input_output_aliases=aliases,
        name="fused_adamw_update",
        interpret=interpret,
    )(scalars, p2, g2, m2, v2)

    def unflat(x, dt):
        x = x.reshape(-1)
        if pad:
            x = x[:n]
        return x.reshape(orig_shape).astype(dt)

    return (unflat(new_p, p.dtype), unflat(new_m, jnp.float32),
            unflat(new_v, jnp.float32))
