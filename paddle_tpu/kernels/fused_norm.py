"""Fused RMSNorm Pallas kernel.

Reference: paddle/phi/kernels/fusion/gpu — fused_rms_norm / the norm stage
of fused_multi_transformer_op.cu (SURVEY.md §2.1 "PHI fused kernels").

One VPU pass per row block: mean-square, rsqrt and scale without writing
the intermediate variance to HBM.  Differentiable via jax.custom_vjp with
a closed-form backward (also one fused pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["fused_rms_norm_pallas"]


def _rms_fwd_kernel(x_ref, w_ref, o_ref, r_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    r_ref[:] = rstd


def _rms_bwd_kernel(x_ref, w_ref, r_ref, g_ref, dx_ref, dw_ref):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    rstd = r_ref[:]
    xhat = x * rstd
    gw = g * w
    # dx = rstd * (gw - xhat * mean(gw * xhat))
    mean_gx = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (gw - xhat * mean_gx)).astype(dx_ref.dtype)
    # dw accumulates across the (sequential) TPU grid: a (1, h) output
    # block per step would violate Mosaic's 8×128 block tiling when the
    # grid is the leading dim, so all steps share one full-array block.
    dw_blk = jnp.sum(g * xhat, axis=0, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = dw_blk

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        dw_ref[:] = dw_ref[:] + dw_blk


def _run_fwd(x2, w, eps, block_rows, interpret):
    rows, h = x2.shape
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, h), x2.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        name="fused_rms_norm_fwd",
        interpret=interpret,
    )(x2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rms_core(x2, w, eps, block_rows, interpret):
    out, _ = _run_fwd(x2, w, eps, block_rows, interpret)
    return out


def _rms_core_fwd(x2, w, eps, block_rows, interpret):
    out, rstd = _run_fwd(x2, w, eps, block_rows, interpret)
    return out, (x2, w, rstd)


def _rms_core_bwd(eps, block_rows, interpret, res, g):
    x2, w, rstd = res
    rows, h = x2.shape
    nblk = rows // block_rows
    dx, dw = pl.pallas_call(
        _rms_bwd_kernel,
        grid=(nblk,),
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, h), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, h), x2.dtype),
                   jax.ShapeDtypeStruct((1, h), jnp.float32)],
        name="fused_rms_norm_bwd",
        interpret=interpret,
    )(x2, w, rstd, g)
    return dx, dw[0].astype(w.dtype)


_rms_core.defvjp(_rms_core_fwd, _rms_core_bwd)


def _flatten_and_pick_block(x):
    """[..., H] -> ([rows, H], block_rows) with block dividing rows.

    Mosaic requires each block's trailing dims be (8, 128)-aligned or
    equal to the full array dims, so the block must be a multiple of 8
    unless it covers all rows.  Returns block 0 when no legal blocking
    exists (callers fall back to the plain XLA form) or the input is
    empty.
    """
    h = x.shape[-1]
    x2 = x.reshape(-1, h)
    rows = x2.shape[0]
    if rows == 0:
        return x2, 0
    # cap block x h x 4B (the f32 working copy) at 2 MiB: the backward
    # holds the x, g and dx blocks plus their f32 temporaries at once —
    # at a 4 MiB copy (256 rows of h=4096) Mosaic's scoped allocation is
    # 22 MiB against the 16 MiB limit and the kernel does not compile
    cap = max(8, min(256, (2 * 1024 * 1024) // (4 * h)))
    if rows <= cap:
        return x2, rows          # one block == full array: always legal
    # sublane tile is 16 for 2-byte dtypes, 8 for f32
    align = 16 if x.dtype.itemsize == 2 else 8
    best = 0
    for b in range(align, cap + 1, align):
        if rows % b == 0:
            best = b
    # no aligned divisor <= 256: a single full-array block would be
    # legal but the backward holds x/g/dx blocks plus f32 temporaries in
    # VMEM at once, so large unaligned rows fall back to XLA instead
    return x2, best


def fused_rms_norm_pallas(x, weight, epsilon: float = 1e-5,
                          interpret=None, block_rows=None):
    """RMSNorm over the last dim; x [..., H], weight [H].

    ``block_rows`` overrides the auto-picked tile height (sweep tuning
    knob); it must divide the flattened row count."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    orig = x.shape
    x2, block = _flatten_and_pick_block(x)
    if block_rows and x2.shape[0] % block_rows == 0:
        block = block_rows
    if block == 0:
        if x.size == 0:
            return x
        # fallback keeps the kernel's rounding (affine in f32, one final
        # cast) so routing cannot change numerics mid-model
        x32 = x.astype(jnp.float32)
        ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(ms + epsilon)
                * weight.astype(jnp.float32)).astype(x.dtype)
    out = _rms_core(x2, weight, float(epsilon), block, interpret)
    return out.reshape(orig)


# ---------------------------------------------------------------- LayerNorm
# (same blocking as RMSNorm; reference: phi fused layer_norm kernels —
# one VPU pass computes mean/var/affine without HBM intermediates; the
# backward is the closed-form xhat projection, also one pass per block)

def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, m_ref, r_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[:] = (xc * rstd * w_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    m_ref[:] = mu
    r_ref[:] = rstd


def _ln_bwd_kernel(x_ref, w_ref, m_ref, r_ref, g_ref, dx_ref, dw_ref,
                   db_ref):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    mu = m_ref[:]
    rstd = r_ref[:]
    xhat = (x - mu) * rstd
    gw = g * w
    mean_gw = jnp.mean(gw, axis=-1, keepdims=True)
    mean_gx = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (gw - mean_gw - xhat * mean_gx)).astype(
        dx_ref.dtype)
    # dw/db accumulate across the sequential grid into one shared block
    # (see _rms_bwd_kernel for the Mosaic tiling rationale)
    dw_blk = jnp.sum(g * xhat, axis=0, keepdims=True)
    db_blk = jnp.sum(g, axis=0, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = dw_blk
        db_ref[:] = db_blk

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        dw_ref[:] = dw_ref[:] + dw_blk
        db_ref[:] = db_ref[:] + db_blk


def _ln_run_fwd(x2, w, b, eps, block_rows, interpret):
    rows, h = x2.shape
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, h), x2.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        name="fused_layer_norm_fwd",
        interpret=interpret,
    )(x2, w, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln_core(x2, w, b, eps, block_rows, interpret):
    out, _, _ = _ln_run_fwd(x2, w, b, eps, block_rows, interpret)
    return out


def _ln_core_fwd(x2, w, b, eps, block_rows, interpret):
    out, mu, rstd = _ln_run_fwd(x2, w, b, eps, block_rows, interpret)
    return out, (x2, w, b, mu, rstd)


def _ln_core_bwd(eps, block_rows, interpret, res, g):
    x2, w, b, mu, rstd = res
    rows, h = x2.shape
    nblk = rows // block_rows
    dx, dw, db = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(nblk,),
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, h), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((1, h), lambda i: (0, 0)),
                   pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, h), x2.dtype),
                   jax.ShapeDtypeStruct((1, h), jnp.float32),
                   jax.ShapeDtypeStruct((1, h), jnp.float32)],
        name="fused_layer_norm_bwd",
        interpret=interpret,
    )(x2, w, mu, rstd, g)
    return dx, dw[0].astype(w.dtype), db[0].astype(b.dtype)


_ln_core.defvjp(_ln_core_fwd, _ln_core_bwd)


def fused_layer_norm_pallas(x, weight, bias, epsilon: float = 1e-5,
                            interpret=None, block_rows=None):
    """LayerNorm over the last dim; x [..., H], weight/bias [H].

    ``block_rows`` overrides the auto-picked tile height (sweep tuning
    knob); it must divide the flattened row count."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    orig = x.shape
    x2, block = _flatten_and_pick_block(x)
    if block_rows and x2.shape[0] % block_rows == 0:
        block = block_rows
    if block == 0:
        if x.size == 0:
            return x
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        xc = x32 - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return (xc * jax.lax.rsqrt(var + epsilon)
                * weight.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)
    out = _ln_core(x2, weight, bias, float(epsilon), block, interpret)
    return out.reshape(orig)


__all__.append("fused_layer_norm_pallas")
