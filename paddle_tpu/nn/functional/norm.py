"""Normalization functionals.

Reference: python/paddle/nn/functional/norm.py — batch_norm, layer_norm,
instance_norm, group_norm, normalize; incubate rms_norm.  XLA fuses these
into surrounding ops on TPU (the reference needs
fused_bias_dropout_residual_layer_norm CUDA kernels for the same effect —
paddle/phi/kernels/fusion/gpu).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["batch_norm", "layer_norm", "instance_norm", "group_norm",
           "normalize", "rms_norm", "local_response_norm"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon: float = 1e-5,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    with jax.named_scope("norm"):
        axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
        # fused Pallas path: the common last-dim affine case on TPU (one VPU
        # pass, no HBM intermediates).  Constraints keep it strictly better
        # than XLA: dtype-preserving params (no public dtype change vs the
        # promoting XLA path), lane-aligned h bounded for VMEM, and row
        # counts that tile into real blocks (no degenerate 1-row grids).
        h_last = x.shape[-1]
        rows = x.size // h_last if h_last else 0
        if (len(axes) == 1 and axes[0] == x.ndim - 1 and weight is not None
                and bias is not None and h_last % 128 == 0 and h_last <= 8192
                and rows and rows % 8 == 0
                and getattr(weight, "dtype", None) == x.dtype
                and getattr(bias, "dtype", None) == x.dtype):
            from ...core.flags import flags as _flags
            from ...kernels.routing import use_pallas as _route
            if (_flags.use_pallas_norm and _on_tpu()
                    and _route("layer_norm", rows=rows, h=h_last)):
                import paddle_tpu.kernels as _k
                return _k.fused_layer_norm_pallas(x, weight, bias, epsilon,
                                                  interpret=False)
        x32 = x.astype(jnp.float32) if x.dtype in (jnp.float16, jnp.bfloat16) else x
        mean = jnp.mean(x32, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=axes, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + epsilon)
        y = y.astype(x.dtype)
        if weight is not None:
            y = y * weight
        if bias is not None:
            y = y + bias
        return y


def rms_norm(x, weight=None, bias=None, epsilon: float = 1e-6, begin_norm_axis: int = -1):
    """paddle.incubate.nn.functional.rms_norm parity (Llama-family norm)."""
    with jax.named_scope("norm"):
        axes = tuple(range(begin_norm_axis % x.ndim, x.ndim)) if begin_norm_axis != -1 else (-1,)
        x32 = x.astype(jnp.float32) if x.dtype in (jnp.float16, jnp.bfloat16) else x
        ms = jnp.mean(jnp.square(x32), axis=axes, keepdims=True)
        y = (x32 * jax.lax.rsqrt(ms + epsilon)).astype(x.dtype)
        if weight is not None:
            y = y * weight
        if bias is not None:
            y = y + bias
        return y


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training: bool = False, momentum: float = 0.9,
               epsilon: float = 1e-5, data_format: str = "NCHW",
               use_global_stats: Optional[bool] = None, name=None):
    """Returns (y, new_running_mean, new_running_var) when training else y.

    NOTE deviation from the reference's in-place running-stat mutation: the
    functional form returns updated stats; nn.BatchNorm layers write them
    into buffers so functional_call captures them.
    """
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    ch_axis = x.ndim - 1 if channel_last else 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = tuple(x.shape[ch_axis] if i == ch_axis else 1 for i in range(x.ndim))

    use_stats = (not training) if use_global_stats is None else use_global_stats
    x32 = x.astype(jnp.float32) if x.dtype in (jnp.float16, jnp.bfloat16) else x
    if use_stats:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    else:
        mean = jnp.mean(x32, axis=reduce_axes)
        var = jnp.mean(jnp.square(x32), axis=reduce_axes) - jnp.square(mean)
        # paddle momentum semantics: r = m*r + (1-m)*batch
        new_rm = momentum * running_mean + (1 - momentum) * mean
        n = x.size / x.shape[ch_axis]
        unbiased = var * (n / max(n - 1, 1))
        new_rv = momentum * running_var + (1 - momentum) * unbiased
    y = (x32 - mean.reshape(bshape)) * jax.lax.rsqrt(var.reshape(bshape) + epsilon)
    y = y.astype(x.dtype)
    if weight is not None:
        y = y * weight.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    if training and not use_stats:
        return y, new_rm, new_rv
    return y


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats: bool = True, momentum: float = 0.9,
                  eps: float = 1e-5, data_format: str = "NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    ch_axis = x.ndim - 1 if channel_last else 1
    spatial = tuple(i for i in range(x.ndim) if i not in (0, ch_axis))
    x32 = x.astype(jnp.float32) if x.dtype in (jnp.float16, jnp.bfloat16) else x
    mean = jnp.mean(x32, axis=spatial, keepdims=True)
    var = jnp.var(x32, axis=spatial, keepdims=True)
    y = ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    bshape = tuple(x.shape[ch_axis] if i == ch_axis else 1 for i in range(x.ndim))
    if weight is not None:
        y = y * weight.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return y


def group_norm(x, num_groups: int, epsilon: float = 1e-5, weight=None,
               bias=None, data_format: str = "NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    if channel_last:
        x_t = jnp.moveaxis(x, -1, 1)
        y = group_norm(x_t, num_groups, epsilon, weight, bias, "NCHW")
        return jnp.moveaxis(y, 1, -1)
    n, c = x.shape[:2]
    g = num_groups
    x32 = x.astype(jnp.float32) if x.dtype in (jnp.float16, jnp.bfloat16) else x
    xg = x32.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape).astype(x.dtype)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if weight is not None:
        y = y * weight.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return y


def normalize(x, p: float = 2, axis: int = 1, epsilon: float = 1e-12, name=None):
    if p == 2:
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    else:
        norm = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(norm, epsilon)


def local_response_norm(x, size: int, alpha: float = 1e-4, beta: float = 0.75,
                        k: float = 1.0, data_format: str = "NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    ch_axis = x.ndim - 1 if channel_last else 1
    sq = jnp.square(x)
    half = size // 2
    pad_cfg = [(0, 0)] * x.ndim
    pad_cfg[ch_axis] = (half, size - half - 1)
    sq = jnp.pad(sq, pad_cfg)
    # sliding sum over channel axis
    idx = [slice(None)] * x.ndim
    acc = jnp.zeros_like(x)
    for i in range(size):
        idx[ch_axis] = slice(i, i + x.shape[ch_axis])
        acc = acc + sq[tuple(idx)]
    return x / ((k + alpha * acc) ** beta)
