"""Pallas TPU flash attention (fwd + bwd, custom_vjp).

Reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu — FlashAttnKernel /
FlashAttnGradKernel wrapping the external CUTLASS flash-attn-2 library
(cmake/external/flashattn.cmake), exposed as
F.scaled_dot_product_attention (SURVEY.md §2.1 "FlashAttention
integration").

TPU-native: the classic online-softmax blockwise algorithm written directly
in Pallas.  K/V STREAM through VMEM in (block_k, d) tiles via the grid's
innermost ("arbitrary") dimension, with the running max/denominator/
accumulator carried in VMEM scratch across k iterations — K/V never sit
whole-sequence resident in VMEM, so sequence length is bounded by HBM, not
VMEM (round-2 re-block; round-1 held full K/V per grid step).  The MXU does
the two matmuls per block in f32 accumulation.  Backward is the standard
two-kernel flash bwd (dq by q rows with k innermost; dk/dv by k columns
with q innermost) using the saved LSE and the delta = rowsum(dO ⊙ O) trick.

Mosaic tiling notes: per-row residuals (LSE, delta) are stored as
[B*H, S, 1] so their block shapes ((1, block_q, 1)) satisfy the TPU
lowering's last-two-dims rule; the in-kernel running m/l live in
(block_q, 128) lane-broadcast VMEM scratch (the layout the official TPU
kernels use).  The causal path clamps the streamed K/V block index so
skipped blocks re-reference the previous tile instead of paying HBM
bandwidth.

The causal mask is bottom-right aligned (kpos <= qpos + (sk - sq)),
matching sdpa_reference and the flash-attn-2 convention for sq != sk.

Layout is paddle's [batch, seq, heads, head_dim]; internally [B*H, S, D].
Falls back onto interpret mode automatically off-TPU so CPU tests exercise
the same code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_varlen"]

_NEG_INF = float("-inf")
_LANES = 128


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def _dimension_semantics(n: int, interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=(("parallel",) * (n - 1)) + ("arbitrary",))


def _causal_hi(qi, block_q, block_k, off, nk):
    """Index of the last k block a causal q block touches (clamped)."""
    return jnp.clip((qi * block_q + block_q - 1 + off) // block_k, 0, nk - 1)


def _causal_lo(ki, block_q, block_k, off, nq):
    """Index of the first q block that sees causal k block ``ki``."""
    return jnp.clip(jnp.maximum(ki * block_k - off, 0) // block_q, 0, nq - 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                nk, off, seg=False):
    if seg:
        qs_ref, ks_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
    else:
        o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    should = (ki * block_k <= qi * block_q + block_q - 1 + off) \
        if causal else True

    @pl.when(should)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # [bq, D]
        k = k_ref[0].astype(jnp.float32)                # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos + off, s, _NEG_INF)
        if seg:
            # varlen/packed sequences: only same-segment pairs attend
            s = jnp.where(qs_ref[0] == ks_ref[0].reshape(1, block_k),
                          s, _NEG_INF)
        m_prev = m_sc[...]                              # [bq, 128]
        l_prev = l_sc[...]
        m_curr = jnp.max(s, axis=1)[:, None]            # [bq, 1]
        m_next = jnp.maximum(m_prev, m_curr)            # [bq, 128]
        # fully-masked rows keep m == -inf; subtract a finite stand-in so
        # exp() sees -inf - 0 = -inf, not -inf - -inf = nan
        m_safe = jnp.where(m_next == _NEG_INF, 0.0, m_next)
        p = jnp.exp(s - m_safe[:, :1])                  # [bq, bk]
        alpha = jnp.exp(m_prev - m_safe)                # [bq, 128]
        l_next = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_sc[...] = m_next
        l_sc[...] = l_next
        acc_sc[...] = acc_sc[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        l = l_sc[...][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        m = m_sc[...][:, :1]
        lse = jnp.where(l == 0.0, _NEG_INF,
                        m + jnp.log(jnp.where(l == 0.0, 1.0, l)))
        lse_ref[0] = lse.astype(jnp.float32)


def _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
               qs3=None, ks3=None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    off = sk - sq
    nq = sq // block_q
    nk = sk // block_k
    grid = (bh, nq, nk)
    seg = qs3 is not None

    if causal:
        def kv_idx(b, qi, ki):
            return (b, jnp.minimum(ki, _causal_hi(qi, block_q, block_k,
                                                  off, nk)), 0)
    else:
        def kv_idx(b, qi, ki):
            return (b, ki, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, d), kv_idx),
        pl.BlockSpec((1, block_k, d), kv_idx),
    ]
    args = [q3, k3, v3]
    if seg:
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, 1), kv_idx),
        ]
        args += [qs3, ks3]

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk, off=off,
                          seg=seg),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_dimension_semantics(3, interpret),
        name="flash_attention_fwd",
        interpret=interpret,
    )(*args)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, block_q, block_k, nk, off, seg=False):
    if seg:
        qs_ref, ks_ref, dq_ref, acc_sc = rest
    else:
        dq_ref, acc_sc = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    should = (ki * block_k <= qi * block_q + block_q - 1 + off) \
        if causal else True

    @pl.when(should)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                # [bq, 1]
        delta = delta_ref[0]                            # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos + off, s, _NEG_INF)
        if seg:
            s = jnp.where(qs_ref[0] == ks_ref[0].reshape(1, block_k),
                          s, _NEG_INF)
        lse_safe = jnp.where(lse == _NEG_INF, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        dq_ref[0] = (acc_sc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, block_q, block_k, nq, off, seg=False):
    if seg:
        qs_ref, ks_ref, dk_ref, dv_ref, dk_sc, dv_sc = rest
    else:
        dk_ref, dv_ref, dk_sc, dv_sc = rest
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    should = (qi * block_q + block_q - 1 + off >= ki * block_k) \
        if causal else True

    @pl.when(should)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)                # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                # [bq, 1]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos + off, s, _NEG_INF)
        if seg:
            s = jnp.where(qs_ref[0] == ks_ref[0].reshape(1, block_k),
                          s, _NEG_INF)
        lse_safe = jnp.where(lse == _NEG_INF, 0.0, lse)
        p = jnp.exp(s - lse_safe)                       # [bq, bk]
        dv_sc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, block_q, block_k, interpret,
               qs3=None, ks3=None):
    q3, k3, v3, out, lse = res
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    off = sk - sq
    nq = sq // block_q
    nk = sk // block_k
    seg = qs3 is not None
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse3 = lse[..., None]                               # [bh, sq, 1]
    delta3 = delta[..., None]

    if causal:
        def kv_idx(b, qi, ki):
            return (b, jnp.minimum(ki, _causal_hi(qi, block_q, block_k,
                                                  off, nk)), 0)

        def q_idx_kv(b, ki, qi):
            return (b, jnp.maximum(qi, _causal_lo(ki, block_q, block_k,
                                                  off, nq)), 0)
    else:
        def kv_idx(b, qi, ki):
            return (b, ki, 0)

        def q_idx_kv(b, ki, qi):
            return (b, qi, 0)

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, d), kv_idx),
        pl.BlockSpec((1, block_k, d), kv_idx),
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
    ]
    dq_args = [q3, k3, v3, g, lse3, delta3]
    if seg:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, 1), kv_idx),
        ]
        dq_args += [qs3, ks3]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk, off=off,
                          seg=seg),
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_dimension_semantics(3, interpret),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(*dq_args)

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), q_idx_kv),
        pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        pl.BlockSpec((1, block_q, d), q_idx_kv),
        pl.BlockSpec((1, block_q, 1), q_idx_kv),
        pl.BlockSpec((1, block_q, 1), q_idx_kv),
    ]
    dkv_args = [q3, k3, v3, g, lse3, delta3]
    if seg:
        dkv_in_specs += [
            pl.BlockSpec((1, block_q, 1), q_idx_kv),
            pl.BlockSpec((1, block_k, 1), lambda b, ki, qi: (b, ki, 0)),
        ]
        dkv_args += [qs3, ks3]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq, off=off,
                          seg=seg),
        grid=(bh, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_dimension_semantics(3, interpret),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(*dkv_args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _pick_block(seq: int, want: Optional[int] = None,
                flag: str = "flash_block_q") -> int:
    """Resolve a block size: explicit arg wins, else the FLAGS_* value
    (env-tunable so on-chip block sweeps need no code edits), clamped to
    a divisor of ``seq``."""
    if want is None:
        from ..core.flags import get_flags
        want = int(get_flags(flag)[flag])
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q3, k3, v3, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                        interpret)
    return out


def _flash_core_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                          interpret)
    return out, (q3, k3, v3, out, lse)


def _flash_core_bwd(scale, causal, block_q, block_k, interpret, res, g):
    return _flash_bwd(res, g, scale, causal, block_q, block_k, interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_core_seg(q3, k3, v3, qs3, ks3, scale, causal, block_q, block_k,
                    interpret):
    out, _ = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                        interpret, qs3=qs3, ks3=ks3)
    return out


def _flash_core_seg_fwd(q3, k3, v3, qs3, ks3, scale, causal, block_q,
                        block_k, interpret):
    out, lse = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                          interpret, qs3=qs3, ks3=ks3)
    return out, (q3, k3, v3, out, lse, qs3, ks3)


def _flash_core_seg_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q3, k3, v3, out, lse, qs3, ks3 = res
    dq, dk, dv = _flash_bwd((q3, k3, v3, out, lse), g, scale, causal,
                            block_q, block_k, interpret, qs3=qs3, ks3=ks3)
    # int segment ids take float0 cotangents (non-differentiable)
    import numpy as _np
    zq = _np.zeros(qs3.shape, dtype=jax.dtypes.float0)
    zk = _np.zeros(ks3.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, zq, zk


_flash_core_seg.defvjp(_flash_core_seg_fwd, _flash_core_seg_bwd)


def flash_attention(query, key, value, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention over paddle layout [B, S, H, D]; differentiable.

    GQA (kv heads < q heads) is handled by head repetition before the
    kernel (broadcast, not copy, under XLA).
    """
    b, sq, h, d = query.shape
    kh = key.shape[2]
    if kh != h:
        rep = h // kh
        key = jnp.repeat(key, rep, axis=2)
        value = jnp.repeat(value, rep, axis=2)
    if interpret is None:
        interpret = _interpret_default()
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    sk = key.shape[1]
    bq = _pick_block(sq, block_q, "flash_block_q")
    bk = _pick_block(sk, block_k, "flash_block_k")

    def to3(x):
        return jnp.moveaxis(x, 1, 2).reshape(b * h, x.shape[1], d)

    out3 = _flash_core(to3(query), to3(key), to3(value), scale, causal,
                       bq, bk, interpret)
    return jnp.moveaxis(out3.reshape(b, h, sq, d), 1, 2)


def flash_attention_varlen(query, key, value, q_segments, k_segments,
                           causal: bool = False,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Segment-masked (varlen/packed) flash attention; differentiable.

    query [B, Sq, H, D], key/value [B, Sk, H, D]; q_segments [B, Sq] /
    k_segments [B, Sk] int32 — only same-segment (query, key) pairs
    attend (reference varlen semantics: flash_attn_unpadded's cu_seqlens
    become segment ids).  Use a distinct id (e.g. -1) for padding.  With
    ``causal`` the bottom-right-aligned causal mask composes on top.
    """
    b, sq, h, d = query.shape
    kh = key.shape[2]
    if kh != h:
        rep = h // kh
        key = jnp.repeat(key, rep, axis=2)
        value = jnp.repeat(value, rep, axis=2)
    if interpret is None:
        interpret = _interpret_default()
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    sk = key.shape[1]
    bq = _pick_block(sq, block_q, "flash_block_q")
    bk = _pick_block(sk, block_k, "flash_block_k")

    def to3(x):
        return jnp.moveaxis(x, 1, 2).reshape(b * h, x.shape[1], d)

    def seg3(s, n):
        s = jnp.asarray(s, jnp.int32)
        return jnp.repeat(s[:, None, :], h, axis=1).reshape(b * h, n, 1)

    out3 = _flash_core_seg(to3(query), to3(key), to3(value),
                           seg3(q_segments, sq), seg3(k_segments, sk),
                           scale, causal, bq, bk, interpret)
    return jnp.moveaxis(out3.reshape(b, h, sq, d), 1, 2)


def flash_attention_with_lse(query, key, value, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Forward-only variant that also returns logsumexp [B, H, S] (used by
    ring attention to combine per-shard partial attentions).

    GQA handled like flash_attention: kv heads repeated up to q heads.
    """
    b, sq, h, d = query.shape
    kh = key.shape[2]
    if kh != h:
        rep = h // kh
        key = jnp.repeat(key, rep, axis=2)
        value = jnp.repeat(value, rep, axis=2)
    if interpret is None:
        interpret = _interpret_default()
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    sk = key.shape[1]
    bq = _pick_block(sq, block_q, "flash_block_q")
    bk = _pick_block(sk, block_k, "flash_block_k")

    def to3(x):
        return jnp.moveaxis(x, 1, 2).reshape(b * h, x.shape[1], d)

    out3, lse = _flash_fwd(to3(query), to3(key), to3(value), scale, causal,
                           bq, bk, interpret)
    return (jnp.moveaxis(out3.reshape(b, h, sq, d), 1, 2),
            lse.reshape(b, h, sq))
