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
VMEM (round-2 re-block; round-1 held full K/V per grid step).  Backward is
the standard two-kernel flash bwd (dq by q rows with k innermost; dk/dv by
k columns with q innermost) using the saved LSE and the delta =
rowsum(dO ⊙ O) trick.

Precision: every matmul takes its operands in the dtype they were given
(``_mxu``: q, k, v and dO as loaded; P and dS rounded to that dtype for
their matmuls only, the published models' own semantics and
``sdpa_reference``'s) and accumulates in float32, so bf16 inputs cost one
MXU pass a product and float32 inputs behave as they always did.  The
scores, the softmax statistics (m, l, LSE, delta) and every accumulator
are float32 whatever the inputs are.  Tile sizes come from the shapes and
the dtype under an explicit VMEM budget (``flash_attention_plan``).

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
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_varlen", "flash_attention_plan"]

_NEG_INF = float("-inf")
_LANES = 128
# What a grid step of any of the three kernels may hold in VMEM
# (``_vmem_bytes``), and the scoped-VMEM limit handed to Mosaic with it:
# passing the limit pins it to one value standalone and inside a program
# (XLA's default differs between the two; a v5e core has 128 MiB).
VMEM_BUDGET = 32 * 1024 * 1024
# Tile sides, from ``scripts/flash_attention_cost.py --sweep 256,512,1024``
# on a v5e, bf16 causal (PERF.md section 6, PR 35): at [64, 4096, 128] a
# 1024 x 1024 tile wins in all three kernels (the cost of a grid step is
# per row and per step as much as per element, and a 4 MiB score tile
# still schedules at 6 to 8.5 bundles a vreg where 256 x 512 took 8 to
# 13); at [16, 2048, 128] the two backward kernels, which then run at the
# MXU's pace, win with 512 x 512, because 1024-row tiles leave two a side
# and the causal diagonal makes three of the four compute.  So: 1024 where
# the sequence holds at least this many tiles a side, else 512.
_MIN_TILES_A_SIDE = {"fwd": 2, "bwd_dq": 4, "bwd_dkv": 4}


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def _dimension_semantics(n: int, interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=(("parallel",) * (n - 1)) + ("arbitrary",),
        vmem_limit_bytes=VMEM_BUDGET)


def _causal_hi(qi, block_q, block_k, off, nk):
    """Index of the last k block a causal q block touches (clamped)."""
    return jnp.clip((qi * block_q + block_q - 1 + off) // block_k, 0, nk - 1)


def _causal_lo(ki, block_q, block_k, off, nq):
    """Index of the first q block that sees causal k block ``ki``."""
    return jnp.clip(jnp.maximum(ki * block_k - off, 0) // block_q, 0, nq - 1)


def _mxu(a, b, contract):
    """``dot_general`` of two tiles with float32 accumulation, operands as
    given.  bf16 operands take ONE native MXU pass whatever the ambient
    ``jax_default_matmul_precision`` (their products are exact in float32,
    and Mosaic refuses a float32-precision contraction of bf16 operands);
    float32 operands keep the ambient precision, as they always did."""
    precision = jax.lax.Precision.DEFAULT \
        if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _scores(q, k, qi, ki, seg_refs, *, scale, causal, block_q, block_k, off):
    """The tile's float32 scores ``scale * q k^T`` with the causal and
    segment masks applied (``-inf`` where a pair may not attend)."""
    s = _mxu(q, k, ((1,), (1,))) * scale                # [bq, bk]
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos + off, s, _NEG_INF)
    if seg_refs is not None:
        # varlen/packed sequences: only same-segment pairs attend
        qs_ref, ks_ref = seg_refs
        s = jnp.where(qs_ref[0] == ks_ref[0].reshape(1, block_k),
                      s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                nk, off, seg=False):
    seg_refs = rest[:2] if seg else None
    o_ref, lse_ref, m_sc, l_sc, acc_sc = rest[2:] if seg else rest
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
        v = v_ref[0]                                    # [bk, D]
        s = _scores(q_ref[0], k_ref[0], qi, ki, seg_refs, scale=scale,
                    causal=causal, block_q=block_q, block_k=block_k, off=off)
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
        acc_sc[...] = acc_sc[...] * alpha[:, :1] + _mxu(
            p.astype(v.dtype), v, ((1,), (0,)))

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

def _probs_and_dscores(q, k, v, do, lse, delta, qi, ki, seg_refs, **mask):
    """What both backward kernels rebuild from the saved LSE: the tile's
    float32 probabilities ``p`` and score gradients ``ds = p (dp - delta)``
    (in units of the SCALED scores; the callers fold ``scale`` in when
    they emit)."""
    s = _scores(q, k, qi, ki, seg_refs, **mask)
    lse_safe = jnp.where(lse == _NEG_INF, 0.0, lse)     # [bq, 1]
    p = jnp.exp(s - lse_safe)                           # [bq, bk]
    dp = _mxu(do, v, ((1,), (1,)))
    return p, p * (dp - delta)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, block_q, block_k, nk, off, seg=False):
    seg_refs = rest[:2] if seg else None
    dq_ref, acc_sc = rest[2:] if seg else rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    should = (ki * block_k <= qi * block_q + block_q - 1 + off) \
        if causal else True

    @pl.when(should)
    def _compute():
        k = k_ref[0]                                    # [bk, D]
        _, ds = _probs_and_dscores(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
            qi, ki, seg_refs, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, off=off)
        acc_sc[...] += _mxu(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(ki == nk - 1)
    def _emit():
        dq_ref[0] = (acc_sc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, block_q, block_k, nq, off, seg=False):
    seg_refs = rest[:2] if seg else None
    dk_ref, dv_ref, dk_sc, dv_sc = rest[2:] if seg else rest
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
        q = q_ref[0]                                    # [bq, D]
        do = do_ref[0]
        p, ds = _probs_and_dscores(
            q, k_ref[0], v_ref[0], do, lse_ref[0], delta_ref[0],
            qi, ki, seg_refs, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, off=off)
        dv_sc[...] += _mxu(p.astype(do.dtype), do, ((0,), (0,)))
        dk_sc[...] += _mxu(ds.astype(q.dtype), q, ((0,), (0,)))

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd_dq(q3, k3, v3, g, lse3, delta3, scale, causal, block_q,
                  block_k, interpret, qs3=None, ks3=None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    off = sk - sq
    nk = sk // block_k
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
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
    ]
    args = [q3, k3, v3, g, lse3, delta3]
    if seg:
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, 1), kv_idx),
        ]
        args += [qs3, ks3]
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk, off=off,
                          seg=seg),
        grid=(bh, sq // block_q, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_dimension_semantics(3, interpret),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(*args)


def _flash_bwd_dkv(q3, k3, v3, g, lse3, delta3, scale, causal, block_q,
                   block_k, interpret, qs3=None, ks3=None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    off = sk - sq
    nq = sq // block_q
    seg = qs3 is not None

    if causal:
        def q_idx(b, ki, qi):
            return (b, jnp.maximum(qi, _causal_lo(ki, block_q, block_k,
                                                  off, nq)), 0)
    else:
        def q_idx(b, ki, qi):
            return (b, qi, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_idx),
        pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        pl.BlockSpec((1, block_q, d), q_idx),
        pl.BlockSpec((1, block_q, 1), q_idx),
        pl.BlockSpec((1, block_q, 1), q_idx),
    ]
    args = [q3, k3, v3, g, lse3, delta3]
    if seg:
        in_specs += [
            pl.BlockSpec((1, block_q, 1), q_idx),
            pl.BlockSpec((1, block_k, 1), lambda b, ki, qi: (b, ki, 0)),
        ]
        args += [qs3, ks3]
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq, off=off,
                          seg=seg),
        grid=(bh, sk // block_k, nq),
        in_specs=in_specs,
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
    )(*args)


def _flash_bwd(res, g, scale, causal, tiles, interpret, qs3=None, ks3=None):
    """dq, dk, dv from the forward's residuals and the output's cotangent;
    ``tiles`` (a :class:`_Tiles`) gives each backward kernel its own
    ``(block_q, block_k)``."""
    q3, k3, v3, out, lse = res
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    operands = (q3, k3, v3, g, lse[..., None], delta[..., None],
                scale, causal)                          # lse, delta [bh, sq, 1]
    dq = _flash_bwd_dq(*operands, *tiles.bwd_dq, interpret, qs3, ks3)
    dk, dv = _flash_bwd_dkv(*operands, *tiles.bwd_dkv, interpret, qs3, ks3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------

class _Tiles(NamedTuple):
    """``(block_q, block_k)`` of each of the three kernels."""
    fwd: Tuple[int, int]
    bwd_dq: Tuple[int, int]
    bwd_dkv: Tuple[int, int]


def _pick_block(seq: int, want: int) -> int:
    """``want`` clamped to a divisor of ``seq`` (halved until it divides)."""
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


def _vmem_bytes(kernel: str, block_q: int, block_k: int, head_dim: int,
                itemsize: int) -> int:
    """What one grid step of ``kernel`` holds in VMEM at most: the
    pipelined operand and result tiles twice (double-buffered), the
    float32 scratch accumulators, and the float32 score-shaped
    temporaries its body keeps at once.  Four-byte operands count half
    as much again, once, with every score-shaped matmul operand: at
    ``highest`` precision Mosaic splits each into three bf16 parts.  An
    upper estimate (Mosaic's own need is 0.4 to 0.9 of it,
    ``tests/test_chip_compile.py`` compiles the planned tiles under
    ``VMEM_BUDGET``)."""
    q_tile = block_q * head_dim * itemsize
    k_tile = block_k * head_dim * itemsize
    column = block_q * _LANES * 4           # a (block_q, 1) float32 column
    scores = block_q * block_k * 4
    if kernel == "fwd":                     # q, o; k, v; lse
        tiles = 2 * q_tile + 2 * k_tile + column
        operands, score_operands = q_tile + 2 * k_tile, 1       # p
        scratch = 2 * column + block_q * head_dim * 4
        temps = 3                           # s, p, p rounded
    elif kernel == "bwd_dq":                # q, do, dq; k, v; lse, delta
        tiles = 3 * q_tile + 2 * k_tile + 2 * column
        operands, score_operands = 2 * q_tile + 2 * k_tile, 1   # ds
        scratch = block_q * head_dim * 4
        temps = 4                           # s / p, dp, ds, ds rounded
    else:                                   # q, do; k, v, dk, dv; lse, delta
        tiles = 2 * q_tile + 4 * k_tile + 2 * column
        operands, score_operands = 2 * q_tile + 2 * k_tile, 2   # p, ds
        scratch = 2 * block_k * head_dim * 4
        temps = 4
    split = (3 * operands + score_operands * scores) // 2 \
        if itemsize == 4 else 0
    return 2 * tiles + scratch + temps * scores + split


def _planned_tile(kernel: str, seq_q: int, seq_k: int, head_dim: int,
                  dtype) -> Tuple[int, int]:
    """The kernel's tile: 1024 a side where the sequence holds
    ``_MIN_TILES_A_SIDE`` of them, else 512, clamped to a divisor of the
    sequence; then the larger side halved while a grid step would not
    fit ``VMEM_BUDGET``."""
    itemsize = jnp.dtype(dtype).itemsize
    bq, bk = (_pick_block(seq, 1024 if seq >= 1024 * _MIN_TILES_A_SIDE[kernel]
                          else 512) for seq in (seq_q, seq_k))
    while _vmem_bytes(kernel, bq, bk, head_dim, itemsize) > VMEM_BUDGET:
        if bk >= bq and bk % 2 == 0:
            bk //= 2
        elif bq % 2 == 0:
            bq //= 2
        else:
            break
    return bq, bk


def _tiles(seq_q: int, seq_k: int, head_dim: int, dtype,
           block_q: Optional[int] = None,
           block_k: Optional[int] = None) -> _Tiles:
    """Every kernel's tile: the planned one, or the caller's explicit
    ``block_q`` / ``block_k`` (clamped to a divisor) for all three."""
    def one(kernel):
        bq, bk = _planned_tile(kernel, seq_q, seq_k, head_dim, dtype)
        return (bq if block_q is None else _pick_block(seq_q, block_q),
                bk if block_k is None else _pick_block(seq_k, block_k))
    return _Tiles(*(one(kernel) for kernel in _Tiles._fields))


def flash_attention_plan(seq_q: int, seq_k: int, head_dim: int, dtype,
                         causal: bool = False) -> dict:
    """What the three kernels do at these shapes, by kernel (``fwd``,
    ``bwd_dq``, ``bwd_dkv``): ``block_q``, ``block_k``, the dtype their
    matmuls take their operands in, the grid steps one (batch, head) pair
    walks and how many of them compute (a causal grid steps over the tiles
    above the diagonal without computing or fetching them), and the VMEM a
    step holds.  Pure arithmetic on the arguments: the choice is static
    per program, so it is a function and not a counter."""
    off = seq_k - seq_q
    dtype = jnp.dtype(dtype)
    plan = {}
    for kernel, (bq, bk) in _tiles(seq_q, seq_k, head_dim,
                                   dtype)._asdict().items():
        nq, nk = seq_q // bq, seq_k // bk
        computing = nq * nk
        if causal:
            computing = sum(
                min(nk, max(0, (qi * bq + bq - 1 + off) // bk + 1))
                for qi in range(nq))
        plan[kernel] = {
            "block_q": bq, "block_k": bk, "operand_dtype": dtype.name,
            "grid_steps": nq * nk, "computing_steps": computing,
            "vmem_bytes": _vmem_bytes(kernel, bq, bk, head_dim,
                                      dtype.itemsize)}
    return plan


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q3, k3, v3, scale, causal, tiles, interpret):
    out, _ = _flash_fwd(q3, k3, v3, scale, causal, *tiles.fwd, interpret)
    return out


def _flash_core_fwd(q3, k3, v3, scale, causal, tiles, interpret):
    out, lse = _flash_fwd(q3, k3, v3, scale, causal, *tiles.fwd, interpret)
    return out, (q3, k3, v3, out, lse)


def _flash_core_bwd(scale, causal, tiles, interpret, res, g):
    return _flash_bwd(res, g, scale, causal, tiles, interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core_seg(q3, k3, v3, qs3, ks3, scale, causal, tiles, interpret):
    out, _ = _flash_fwd(q3, k3, v3, scale, causal, *tiles.fwd, interpret,
                        qs3=qs3, ks3=ks3)
    return out


def _flash_core_seg_fwd(q3, k3, v3, qs3, ks3, scale, causal, tiles,
                        interpret):
    out, lse = _flash_fwd(q3, k3, v3, scale, causal, *tiles.fwd, interpret,
                          qs3=qs3, ks3=ks3)
    return out, (q3, k3, v3, out, lse, qs3, ks3)


def _flash_core_seg_bwd(scale, causal, tiles, interpret, res, g):
    q3, k3, v3, out, lse, qs3, ks3 = res
    dq, dk, dv = _flash_bwd((q3, k3, v3, out, lse), g, scale, causal,
                            tiles, interpret, qs3=qs3, ks3=ks3)
    # int segment ids take float0 cotangents (non-differentiable)
    import numpy as _np
    zq = _np.zeros(qs3.shape, dtype=jax.dtypes.float0)
    zk = _np.zeros(ks3.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, zq, zk


_flash_core_seg.defvjp(_flash_core_seg_fwd, _flash_core_seg_bwd)


def _head_major(query, key, value, scale, block_q, block_k, interpret):
    """The public functions' shared prologue: kv heads repeated up to the
    query heads (GQA: broadcast, not copy, under XLA), ``[B, S, H, D]`` ->
    ``[B*H, S, D]``, the default scale and tiles.  Returns the three
    operands, ``(scale, tiles, interpret)`` and the way back for an
    ``[B*H, Sq, ...]`` result."""
    b, sq, h, d = query.shape
    kh = key.shape[2]
    if kh != h:
        rep = h // kh
        key = jnp.repeat(key, rep, axis=2)
        value = jnp.repeat(value, rep, axis=2)
    if interpret is None:
        interpret = _interpret_default()
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    tiles = _tiles(sq, key.shape[1], d, query.dtype, block_q, block_k)

    def to3(x):
        return jnp.moveaxis(x, 1, 2).reshape(b * h, x.shape[1], d)

    def back(out3):
        return jnp.moveaxis(out3.reshape(b, h, sq, d), 1, 2)

    return (to3(query), to3(key), to3(value)), (scale, tiles, interpret), \
        back


def flash_attention(query, key, value, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention over paddle layout [B, S, H, D]; differentiable.

    GQA (kv heads < q heads) is handled by head repetition before the
    kernel (broadcast, not copy, under XLA).  ``block_q`` / ``block_k``
    override the planned tiles of all three kernels.
    """
    qkv, (scale, tiles, interpret), back = _head_major(
        query, key, value, scale, block_q, block_k, interpret)
    return back(_flash_core(*qkv, scale, causal, tiles, interpret))


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
    b, _, h, _ = query.shape
    qkv, (scale, tiles, interpret), back = _head_major(
        query, key, value, scale, block_q, block_k, interpret)

    def seg3(s):
        s = jnp.asarray(s, jnp.int32)
        return jnp.repeat(s[:, None, :], h, axis=1).reshape(b * h, -1, 1)

    return back(_flash_core_seg(*qkv, seg3(q_segments), seg3(k_segments),
                                scale, causal, tiles, interpret))


def flash_attention_with_lse(query, key, value, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Forward-only variant that also returns logsumexp [B, H, S] (used by
    ring attention to combine per-shard partial attentions).

    GQA handled like flash_attention: kv heads repeated up to q heads.
    """
    b, sq, h, _ = query.shape
    qkv, (scale, tiles, interpret), back = _head_major(
        query, key, value, scale, block_q, block_k, interpret)
    out3, lse = _flash_fwd(*qkv, scale, causal, *tiles.fwd, interpret)
    return back(out3), lse.reshape(b, h, sq)
