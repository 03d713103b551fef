"""Pallas TPU decode attention: one (or few) query tokens against a long
KV cache, with per-sequence lengths.

Reference: the attention core of
paddle/phi/kernels/fusion/gpu/fused_multi_transformer_op.cu (fmha_ref.h
masked decode attention over cache_kv at time_step) — the hot kernel of
the reference's inference path (SURVEY.md §2.1 "PHI fused kernels").

TPU-native: decode attention is HBM-bandwidth-bound (the whole KV cache
streams once per token), so the kernel's job is to stream K/V tiles
through VMEM exactly once with the online-softmax recurrence and never
materialise logits — same recurrence as flash_attention.py but specialised
for tiny seq_q (the MXU runs [sq<=8, D] x [D, block_k] matmuls, padded to
a sublane):

  grid = (B*H, num_kv_blocks), kv innermost ("arbitrary"); m/l/acc carried
  in VMEM scratch; a per-batch ``seq_lens`` vector masks positions beyond
  the live cache length (mosaic-legal [B, 1] layout, streamed per grid b).

Layout: q [B, S_q(small), H, D]; k/v cache [B, S_max, H, D] (the
batch-major cache the incubate FusedMultiTransformer keeps); seq_lens [B]
int32 = number of VALID cache positions (including any freshly-written
current tokens).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "decode_attention_reference",
           "decode_attention_auto"]

_NEG_INF = float("-inf")


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale, block_k, nk, sq, causal_tail):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    seq_len = len_ref[0, 0, 0]                           # [1,1,1] tile
    should = ki * block_k < seq_len

    @pl.when(should)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale         # [sq, D]
        k = k_ref[0].astype(jnp.float32)                 # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (sq, block_k), 1)
        valid = kpos < seq_len
        if causal_tail:
            # the sq query tokens occupy cache slots
            # [seq_len - sq, seq_len): query t sees kpos <= seq_len-sq+t
            qpos = jax.lax.broadcasted_iota(jnp.int32, (sq, block_k), 0)
            valid = jnp.logical_and(valid,
                                    kpos <= seq_len - sq + qpos)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_sc[...]
        l_prev = l_sc[...]
        m_curr = jnp.max(s, axis=1)[:, None]
        m_next = jnp.maximum(m_prev, m_curr)
        m_safe = jnp.where(m_next == _NEG_INF, 0.0, m_next)
        p = jnp.exp(s - m_safe[:, :1])
        alpha = jnp.exp(m_prev - m_safe)
        l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_sc[...] = m_next
        acc_sc[...] = acc_sc[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        l = l_sc[...][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, seq_lens,
                     scale: Optional[float] = None, block_k: int = 1024,
                     causal_tail: bool = True,
                     interpret: Optional[bool] = None):
    """Masked attention of a short query block against the KV cache.

    q [B, sq, H, D] (sq is the freshly-appended chunk; 1 for pure decode),
    k_cache/v_cache [B, S_max, H, D], seq_lens [B] int32 valid lengths
    (counting the new chunk).  Returns [B, sq, H, D].

    ``causal_tail`` masks within the fresh chunk (query t attends up to
    cache slot seq_len - sq + t), matching the models' chunked-prefill
    semantics.

    ``block_k`` default 1024 per the r4 on-chip sweep: bk1024 was the
    fastest tile at every cache length tried (kv2048..16384), flipping
    the kv4096 row from 0.93x to >=1.0x vs the XLA dense path.
    """
    b, sq, h, d = q.shape
    s_max = k_cache.shape[1]
    kh = k_cache.shape[2]
    if kh != h:                                 # GQA: repeat kv heads
        rep = h // kh
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bk = min(block_k, s_max)
    while s_max % bk:
        bk //= 2
    nk = s_max // bk

    def to3(x):
        return jnp.moveaxis(x, 1, 2).reshape(b * h, x.shape[1], d)

    # per-(b,h) program: lens broadcast over heads -> [B*H, 1, 1]
    # (the trailing dims are both 1 so the (1, 1, 1) block satisfies the
    # mosaic last-two-dims rule by equality — a [B*H, 1] layout would not)
    lens3 = jnp.repeat(seq_lens.astype(jnp.int32), h)[:, None, None]

    compiler_params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    out3 = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=bk, nk=nk, sq=sq,
                          causal_tail=causal_tail),
        grid=(b * h, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, sq, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, sq, d), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((sq, 128), jnp.float32),
            pltpu.VMEM((sq, 128), jnp.float32),
            pltpu.VMEM((sq, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        name="decode_attention",
        interpret=interpret,
    )(lens3, to3(q), to3(k_cache), to3(v_cache))
    return jnp.moveaxis(out3.reshape(b, h, sq, d), 1, 2)


def decode_attention_reference(q, k_cache, v_cache, seq_lens,
                               scale: Optional[float] = None,
                               causal_tail: bool = True):
    """Dense XLA form with EXACTLY the kernel's masking semantics (valid =
    kpos < seq_len, plus the causal tail within the fresh chunk) and its
    rounding (f32 softmax/accumulate, one final cast).  The routed
    fallback for long caches where the measured table ties toward XLA."""
    b, sq, h, d = q.shape
    kh = k_cache.shape[2]
    if kh != h:
        rep = h // kh
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    s_max = k_cache.shape[1]
    kpos = jnp.arange(s_max)[None, None, None, :]
    lens = seq_lens.astype(jnp.int32)[:, None, None, None]
    valid = kpos < lens
    if causal_tail:
        qpos = jnp.arange(sq)[None, None, :, None]
        valid = jnp.logical_and(kpos <= lens - sq + qpos, valid)
    s = jnp.where(valid, s, float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(valid, -1, keepdims=True), p, 0.0)
    out = jnp.einsum("bhqs,bshd->bqhd", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_auto(q, k_cache, v_cache, seq_lens,
                          scale: Optional[float] = None,
                          causal_tail: bool = True,
                          interpret: Optional[bool] = None):
    """Empirically-routed decode attention: the Pallas streaming kernel
    where the measured table says it wins (cache <= 6144 on v5e), the
    dense XLA form beyond (statistical tie, tie-break to XLA — see
    kernels/routing.py)."""
    import jax as _jax
    from ..core.flags import flags
    from .routing import use_pallas
    # "never" must win everywhere, including the CPU interpret path (the
    # flag's contract: all Pallas off — a user chasing a numerical
    # discrepancy gets the pure-XLA form on any backend)
    if getattr(flags, "pallas_routing", "auto") == "never":
        return decode_attention_reference(q, k_cache, v_cache, seq_lens,
                                          scale=scale,
                                          causal_tail=causal_tail)
    on_cpu = _jax.default_backend() == "cpu"
    if not on_cpu and not use_pallas("decode_attention",
                                     kv_len=k_cache.shape[1]):
        return decode_attention_reference(q, k_cache, v_cache, seq_lens,
                                          scale=scale,
                                          causal_tail=causal_tail)
    return decode_attention(q, k_cache, v_cache, seq_lens, scale=scale,
                            causal_tail=causal_tail, interpret=interpret)
