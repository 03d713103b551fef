"""Attention functionals.

Reference: python/paddle/nn/functional/flash_attention.py —
scaled_dot_product_attention / flash_attention routing to the CUDA
flash-attn-2 kernels (paddle/phi/kernels/gpu/flash_attn_kernel.cu, built by
cmake/external/flashattn.cmake).

TPU-native: the default path is a pure-XLA softmax(QK^T)V which XLA already
executes well for moderate seq; long-seq routes to the Pallas flash kernel
(paddle_tpu/kernels/flash_attention.py) when FLAGS_use_pallas_attention and
the platform is TPU.  Layout is paddle's: [batch, seq, heads, head_dim].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.flags import flags

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdpa_reference"]


_WARNED = set()


def _warn_once(key: str, msg: str):
    if key not in _WARNED:
        _WARNED.add(key)
        import logging
        logging.getLogger("paddle_tpu").warning(msg)


def _warn_traced_fallback():
    _warn_once("varlen_traced",
               "flash_attn_unpadded: causal varlen with traced, distinct "
               "cu_seqlens cannot prove q/k alignment — using the dense "
               "path; pass assume_aligned=True if the packs match")


def _causal_mask(sq, sk, dtype):
    i = jnp.arange(sq)[:, None]
    j = jnp.arange(sk)[None, :]
    return jnp.where(j <= i + (sk - sq), 0.0, jnp.finfo(dtype).min)


def sdpa_reference(query, key, value, attn_mask=None, dropout_p: float = 0.0,
                   is_causal: bool = False, scale: Optional[float] = None,
                   training: bool = True):
    """Pure-XLA reference path. q/k/v: [B, S, H, D] (paddle layout)."""
    from ...amp.auto_cast import maybe_cast
    query = maybe_cast(query, "attention")
    key = maybe_cast(key, "attention")
    value = maybe_cast(value, "attention")
    b, sq, h, d = query.shape
    sk = key.shape[1]
    kh = key.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    q = jnp.moveaxis(query, 1, 2)   # [B,H,Sq,D]
    k = jnp.moveaxis(key, 1, 2)
    v = jnp.moveaxis(value, 1, 2)
    if kh != h:  # grouped-query attention: repeat kv heads
        rep = h // kh
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if is_causal:
        logits = logits + _causal_mask(sq, sk, jnp.float32)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, jnp.finfo(jnp.float32).min)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and training:
        from .common import dropout as _dropout
        probs = _dropout(probs, p=dropout_p, training=True)
    probs = probs.astype(v.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.moveaxis(out, 1, 2)  # back to [B,S,H,D]


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p: float = 0.0, is_causal: bool = False,
                                 training: bool = True, name=None):
    """Parity: paddle F.scaled_dot_product_attention ([B,S,H,D] layout).

    Routes to the Pallas TPU flash kernel when profitable, else pure XLA.
    """
    from ...kernels.routing import use_pallas as _route
    use_pallas = (
        flags.use_pallas_attention
        and attn_mask is None
        and dropout_p == 0.0
        and _route("flash_attention", seq_q=query.shape[1],
                   seq_k=key.shape[1])
        and query.shape[-1] in (64, 128, 256)
        and jax.default_backend() not in ("cpu",)
    )
    if use_pallas:
        # a kernel the route selected either runs or raises: a failure
        # that quietly went dense would read as a slow chip
        from ...kernels.flash_attention import flash_attention as _pallas_fa
        return _pallas_fa(query, key, value, causal=is_causal)
    return sdpa_reference(query, key, value, attn_mask, dropout_p, is_causal,
                          training=training)


def flash_attention(query, key, value, dropout: float = 0.0,
                    causal: bool = False, return_softmax: bool = False,
                    fixed_seed_offset=None, rng_name: str = "", training=True,
                    name=None):
    """Parity: paddle F.flash_attention.flash_attention -> (out, softmax)."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training=training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale: float,
                        dropout: float = 0.0, causal: bool = False,
                        return_softmax: bool = False, name=None,
                        assume_aligned: Optional[bool] = None):
    """Varlen API parity: total-token packed layout [T, H, D] with
    cu_seqlens.  Routes to the segment-masked Pallas flash kernel
    (kernels/flash_attention.py — flash_attention_varlen) when the flag
    allows, padding T to a lane multiple with an unmatched segment id;
    dense segment-masked path otherwise (the test oracle)."""
    t, h, d = query.shape
    tk = key.shape[0]
    seg_q = jnp.cumsum(jnp.zeros(t, jnp.int32).at[cu_seqlens_q[1:-1]].add(1))
    seg_k = jnp.cumsum(jnp.zeros(tk, jnp.int32).at[cu_seqlens_k[1:-1]].add(1))
    # kernel route: global-causal ∧ same-segment == per-segment causal only
    # when q/k packs are aligned (self-attention) — gate causal cross packs
    # onto the dense path.  Alignment check: value equality when both
    # cu_seqlens are concrete, object identity under trace.
    def _aligned():
        if not causal:
            return True
        if assume_aligned is not None:
            # explicit caller contract (extension kwarg): under jit the
            # values are traced and alignment is unprovable here
            return bool(assume_aligned) and t == tk
        if t != tk:
            return False
        if cu_seqlens_q is cu_seqlens_k:
            return True
        try:
            import numpy as _np
            return bool(_np.array_equal(_np.asarray(cu_seqlens_q),
                                        _np.asarray(cu_seqlens_k)))
        except Exception:
            # traced, distinct arrays: fall back to the dense path, but
            # say so once — callers who KNOW q/k packs match should pass
            # assume_aligned=True to keep the kernel route under jit
            _warn_traced_fallback()
            return False

    kernel_ok = (
        flags.use_pallas_attention
        and dropout == 0.0
        and d in (64, 128, 256)
        and jax.default_backend() not in ("cpu",)   # dense XLA wins on CPU
        and _aligned())
    if kernel_ok:
        from ...kernels.flash_attention import flash_attention_varlen
        pad_q = (-t) % 128
        pad_k = (-tk) % 128
        qp = jnp.pad(query, [(0, pad_q), (0, 0), (0, 0)])
        kp = jnp.pad(key, [(0, pad_k), (0, 0), (0, 0)])
        vp = jnp.pad(value, [(0, pad_k), (0, 0), (0, 0)])
        # padding rows: ids that match nothing real (nor each other)
        sq = jnp.pad(seg_q, (0, pad_q), constant_values=-1)[None]
        sk_ = jnp.pad(seg_k, (0, pad_k), constant_values=-2)[None]
        out = flash_attention_varlen(qp[None], kp[None], vp[None], sq,
                                     sk_, causal=causal, scale=scale)[0]
        return out[:t], None
    logits = jnp.einsum("qhd,khd->hqk", query, key,
                        preferred_element_type=jnp.float32) * scale
    mask = seg_q[:, None] == seg_k[None, :]
    if causal:
        pos_q = jnp.arange(t) - jnp.take(cu_seqlens_q, seg_q)
        pos_k = jnp.arange(tk) - jnp.take(cu_seqlens_k, seg_k)
        mask = mask & (pos_k[None, :] <= pos_q[:, None])
    logits = jnp.where(mask[None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(value.dtype)
    out = jnp.einsum("hqk,khd->qhd", probs, value)
    return (out, None)


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """CSR-masked attention (reference: F.sparse_attention,
    sparse_attention_op): softmax runs only over each query row's CSR
    column set.  q/k/v [B, H, S, D]; offset [B, H, S+1]; columns
    [B, H, nnz].

    TPU-native: static-shape mask materialization + dense MXU matmuls —
    on TPU the structured-sparsity win comes from blockwise masking
    inside the flash kernel (flash_attention_varlen covers the varlen
    case); this op exists for API/semantics parity at CSR granularity.
    """
    q = jnp.asarray(query).astype(jnp.float32)
    k = jnp.asarray(key).astype(jnp.float32)
    v = jnp.asarray(value).astype(jnp.float32)
    b, h, s, d = q.shape
    off = jnp.asarray(sparse_csr_offset).reshape(b * h, s + 1)
    cols = jnp.asarray(sparse_csr_columns).reshape(b * h, -1)
    nnz = cols.shape[-1]

    def row_mask(off_i, cols_i):
        rows = jnp.searchsorted(off_i, jnp.arange(nnz),
                                side="right") - 1
        rows = jnp.clip(rows, 0, s - 1)
        valid = jnp.arange(nnz) < off_i[-1]
        m = jnp.zeros((s, s), bool)
        return m.at[rows, cols_i].max(valid)

    mask = jax.vmap(row_mask)(off, cols).reshape(b, h, s, s)
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / (d ** 0.5)
    if attn_mask is not None:
        scores = scores + jnp.asarray(attn_mask)
    if key_padding_mask is not None:
        kp = jnp.asarray(key_padding_mask).astype(bool)
        mask = mask & kp[:, None, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(mask, p, 0.0)   # rows with empty column sets -> 0
    out = jnp.einsum("bhst,bhtd->bhsd", p, v)
    return out.astype(jnp.asarray(query).dtype)


__all__ += ["sparse_attention"]
