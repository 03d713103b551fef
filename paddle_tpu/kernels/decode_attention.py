"""Pallas TPU decode attention: one (or few) query tokens against a long
KV cache, with per-sequence lengths.

Reference: the attention core of
paddle/phi/kernels/fusion/gpu/fused_multi_transformer_op.cu (fmha_ref.h
masked decode attention over cache_kv at time_step) — the hot kernel of
the reference's inference path (SURVEY.md §2.1 "PHI fused kernels").

TPU-native: decode attention is HBM-bandwidth-bound (the live KV rows
stream once per token), so the kernel's job is to stream K/V tiles
through VMEM exactly once with the online-softmax recurrence and never
materialise logits.  Two kernels, one recurrence, chosen by the
operands' shapes (:func:`pallas_attention_route`, never a flag):

  * **slab in place** (decode, the speculative verify window): the
    cache is read WHERE IT LIES, ``[slots, rows, slab_heads, head_dim]``
    as ``serving.kv_pool`` holds it.  grid = (slots,), one program per
    slot over all ``kv_heads`` of the window; ``[bk, KH, Dh]`` tiles —
    whole-head rows, the only window of a row-major slab a DMA can
    address (:func:`mosaic_slab_rule`) — are DMA'd through a
    double-buffered VMEM window, live tiles only, and every query of
    the slot (``sq`` tokens x ``rep`` query heads per kv head: GQA
    without repeating the cache) runs the recurrence on the VPU.  A
    many-plane slab (models/ouro.py) is windowed by ``head0``, a traced
    scalar: no plane is ever sliced out.  :func:`stream_slab_attention`
    is the core, shared with the fused block's attention kernel
    (kernels/decode_block.py).  Through :func:`append_and_attend` the
    same kernel also WRITES the step's fresh K and V rows into the
    (aliased) slabs, one DMA each per slot (:func:`slab_row_writes`):
    the models scatter nothing beforehand.
  * **head-major copy** (a prefill chunk: MXU work; slabs Mosaic cannot
    window): grid = (B*KH, num_kv_blocks), kv innermost ("arbitrary"),
    ``[rep*sq, D] x [D, block_k]`` matmuls, m/l/acc carried in VMEM
    scratch.  Its operands are the HEAD-MAJOR ``[B*KH, S, D]`` view of
    the slab, which only exists written out: one copy of K and of V per
    call, whatever the rows hold.

Layout: q [B, S_q(small), H, D]; k/v cache [B, S_max, slab_heads, D];
seq_lens [B] int32 = number of VALID cache positions (including any
freshly-written current tokens).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "decode_attention_reference",
           "decode_attention_auto", "decode_attention_route",
           "append_and_attend", "pallas_attention_route",
           "mosaic_slab_rule", "stream_slab_attention",
           "slab_row_writes", "online_softmax_update"]

_NEG_INF = float("-inf")
# slab rows the streaming core up-casts and reduces at a time
ATTN_CHUNK = 16
# rows of one streamed K (or V) tile of the in-place kernel, and the
# bytes that caps them at for very wide rows.  A slot's last tile is
# half dead rows on average and a parked slot still fetches one, so
# ragged short prefixes want small tiles, full slots middling ones: on
# a v5e (PR 29, 8 calls of 16 slots x 2048 x 32 x 128 bf16, us a call)
# 32 / 64 / 128 rows read 85 / 89 / 107 at 2,290 live rows and - / 807
# / 769 at all 32,768; Ouro's plane (8 x 512 x 16 x 128) 27 / 28 / 33
# at 1,021 live rows and - / 75 / 75 at all 4,096, 256 rows 45 and 84
SLAB_TILE_ROWS = 64
SLAB_TILE_BYTES = 1 << 20
# queries (sq x rep) per kv head the in-place kernel unrolls on the
# VPU, and the widest fresh chunk it takes; past either the MXU kernel
# is the better engine
SLAB_MAX_QUERIES = 16
SLAB_MAX_SQ = 8
# the scoped-VMEM limit handed to Mosaic explicitly (here and by the
# decode-block kernels): passing it pins the limit to the same value
# standalone and inside an engine program (XLA's default scoped limit
# differs between the two contexts)
VMEM_LIMIT = 16 * 1024 * 1024


def mosaic_slab_rule(kv_heads: int, head_dim: int,
                     slab_heads: Optional[int] = None):
    """Why Mosaic cannot window ``[.., kv_heads, head_dim]`` rows out
    of a ``[.., slab_heads, head_dim]`` slab, or None.  The last two are
    the slab's tiled minor dims and ``tpu.memref_slice`` takes whole
    tiles only; the compiler's words are quoted so the refusal is
    static, never a failed dispatch (the interpreted CPU kernels have
    no such limit).  A slab of 2 or 4 heads is tiled that small; a
    window narrower than the slab (one plane of a many-plane slab,
    from a traced first head) is not."""
    if head_dim % 128:
        return (f"mosaic: head_dim {head_dim} is not a multiple of the "
                f"128-lane tile ('Slice shape along dimension 3 must be "
                f"aligned to tiling (128), but is {head_dim}')")
    whole = slab_heads in (None, kv_heads)
    if kv_heads % 8 and not (whole and kv_heads in (2, 4)):
        of = "" if whole else f" of the slab's {slab_heads}"
        return (f"mosaic: {kv_heads} kv heads per device{of} do not fill "
                f"the slab's sublane tile ('Slice shape along dimension "
                f"2 must be aligned to tiling (8), but is {kv_heads}')")
    return None


def _plane(slab, head0, kv_heads: int):
    """``slab[:, :, head0 : head0 + kv_heads]`` WRITTEN OUT (the routes
    that cannot window a many-plane slab in place)."""
    b, s_max, slab_heads, d = slab.shape
    if slab_heads == kv_heads:
        return slab
    return jax.lax.dynamic_slice(slab, (0, 0, head0, 0),
                                 (b, s_max, kv_heads, d))


# ===================================================== the streaming core

def online_softmax_update(state, s_blk, v_blk):
    """One online-softmax step for one query per kv head: ``s_blk [n,
    KH, 1]`` masked scores, ``v_blk [n, KH, Dh]``; the running max /
    sum / accumulator are ``[1, KH, 1|Dh]``, all float32."""
    m_prev, l_prev, acc = state
    m_next = jnp.maximum(m_prev, jnp.max(s_blk, axis=0, keepdims=True))
    m_safe = jnp.where(m_next == _NEG_INF, 0.0, m_next)
    p = jnp.exp(s_blk - m_safe)
    alpha = jnp.exp(m_prev - m_safe)
    return (m_next,
            alpha * l_prev + jnp.sum(p, axis=0, keepdims=True),
            acc * alpha + jnp.sum(p * v_blk, axis=0, keepdims=True))


def stream_slab_attention(k_any, v_any, kbuf, vbuf, rsem, *, b, heads,
                          qs, lims, n_rows, bk: int, ck: int):
    """Stream slot ``b``'s first ``n_rows`` rows of the HBM slabs
    ``k_any / v_any [slots, S, slab_heads, Dh]`` through the
    double-buffered VMEM windows ``kbuf / vbuf [2, bk, KH, Dh]`` ONCE,
    and run the online-softmax recurrence for every query in ``qs``
    (each ``[1, KH, Dh]`` float32, pre-scaled: one query per kv head)
    at once on the VPU: scores are a lane reduction of ``K * q``, the
    value sum a leading-dim reduction of ``p * V``.  Query ``j`` sees
    the positions ``kpos < lims[j]`` (``lims[j] <= n_rows``).
    ``heads`` indexes the slab's head axis (``slice(None)``, or a
    ``pl.ds`` window of a many-plane slab).  Tiles wholly past
    ``n_rows`` are never DMA'd, chunks of ``ck`` rows past it never
    computed.  Returns one ``(m, l, acc)`` per query."""
    nlive = jax.lax.div(n_rows + bk - 1, bk)

    def k_cp(slot, ki):
        return pltpu.make_async_copy(
            k_any.at[b, pl.ds(ki * bk, bk), heads], kbuf.at[slot],
            rsem.at[0, slot])

    def v_cp(slot, ki):
        return pltpu.make_async_copy(
            v_any.at[b, pl.ds(ki * bk, bk), heads], vbuf.at[slot],
            rsem.at[1, slot])

    @pl.when(nlive > 0)
    def _prefetch():
        k_cp(0, 0).start()
        v_cp(0, 0).start()

    def _tile(ki, state):
        slot = jax.lax.rem(ki, 2)

        @pl.when(ki + 1 < nlive)
        def _next():
            k_cp(1 - slot, ki + 1).start()
            v_cp(1 - slot, ki + 1).start()

        k_cp(slot, ki).wait()
        v_cp(slot, ki).wait()

        def _chunk(ci, state):
            # ck rows at a time: the f32 working copies of a whole
            # [bk, KH, Dh] tile are not in the VMEM plan
            r0 = pl.multiple_of(ci * ck, ck)
            kt = kbuf[slot, pl.ds(r0, ck)].astype(jnp.float32)
            vt = vbuf[slot, pl.ds(r0, ck)].astype(jnp.float32)
            kpos = ki * bk + r0 + jax.lax.broadcasted_iota(
                jnp.int32, (ck, 1, 1), 0)
            out = []
            for q, lim, st in zip(qs, lims, state):
                s_blk = jnp.sum(kt * q, axis=-1, keepdims=True)
                s_blk = jnp.where(kpos < lim, s_blk, _NEG_INF)
                out.append(online_softmax_update(st, s_blk, vt))
            return tuple(out)

        live = jnp.minimum(n_rows - ki * bk, bk)
        return jax.lax.fori_loop(0, jax.lax.div(live + ck - 1, ck),
                                 _chunk, state)

    _, kh, dh = qs[0].shape
    init = tuple((jnp.full((1, kh, 1), _NEG_INF, jnp.float32),
                  jnp.zeros((1, kh, 1), jnp.float32),
                  jnp.zeros((1, kh, dh), jnp.float32)) for _ in qs)
    return jax.lax.fori_loop(0, nlive, _tile, init)


def slab_tiles(max_seq: int, want: int):
    """``(bk, ck)``: the largest tile of at most ``want`` rows that
    divides ``max_seq`` by halving, and the chunk of at most
    ``ATTN_CHUNK`` rows that divides the tile."""
    bk = max(1, min(want, max_seq))
    while max_seq % bk:
        bk //= 2
    ck = min(bk, ATTN_CHUNK)
    while bk % ck:
        ck //= 2
    return bk, ck


def slab_row_writes(k_rows, v_rows, k_any, v_any, wsem, *, b, row0,
                    heads=slice(None)):
    """The in-kernel KV append: the K and the V ``make_async_copy`` of
    the fresh ``k_rows / v_rows [n, KH, Dh]`` (VMEM, the slab's dtype)
    into rows ``row0 .. row0 + n`` of slot ``b`` of the HBM slabs
    ``k_any / v_any`` (the kernel's aliased outputs), ``heads`` as in
    :func:`stream_slab_attention`; ``wsem`` holds two DMA semaphores.
    The caller starts both and waits on both before its program ends."""
    def write(rows, slab, sem):
        return pltpu.make_async_copy(
            rows, slab.at[b, pl.ds(row0, rows.shape[0]), heads], sem)
    return write(k_rows, k_any, wsem.at[0]), write(v_rows, v_any, wsem.at[1])


# ================================================== slab in place (VPU)

def _slab_kernel(len_ref, head0_ref, q_ref, *refs, S, kh, sq, rep, bk, ck,
                 scale, causal_tail, windowed, append):
    """One slot's attention over the ``kh`` kv heads of its window:
    ``q_ref [1, sq*rep, KH, Dh]`` (query ``t*rep + r`` is token ``t``'s
    ``r``-th query head of every kv head).

    ``append``: ``len_ref`` holds the rows the slot held BEFORE this
    chunk, ``kn_ref / vn_ref [1, sq, KH, Dh]`` are the chunk's fresh
    rows in the slab's dtype, and the program DMAs them into the
    aliased slabs at ``posw = clip(pos, 0, S - sq)``
    (``dynamic_update_slice``'s clamp: a full slot overwrites its last
    rows).  The read-after-write hazard is closed by construction, the
    fused block's way: the stream covers the rows ``< posw`` only,
    which the write never touches, and the fresh rows fold in last from
    VMEM at their stored rounding; fresh row ``i`` lies at ``posw + i``
    and query ``t`` sees it where the unfused form's mask would
    (``posw + i < pos + t + 1`` under the causal tail)."""
    if append:
        kn_ref, vn_ref, k_any, v_any, o_ref, ko_any, vo_any, \
            kbuf, vbuf, rsem, wsem = refs
    else:
        k_any, v_any, o_ref, kbuf, vbuf, rsem = refs
    b = pl.program_id(0)
    heads = slice(None)
    if windowed:
        heads = pl.ds(pl.multiple_of(head0_ref[0], kh), kh)
    if append:
        pos = len_ref[b]
        n_rows = jnp.clip(pos, 0, S - sq)
        writes = slab_row_writes(kn_ref.at[0], vn_ref.at[0], ko_any,
                                 vo_any, wsem, b=b, row0=n_rows,
                                 heads=heads)
        for cp in writes:
            cp.start()
        seq_len = pos + sq
    else:
        seq_len = len_ref[b]
        n_rows = jnp.clip(seq_len, 0, S)
    qs = [(q_ref[0, j].astype(jnp.float32) * scale)[None]
          for j in range(sq * rep)]
    # the sq query tokens occupy cache rows [seq_len - sq, seq_len):
    # query t sees kpos <= seq_len - sq + t
    lims = [seq_len - sq + t + 1 if causal_tail else seq_len
            for t in range(sq) for _ in range(rep)]
    # appending, the stream ends where the write starts, and every
    # query sees all of it
    state = stream_slab_attention(
        k_any, v_any, kbuf, vbuf, rsem, b=b, heads=heads, qs=qs,
        lims=[n_rows] * len(lims) if append else lims, n_rows=n_rows,
        bk=bk, ck=ck)
    if append:
        kq = kn_ref[0].astype(jnp.float32)                  # [sq, KH, Dh]
        vq = vn_ref[0].astype(jnp.float32)
        row = n_rows + jax.lax.broadcasted_iota(jnp.int32, (sq, 1, 1), 0)
        state = [online_softmax_update(
            st, jnp.where(row < lim,
                          jnp.sum(kq * q, axis=-1, keepdims=True),
                          _NEG_INF), vq)
            for q, lim, st in zip(qs, lims, state)]
    for j, (_, l, acc) in enumerate(state):
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, j] = (acc / l_safe)[0].astype(o_ref.dtype)
    if append:
        for cp in writes:
            cp.wait()


def _slab_in_place(q, k_cache, v_cache, seq_lens, head0, kh, *, scale,
                   block_k, causal_tail, interpret, fresh=None):
    """``fresh``: None, or the chunk's ``(k_new, v_new) [b, sq, KH,
    Dh]``: the kernel appends them (``seq_lens`` is then the rows held
    BEFORE the chunk) and the updated slabs come back beside the
    output."""
    b, sq, h, d = q.shape
    s_max, slab_heads = k_cache.shape[1:3]
    rep = h // kh
    row_bytes = kh * d * jnp.dtype(k_cache.dtype).itemsize
    bk, ck = slab_tiles(s_max, min(block_k, SLAB_TILE_ROWS,
                                   max(8, SLAB_TILE_BYTES // row_bytes)))
    # queries t-major, then r, with the kv head next to Dh: the kernel
    # picks "query j of every kv head" by a leading index
    qr = jnp.moveaxis(q.reshape(b, sq, kh, rep, d), 3, 2) \
        .reshape(b, sq * rep, kh, d)
    append = fresh is not None
    kernel = functools.partial(
        _slab_kernel, S=s_max, kh=kh, sq=sq, rep=rep, bk=bk, ck=ck,
        scale=scale, causal_tail=causal_tail,
        windowed=slab_heads != kh, append=append)
    # lengths and the window's first head ride as scalar-prefetch
    # operands: in SMEM before the grid starts (a (1,) SMEM block per
    # program is not lowerable)
    qspec = pl.BlockSpec((1, sq * rep, kh, d),
                         lambda bi, lens, h0: (bi, 0, 0, 0))
    slab = pl.BlockSpec(memory_space=pl.ANY)
    rows = pl.BlockSpec((1, sq, kh, d), lambda bi, lens, h0: (bi, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, sq * rep, kh, d), q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[qspec] + [rows, rows] * append + [slab, slab],
        out_specs=[qspec, slab, slab] if append else qspec,
        scratch_shapes=[
            pltpu.VMEM((2, bk, kh, d), k_cache.dtype),
            pltpu.VMEM((2, bk, kh, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ] + ([pltpu.SemaphoreType.DMA((2,))] if append else []),
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out_shape,
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)]
        if append else out_shape,
        # operand indices count the two scalar-prefetch arguments
        input_output_aliases={5: 1, 6: 2} if append else {},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        name="decode_attention_slab",
        interpret=interpret,
    )(seq_lens.astype(jnp.int32),
      jnp.asarray(head0, jnp.int32).reshape(1), qr,
      *(fresh or ()), k_cache, v_cache)
    out, *slabs = res if append else (res,)
    out = jnp.moveaxis(out.reshape(b, sq, rep, kh, d), 2, 3) \
        .reshape(b, sq, h, d)
    return (out, *slabs) if append else out


# =============================================== head-major copy (MXU)

def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale, block_k, nk, sq, rows, causal_tail):
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
        q = q_ref[0].astype(jnp.float32) * scale         # [rows, D]
        k = k_ref[0].astype(jnp.float32)                 # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        valid = kpos < seq_len
        if causal_tail:
            # the sq query tokens occupy cache slots
            # [seq_len - sq, seq_len): query t sees kpos <= seq_len-sq+t
            # (row r*sq + t is token t of the kv head's r-th query head)
            qpos = jax.lax.rem(jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 0), sq)
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


def _head_major_copy(q, k_cache, v_cache, seq_lens, head0, kh, *, scale,
                     block_k, causal_tail, interpret):
    b, sq, h, d = q.shape
    s_max = k_cache.shape[1]
    rep = h // kh
    rows = rep * sq
    k_cache, v_cache = _plane(k_cache, head0, kh), _plane(v_cache, head0, kh)
    bk, _ = slab_tiles(s_max, block_k)
    nk = s_max // bk

    def to3(x):
        return jnp.moveaxis(x, 1, 2).reshape(b * kh, x.shape[1], d)

    # a kv head's rep query heads ride as rows of ONE program's query
    # block (GQA: the cache is never repeated)
    q3 = jnp.moveaxis(q.reshape(b, sq, kh, rep, d), 1, 3) \
        .reshape(b * kh, rows, d)
    # per-(b,kh) program: lens broadcast over heads -> [B*KH, 1, 1]
    # (the trailing dims are both 1 so the (1, 1, 1) block satisfies the
    # mosaic last-two-dims rule by equality — a [B*KH, 1] layout would not)
    lens3 = jnp.repeat(seq_lens.astype(jnp.int32), kh)[:, None, None]

    compiler_params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    out3 = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=bk, nk=nk, sq=sq,
                          rows=rows, causal_tail=causal_tail),
        grid=(b * kh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, rows, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, d), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kh, rows, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        name="decode_attention",
        interpret=interpret,
    )(lens3, q3, to3(k_cache), to3(v_cache))
    return jnp.moveaxis(out3.reshape(b, kh, rep, sq, d), 3, 1) \
        .reshape(b, sq, h, d)


# ============================================================== the choice

def pallas_attention_route(q_shape, slab_shape, dtype,
                           kv_heads: Optional[int] = None):
    """Which Pallas kernel serves these operands: ``("slab_in_place",
    None)`` or ``("head_major_copy", why)``.  A function of the shapes
    and the slab's dtype alone: the in-place kernel takes a short fresh
    chunk (decode, the speculative verify window) of a slab whose
    whole-head rows Mosaic can window; a prefill chunk is MXU work and
    keeps the matmul kernel."""
    _, sq, h, d = q_shape
    slab_heads = slab_shape[2]
    kh = kv_heads or slab_heads
    rep = h // kh
    dt = jnp.dtype(dtype)
    if sq > SLAB_MAX_SQ or sq * rep > SLAB_MAX_QUERIES:
        return "head_major_copy", (
            f"{sq} query tokens x {rep} query heads per kv head: past "
            f"the {SLAB_MAX_SQ} tokens / {SLAB_MAX_QUERIES} queries the "
            f"in-place kernel unrolls on the VPU (MXU work)")
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return "head_major_copy", (f"slab dtype {dt.name} not in "
                                   f"(float32, bfloat16)")
    why = mosaic_slab_rule(kh, d, slab_heads)
    if why is not None:
        return "head_major_copy", why
    return "slab_in_place", None


def decode_attention(q, k_cache, v_cache, seq_lens,
                     scale: Optional[float] = None, block_k: int = 1024,
                     causal_tail: bool = True,
                     interpret: Optional[bool] = None,
                     head0=0, kv_heads: Optional[int] = None):
    """Masked attention of a short query block against the KV cache.

    q [B, sq, H, D] (sq is the freshly-appended chunk; 1 for pure decode),
    k_cache/v_cache [B, S_max, slab_heads, D], seq_lens [B] int32 valid
    lengths (counting the new chunk).  Returns [B, sq, H, D].

    The attended kv heads are ``slab[:, :, head0 : head0 + kv_heads]``:
    ``kv_heads`` (static) defaults to all the slab holds; ``head0`` (a
    traced scalar, a multiple of ``kv_heads``) picks one plane of a
    many-plane slab (models/ouro.py) without slicing it out.  ``H`` is a
    multiple of ``kv_heads`` (GQA: the cache is never repeated).

    ``causal_tail`` masks within the fresh chunk (query t attends up to
    cache slot seq_len - sq + t), matching the models' chunked-prefill
    semantics.

    ``block_k`` caps the streamed tile's rows.  The default 1024 is the
    head-major kernel's, per the r4 on-chip sweep (the fastest tile at
    every cache length tried, kv2048..16384); the in-place kernel also
    holds its tiles to ``SLAB_TILE_ROWS`` and ``SLAB_TILE_BYTES``.
    """
    b, sq, h, d = q.shape
    kh = kv_heads or k_cache.shape[2]
    if h % kh or k_cache.shape[2] % kh:
        raise ValueError(f"decode_attention: {h} query heads / "
                         f"{k_cache.shape[2]} slab heads are not "
                         f"multiples of kv_heads {kh}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    route, _ = pallas_attention_route(q.shape, k_cache.shape,
                                      k_cache.dtype, kh)
    fn = _slab_in_place if route == "slab_in_place" else _head_major_copy
    return fn(q, k_cache, v_cache, seq_lens, head0, kh, scale=scale,
              block_k=block_k, causal_tail=causal_tail,
              interpret=interpret)


def decode_attention_reference(q, k_cache, v_cache, seq_lens,
                               scale: Optional[float] = None,
                               causal_tail: bool = True,
                               head0=0, kv_heads: Optional[int] = None):
    """Dense XLA form with EXACTLY the kernels' masking semantics (valid =
    kpos < seq_len, plus the causal tail within the fresh chunk), their
    window (``head0``, ``kv_heads``) and their rounding (f32
    softmax/accumulate, one final cast).  The routed fallback for long
    caches where the measured table ties toward XLA."""
    b, sq, h, d = q.shape
    kh = kv_heads or k_cache.shape[2]
    k_cache, v_cache = _plane(k_cache, head0, kh), _plane(v_cache, head0, kh)
    s_max = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # GQA as a grouped contraction: the cache is never repeated
    qg = q.astype(jnp.float32).reshape(b, sq, kh, h // kh, d)
    s = jnp.einsum("bqkrd,bskd->bkrqs", qg,
                   k_cache.astype(jnp.float32)) * scale
    kpos = jnp.arange(s_max)[None, None, None, None, :]
    lens = seq_lens.astype(jnp.int32)[:, None, None, None, None]
    valid = kpos < lens
    if causal_tail:
        qpos = jnp.arange(sq)[None, None, None, :, None]
        valid = jnp.logical_and(kpos <= lens - sq + qpos, valid)
    s = jnp.where(valid, s, float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(valid, -1, keepdims=True), p, 0.0)
    out = jnp.einsum("bkrqs,bskd->bqkrd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def decode_attention_route(q_shape, slab_shape, dtype,
                           kv_heads: Optional[int] = None):
    """The attention route of a program traced HERE for these operands,
    and why it is not the in-place kernel: ``("slab_in_place", None)``,
    ``("head_major_copy", why)`` or ``("xla_dense", why)``.  Static per
    compiled program; the engine's ``decode_block`` obs event carries
    it.  ``FLAGS_pallas_routing=never`` wins everywhere, including the
    CPU interpret path (the flag's contract: all Pallas off — a user
    chasing a numerical discrepancy gets the pure-XLA form on any
    backend); on a chip the measured table and the mesh in scope
    (kernels/routing.py) send long caches and XLA-partitioned calls to
    the dense form."""
    from ..core.flags import flags
    from .routing import partition_refusal, use_pallas
    if getattr(flags, "pallas_routing", "auto") == "never":
        return "xla_dense", "FLAGS_pallas_routing=never"
    kv_len = slab_shape[1]
    if jax.default_backend() != "cpu" and not use_pallas(
            "decode_attention", kv_len=kv_len):
        return "xla_dense", partition_refusal() or (
            f"routing: kv_len {kv_len} beyond the measured pallas win "
            f"region (<= 6144)")
    return pallas_attention_route(q_shape, slab_shape, dtype, kv_heads)


def _routed(route, q, k_cache, v_cache, seq_lens, *, interpret, **kw):
    """Attention over slabs that already hold the chunk, by ``route``."""
    if route == "xla_dense":
        return decode_attention_reference(q, k_cache, v_cache, seq_lens,
                                          **kw)
    return decode_attention(q, k_cache, v_cache, seq_lens,
                            interpret=interpret, **kw)


def decode_attention_auto(q, k_cache, v_cache, seq_lens,
                          scale: Optional[float] = None,
                          causal_tail: bool = True,
                          interpret: Optional[bool] = None,
                          head0=0, kv_heads: Optional[int] = None):
    """Empirically-routed decode attention (:func:`decode_attention_route`):
    the Pallas kernels where the measured table says they win (cache <=
    6144 on v5e), the dense XLA form beyond (statistical tie, tie-break
    to XLA — see kernels/routing.py)."""
    route, _ = decode_attention_route(q.shape, k_cache.shape,
                                      k_cache.dtype, kv_heads)
    return _routed(route, q, k_cache, v_cache, seq_lens, scale=scale,
                   causal_tail=causal_tail, interpret=interpret,
                   head0=head0, kv_heads=kv_heads)


def append_and_attend(q, k_new, v_new, k_slab, v_slab, pos, *, head0=0,
                      kv_heads: Optional[int] = None,
                      scale: Optional[float] = None,
                      causal_tail: bool = True,
                      interpret: Optional[bool] = None):
    """Append the fresh chunk to the KV slabs and attend to them: what
    a cached attention layer does each step, as ONE call.

    q [B, sq, H, D]; k_new / v_new [B, sq, kv_heads, D] the chunk's
    rows in the slabs' dtype; k_slab / v_slab [B, S_max, slab_heads, D];
    ``pos`` the rows held BEFORE this chunk, a scalar or ``[B]`` int32
    (``models/kv_cache.py``).  ``head0`` / ``kv_heads`` window one plane
    of a many-plane slab as in :func:`decode_attention`.  Returns
    ``(out [B, sq, H, D], k_slab', v_slab')``; the slabs equal
    ``kv_cache.append_kv``'s bit for bit, its clamp of a write past the
    slab's end included.

    :func:`decode_attention_route` decides, from the operands' shapes
    and dtype: ``slab_in_place`` appends inside the kernel (one DMA of
    K and of V per slot into the aliased slabs, no XLA scatter);
    ``head_major_copy`` and ``xla_dense`` append with ``append_kv``
    first and attend as :func:`decode_attention_auto` does."""
    from ..models.kv_cache import append_kv, cache_lens
    b, sq, _, d = q.shape
    kh = kv_heads or k_slab.shape[2]
    route, _ = decode_attention_route(q.shape, k_slab.shape, k_slab.dtype,
                                      kv_heads)
    if route != "slab_in_place":
        with jax.named_scope("kv_append"):
            k_slab, v_slab = append_kv(k_slab, v_slab, k_new, v_new, pos,
                                       head0)
        out = _routed(route, q, k_slab, v_slab, cache_lens(pos, sq, b),
                      scale=scale, causal_tail=causal_tail,
                      interpret=interpret, head0=head0, kv_heads=kv_heads)
        return out, k_slab, v_slab
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _slab_in_place(
        q, k_slab, v_slab, jnp.broadcast_to(jnp.asarray(pos), (b,)),
        head0, kh,
        scale=scale if scale is not None else 1.0 / (d ** 0.5),
        block_k=SLAB_TILE_ROWS, causal_tail=causal_tail,
        interpret=interpret, fresh=(k_new, v_new))
