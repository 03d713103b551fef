"""Decode attention over a LATENT cache (models/deepseek_v3.py): one
shared row a position, ``[c_t, R(kr_t)]`` of ``lora + rope`` values,
under every query head, whose V is the first ``lora`` columns of its K.

The absorbed form of the attention is two matrix products against the
same rows: scores ``q [heads, lora + rope] . rows^T`` and values ``p
[heads, rows] . rows[:, :lora]``.  Written as two XLA dots, the decode
program reads every slot's whole slab twice whatever is live, turns its
layout over between them (one dot contracts the row's columns, the
other the positions: 0.53 ms a layer at 32 slots x 4096 rows on a v5e)
and appends the step's fresh rows by a 32-trip while loop.  Here ONE
program a slot streams the slot's LIVE rows through VMEM once, in tiles
of ``TILE_ROWS`` positions, runs both products on the MXU from the tile
with the online-softmax recurrence between them, and writes the fresh
row into the slab in place (``kernels/decode_attention.py``'s
discipline: the stream ends where the write starts, the fresh row folds
in last from VMEM at its stored rounding).  Positions lie on the slab's
SUBLANE axis here, and two bfloat16 rows share a packed sublane, so one
row cannot be DMA'd alone: the program reads the aligned ``WRITE_ROWS``
block that holds the row's place while it streams, sets the row in
VMEM, and writes the block back when the stream has ended.  A parked
slot (``pos`` 0) streams nothing.

A cached prefill CHUNK (``latent_chunk_attention``) attends the same way
after the XLA append has put its rows into the request's staging: a grid
step takes ``QUERY_TILE`` positions under all the heads (one shared row
under every head, so ``QUERY_TILE x heads`` MXU rows) and streams the
staging's tiles from row 0 up to the tile that holds its last query's
own row, never the ``max_seq`` rows past it.  Within the last tile a
score is masked by ``key <= pos + query`` and a row past the tile's last
query is zeroed before it reaches a product, so whatever the staging
holds there never reaches the result.

``decode_attention.py``'s kernels do not serve this cache: 32 queries a
KV head is past ``SLAB_MAX_QUERIES``, 576 fails ``mosaic_slab_rule``'s
``head_dim % 128``, and they hold K and V apart.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["latent_decode_attention", "latent_attention_route",
           "latent_chunk_attention", "latent_chunk_route", "attended_rows",
           "TILE_ROWS", "QUERY_TILE"]

# positions one VMEM tile of the stream holds (two are in flight):
# 512 x 640 lanes x 2 B = 655 KB each
TILE_ROWS = 512
# chunk positions one grid step of the chunk kernel takes, under every
# head: 32 x 32 heads = 1,024 MXU rows, a [1024, 640] bfloat16 query
# tile (1.3 MB) and a [1024, 512] float32 accumulator (2 MB).  At 64
# the program needs over 24 MiB of VMEM and the compiler's static
# schedule is no shorter per row
QUERY_TILE = 32
# scoped VMEM the chunk kernel may use: its blocks double-buffered, the
# row tiles, the statistics and a [1024, 512] float32 score tile's
# temporaries (Mosaic needs between 12 and 16 MiB at the JoyAI widths)
CHUNK_VMEM_LIMIT = 24 * 1024 * 1024
# the aligned block of positions the fresh row is written back in (a
# packed bfloat16 tile is 16 sublanes)
WRITE_ROWS = 16
_NEG_INF = float("-inf")
# the operands' own precision, whatever the process's default (Mosaic
# refuses a float32-precision product of bfloat16 operands)
_MXU = jax.lax.Precision.DEFAULT


def latent_attention_route(slab_shape, dtype):
    """``(route, reason)`` of a decode step's attention over latent
    slabs ``[slots, max_seq, 1, width]``, traced HERE:
    ``("latent_in_place", None)`` or ``("xla_dense", why)``.  Static per
    compiled program, a function of platform, shape and dtype."""
    from ..core.flags import flags
    if getattr(flags, "pallas_routing", "auto") == "never":
        return "xla_dense", "FLAGS_pallas_routing=never"
    _, max_seq, heads, _ = slab_shape
    if heads != 1:
        return "xla_dense", f"{heads} row heads: the kernel streams ONE " \
                            f"shared row a position"
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return "xla_dense", f"dtype {jnp.dtype(dtype).name}"
    if max_seq % WRITE_ROWS:
        return "xla_dense", (f"max_seq {max_seq} is not a multiple of "
                             f"{WRITE_ROWS}")
    return "latent_in_place", None


def latent_chunk_route(staging_shape, width: int, dtype):
    """``(route, reason)`` of a cached prefill chunk's attention over a
    request's latent staging ``[1, max_seq, 1, row width]``, traced
    HERE: ``("latent_chunk", None)`` or ``("xla_dense", why)``.  The
    decode kernel's rules (the flag, one row head, the dtype, whole
    16-row blocks), and the chunk must fit the staging.  Static per
    compiled program, a function of shape, width and dtype."""
    route, why = latent_attention_route(staging_shape, dtype)
    if route != "latent_in_place":
        return route, why
    if width > staging_shape[1]:
        return "xla_dense", (f"chunk width {width} is past max_seq "
                             f"{staging_shape[1]}")
    return "latent_chunk", None


def _tile_rows(max_seq: int) -> int:
    bk = min(TILE_ROWS, max_seq)
    while max_seq % bk:
        bk //= 2
    return bk


def _key_tiles(end, bk: int):
    """Row tiles that hold positions ``[0, end)``: a Python int on the
    host, a traced scalar in the kernel."""
    return (end + bk - 1) // bk


def attended_rows(max_seq: int, offset: int, width: int) -> int:
    """Staging rows a layer of the chunk kernel streams for a chunk of
    ``width`` positions appended at ``offset``: whole tiles up to the one
    that holds the chunk's last row (what its last query tile reads)."""
    bk = _tile_rows(max_seq)
    return min(_key_tiles(offset + width, bk), max_seq // bk) * bk


def _fetch(rows_any, buf, sem, b, slot, ki, bk: int):
    """The DMA of row tile ``ki`` of slab row ``b`` into ``buf[slot]``."""
    return pltpu.make_async_copy(rows_any.at[b, pl.ds(ki * bk, bk)],
                                 buf.at[slot], sem.at[slot])


def _fold(state, s, values):
    """One online-softmax step: scores ``s [n, k]`` (masked), ``values
    [k, lora]`` in the rows' dtype, ``state`` the float32 ``(m [n, 1],
    l [n, 1], acc [n, lora])``."""
    m_prev, l_prev, acc = state
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(m_next == _NEG_INF, 0.0, m_next)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(m_prev - m_safe)
    pv = jax.lax.dot_general(
        p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
        precision=_MXU, preferred_element_type=jnp.float32)
    return (m_next, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
            acc * alpha + pv)


def _kernel(len_ref, q_ref, new_ref, rows_any, o_ref, out_any, buf, wbuf,
            rsem, wsem, *, S, bk, lora, scale):
    """Slot ``b``: ``q_ref [1, heads, w]``, ``new_ref [1, 1, w]`` the
    fresh row, ``rows_any / out_any [slots, S, w]`` the slab and its
    aliased output, ``len_ref`` the rows held BEFORE this step."""
    b = pl.program_id(0)
    n_rows = jnp.clip(len_ref[b], 0, S - 1)
    block0 = pl.multiple_of(
        jax.lax.div(n_rows, WRITE_ROWS) * WRITE_ROWS, WRITE_ROWS)
    held = pltpu.make_async_copy(
        rows_any.at[b, pl.ds(block0, WRITE_ROWS)], wbuf, wsem.at[0])
    held.start()
    q = q_ref[0]                                            # [H, w]
    heads = q.shape[0]
    nlive = jax.lax.div(n_rows + bk - 1, bk)

    def fetch(slot, ki):
        return _fetch(rows_any, buf, rsem, b, slot, ki, bk)

    @pl.when(nlive > 0)
    def _prefetch():
        fetch(0, 0).start()

    def tile(ki, state):
        slot = jax.lax.rem(ki, 2)

        @pl.when(ki + 1 < nlive)
        def _next():
            fetch(1 - slot, ki + 1).start()

        fetch(slot, ki).wait()
        rows = buf[slot]                                    # [bk, w]
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), precision=_MXU,
            preferred_element_type=jnp.float32) * scale     # [H, bk]
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos < n_rows, s, _NEG_INF)
        return _fold(state, s, rows[:, :lora])

    init = (jnp.full((heads, 1), _NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, lora), jnp.float32))
    state = jax.lax.fori_loop(0, nlive, tile, init)
    # the fresh row last, from VMEM at its stored rounding
    new = new_ref[0]                                        # [1, w]
    s_new = jnp.sum(q.astype(jnp.float32) * new.astype(jnp.float32),
                    axis=1, keepdims=True) * scale          # [H, 1]
    m_prev, l_prev, acc = state
    m_next = jnp.maximum(m_prev, s_new)
    p = jnp.exp(s_new - m_next)
    alpha = jnp.exp(m_prev - m_next)
    l = alpha * l_prev + p
    acc = acc * alpha + p * new[:, :lora].astype(jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # the row into its block, the block back: every other row of it is
    # written as it was read
    held.wait()
    at = block0 + jax.lax.broadcasted_iota(jnp.int32, (WRITE_ROWS, 1), 0)
    wbuf[...] = jnp.where(at == n_rows, new, wbuf[...])
    back = pltpu.make_async_copy(
        wbuf, out_any.at[b, pl.ds(block0, WRITE_ROWS)], wsem.at[1])
    back.start()
    back.wait()


def latent_decode_attention(q, new_row, slab, pos, *, lora: int,
                            scale: float,
                            interpret: Optional[bool] = None):
    """One decode token a slot against the slot's latent rows, the fresh
    row appended on the way.

    ``q [b, heads, w]`` (the absorbed query ``[W_uk^T qn, R(qr)]``, the
    slab's dtype), ``new_row [b, 1, 1, w]`` this step's row, ``slab [b,
    max_seq, 1, w]``, ``pos [b]`` int32 the rows each slot held BEFORE
    the step (the row is written at ``clip(pos, 0, max_seq - 1)``, as
    ``dynamic_update_slice`` clamps).  Returns ``(ol [b, heads, lora]
    float32, slab')``: ``ol_i = sum_s p_s c_s`` over the ``pos + 1``
    rows, the slab updated in place (donated callers)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, heads, w = q.shape
    max_seq = slab.shape[1]
    bk = _tile_rows(max_seq)
    kernel = functools.partial(_kernel, S=max_seq, bk=bk, lora=lora,
                               scale=scale)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, heads, w), lambda bi, lens: (bi, 0, 0)),
                  pl.BlockSpec((1, 1, w), lambda bi, lens: (bi, 0, 0)),
                  any_space],
        out_specs=[pl.BlockSpec((1, heads, lora),
                                lambda bi, lens: (bi, 0, 0)), any_space],
        scratch_shapes=[pltpu.VMEM((2, bk, w), slab.dtype),
                        pltpu.VMEM((WRITE_ROWS, w), slab.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))])
    flat = slab.reshape(b, max_seq, w)
    ol, flat = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, heads, lora), jnp.float32),
                   jax.ShapeDtypeStruct(flat.shape, flat.dtype)],
        # operand indices count the scalar-prefetch argument
        input_output_aliases={3: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_decode_attention",
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32), q, new_row.reshape(b, 1, w), flat)
    return ol, flat.reshape(slab.shape)


def _query_tile(width: int) -> int:
    tq = min(QUERY_TILE, width)
    while width % tq:
        tq //= 2
    return tq


def _chunk_kernel(pos_ref, q_ref, rows_any, o_ref, buf, m_ref, l_ref, rsem,
                  *, S, bk, tq, heads, lora, scale):
    """Row ``b`` of the staging, query tile ``qi``: ``q_ref [1, tq x
    heads, w]`` (position-major: MXU row ``r`` is position ``r //
    heads``), ``rows_any [b, S, w]`` the staging with the chunk's rows
    in it, ``pos_ref`` the rows held BEFORE the chunk.  ``o_ref [1, tq x
    heads, lora]`` float32 is the accumulator."""
    b, qi = pl.program_id(0), pl.program_id(1)
    first = pos_ref[b] + qi * tq            # the tile's first query's row
    end = first + tq                        # rows [0, end) reach the tile
    nk = jnp.minimum(_key_tiles(end, bk), S // bk)
    _fetch(rows_any, buf, rsem, b, 0, 0, bk).start()
    n = tq * heads
    m_ref[...] = jnp.full((n, 1), _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros((n, 1), jnp.float32)
    o_ref[0] = jnp.zeros((n, lora), jnp.float32)
    # the last row each MXU row's query sees
    last = first + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) // heads

    def tile(ki, carry):
        slot = jax.lax.rem(ki, 2)

        @pl.when(ki + 1 < nk)
        def _next():
            _fetch(rows_any, buf, rsem, b, 1 - slot, ki + 1, bk).start()

        _fetch(rows_any, buf, rsem, b, slot, ki, bk).wait()
        at = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        rows = jnp.where(at < end, buf[slot], 0)            # [bk, w]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())), precision=_MXU,
            preferred_element_type=jnp.float32) * scale     # [n, bk]
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos <= last, s, _NEG_INF)
        m_ref[...], l_ref[...], o_ref[0] = _fold(
            (m_ref[...], l_ref[...], o_ref[0]), s, rows[:, :lora])
        return carry

    jax.lax.fori_loop(0, nk, tile, 0)
    o_ref[0] = o_ref[0] / l_ref[...]


def latent_chunk_attention(q, staging, pos, *, lora: int, scale: float,
                           interpret: Optional[bool] = None):
    """A cached prefill chunk's queries against the latent rows held,
    its own rows included (appended before the call).

    ``q [b, s, heads, w]`` (the absorbed queries ``[W_uk^T qn, R(qr)]``,
    the staging's dtype), ``staging [b, max_seq, 1, w]``, ``pos [b]``
    int32 the rows each staging row held BEFORE the chunk.  Returns ``ol
    [b, s, heads, lora]`` float32: ``ol_i = sum_r p_r c_r`` over the rows
    ``r <= pos + i``, each query tile reading the staging only up to the
    tile that holds its last query's row (:func:`attended_rows`)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, s, heads, w = q.shape
    max_seq = staging.shape[1]
    bk, tq = _tile_rows(max_seq), _query_tile(s)
    n = tq * heads
    kernel = functools.partial(_chunk_kernel, S=max_seq, bk=bk, tq=tq,
                               heads=heads, lora=lora, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // tq),
        in_specs=[pl.BlockSpec((1, n, w), lambda bi, qi, p: (bi, qi, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n, lora), lambda bi, qi, p: (bi, qi, 0)),
        scratch_shapes=[pltpu.VMEM((2, bk, w), staging.dtype),
                        pltpu.VMEM((n, 1), jnp.float32),
                        pltpu.VMEM((n, 1), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))])
    ol = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s * heads, lora), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_LIMIT),
        name="latent_chunk_attention",
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32), q.reshape(b, s * heads, w),
      staging.reshape(b, max_seq, w))
    return ol.reshape(b, s, heads, lora)
