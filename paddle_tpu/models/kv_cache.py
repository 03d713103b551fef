"""Shared KV-cache position plumbing for the decoder models.

The functional cache every causal LM here carries is a per-layer tuple
``(k_buf, v_buf, pos)`` with ``k_buf/v_buf [batch, max_len, heads, dim]``.
Historically ``pos`` was a single scalar — every row of the batch sat at
the same context length.  Continuous batching (paddle_tpu.serving) packs
requests of DIFFERENT lengths into one fixed-shape batch, so ``pos`` may
now also be an int32 VECTOR ``[batch]`` of per-row cache positions:

  * scalar ``pos``  — the whole chunk lands at one offset
    (``dynamic_update_slice``), the classic dense-batch decode;
  * vector ``pos``  — row r's chunk lands at ``pos[r]`` (a vmapped
    per-row ``dynamic_update_slice``), and the attention mask uses row
    r's own length.

Both forms stay fixed-shape: the cache buffers never reallocate, only
the write offset and the masking length vary — graftlint's
recompile-hazard rule is the design constraint.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["append_kv", "append_rows", "cache_lens", "gather_block_rows",
           "scatter_block_rows"]


def _is_per_row(pos) -> bool:
    return getattr(pos, "ndim", 0) >= 1


def append_kv(pk, pv, k, v, pos, head0=0):
    """Write the fresh chunk ``k/v [b, s, h, d]`` into the cache buffers
    ``pk/pv [b, max_len, slab_heads, d]`` at rows ``pos..`` (scalar, or
    ``[b]`` int32 for per-row offsets) of the heads ``head0 .. head0 +
    h`` (traced: one plane of a looped model's many-plane slab; 0 where
    the slab is the plane).  Returns the updated full buffers.  A start
    past ``max_len - s`` clamps to it (``dynamic_update_slice``).

    The XLA form of the append.  Decode and the verify window take it
    inside the attention kernel
    (``kernels.decode_attention.append_and_attend``), which falls back
    to this one.  Per row it is ONE scatter: unrolled into a
    ``dynamic_update_slice`` a row, XLA:TPU no longer updates a
    many-plane slab in place (``temp`` 3.2 GB, a whole slab, compiled
    for the chip in PR 28)."""
    if _is_per_row(pos):
        def row(buf, new, p):
            return jax.lax.dynamic_update_slice(buf, new, (p, head0, 0))
        upd = jax.vmap(row)
        p = jnp.asarray(pos, jnp.int32)
        return upd(pk, k, p), upd(pv, v, p)
    return (jax.lax.dynamic_update_slice(pk, k, (0, pos, head0, 0)),
            jax.lax.dynamic_update_slice(pv, v, (0, pos, head0, 0)))


def append_rows(buf, new, pos):
    """:func:`append_kv` for a cache of ONE row kind (a latent row: no K
    and V apart): the chunk ``new [b, s, h, d]`` into ``buf [b, max_len,
    h, d]`` at rows ``pos..``."""
    if _is_per_row(pos):
        return jax.vmap(lambda b, n, p: jax.lax.dynamic_update_slice(
            b, n, (p, 0, 0)))(buf, new, jnp.asarray(pos, jnp.int32))
    return jax.lax.dynamic_update_slice(buf, new, (0, pos, 0, 0))


def gather_block_rows(block_buf, idx):
    """Assemble a contiguous cache row from block-pool rows: gather
    ``idx`` ([n] int32 block ids, clamped in bounds) out of ``block_buf``
    ([num_blocks, block_len, h, d]) and flatten to ``[n * block_len, h,
    d]`` — the cache-view a slot adopts its shared prefix from.  Entries
    past the true match count gather stale rows; callers mask them via
    the per-row ``seq_lens`` (exactly the slot-reuse discipline of
    ``KVPool``), so no in-kernel validity select is needed."""
    rows = jnp.take(block_buf, jnp.asarray(idx, jnp.int32), axis=0,
                    mode="clip")
    n, bl, h, d = rows.shape
    return rows.reshape(n * bl, h, d)


def scatter_block_rows(block_buf, row, dest):
    """Inverse of :func:`gather_block_rows`: split a contiguous cache row
    ``[n * block_len, h, d]`` into block_len pieces and scatter piece j
    into ``block_buf[dest[j]]``.  ``dest`` entries >= num_blocks are
    DROPPED (out-of-bounds scatter mode) — the one-program way to write
    an arbitrary SUBSET of a prompt's blocks (only the freshly computed
    ones; already-cached prefix blocks stay untouched)."""
    nb, bl, h, d = block_buf.shape
    pieces = row.reshape(-1, bl, h, d)
    return block_buf.at[jnp.asarray(dest, jnp.int32)].set(pieces,
                                                          mode="drop")


def cache_lens(pos, s: int, batch: int):
    """Per-row valid cache lengths AFTER appending an ``s``-token chunk at
    ``pos`` — the ``seq_lens`` the ragged decode-attention kernel masks
    by.  A scalar ``pos`` broadcasts to every row; a ``[batch]`` vector is
    each row's own context length (ragged continuous-batching decode)."""
    if _is_per_row(pos):
        return (jnp.asarray(pos, jnp.int32) + s).astype(jnp.int32)
    return jnp.full((batch,), pos + s, jnp.int32)
