"""Context parallelism (the hybrid topology's ``sep`` axis): long-sequence
attention sharded across devices.

Reference surface (SURVEY.md §5 "long-context"):
  - sep axis: fleet/base/topology.py — HybridCommunicateGroup(sep_degree),
    splitting activations on the sequence dim across the sep group.
  - Ulysses all-to-all (head<->seq swap) utilities in fleet/utils.
  - Ring flash attention: PaddleNLP ring_flash_attention layered on core
    send/recv — implemented natively here since it is a first-class
    capability of this framework.

TPU-native: both schemes are shard_map programs over the ``sep`` mesh axis.
Ring attention rotates K/V blocks around the ICI ring with
``jax.lax.ppermute`` while accumulating a numerically-stable online
softmax (the flash-attention recurrence), so peak memory is O(S/n) and the
transfer rides neighbor links.  Ulysses swaps which dim is sharded
(seq -> heads) with ``jax.lax.all_to_all``, runs ordinary attention on
full-length sequences for H/n heads, and swaps back.

Both functions work in two modes:
  - eager/top-level: pass ``mesh`` (or rely on the fleet HCG mesh); they
    wrap themselves in shard_map.
  - already inside a shard_map/jit with the axis in scope: pass
    ``inside_shard_map=True`` and they use the collectives directly.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .._jax_compat import axis_size as _axis_size
from ..topology import get_hybrid_communicate_group

# graftcomm seam marker: the ring-attention K/V (and gradient) blocks
# travel one neighbor hop per step over the "sep" axis — a cross-host
# seam on sequence-parallel meshes.  Forward ships the K/V block pair
# per hop; backward additionally rotates the dk/dv accumulators, so the
# roles differ and are pinned separately.
__remote_dma_seams__ = {
    "_ring_fwd_impl": {
        "role": "cp-ring-fwd",
        "payload": "max_seq // tp * kv_heads * head_dim * itemsize"},
    "_ring_core_bwd": {
        "role": "cp-ring-bwd",
        "payload": "max_seq // tp * kv_heads * head_dim * itemsize"},
}


def _shard_map(body, mesh, in_specs, out_specs, manual_axes):
    """jax.shard_map in partial-manual mode: only ``manual_axes`` are
    manual (collectives address them); other mesh axes stay GSPMD-auto so
    this composes inside a pjit program sharded over dp/mp/etc.

    When already tracing inside an enclosing shard_map (e.g. the fused
    pipeline schedule with pp manual), the nested map must be built on the
    AMBIENT abstract mesh — passing the concrete Mesh raises a context-
    mismatch because the ambient mesh carries Manual axis types.  This is
    the cp-inside-pp composition seam (r4 dryrun leg 4)."""
    from .._jax_compat import shard_map
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs,
                     axis_names=frozenset(manual_axes), check_vma=False)


def _axis_is_manual(axis_name: str) -> bool:
    """True when tracing inside a shard_map that already binds
    ``axis_name`` as manual (e.g. the fused pipeline schedule running with
    sep in its manual set) — the attention entry points then use the
    collectives directly instead of opening their own shard_map (nested
    binding is rejected by the sdy lowering)."""
    try:
        am = jax.sharding.get_abstract_mesh()
        names = getattr(am, "axis_names", None) or ()
        if axis_name not in names:
            return False
        types = dict(zip(names, getattr(am, "axis_types", ())))
        return types[axis_name] == jax.sharding.AxisType.Manual
    except Exception:
        return False

__all__ = ["ring_attention", "ulysses_attention", "RingAttention",
           "split_sequence", "gather_sequence"]


def _resolve_mesh(mesh: Optional[Mesh]) -> Mesh:
    if mesh is not None:
        return mesh
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        raise ValueError("no mesh: pass mesh= or fleet.init first")
    return hcg.get_mesh()


def split_sequence(x, axis_name: str = "sep", seq_dim: int = 1, mesh=None):
    """Constrain x to sequence-sharded layout over the sep axis (reference:
    the sep group's scatter of activations along seq)."""
    spec = [None] * x.ndim
    spec[seq_dim] = axis_name
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x


def gather_sequence(x, axis_name: str = "sep", seq_dim: int = 1, mesh=None):
    try:
        return jax.lax.with_sharding_constraint(x, P(*([None] * x.ndim)))
    except Exception:
        return x


# --------------------------------------------------------------------------
# Ring attention
# --------------------------------------------------------------------------

def _ring_fwd_impl(q, k, v, axis_name: str, axis_size: int, causal: bool,
                   scale: float):
    """Per-device fwd: q,k,v are the LOCAL sequence blocks [B,Sl,H,D].

    Ring flash recurrence: each of the ``axis_size`` hops runs the Pallas
    flash kernel (paddle_tpu/kernels/flash_attention.py) on the local q
    against the K/V block currently held, then combines the normalized
    per-hop results with their logsumexps — block logits never materialise
    (round-2: the previous jnp path built full [B,H,Sl,Sl] logits per hop).

    Causal structure under the ring: at hop t the block held came from rank
    src = (my - t) mod n.  t == 0 is the diagonal (causal flash); t >= 1 is
    valid iff src < my, i.e. my >= t (then it is a fully-unmasked block);
    otherwise the hop contributes nothing (lse = -inf).

    Returns (out [B,Sl,H,D], lse [B,H,Sl] f32).
    """
    from ...kernels.flash_attention import flash_attention_with_lse
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def flash(k_blk, v_blk, causal_):
        o, lse = flash_attention_with_lse(q, k_blk, v_blk, causal=causal_,
                                          scale=scale)
        return o.astype(jnp.float32), lse      # [B,Sl,H,D], [B,H,Sl]

    out, lse = flash(k, v, causal)
    k_blk, v_blk = k, v
    for t in range(1, axis_size):              # static unroll over ring hops
        # receive the next lower rank's block (ring walk over ICI neighbors)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        o_t, lse_t = flash(k_blk, v_blk, False)
        if causal:
            lse_t = jnp.where(my >= t, lse_t, -jnp.inf)
        lse_new = jnp.logaddexp(lse, lse_t)
        safe = jnp.where(jnp.isneginf(lse_new), 0.0, lse_new)

        def w(ls):                              # [B,H,Sl] -> [B,Sl,H,1]
            wt = jnp.where(jnp.isneginf(ls), 0.0, jnp.exp(ls - safe))
            return jnp.swapaxes(wt, 1, 2)[..., None]

        out = out * w(lse) + o_t * w(lse_t)
        lse = lse_new
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_core(q, k, v, axis_name, axis_size, causal, scale):
    out, _ = _ring_fwd_impl(q, k, v, axis_name, axis_size, causal, scale)
    return out


def _ring_core_fwd(q, k, v, axis_name, axis_size, causal, scale):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, axis_size, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(axis_name, axis_size, causal, scale, res, g):
    """Reverse ring pass (classic ring-flash bwd): per hop, run the flash
    backward kernels against the K/V block currently held using the GLOBAL
    lse (p = exp(s·scale - lse_global) is then the exact softmax slice),
    accumulate dq locally while dk/dv travel WITH their block — after the
    full cycle (+1 closing rotation) they are back at the owner rank."""
    from ...kernels.flash_attention import _flash_bwd, _tiles, \
        _interpret_default
    q, k, v, out, lse = res
    B, Sl, H, D = q.shape
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    interpret = _interpret_default()
    tiles = _tiles(Sl, Sl, D, q.dtype)

    def to3(x):
        return jnp.moveaxis(x, 1, 2).reshape(B * H, x.shape[1], D)

    def from3(x3):
        return jnp.moveaxis(x3.reshape(B, H, Sl, D), 1, 2)

    q3, o3, g3 = to3(q), to3(out), to3(g.astype(q.dtype))
    lse3 = lse.reshape(B * H, Sl)

    dq3 = jnp.zeros_like(q3, jnp.float32)
    dk = jnp.zeros_like(k, jnp.float32)
    dv = jnp.zeros_like(v, jnp.float32)
    k_blk, v_blk = k, v
    for t in range(axis_size):
        if t > 0:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            dk = jax.lax.ppermute(dk, axis_name, perm)
            dv = jax.lax.ppermute(dv, axis_name, perm)
        dq_t, dk_t, dv_t = _flash_bwd(
            (q3, to3(k_blk), to3(v_blk), o3, lse3), g3, scale,
            causal and t == 0, tiles, interpret)
        if causal and t > 0:
            w = (my >= t).astype(jnp.float32)
            dq_t, dk_t, dv_t = dq_t * w, dk_t * w, dv_t * w
        dq3 = dq3 + dq_t.astype(jnp.float32)
        dk = dk + from3(dk_t).astype(jnp.float32)
        dv = dv + from3(dv_t).astype(jnp.float32)
    # closing rotation: dk/dv for the block seen at hop t have now had
    # (axis_size-1-t) rotations; one more completes the cycle home
    dk = jax.lax.ppermute(dk, axis_name, perm)
    dv = jax.lax.ppermute(dv, axis_name, perm)
    return (from3(dq3).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def _ring_attention_local(q, k, v, axis_name: str, axis_size: int,
                          causal: bool, scale: float):
    """Differentiable per-device ring attention body (see _ring_fwd_impl);
    requires kv heads == q heads (repeat before calling for GQA — the ring
    bwd returns grads in the repeated layout otherwise)."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return _ring_core(q, k, v, axis_name, axis_size, causal, scale)


def ring_attention(q, k, v, causal: bool = True, axis_name: str = "sep",
                   mesh: Optional[Mesh] = None, batch_spec: P = None,
                   inside_shard_map: bool = False, scale: Optional[float] = None):
    """Ring attention over the ``sep`` mesh axis.  q/k/v: [B, S, H, D]
    (global shapes at top level; local blocks when inside_shard_map)."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if inside_shard_map or _axis_is_manual(axis_name):
        size = _axis_size(axis_name)
        return _ring_attention_local(q, k, v, axis_name, size, causal, scale)

    mesh = _resolve_mesh(mesh)
    size = mesh.shape[axis_name]
    if q.shape[1] % size:
        raise ValueError(f"seq len {q.shape[1]} not divisible by "
                         f"{axis_name} degree {size}")
    b_axis = batch_spec if batch_spec is not None else None
    spec = P(b_axis, axis_name, None, None)
    manual = {axis_name} | ({b_axis} if b_axis else set())
    fn = _shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          axis_size=size, causal=causal, scale=scale),
        mesh, (spec, spec, spec), spec, manual)
    return fn(q, k, v)


class RingAttention:
    """Layer-ish wrapper for ported code (PaddleNLP RingFlashAttention)."""

    def __init__(self, axis_name: str = "sep", causal: bool = True):
        self.axis_name = axis_name
        self.causal = causal

    def __call__(self, q, k, v, **kw):
        return ring_attention(q, k, v, causal=self.causal,
                              axis_name=self.axis_name, **kw)


# --------------------------------------------------------------------------
# Ulysses (DeepSpeed-style) all-to-all attention
# --------------------------------------------------------------------------

def _ulysses_local(q, k, v, axis_name: str, causal: bool, scale: float,
                   attn_fn=None):
    """Per-device body: [B, Sl, H, D] -> all_to_all -> [B, S, Hl, D] ->
    attention -> swap back."""
    def seq2head(x):
        # split heads (dim 2) across the axis, concat seq (dim 1)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def head2seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = seq2head(q), seq2head(k), seq2head(v)    # [B, S, H/n, D]
    if attn_fn is None:
        # full-length attention over H/n heads via the Pallas flash kernel
        # (differentiable custom_vjp; interpret mode on CPU) — logits never
        # materialise at the long post-all-to-all sequence length
        from ...kernels.flash_attention import flash_attention
        out = flash_attention(qg, kg, vg, causal=causal, scale=scale)
    else:
        out = attn_fn(qg, kg, vg)
    return head2seq(out)                                   # [B, Sl, H, D]


def ulysses_attention(q, k, v, causal: bool = True, axis_name: str = "sep",
                      mesh: Optional[Mesh] = None, batch_spec: P = None,
                      inside_shard_map: bool = False,
                      scale: Optional[float] = None):
    """Ulysses context parallelism: all-to-all head<->seq swap, full-seq
    attention on H/n heads, swap back.  Requires num_heads % sep == 0."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if inside_shard_map or _axis_is_manual(axis_name):
        return _ulysses_local(q, k, v, axis_name, causal, scale)

    mesh = _resolve_mesh(mesh)
    size = mesh.shape[axis_name]
    if q.shape[1] % size or q.shape[2] % size:
        raise ValueError(
            f"seq {q.shape[1]} and heads {q.shape[2]} must divide "
            f"{axis_name} degree {size}")
    b_axis = batch_spec if batch_spec is not None else None
    spec = P(b_axis, axis_name, None, None)
    manual = {axis_name} | ({b_axis} if b_axis else set())
    fn = _shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name, causal=causal,
                          scale=scale),
        mesh, (spec, spec, spec), spec, manual)
    return fn(q, k, v)
