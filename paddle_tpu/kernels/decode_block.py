"""Decode-block megakernel: a transformer layer's decode step as
tile-streaming Pallas TPU kernels.

Reference: the whole-layer fusion of
paddle/phi/kernels/fusion/gpu/fused_multi_transformer_op.cu — the
reference's decode path runs norm -> qkv -> cache write -> masked decode
attention -> out-proj -> ffn as ONE fused op per layer, not a kernel per
op (SURVEY.md §2.1).  FlashFuser / ClusterFusion++ (PAPERS.md) make the
same point for modern serving: decode latency lives at BLOCK-level
fusion, because the [B, 1, D] activation is tiny and every per-op HBM
round-trip costs more than the compute it carries.

Kernels (one grid for the whole layer would have to keep QKV +
out-proj + both MLP matrices resident at once — infeasible past small
hidden sizes under the ~16 MB VMEM budget, so the layer splits at its
natural seams; every weight STREAMS in tiles, nothing is resident):

  * **norm + projection** — grid ``(N // bn,)``, once each for q, k and
    v: fused LayerNorm/RMSNorm of the ``[B, D]`` rows into VMEM scratch
    at step 0, then one ``[B, D] x [D, bn]`` dot per streamed weight
    column tile (all slots share each MXU weight load).
  * **slab attention** — grid ``(B,)``, one slot per program over ALL
    its kv heads (the slabs are ``[B, S, KH, Dh]`` with ``(KH, Dh)``
    the tiled minor dims, so whole-head rows are the only slab window a
    DMA can address): optional rotary embedding (matrix form:
    ``x*cos + (x@R)*sin`` with a constant rotate-half matrix — no
    lane-slicing) -> the fresh K/V row is DMA'd **in-kernel** into the
    ``serving.kv_pool`` slot slab at this slot's ``seq_pos`` (the slab
    rides through as an aliased ANY-space operand, so the pool buffer
    is updated in place — no extra copy of the slab, ever) -> decode
    attention streams the slab's live ``[bk, KH, Dh]`` tiles through a
    double-buffered VMEM window ONCE with the same online-softmax
    recurrence and masking semantics as ``kernels/decode_attention.py``
    (ragged per-slot ``seq_pos``; tiles past the live length are never
    even DMA'd — a strict improvement over the BlockSpec pipeline,
    which streams dead tiles and masks them) -> the fresh token's own
    K/V folds in last, always valid.
  * **proj+MLP block** — grid ``(HD // bo + F // bf,)``: the
    out-projection accumulates over contraction-row tiles of ``wo``
    (+residual), fused norm2 into VMEM scratch, then the MLP streams
    its two (three for SwiGLU) weight matrices tile-by-tile,
    accumulating the down-projection in a [B, D] f32 scratch; the
    second residual lands in the final tile.  The activation never
    leaves VMEM between the out-projection and the layer output.

Numerics: norms, softmax and every accumulation run in f32; each dot's
operands are in the WEIGHT dtype (activations round to it first, as
the composed path's do), so no weight tile is ever up-cast in VMEM.

Masking contract (exactly ``decode_attention``'s semantics specialised
to sq=1, matching the unfused ``decode_attention.append_and_attend``
path token-for-token): with ``pos`` = the slot's cache length BEFORE the
step, streamed positions ``kpos < min(pos, S-1)`` are valid and the
fresh token is appended at ``min(pos, S-1)`` (``dynamic_update_slice``'s
clamp) and always attends to itself.  A full slot (``pos >= S``)
therefore overwrites its last row, and a free slot (``pos == 0``)
attends only to its own ride-along token — byte-identical lifecycle
behaviour to the unfused engine path.

VMEM budgeting (``plan_decode_block``): the tiles shrink until each
kernel's working set — residents plus TWO copies of every streamed
tile, which is how the grid pipeline allocates them — fits
``vmem_budget`` (default 12 MiB; every call hands Mosaic
``VMEM_LIMIT`` = 16 MiB, the rest is the compiler's own temporaries);
if the irreducible residents cannot fit at ANY tile size the plan
refuses and ``fusion_legal`` reports the reason, as it does for slab
rows Mosaic cannot window (``decode_attention.mosaic_slab_rule``) — the routed
fallback is the composed unfused path (see kernels/routing.py and
docs/serving.md's fallback matrix).

CPU tier-1 runs the exact same kernels under ``interpret=True``
(default off-TPU), including the in-kernel DMA append and the aliased
slab update, so every contract here is exercised on every CPU test run.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (ATTN_CHUNK, VMEM_LIMIT, mosaic_slab_rule,
                               online_softmax_update, slab_row_writes,
                               slab_tiles, stream_slab_attention)

__all__ = ["decode_block_attn", "decode_block_mlp", "decode_block_layer",
           "decode_block_reference", "plan_decode_block", "fusion_legal",
           "decode_block_route", "resolve_fused_decode"]

# default VMEM working-set budget: 16 MiB/core minus headroom for
# Mosaic's own spills/temporaries (same posture as fused_norm's 4 MiB
# per-block cap, scaled to a whole-layer working set)
VMEM_BUDGET = 12 * 1024 * 1024
# VMEM_LIMIT (decode_attention's, shared): the scoped-VMEM limit every
# decode-block pallas_call hands Mosaic explicitly: the planned working
# set (<= VMEM_BUDGET) plus the compiler's own temporaries

# graftmem marker (tools/analysis/memory.py): the memory-budget rule
# re-derives this plan's per-grid-step working set through an integer
# mirror and proves every reference tiling fits VMEM_BUDGET
__vmem_plans__ = ("plan_decode_block",)

_ROT_CACHE = {}


def _rotate_half_matrix(dh: int):
    """Constant R with ``x @ R == rotate_half(x)`` (= concat(-x2, x1)).
    Lets the kernel apply rotary as ``x*cos + (x@R)*sin`` — one tiny MXU
    op instead of lane-granular slicing, which Mosaic cannot tile for
    head dims below the 128-lane register width.  The cache holds the
    HOST matrix: a cached ``jnp.asarray`` built inside one jit trace
    would leak that trace's tracer into every later program."""
    m = _ROT_CACHE.get(dh)
    if m is None:
        half = dh // 2
        m = np.zeros((dh, dh), np.float32)
        for j in range(half):
            m[j + half, j] = -1.0       # out[:half] = -x2
            m[j, j + half] = 1.0        # out[half:] = x1
        _ROT_CACHE[dh] = m
    return jnp.asarray(m)


def _norm_f32(x, w, b, norm: str, eps: float):
    """The models' norm numerics (f32 math, affine after the rsqrt)."""
    if norm == "layer":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + eps) * w
        return y + b if b is not None else y
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w
    return y + b if b is not None else y


def _grid_params(interpret: bool):
    """Mosaic parameters of every decode-block kernel (all 1-D grids
    whose steps carry VMEM state forward): the explicit scoped-VMEM
    limit; nothing when interpreted."""
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=VMEM_LIMIT)


def _mxu_dot(a, w):
    """``a @ w`` with f32 accumulation, operands as stored.  bf16
    operands take ONE native MXU pass whatever the ambient
    ``jax_default_matmul_precision`` (their products are exact in f32,
    and asked for an f32-precision contraction of bf16 operands Mosaic
    refuses: "Bad lhs type"); f32 operands keep the ambient precision."""
    precision = jax.lax.Precision.DEFAULT \
        if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, w, (((1,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


# ======================================================== planning / legality

def _fit_tile(dim: int, per_unit: int, fixed: int, budget: int):
    """Largest tile dividing ``dim`` whose streamed working set
    ``fixed + per_unit * tile`` fits ``budget``; 128-multiples
    preferred (the Mosaic lane rule), any divisor as the shrink
    fallback — the same never-escalate posture as ``_dividing_tile``.
    None when no divisor fits."""
    lane = [t for t in range(128, dim + 1, 128) if dim % t == 0]
    for t in sorted(lane, reverse=True):
        if fixed + per_unit * t <= budget:
            return t
    for t in sorted((t for t in range(1, dim + 1) if dim % t == 0),
                    reverse=True):
        if fixed + per_unit * t <= budget:
            return t
    return None


def _slab_row_bytes(kv_heads: int, head_dim: int, itemsize: int):
    """VMEM bytes of ONE slab row ``[KH, Dh]`` in the storage dtype and
    as an f32 working copy: ``(KH, Dh)`` are the tiled minor dims, so
    each pads to the dtype's (sublane, 128-lane) tile."""
    lanes = -(-head_dim // 128) * 128
    sub = 8 * (4 // itemsize)
    return (-(-kv_heads // sub) * sub * lanes * itemsize,
            -(-kv_heads // 8) * 8 * lanes * 4)


def _plan_slab_attention(max_seq: int, kv_heads: int, rep: int,
                         head_dim: int, itemsize: int, vmem_budget: int):
    """``(block_k, bytes)`` for the slab attention kernel, or
    ``(None, bytes)`` when even the smallest window busts the budget.
    Residents: the double-buffered K and V windows, the f32 working
    copies of one ``ATTN_CHUNK``-row chunk (k, v, k*q, p*v), and the
    per-query-head q / accumulator / fresh-row tiles."""
    row, row32 = _slab_row_bytes(kv_heads, head_dim, itemsize)
    fixed = (4 * min(ATTN_CHUNK, max_seq) * row32
             + (2 * rep + 4) * row32 + 2 * row
             + 3 * head_dim * max(head_dim, 128) * 4)        # rope tables + R
    bk = min(1024, max_seq)
    while max_seq % bk:
        bk //= 2
    while bk > 8 and fixed + 4 * bk * row > vmem_budget:
        bk //= 2
    need = fixed + 4 * bk * row
    return (bk if need <= vmem_budget else None), need


def plan_decode_block(*, max_seq: int, hidden: int, heads: int,
                      kv_heads: int, head_dim: int, ffn: int, batch: int,
                      itemsize: int, gated: bool = False, tp: int = 1,
                      vmem_budget: int = VMEM_BUDGET):
    """Pick the tiles of the three kernels under the VMEM budget, or
    explain why no tiling fits.  Returns ``(plan_dict, None)`` or
    ``(None, reason)``.

    Every weight streams, double-buffered by the grid pipeline, so each
    kernel's working set is its resident activations plus TWO copies of
    every tile in flight: ``block_n`` columns of a QKV projection,
    ``block_k`` slab rows of every kv head, ``block_o`` contraction
    rows of the out-projection next to ``block_f`` columns/rows of the
    MLP matrices (one grid, so both sets are allocated together).
    Shrinking the tiles is the only lever; when the irreducible parts
    alone bust the budget the layer cannot fuse at this shape.

    ``tp > 1`` plans the SHARDED variant instead
    (``decode_block_tp.plan_decode_block_tp``): the per-shard working
    set — weights/tp plus the ring hop tile buffers — against the same
    budget; the plan dict then carries the per-seam ring tiles
    (``block_qkv``/``block_o``/``block_up``/``block_down``) next to
    ``block_k``."""
    if tp > 1:
        from .decode_block_tp import plan_decode_block_tp
        return plan_decode_block_tp(
            max_seq=max_seq, hidden=hidden, heads=heads,
            kv_heads=kv_heads, head_dim=head_dim, ffn=ffn, batch=batch,
            itemsize=itemsize, tp=tp, gated=gated,
            vmem_budget=vmem_budget)
    rep = heads // kv_heads
    dh = head_dim

    # ---- slab attention kernel
    bk, vmem_attn = _plan_slab_attention(max_seq, kv_heads, rep, dh,
                                         itemsize, vmem_budget)
    if bk is None:
        return None, (f"vmem: attention residents {vmem_attn} bytes exceed "
                      f"budget {vmem_budget} even at block_k=8")

    # ---- norm + projection kernel: x and its normed copy resident,
    # weight / bias / f32 output column tiles stream
    proj_fixed = batch * hidden * 2 * itemsize + 2 * hidden * 4
    proj_unit = 2 * (hidden * itemsize + itemsize + batch * 4)
    bn = _fit_tile(kv_heads * dh, proj_unit, proj_fixed, vmem_budget)
    if bn is None:
        return None, (f"vmem: projection residents {proj_fixed} bytes + "
                      f"weight tiles exceed budget {vmem_budget} at any "
                      f"tile of the K/V width {kv_heads * dh}")

    # ---- out-projection + MLP kernel
    mlp_fixed = (batch * hidden * 2 * itemsize            # x in, y out
                 + batch * hidden * (8 + itemsize)        # xmid/acc + h
                 + 4 * hidden * 4)                        # norm/bias rows
    o_unit = 2 * (hidden + batch) * itemsize              # wo rows + attn
    n_mats = 3 if gated else 2
    f_unit = 2 * (n_mats * hidden + 1) * itemsize
    bo = bf = None
    # candidate MLP tiles: divisors of ffn that are 128-multiples
    # (Mosaic lane rule for a [D, bf] block), or the whole ffn when it
    # is small; the out-projection takes what the largest fitting MLP
    # tile leaves
    cands = [f for f in range(128, ffn + 1, 128) if ffn % f == 0] or [ffn]
    for c in sorted(cands, reverse=True):
        bo = _fit_tile(heads * dh, o_unit, mlp_fixed + f_unit * c,
                       vmem_budget)
        if bo is not None:
            bf = c
            break
    if bf is None:
        need = mlp_fixed + f_unit * min(cands) + o_unit
        return None, (f"vmem: proj+MLP residents {need} bytes exceed "
                      f"budget {vmem_budget} even at block_f={min(cands)}")
    return {"block_k": bk, "block_n": bn, "block_o": bo, "block_f": bf,
            "vmem_attn": vmem_attn,
            "vmem_proj": proj_fixed + proj_unit * bn,
            "vmem_mlp": mlp_fixed + o_unit * bo + f_unit * bf}, None


def fusion_legal(*, max_seq: int, hidden: int, heads: int, kv_heads: int,
                 head_dim: int, ffn: int, batch: int, dtype,
                 gated: bool = False, tp: int = 1,
                 vmem_budget: int = VMEM_BUDGET):
    """Static legality of the fused decode block for this shape/dtype.
    Returns ``(ok, reason)``; ``reason`` names the first failing check —
    the engine surfaces it in the ``decode_block`` obs event as the
    fallback cause.

    ``tp > 1`` checks the SHARDED variant (``decode_block_tp``): the
    kv-head axis must tile the mesh (the slabs shard on it, so each
    device's attention grid owns whole head groups), the batch must
    slot-shard (the residual stream rides ``[B/tp, D]`` between the
    ring collectives), the ffn must column-shard, and the per-shard
    working set must fit the same VMEM budget."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False, f"dtype {dt.name} not in (float32, bfloat16)"
    if heads * head_dim != hidden:
        return False, (f"hidden {hidden} != heads*head_dim "
                       f"{heads}*{head_dim}")
    if kv_heads < 1 or heads % kv_heads:
        return False, f"heads {heads} not a multiple of kv_heads {kv_heads}"
    if head_dim % 2:
        return False, f"head_dim {head_dim} must be even (rotary halves)"
    if kv_heads % tp == 0 and jax.default_backend() != "cpu":
        why = mosaic_slab_rule(kv_heads // tp, head_dim)
        if why is not None:
            return False, why
    if tp > 1:
        if kv_heads % tp:
            return False, (f"kv_heads {kv_heads} not divisible by "
                           f"tensor_parallel {tp} (the slab shards on "
                           f"the kv-head axis)")
        if batch % tp:
            return False, (f"batch {batch} not divisible by "
                           f"tensor_parallel {tp} (the residual stream "
                           f"slot-shards between the ring collectives)")
        if ffn % tp:
            return False, (f"ffn {ffn} not divisible by "
                           f"tensor_parallel {tp} (MLP column shards)")
    plan, why = plan_decode_block(
        max_seq=max_seq, hidden=hidden, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, ffn=ffn, batch=batch, itemsize=dt.itemsize,
        gated=gated, tp=tp, vmem_budget=vmem_budget)
    if plan is None:
        return False, why
    return True, None


def decode_block_route(kv_len: int):
    """Routing policy for the fused path (on top of ``fusion_legal``):
    ``FLAGS_pallas_routing`` "never" wins everywhere including CPU (the
    flag's all-Pallas-off contract); otherwise CPU always takes the
    interpreted kernel (tier-1 exercises it), and on-chip the measured
    decode-attention crossover (Pallas wins at kv <= 6144, statistical
    tie beyond — kernels/routing.py) gates the fused path too, since
    its inner loop is the same KV streaming pattern.  A tensor-parallel
    mesh no longer refuses here — routing is mesh-agnostic: the sharded
    kernels (kernels/decode_block_tp.py) serve tp > 1, and the REAL
    mesh legality — kv_heads/batch/ffn divisibility, head alignment,
    the per-shard VMEM plan — lives in ``fusion_legal(tp=...)``, not in
    a blanket policy.  The fused-vs-composed ``kernel_compare`` rows
    (tp included) are the pending evidence to widen the win region.
    Returns ``(ok, reason)``."""
    from ..core.flags import flags
    from .routing import use_pallas
    if getattr(flags, "pallas_routing", "auto") == "never":
        return False, "FLAGS_pallas_routing=never"
    if jax.default_backend() == "cpu":
        return True, None
    if not use_pallas("decode_block", kv_len=kv_len):
        return False, (f"routing: kv_len {kv_len} beyond the measured "
                       f"pallas win region (<= 6144)")
    return True, None


def resolve_fused_decode(model, *, batch: int, kv_len: int, tp: int = 1):
    """The full fused-vs-unfused fallback chain for a model at
    ``(batch, kv_len)``: model support (``fused_decode_step`` +
    ``fused_decode_supported``) -> mesh legality (``tp > 1`` needs the
    model's ``tp_decode_weights`` bundle — the sharded Pallas block
    consumes the same per-device head-aligned layout as serving/tp.py's
    composed program — and its ``tp_decode_supported`` divisibility) ->
    routing policy (:func:`decode_block_route`) -> shape/dtype/VMEM
    legality (the model's ``fused_decode_supported`` ->
    :func:`fusion_legal(tp=...)`, which under tp > 1 checks the
    per-shard plan: kv_heads/batch/ffn tiling and the ring working
    set).  ``engine._resolve_decode_path`` calls it, so the fallback
    matrix lives in exactly one place.
    Returns ``(ok, reason)``; ``reason`` is None when the fused path
    may engage."""
    supported = getattr(model, "fused_decode_supported", None)
    if supported is None:
        return False, "model has no fused_decode_step"
    if not hasattr(model, "fused_decode_step"):
        # a model without the fused step may still say why (its layer is
        # not one the block kernels compute: models/ouro.py)
        return False, (supported(batch=batch, kv_len=kv_len, tp=tp)[1]
                       or "model has no fused_decode_step")
    if tp > 1:
        if not hasattr(model, "tp_decode_weights") \
                or not hasattr(model, "tp_decode_supported"):
            return False, ("model has no tp_decode_weights (the sharded "
                           "decode block consumes the TP bundle layout)")
        ok, reason = model.tp_decode_supported(tp)
        if not ok:
            return False, reason
    ok, reason = decode_block_route(kv_len)
    if not ok:
        return False, reason
    return supported(batch=batch, kv_len=kv_len, tp=tp)


# ======================================================= norm + projection

def _proj_kernel(x_ref, nw_ref, nb_ref, w_ref, b_ref, o_ref, h_sc, *,
                 eps, norm):
    """One column tile of ``norm(x) @ w + b``: the normed rows are
    computed once (grid step 0) into VMEM scratch in the WEIGHT dtype,
    then every step runs one [B, D] x [D, bn] dot with f32 accumulation
    — the composed path's numerics, with no weight tile ever up-cast in
    VMEM."""
    @pl.when(pl.program_id(0) == 0)
    def _norm():
        nb = nb_ref[...].astype(jnp.float32) if norm == "layer" else None
        h_sc[...] = _norm_f32(x_ref[...].astype(jnp.float32),
                              nw_ref[...].astype(jnp.float32), nb, norm,
                              eps).astype(h_sc.dtype)

    o_ref[...] = _mxu_dot(h_sc[...], w_ref[...]) \
        + b_ref[...].astype(jnp.float32)


def _norm_proj(x2, nw, nb, w, bias, *, norm, eps, block_n, interpret):
    """``norm(x2) @ w (+ bias)`` as f32 ``[B, N]``: x2 [B, D] stays
    resident, ``[D, block_n]`` weight tiles stream."""
    b, d = x2.shape
    n = w.shape[1]
    bn = _dividing_tile(n, block_n)
    bias2 = (bias if bias is not None
             else jnp.zeros((n,), w.dtype)).reshape(1, n)
    return pl.pallas_call(
        functools.partial(_proj_kernel, eps=float(eps), norm=norm),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((b, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, d), w.dtype)],
        compiler_params=_grid_params(interpret),
        name="decode_block_norm_proj",
        interpret=interpret,
    )(x2, nw, nb, w, bias2)


def _dividing_tile(dim: int, want: Optional[int]) -> int:
    """The largest tile <= ``want`` that divides ``dim``, preferring
    128-multiples (the Mosaic lane rule), else any divisor — never
    escalating toward full residency (that is the exact failure the
    VMEM plan exists to prevent)."""
    t = min(want or dim, dim)
    if dim % t == 0:
        return t
    cand = (t // 128) * 128
    while cand >= 128 and dim % cand:
        cand -= 128
    if cand >= 128:
        return cand
    while dim % t:
        t -= 1
    return t


# ========================================================== slab attention

def _slab_attn_kernel(pos_ref, q_ref, kn_ref, vn_ref, cos_ref, sin_ref,
                      rot_ref, k_any, v_any,
                      attn_ref, ko_any, vo_any,
                      knew_sc, vnew_sc, kbuf, vbuf, rsem, wsem, *,
                      S, rep, bk, ck, scale, use_rope):
    """One slot's decode attention over ALL its (local) kv heads.

    The slabs are ``[B, S, KH, Dh]`` with ``(KH, Dh)`` the tiled minor
    dims, so the only slab windows a DMA can address are whole-head
    rows ``[rows, KH, Dh]`` — a single kv head is not sliceable.  The
    kernel therefore streams ``[bk, KH, Dh]`` tiles and runs the
    online-softmax recurrence for every head at once on the VPU:
    scores are a lane reduction of ``K * q`` (sq=1 leaves the MXU one
    row per head anyway), the value sum a leading-dim reduction of
    ``p * V``."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    dims = (((1,), (0,)), ((), ()))

    kx = kn_ref[0].astype(jnp.float32)                      # [KH, Dh]
    vx = vn_ref[0].astype(jnp.float32)
    qs = [q_ref[0, r].astype(jnp.float32) for r in range(rep)]
    if use_rope:
        c = cos_ref[0].astype(jnp.float32)                  # [1, Dh]
        s = sin_ref[0].astype(jnp.float32)
        rot = rot_ref[...]

        def rope(t):
            return t * c + jax.lax.dot_general(
                t, rot, dims, preferred_element_type=jnp.float32) * s
        kx = rope(kx)
        qs = [rope(q) for q in qs]
    qs = [(q * scale)[None] for q in qs]                    # [1, KH, Dh]

    # ---- in-kernel KV append: DMA the fresh row into the slot slab at
    # this slot's position (clamped exactly like dynamic_update_slice —
    # a full slot overwrites its last row, matching the unfused path)
    posw = jnp.minimum(pos, S - 1)
    knew_sc[...] = kx[None].astype(knew_sc.dtype)
    vnew_sc[...] = vx[None].astype(vnew_sc.dtype)
    kw_cp, vw_cp = slab_row_writes(knew_sc, vnew_sc, ko_any, vo_any, wsem,
                                   b=b, row0=posw)
    kw_cp.start()
    vw_cp.start()

    # ---- stream the live prefix once (decode_attention's core, shared
    # with the unfused path's in-place kernel): valid is kpos < posw
    state = stream_slab_attention(
        k_any, v_any, kbuf, vbuf, rsem, b=b, heads=slice(None), qs=qs,
        lims=[posw] * rep, n_rows=posw, bk=bk, ck=ck)

    # ---- the fresh token folds in last, always valid (it reads its own
    # STORED k/v so storage-dtype rounding matches the unfused path)
    kq = knew_sc[...].astype(jnp.float32)                   # [1, KH, Dh]
    vq = vnew_sc[...].astype(jnp.float32)
    for r, (q, st) in enumerate(zip(qs, state)):
        s_new = jnp.sum(kq * q, axis=-1, keepdims=True)     # [1, KH, 1]
        _, l, acc = online_softmax_update(st, s_new, vq)
        attn_ref[0, r] = (acc / l)[0].astype(attn_ref.dtype)
    kw_cp.wait()
    vw_cp.wait()


def slab_decode_attention(q, k_new, v_new, k_slab, v_slab, seq_pos, *,
                          scale: Optional[float] = None,
                          rope_cos=None, rope_sin=None,
                          block_k: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Rotary -> in-kernel KV append -> streaming decode attention over
    the slot slabs, for every kv head the slabs hold (all of them at
    tp=1, this device's group under tensor parallelism).

    q [B, KH, rep, Dh], k_new/v_new [B, KH, Dh] the fresh projections
    (pre-rotary); k_slab/v_slab [B, S, KH, Dh] (updated IN PLACE via
    kernel aliasing); seq_pos [B] int32 cache lengths BEFORE this
    token; rope_cos/rope_sin [B, Dh] full-width tables (halves
    duplicated) or None.  Returns ``(attn [B, KH, rep, Dh] in the slab
    dtype, k_slab', v_slab')``."""
    b, kh, rep, dh = q.shape
    s_max = k_slab.shape[1]
    assert k_slab.shape[2:] == (kh, dh), (k_slab.shape, q.shape)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    # scalar seq_pos (single-request decode_step caches) broadcasts to
    # the per-slot vector the kernel grid indexes by
    pos1 = jnp.asarray(seq_pos, jnp.int32)
    if pos1.ndim == 0:
        pos1 = jnp.broadcast_to(pos1, (b,))
    bk, ck = slab_tiles(s_max, block_k or 1024)
    use_rope = rope_cos is not None
    if use_rope:
        cosf = rope_cos.reshape(b, 1, dh)
        sinf = rope_sin.reshape(b, 1, dh)
        rot = _rotate_half_matrix(dh)
    else:
        cosf = jnp.ones((b, 1, dh), jnp.float32)
        sinf = jnp.zeros((b, 1, dh), jnp.float32)
        rot = jnp.zeros((dh, dh), jnp.float32)
    # query heads r-major so the kernel picks "query head r of every kv
    # head" by a leading index: [B, rep, KH, Dh]
    qr = jnp.swapaxes(q, 1, 2)

    kernel = functools.partial(_slab_attn_kernel, S=s_max, rep=rep, bk=bk,
                               ck=ck, scale=scale, use_rope=use_rope)
    # seq_pos rides as a scalar-prefetch operand: the whole [B] vector
    # lands in SMEM before the grid starts and each program reads its
    # slot's entry (a (1,) SMEM block per program is not lowerable)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, rep, kh, dh), lambda bi, pos: (bi, 0, 0, 0)),
            pl.BlockSpec((1, kh, dh), lambda bi, pos: (bi, 0, 0)),
            pl.BlockSpec((1, kh, dh), lambda bi, pos: (bi, 0, 0)),
            pl.BlockSpec((1, 1, dh), lambda bi, pos: (bi, 0, 0)),
            pl.BlockSpec((1, 1, dh), lambda bi, pos: (bi, 0, 0)),
            pl.BlockSpec((dh, dh), lambda bi, pos: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, rep, kh, dh), lambda bi, pos: (bi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, kh, dh), k_slab.dtype),
            pltpu.VMEM((1, kh, dh), v_slab.dtype),
            pltpu.VMEM((2, bk, kh, dh), k_slab.dtype),
            pltpu.VMEM((2, bk, kh, dh), v_slab.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    attn, k2, v2 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, rep, kh, dh), k_slab.dtype),
            jax.ShapeDtypeStruct(k_slab.shape, k_slab.dtype),
            jax.ShapeDtypeStruct(v_slab.shape, v_slab.dtype),
        ],
        # operand indices count the scalar-prefetch argument
        input_output_aliases={7: 1, 8: 2},
        compiler_params=_grid_params(interpret),
        name="slab_decode_attention",
        interpret=interpret,
    )(pos1, qr, k_new, v_new, cosf, sinf, rot, k_slab, v_slab)
    return jnp.swapaxes(attn, 1, 2), k2, v2


def decode_block_attn(x, k_slab, v_slab, seq_pos, norm_w, norm_b,
                      wq, wk, wv, bq=None, bkv=None, bv=None, *,
                      kv_heads: int, head_dim: int, norm: str = "layer",
                      eps: float = 1e-5, scale: Optional[float] = None,
                      rope_cos=None, rope_sin=None,
                      block_k: Optional[int] = None,
                      block_n: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """Fused norm -> QKV -> in-kernel KV append -> streaming decode
    attention over the slot slabs.

    x [B, 1, D]; k_slab/v_slab [B, S, KH, Dh] (the ``KVPool`` slabs,
    updated IN PLACE via kernel aliasing); seq_pos [B] int32 cache
    lengths BEFORE this token; wq [D, H*Dh], wk/wv [D, KH*Dh];
    rope_cos/rope_sin [B, Dh] full-width tables (halves duplicated) or
    None.  Returns ``(attn [B, 1, H*Dh], k_slab', v_slab')`` — attn is
    the pre-out-projection head concat, fed to
    :func:`decode_block_mlp`."""
    b, sq, d = x.shape
    if sq != 1:
        raise ValueError(f"decode_block_attn is a decode kernel (sq=1), "
                         f"got sq={sq}")
    kh_, dh = k_slab.shape[2], k_slab.shape[3]
    assert kh_ == kv_heads and dh == head_dim
    heads = wq.shape[1] // head_dim
    rep = heads // kv_heads
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    x2 = x[:, 0]
    nw = norm_w.reshape(1, d)
    nb = norm_b.reshape(1, d) if norm == "layer" else jnp.zeros_like(nw)
    # each bias is independently optional (the reference applies them
    # independently too); the three projections share one kernel — the
    # norm is recomputed per call, a [B, D] VPU pass
    proj = functools.partial(_norm_proj, x2, nw, nb, norm=norm, eps=eps,
                             block_n=block_n, interpret=interpret)
    q = proj(wq, bq).reshape(b, kv_heads, rep, dh)
    kx = proj(wk, bkv).reshape(b, kv_heads, dh)
    vx = proj(wv, bv).reshape(b, kv_heads, dh)
    attn, k2, v2 = slab_decode_attention(
        q, kx, vx, k_slab, v_slab, seq_pos, scale=scale,
        rope_cos=rope_cos, rope_sin=rope_sin, block_k=block_k,
        interpret=interpret)
    return attn.reshape(b, 1, heads * dh).astype(x.dtype), k2, v2


# ============================================================= proj+MLP block

def _mlp_kernel(x_ref, attn_ref, wo_ref, bo_ref, n2w_ref, n2b_ref,
                w1_ref, b1_ref, wg_ref, w2_ref, b2_ref, o_ref,
                xmid_sc, h_sc, acc_sc, *,
                no, nf, eps, norm, act, has_bias, gated):
    """Grid ``(no + nf,)``: the first ``no`` steps accumulate the
    out-projection over contraction-row tiles of ``wo`` (which streams
    like every other weight — a resident copy would be double-buffered
    by the grid pipeline), the remaining ``nf`` steps stream the MLP
    tiles.  Dots run in the weight dtype with f32 accumulation."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(i < no)
    def _proj():
        acc_sc[...] = acc_sc[...] + _mxu_dot(attn_ref[...], wo_ref[...])

    @pl.when(i == no - 1)
    def _mid():
        xm = x_ref[...].astype(jnp.float32) + acc_sc[...]
        if has_bias:
            xm = xm + bo_ref[...].astype(jnp.float32)
        xmid_sc[...] = xm
        n2b = n2b_ref[...].astype(jnp.float32) if norm == "layer" else None
        h_sc[...] = _norm_f32(xm, n2w_ref[...].astype(jnp.float32), n2b,
                              norm, eps).astype(h_sc.dtype)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(i >= no)
    def _mlp():
        h = h_sc[...]
        t = _mxu_dot(h, w1_ref[...])
        if has_bias:
            t = t + b1_ref[...].astype(jnp.float32)
        if gated:
            a = jax.nn.silu(_mxu_dot(h, wg_ref[...])) * t
        elif act == "gelu_tanh":
            a = jax.nn.gelu(t, approximate=True)
        else:
            a = jax.nn.gelu(t, approximate=False)
        acc_sc[...] = acc_sc[...] + _mxu_dot(a.astype(w2_ref.dtype),
                                             w2_ref[...])

    @pl.when(i == no + nf - 1)
    def _emit():
        y = xmid_sc[...] + acc_sc[...]
        if has_bias:
            y = y + b2_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


def decode_block_mlp(x, attn, wo, bo, norm_w, norm_b, w1, b1, w2, b2,
                     w_gate=None, *, norm: str = "layer",
                     eps: float = 1e-5, act: str = "gelu_tanh",
                     block_f: Optional[int] = None,
                     block_o: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Fused out-projection (+residual) -> norm2 -> MLP (+residual).

    x [B, 1, D] is the layer input (the residual stream); attn is
    :func:`decode_block_attn`'s output.  ``w_gate`` switches the MLP to
    SwiGLU (``down(silu(gate)*up)`` with w1=up, w2=down).  The [B, D]
    activation stays in VMEM scratch from the out-projection to the
    final residual; every weight streams tile-by-tile."""
    b, sq, d = x.shape
    hd = attn.shape[-1]
    ffn = w1.shape[1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    gated = w_gate is not None
    has_bias = bo is not None or b1 is not None or b2 is not None
    bf = _dividing_tile(ffn, block_f)
    bo_t = _dividing_tile(hd, block_o)
    nf, no = ffn // bf, hd // bo_t
    # each bias independently optional, matching the reference's
    # per-bias application; absent ones ride as zeros.  Row operands
    # ride as [1, n] (Mosaic refuses rank-1 blocks)
    row = lambda v, n: (v if v is not None
                        else jnp.zeros((n,), x.dtype)).reshape(1, n)
    n2w = norm_w.reshape(1, d)
    n2b = norm_b.reshape(1, d) if norm == "layer" else jnp.zeros_like(n2w)
    # index maps clamp each operand to its own phase: the pipeline
    # re-fetches a block only when its index changes, so wo tiles are
    # not re-read during the MLP phase nor MLP tiles during the
    # out-projection
    o_idx = lambda i: jnp.minimum(i, no - 1)
    f_idx = lambda i: jnp.maximum(i - no, 0)
    if gated:
        wg = w_gate
        wg_spec = pl.BlockSpec((d, bf), lambda i: (0, f_idx(i)))
    else:
        # the kernel body never reads wg when not gated, but the grid
        # pipeline DMAs every spec'd block regardless — a one-tile
        # placeholder with a CONSTANT index map keeps the dead operand
        # from re-streaming the full [D, ffn] up-projection each step
        wg = jnp.zeros((d, bf), x.dtype)
        wg_spec = pl.BlockSpec((d, bf), lambda i: (0, 0))

    kernel = functools.partial(
        _mlp_kernel, no=no, nf=nf, eps=float(eps), norm=norm, act=act,
        has_bias=has_bias, gated=gated)
    out = pl.pallas_call(
        kernel,
        grid=(no + nf,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((b, bo_t), lambda i: (0, o_idx(i))),
            pl.BlockSpec((bo_t, d), lambda i: (o_idx(i), 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, bf), lambda i: (0, f_idx(i))),
            pl.BlockSpec((1, bf), lambda i: (0, f_idx(i))),
            wg_spec,
            pl.BlockSpec((bf, d), lambda i: (f_idx(i), 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((b, d), jnp.float32),
            pltpu.VMEM((b, d), w1.dtype),
            pltpu.VMEM((b, d), jnp.float32),
        ],
        compiler_params=_grid_params(interpret),
        name="decode_block_mlp",
        interpret=interpret,
    )(x[:, 0], attn[:, 0].astype(wo.dtype), wo, row(bo, d), n2w, n2b,
      w1, row(b1, ffn), wg, w2, row(b2, d))
    return out[:, None]


# ============================================================== layer wrapper

def decode_block_layer(x, k_slab, v_slab, seq_pos, *, kv_heads, head_dim,
                       norm, eps1, eps2, norm1_w, norm1_b, wq, wk, wv,
                       bq, bkv, bv, wo, bo, norm2_w, norm2_b,
                       w1, b1, w2, b2, w_gate=None, act="gelu_tanh",
                       rope_cos=None, rope_sin=None,
                       block_k=None, block_f=None, interpret=None):
    """One full transformer layer decode step through the fused
    kernels.  Returns ``(y [B, 1, D], k_slab', v_slab')`` with the
    slabs updated in place (kernel aliasing) at each slot's ``seq_pos``.

    Tiles come from :func:`plan_decode_block` at THIS call's shapes —
    the budgeted tiles, not the kernels' untiled defaults — so every
    caller of the layer wrapper (models' ``fused_decode_step``, the
    engine's decode program) launches exactly the working set
    the legality check approved; ``block_k``/``block_f`` override the
    plan's choice.  Raises if no tiling fits: callers are contracted
    to gate on :func:`fusion_legal` / ``fused_decode_supported``
    first, so reaching the raise means the gate was skipped."""
    plan, why = plan_decode_block(
        max_seq=k_slab.shape[1], hidden=x.shape[-1],
        heads=wq.shape[1] // head_dim, kv_heads=kv_heads,
        head_dim=head_dim, ffn=w1.shape[1], batch=x.shape[0],
        itemsize=jnp.dtype(x.dtype).itemsize, gated=w_gate is not None)
    if plan is None:
        raise ValueError(
            f"decode_block_layer: no VMEM tiling fits this shape "
            f"({why}) — gate on fusion_legal/fused_decode_supported "
            f"before calling the fused path")
    # the two kernels are the layer's two parts (obs/parts.py): each
    # holds its branch's norm, and the second the attention's out-proj
    with jax.named_scope("attention"):
        attn, k2, v2 = decode_block_attn(
            x, k_slab, v_slab, seq_pos, norm1_w, norm1_b, wq, wk, wv,
            bq, bkv, bv, kv_heads=kv_heads, head_dim=head_dim, norm=norm,
            eps=eps1, rope_cos=rope_cos, rope_sin=rope_sin,
            block_k=block_k or plan["block_k"], block_n=plan["block_n"],
            interpret=interpret)
    with jax.named_scope("mlp"):
        y = decode_block_mlp(
            x, attn, wo, bo, norm2_w, norm2_b, w1, b1, w2, b2, w_gate,
            norm=norm, eps=eps2, act=act,
            block_f=block_f or plan["block_f"], block_o=plan["block_o"],
            interpret=interpret)
    return y, k2, v2


def decode_block_reference(x, k_slab, v_slab, seq_pos, *, kv_heads,
                           head_dim, norm, eps1, eps2, norm1_w, norm1_b,
                           wq, wk, wv, bq, bkv, bv, wo, bo, norm2_w,
                           norm2_b, w1, b1, w2, b2, w_gate=None,
                           act="gelu_tanh", rope_cos=None, rope_sin=None):
    """Composed-op XLA form with EXACTLY the kernel's masking semantics
    and rounding — f32 norm / softmax / accumulation, every dot operand
    in the weight dtype (the normed rows, the attention output and the
    MLP activation round to it first, as the models' unfused layer does)
    — the parity oracle for tests, mirroring how the unfused path
    composes append_kv + decode_attention_auto (same math, op by op)."""
    from ..models.kv_cache import append_kv
    from .decode_attention import decode_attention_reference
    b, sq, d = x.shape
    heads = wq.shape[1] // head_dim
    dt = jnp.float32

    def mm(a, w):
        return a.astype(w.dtype).astype(dt) @ w.astype(dt)

    xr = x.astype(dt)
    xn = _norm_f32(xr, norm1_w.astype(dt),
                   norm1_b.astype(dt) if norm == "layer" else None,
                   norm, eps1)
    q = mm(xn, wq).reshape(b, 1, heads, head_dim)
    kx = mm(xn, wk).reshape(b, 1, kv_heads, head_dim)
    vx = mm(xn, wv).reshape(b, 1, kv_heads, head_dim)
    if bq is not None:
        q = q + bq.astype(dt).reshape(heads, head_dim)
    if bkv is not None:
        kx = kx + bkv.astype(dt).reshape(kv_heads, head_dim)
    if bv is not None:
        vx = vx + bv.astype(dt).reshape(kv_heads, head_dim)
    if rope_cos is not None:
        c = rope_cos.astype(dt)[:, None, None, :]
        s = rope_sin.astype(dt)[:, None, None, :]
        rot = _rotate_half_matrix(head_dim)
        q = q * c + (q @ rot) * s
        kx = kx * c + (kx @ rot) * s
    pos = jnp.asarray(seq_pos, jnp.int32)
    k2, v2 = append_kv(k_slab, v_slab, kx.astype(k_slab.dtype),
                       vx.astype(v_slab.dtype), pos)
    lens = pos + 1
    out = decode_attention_reference(q, k2, v2, lens)
    attn = out.reshape(b, 1, heads * head_dim).astype(k_slab.dtype)
    xm = xr + mm(attn, wo)
    if bo is not None:
        xm = xm + bo.astype(dt)
    h = _norm_f32(xm, norm2_w.astype(dt),
                  norm2_b.astype(dt) if norm == "layer" else None,
                  norm, eps2)
    t = mm(h, w1)
    if b1 is not None:
        t = t + b1.astype(dt)
    if w_gate is not None:
        a = jax.nn.silu(mm(h, w_gate)) * t
    else:
        a = jax.nn.gelu(t, approximate=act == "gelu_tanh")
    y = xm + mm(a, w2)
    if b2 is not None:
        y = y + b2.astype(dt)
    return y.astype(x.dtype), k2, v2
