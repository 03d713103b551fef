"""Sharded decode-block megakernel: the fused transformer-layer decode
step of ``kernels/decode_block.py``, re-partitioned over a 1-D
tensor-parallel mesh with the TP collectives riding the kernels.

ClusterFusion++ and the fused computation-collective work (PAPERS.md)
both locate multi-chip decode latency at BLOCK-level fusion *across the
interconnect*: the per-op path pays one serialized collective plus one
HBM round-trip at every TP boundary of the layer.  This module makes
the PR 7 megakernel and the PR 9 collective-fusion program multiply
instead of exclude each other (ROADMAP direction 2's sharded variant):

  * **entry** — the residual stream arrives slot-sharded ``[B/tp, D]``;
    :func:`ring_entry_matmul` lowers ``collective_matmul``'s all-gather
    ring INTO the Pallas grid: each hop's dot runs as a tile-streamed
    Pallas program over the weight shard already held while the
    ``ppermute`` forwards the travelling activation shard (the hop's
    permute and the hop's grid both consume the same buffer and neither
    consumes the other, so XLA overlaps them — the SAME schedule as
    ``allgather_matmul``, shared via ``collective_matmul.ring_schedule``
    so the XLA and in-kernel rings cannot drift).
  * **attention** — :func:`decode_block_attn_tp` is the per-shard
    attention block: ``decode_block.slab_decode_attention`` over the
    LOCAL kv-head group — matrix-form rotary, the fresh K/V row DMA'd
    **in-kernel** into the LOCAL kv-head slab shard at the slot's
    ``seq_pos`` (the ``serving/kv_pool`` slabs partition on the kv-head
    axis, so each device appends exactly its own head rows), then the
    double-buffered online-softmax streaming over the live slab tiles.
  * **exit** — :func:`ring_exit_matmul` lowers the reduce-scatter ring:
    each hop's partial (out-proj / MLP-down) accumulates tile-by-tile
    in the grid's f32 scratch — with the MLP activation (GeLU / SwiGLU
    gate) fused into the tile read, so ``act(up)`` never materializes
    in HBM — while the travelling accumulator ppermutes; hop *i*'s dot
    is data-independent of hop *i-1*'s permute, exactly the
    ``matmul_reduce_scatter`` schedule.

The ring hops themselves stay ``jax.lax.ppermute`` at the shard_map
level on the current jax pin: Pallas TPU remote-DMA collectives
(``make_async_remote_copy`` rings) can replace them without touching
the tile kernels once the pin moves — the seam is exactly the two
``ppermute`` call sites in the ring drivers below, which is why the
per-hop compute is packaged as one Pallas program per hop rather than
fused across hops.

VMEM budgeting (:func:`plan_decode_block_tp`): the per-shard working
set — weights/tp plus the ring tile buffers — must fit the same 12 MiB
budget as the tp=1 plan; the kv streaming tile ``block_k`` and the four
matmul tile sizes shrink until it does, and the plan refuses (composed
``tp_fused`` / GSPMD fallback, see ``decode_block.resolve_fused_decode``)
when the irreducible residents cannot fit.

CPU tier-1 runs these kernels under ``interpret=True`` inside the same
shard_map program over the virtual-device mesh, including the aliased
in-kernel append into the sharded slabs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .collective_matmul import ring_schedule
from .decode_block import VMEM_BUDGET, _dividing_tile, _fit_tile, \
    _grid_params, _mxu_dot, _norm_f32, _plan_slab_attention, \
    slab_decode_attention

__all__ = ["plan_decode_block_tp", "ring_entry_matmul",
           "ring_exit_matmul", "decode_block_attn_tp",
           "tp_fused_block_layer"]

# graftmem marker (tools/analysis/memory.py): the memory-budget rule
# re-derives this plan's working set and checks it against the budget
# imported from decode_block (resolved statically through the import)
__vmem_plans__ = ("plan_decode_block_tp",)

# graftcomm seam marker (tools/analysis/comm.py): these Pallas ring
# drivers share seam roles with the composed XLA drivers in
# kernels/collective_matmul.py — the collective-order rule proves the
# two lowerings issue hop-equivalent ppermute schedules, so either can
# take the remote-DMA swap-in (ROADMAP direction 4)
__remote_dma_seams__ = {
    "ring_entry_matmul": {
        "role": "entry",
        "payload": "num_slots // tp * hidden * itemsize"},
    "ring_exit_matmul": {
        "role": "exit",
        "payload": "num_slots // tp * hidden * itemsize"},
}


# ======================================================== planning / legality

def plan_decode_block_tp(*, max_seq: int, hidden: int, heads: int,
                         kv_heads: int, head_dim: int, ffn: int,
                         batch: int, itemsize: int, tp: int,
                         gated: bool = False,
                         vmem_budget: int = VMEM_BUDGET):
    """Per-shard VMEM plan for the sharded decode block at degree
    ``tp``: the attention kernel's kv streaming tile plus one tile size
    per ring matmul seam (QKV entry, out-proj exit, MLP-up entry,
    MLP-down exit).  Divisibility (kv_heads/ffn/batch over tp) is
    checked by ``decode_block.fusion_legal`` BEFORE this runs.  Returns
    ``(plan_dict, None)`` or ``(None, reason)`` — same contract as
    ``decode_block.plan_decode_block``."""
    rep = heads // kv_heads
    dh = head_dim
    h_l = heads // tp
    kh_l = kv_heads // tp
    f_l = ffn // tp
    b_l = batch // tp
    qkv_l = (h_l + 2 * kh_l) * dh
    up_l = f_l * (2 if gated else 1)

    # ---- per-shard slab attention kernel (grid (B,)) over the LOCAL
    # kv-head group: the same kernel and accounting as tp=1
    bk, vmem_attn = _plan_slab_attention(max_seq, kh_l, rep, dh, itemsize,
                                         vmem_budget)
    if bk is None:
        return None, (f"vmem: tp attention residents {vmem_attn} bytes "
                      f"exceed budget {vmem_budget} even at block_k=8")

    # ---- entry ring hop kernels: the [B/tp, D] travelling shard stays
    # resident while weight/bias/output tiles stream double-buffered
    entry_fixed = b_l * hidden * (itemsize + 4)      # shard + f32 work
    entry_unit = 2 * (hidden + b_l + 1) * itemsize   # w + out + bias tile
    block_qkv = _fit_tile(qkv_l, entry_unit, entry_fixed, vmem_budget)
    if block_qkv is None:
        return None, (f"vmem: tp entry residents {entry_fixed} + weight "
                      f"tiles exceed budget {vmem_budget} at any tile of "
                      f"the per-device QKV width {qkv_l}")
    block_up = _fit_tile(up_l, entry_unit, entry_fixed, vmem_budget)
    if block_up is None:
        return None, (f"vmem: tp entry residents {entry_fixed} + weight "
                      f"tiles exceed budget {vmem_budget} at any tile of "
                      f"the per-device MLP-up width {up_l}")

    # ---- exit ring hop kernels: f32 accumulator + output chunk stay
    # resident; contraction-row weight tiles and activation tiles (two
    # for the fused SwiGLU gate) stream
    exit_fixed = b_l * hidden * (4 + itemsize)       # acc scratch + out
    exit_unit = 2 * (hidden + b_l) * itemsize        # w + act tile
    block_o = _fit_tile(h_l * dh, exit_unit, exit_fixed, vmem_budget)
    if block_o is None:
        return None, (f"vmem: tp exit residents {exit_fixed} + tiles "
                      f"exceed budget {vmem_budget} at any tile of the "
                      f"per-device out-proj rows {h_l * dh}")
    down_unit = exit_unit + 2 * b_l * itemsize * (1 if gated else 0)
    block_down = _fit_tile(f_l, down_unit, exit_fixed, vmem_budget)
    if block_down is None:
        return None, (f"vmem: tp exit residents {exit_fixed} + tiles "
                      f"exceed budget {vmem_budget} at any tile of the "
                      f"per-device MLP-down rows {f_l}")
    return {"block_k": bk, "block_qkv": block_qkv, "block_up": block_up,
            "block_o": block_o, "block_down": block_down,
            "vmem_attn": vmem_attn,
            "vmem_entry": entry_fixed
            + entry_unit * max(block_qkv, block_up),
            "vmem_exit": exit_fixed
            + max(exit_unit * block_o, down_unit * block_down)}, None


# ========================================================== entry ring kernel

def _entry_kernel(x_ref, w_ref, b_ref, o_ref):
    """One output tile of a ring hop's dot: the resident travelling
    shard against one streamed weight column tile (+ its bias tile),
    operands in the storage dtype, f32 accumulation."""
    o_ref[...] = (_mxu_dot(x_ref[...], w_ref[...])
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def ring_entry_matmul(h, w_l, bias_l, axis_name: str, tp: int, *,
                      block_n: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """``concat_all_devices(h) @ w_l (+ bias_l)`` with the all-gather
    riding the Pallas tile dots — the sharded decode block's entry seam.

    ``h [B_l, K]`` is this device's slot shard of the (already normed)
    activation; ``w_l [K, N_l]`` / ``bias_l [N_l]`` the local column
    shard.  Returns ``[B_l * tp, N_l]``.  Each ring hop launches ONE
    Pallas grid streaming ``[K, block_n]`` weight tiles against the
    shard currently held while the ppermute forwards that shard to the
    neighbour (``collective_matmul.ring_schedule`` — the hop's permute
    and the hop's grid are data-independent).  The two ``ppermute``
    lines below are the seam where Pallas remote-DMA collectives swap
    in when the jax pin moves."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b_loc, k = h.shape
    n_l = w_l.shape[1]
    # the bias rides as a [1, N_l] row (Mosaic refuses rank-1 blocks)
    bias = (bias_l if bias_l is not None
            else jnp.zeros((n_l,), h.dtype)).reshape(1, n_l)
    bn = _dividing_tile(n_l, block_n)
    hop_call = pl.pallas_call(
        _entry_kernel,
        grid=(n_l // bn,),
        in_specs=[
            pl.BlockSpec((b_loc, k), lambda i: (0, 0)),
            pl.BlockSpec((k, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((b_loc, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b_loc, n_l), h.dtype),
        compiler_params=_grid_params(interpret),
        name="ring_entry_matmul",
        interpret=interpret,
    )
    if tp == 1:
        return hop_call(h, w_l, bias)
    ring = ring_schedule(tp)
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((b_loc * tp, n_l), h.dtype)
    buf = h
    for hop in range(tp):
        # seam: the in-flight forward of the travelling shard (future
        # Pallas remote-DMA ring); independent of this hop's grid
        nxt = jax.lax.ppermute(buf, axis_name, ring.perm) \
            if hop < tp - 1 else None
        chunk = hop_call(buf, w_l, bias)
        out = jax.lax.dynamic_update_slice(
            out, chunk, (ring.entry_src(idx, hop) * b_loc, 0))
        buf = nxt
    return out


# =========================================================== exit ring kernel

def _exit_kernel(g_ref, y_ref, w_ref, o_ref, acc_sc, *, nk, act):
    """One contraction tile of a ring hop's partial: activation fused
    into the tile read (``act(up)`` never round-trips HBM), f32 scratch
    accumulation, emit on the last tile."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    t = y_ref[...].astype(jnp.float32)
    if act == "swiglu":
        t = jax.nn.silu(g_ref[...].astype(jnp.float32)) * t
    elif act == "gelu_tanh":
        t = jax.nn.gelu(t, approximate=True)
    elif act == "gelu":
        t = jax.nn.gelu(t, approximate=False)
    acc_sc[...] = acc_sc[...] + _mxu_dot(t.astype(w_ref.dtype), w_ref[...])

    @pl.when(i == nk - 1)
    def _emit():
        o_ref[...] = acc_sc[...].astype(o_ref.dtype)


def ring_exit_matmul(y, w_l, axis_name: str, tp: int, *,
                     act: str = "none",
                     block_f: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """``reduce_scatter_over_rows(act(y) @ w_l)`` with the reduction
    riding the Pallas tile dots — the sharded decode block's exit seam.

    ``y [B, K_l]`` holds every slot's rows against this device's
    contraction shard (for ``act="swiglu"``: ``[B, 2*K_l]`` with the
    per-device ``[gate | up]`` halves of the bundle layout); ``w_l
    [K_l, N]`` the row shard of the exit weight.  Returns ``[B//tp,
    N]``.  Each hop's partial runs as ONE Pallas grid (activation fused
    into the tile read, f32 scratch accumulation) while the travelling
    accumulator ppermutes — the add of the arriving accumulator stays
    OUTSIDE the kernel so the hop's grid never waits on the in-flight
    permute, exactly ``matmul_reduce_scatter``'s dataflow."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    gated = act == "swiglu"
    b = y.shape[0]
    k_l = y.shape[1] // (2 if gated else 1)
    n = w_l.shape[1]
    b_l = b // tp
    bf = _dividing_tile(k_l, block_f)
    nk = k_l // bf
    if gated:
        g_spec = pl.BlockSpec((b_l, bf), lambda i: (0, i))
        y_spec = pl.BlockSpec((b_l, bf), lambda i: (0, nk + i))
    else:
        # the kernel never reads the gate when not gated, but the grid
        # pipeline DMAs every spec'd block — a one-tile placeholder with
        # a constant index map keeps the dead operand free (the same
        # posture as decode_block_mlp's ungated wg)
        g_spec = pl.BlockSpec((b_l, bf), lambda i: (0, 0))
        y_spec = pl.BlockSpec((b_l, bf), lambda i: (0, i))
    kernel = functools.partial(_exit_kernel, nk=nk, act=act)
    hop_call = pl.pallas_call(
        kernel,
        grid=(nk,),
        in_specs=[
            g_spec,
            y_spec,
            pl.BlockSpec((bf, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((b_l, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b_l, n), y.dtype),
        scratch_shapes=[pltpu.VMEM((b_l, n), jnp.float32)],
        compiler_params=_grid_params(interpret),
        name="ring_exit_matmul",
        interpret=interpret,
    )

    def part_of(chunk):
        g = chunk if gated else jnp.zeros((b_l, bf), y.dtype)
        return hop_call(g, chunk, w_l)

    if tp == 1:
        return part_of(y)
    ring = ring_schedule(tp)
    idx = jax.lax.axis_index(axis_name)
    acc = None
    for hop in range(tp):
        chunk = jax.lax.dynamic_slice_in_dim(
            y, ring.exit_chunk(idx, hop) * b_l, b_l, axis=0)
        part = part_of(chunk)
        acc = part if acc is None else acc + part
        if hop < tp - 1:
            # seam: the travelling accumulator's forward (future Pallas
            # remote-DMA ring); independent of the NEXT hop's grid
            acc = jax.lax.ppermute(acc, axis_name, ring.perm)
    return acc


# ==================================================== per-shard attention

def decode_block_attn_tp(q, k, v, k_slab, v_slab, seq_pos, *,
                         kv_heads: int, head_dim: int,
                         scale: Optional[float] = None,
                         rope_cos=None, rope_sin=None,
                         block_k: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """Per-shard attention block: rotary -> in-kernel KV append into
    the LOCAL slab shard -> streaming decode attention over the local
    kv-head group — ``decode_block.slab_decode_attention`` on this
    device's slab shard (the ``serving/kv_pool`` slabs partition on the
    kv-head axis, so each device appends exactly its own head rows).

    ``q [B, H_l*Dh]`` / ``k``/``v [B, KH_l*Dh]`` are THIS device's head
    group's fresh projections (the entry ring's output, kv-head-grouped
    columns); ``k_slab``/``v_slab [B, S, KH_l, Dh]`` the local slab
    shards (updated IN PLACE via kernel aliasing); ``seq_pos [B]`` the
    cache lengths BEFORE this token.  ``kv_heads`` is the LOCAL count.
    Returns ``(attn [B, H_l*Dh], k_slab', v_slab')``."""
    b = q.shape[0]
    kh_l, dh = k_slab.shape[2], k_slab.shape[3]
    assert kh_l == kv_heads and dh == head_dim
    attn, k2, v2 = slab_decode_attention(
        q.reshape(b, kv_heads, -1, dh), k.reshape(b, kv_heads, dh),
        v.reshape(b, kv_heads, dh), k_slab, v_slab, seq_pos, scale=scale,
        rope_cos=rope_cos, rope_sin=rope_sin, block_k=block_k,
        interpret=interpret)
    return attn.reshape(b, -1).astype(q.dtype), k2, v2


# ============================================================== layer wrapper

def tp_fused_block_layer(x_s, pk, pv, seq_pos, blk, arch, rope_full,
                         axis_name: str, tp: int, plan,
                         interpret: Optional[bool] = None):
    """One transformer layer of the sharded fused decode program — a
    shard_map-body function mirroring ``serving/tp.py``'s composed
    ``_tp_layer`` dataflow with every seam lowered to the Pallas
    kernels: entry rings for QKV / MLP-up (norm local on the slot
    shard — fusing it into hop 0's grid would serialize the first
    permute behind the whole first dot), the per-shard attention block
    with its in-kernel append, exit rings for out-proj / MLP-down with
    the activation fused into the tile reads.

    ``x_s [B/tp, D]`` slot-sharded residual; ``pk``/``pv`` the local
    slab shards; ``blk``/``arch`` the ``tp_decode_weights`` bundle
    entries (already per-device inside the shard_map); ``rope_full``
    ``(cos [B, Dh], sin [B, Dh])`` full-width tables or None; ``plan``
    from :func:`plan_decode_block_tp`.  Returns ``(x_s', pk', pv')``."""
    dh = arch["head_dim"]
    h_l = arch["heads"] // tp
    kh_l = arch["kv_heads"] // tp
    norm, eps = arch["norm"], arch["eps"]

    def local_norm(x, w, bvec):
        nb = bvec.astype(jnp.float32) \
            if (norm == "layer" and bvec is not None) else None
        return _norm_f32(x.astype(jnp.float32), w.astype(jnp.float32),
                         nb, norm, eps).astype(x.dtype)

    h1 = local_norm(x_s, blk["n1w"], blk["n1b"])
    qkv = ring_entry_matmul(h1, blk["wqkv"], blk["bqkv"], axis_name, tp,
                            block_n=plan["block_qkv"],
                            interpret=interpret)
    q2 = qkv[:, :h_l * dh]
    k2 = qkv[:, h_l * dh:(h_l + kh_l) * dh]
    v2 = qkv[:, (h_l + kh_l) * dh:]
    cos, sin = rope_full if rope_full is not None else (None, None)
    attn, kb, vb = decode_block_attn_tp(
        q2, k2, v2, pk, pv, seq_pos, kv_heads=kh_l, head_dim=dh,
        rope_cos=cos, rope_sin=sin, block_k=plan["block_k"],
        interpret=interpret)
    o = ring_exit_matmul(attn, blk["wo"], axis_name, tp,
                         block_f=plan["block_o"], interpret=interpret)
    if blk["bo"] is not None:
        o = o + blk["bo"]
    x_s = x_s + o
    h2 = local_norm(x_s, blk["n2w"], blk["n2b"])
    up = ring_entry_matmul(h2, blk["wup"], blk["bup"], axis_name, tp,
                           block_n=plan["block_up"], interpret=interpret)
    d = ring_exit_matmul(up, blk["wdown"], axis_name, tp,
                         act=arch["act"], block_f=plan["block_down"],
                         interpret=interpret)
    if blk["bdown"] is not None:
        d = d + blk["bdown"]
    return x_s + d, kb, vb
