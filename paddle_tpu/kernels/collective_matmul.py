"""Fused computation-collective matmuls for tensor-parallel decode.

Reference: "Optimizing Distributed ML Communication with Fused
Computation-Collective Operations" (PAPERS.md) — the TP decode-latency
win is NOT a faster collective, it is a collective that RIDES the matmul
that produces/consumes it instead of serializing after it as a separate
HBM round-trip.  The same block-level-not-per-op lesson FlashFuser
taught for the decode megakernel (kernels/decode_block.py), applied to
the two TP boundaries of a transformer layer:

  * **entry** (``allgather_matmul``) — the residual stream arrives
    slot-sharded ``[B/tp, K]``; the QKV / MLP-up projection needs every
    slot against this device's column shard ``[K, N/tp]``.  Instead of
    ``all_gather -> dot`` we decompose into ``tp`` ring hops: at each
    hop the device multiplies the shard it currently holds while
    ``ppermute`` forwards that shard to its neighbour.  The dot and the
    ppermute have no data dependence on each other (both consume the
    hop's input), so XLA is free to overlap them — the gather rides the
    dot.
  * **exit** (``matmul_reduce_scatter``) — the out-projection / MLP-down
    dot produces per-device PARTIAL sums ``[B, N]`` that must be summed
    and re-scattered over slots.  Instead of ``dot -> psum_scatter`` we
    compute the partial for one destination chunk per ring hop and
    ``ppermute`` the travelling accumulator: hop i's dot is independent
    of hop i-1's ppermute, so the reduction rides the dots.

Both take ``overlap=False`` to run the textbook serialized form
(``all_gather``/``psum_scatter`` around one big dot) — that is the
parity oracle for the ring decomposition
(``tests/test_zz_tp_serving.py::test_collective_matmul_parity``).

These are shard_map-body functions: they MUST run inside a shard_map
binding ``axis_name`` (serving/tp.py owns that program).  ``tp`` is the
static axis size — callers pass it so the ring unrolls at trace time
(fixed shapes, fixed hop count: graftlint's recompile discipline).
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

__all__ = ["RingSchedule", "ring_schedule", "allgather_matmul",
           "matmul_reduce_scatter"]

# graftcomm seam marker: the ppermute call sites in these drivers ARE
# the remote-DMA swap-in seam (ROADMAP direction 4).  `payload` is the
# per-hop transfer as a graftmem byte formula — the travelling
# activation shard [num_slots/tp, hidden] for the entry ring and the
# travelling partial-sum accumulator chunk for the exit ring (same
# shape after the reduce-scatter decomposition).
__remote_dma_seams__ = {
    "allgather_matmul": {
        "role": "entry",
        "payload": "num_slots // tp * hidden * itemsize"},
    "matmul_reduce_scatter": {
        "role": "exit",
        "payload": "num_slots // tp * hidden * itemsize"},
}


class RingSchedule:
    """The ring decomposition's bookkeeping — perm table plus the
    per-hop shard/chunk index walk — as ONE shared object, so the XLA
    rings here and the Pallas decode-block rings
    (kernels/decode_block_tp.py) lower the SAME schedule and cannot
    drift.

    Forward ring: device ``d`` sends to ``d + 1 (mod tp)``.  After
    ``hop`` forward ppermutes a device holds the shard that ORIGINATED
    ``hop`` positions behind it (``entry_src``), and the travelling
    exit accumulator a device computes a partial for at ``hop`` is the
    chunk that finishes at this device after the remaining hops
    (``exit_chunk`` — the final hop lands on the device's OWN chunk).
    ``idx`` may be a traced ``axis_index`` or a host int (tests)."""

    def __init__(self, tp: int):
        if tp < 1:
            raise ValueError(f"ring needs tp >= 1, got {tp}")
        self.tp = tp
        self.perm: List[Tuple[int, int]] = \
            [(d, (d + 1) % tp) for d in range(tp)]

    def entry_src(self, idx, hop: int):
        """Origin device of the shard held at ``hop`` (the entry ring's
        output-row block): walks backwards around the ring."""
        return (idx - hop) % self.tp

    def exit_chunk(self, idx, hop: int):
        """Row chunk whose partial the exit ring computes at ``hop``:
        it finishes at ``idx`` after the remaining ``tp - 1 - hop``
        forward hops; the final hop is the local chunk itself."""
        return (idx - hop - 1) % self.tp


def ring_schedule(tp: int) -> RingSchedule:
    """The shared ring schedule for ``tp`` devices (see
    :class:`RingSchedule`)."""
    return RingSchedule(tp)


def allgather_matmul(x, w, axis_name: str, tp: int, *,
                     overlap: bool = True):
    """``concat_all_devices(x) @ w`` without materializing the gather as
    a separate serialized collective.

    ``x [B_local, K]`` is this device's slot shard of the activation;
    ``w [K, N_local]`` is this device's column shard of the weight.
    Returns ``[B_local * tp, N_local]`` — every slot's rows against the
    local columns.  ``overlap=True`` runs the ring decomposition (one
    ``[B_local, K] @ [K, N_local]`` dot per hop, ppermute in flight);
    ``overlap=False`` runs ``all_gather -> dot`` (the serialized
    baseline — each row's dot contracts the full K locally either way;
    a backend may round a ``[B_local, K]`` and a ``[B, K]`` dot apart)."""
    if tp == 1:
        return x @ w
    if not overlap:
        xa = jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
        return xa @ w
    ring = ring_schedule(tp)
    idx = jax.lax.axis_index(axis_name)
    b_local = x.shape[0]
    out = jnp.zeros((b_local * tp, w.shape[-1]),
                    jnp.result_type(x.dtype, w.dtype))
    buf = x
    for hop in range(tp):
        # the ppermute for hop+1 and this hop's dot both consume `buf`
        # and neither consumes the other: XLA may run them concurrently
        nxt = jax.lax.ppermute(buf, axis_name, ring.perm) \
            if hop < tp - 1 else None
        chunk = buf @ w
        # after `hop` forward hops this device holds the shard that
        # originated entry_src(idx, hop) positions back around the ring
        out = jax.lax.dynamic_update_slice(
            out, chunk, (ring.entry_src(idx, hop) * b_local, 0))
        buf = nxt
    return out


def matmul_reduce_scatter(x, w, axis_name: str, tp: int, *,
                          overlap: bool = True):
    """``reduce_scatter_over_rows(x @ w)`` with the reduction riding the
    dots.

    ``x [B, K_local]`` holds every slot's rows against this device's
    contraction shard (the attention / MLP-up output); ``w [K_local, N]``
    is the row shard of the exit weight.  The full product is the SUM
    over devices of ``x @ w``; device d keeps row chunk d.  Returns
    ``[B // tp, N]``.

    ``overlap=True``: ring decomposition — hop i computes the partial
    for the chunk arriving tp-1-i hops later and ppermutes the
    travelling accumulator; each hop's dot is independent of the
    in-flight ppermute.  ``overlap=False``: one dot then
    ``psum_scatter`` (serialized baseline).  The two forms reduce in
    different orders (ring chain vs tree), so they differ by float
    rounding ulps — the compare row reports the max-abs gap."""
    if tp == 1:
        return x @ w
    if not overlap:
        y = x @ w
        return jax.lax.psum_scatter(y, axis_name, scatter_dimension=0,
                                    tiled=True)
    ring = ring_schedule(tp)
    idx = jax.lax.axis_index(axis_name)
    b_local = x.shape[0] // tp
    acc = None
    for hop in range(tp):
        # chunk destined to finish at this device after the remaining
        # hops: walks d-1, d-2, ..., d (mod tp) — the final hop adds the
        # local partial for this device's OWN chunk
        chunk = ring.exit_chunk(idx, hop)
        part = jax.lax.dynamic_slice_in_dim(x, chunk * b_local, b_local,
                                            axis=0) @ w
        acc = part if acc is None else acc + part
        if hop < tp - 1:
            acc = jax.lax.ppermute(acc, axis_name, ring.perm)
    return acc
