"""The selective state-space recurrence (Mamba-1, Gu & Dao 2023) over a
chunk of positions, from a carried state:

    h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * u_t) (x) B_t
    y_t = sum_n C_t[n] * h_t[n, :]

``u, delta [b, W, D]`` (the convolved input and the softplus-ed step,
float32), ``A [N, D]`` (negative), ``B, C [b, W, N]``, ``h0 [b, N, D]``;
returns ``(y [b, W, D], h_W [b, N, D])``, everything float32.  The state
is held ``[d_state, d_inner]``: ``d_inner`` rides the lanes, so the 16
state rows of a channel tile are whole vregs and the slab pads nothing
(``[d_inner, 16]`` float32 would pad its minor 16 to 128 in HBM: 8 x the
bytes).  A position with ``delta == 0`` leaves the state as it was
(``exp(0) = 1``, nothing added): that is how a caller masks padding.

The decay couples a channel with a state row (``A`` is per ``(n, d)``),
so there is no matmul form of a block as for a scalar-per-head decay:
every form is elementwise work over ``W x N x D``, and they differ in how
often that tensor touches HBM.  Chosen by the operands' shapes
(:func:`scan_route`, never a flag but ``FLAGS_pallas_routing=never``):

  * ``pallas_chunk`` — one kernel keeps a ``[N, 8, 128]`` state tile in
    vregs, walks the positions in order and forms ``exp(delta A)`` on
    the fly: ``u, delta, y`` cross HBM once, ``[W, N, D]`` never exists.
    ``B`` and ``C`` ride in SMEM as scalars.  Needs ``D % 1024 == 0``.
  * ``sequential`` — a ``lax.scan`` over the positions, one step each:
    the definition (:func:`selective_scan_reference`), and what serves a
    shape the kernel cannot tile.
  * ``one_step`` — ``W == 1`` (decode): the update written directly.

Measured on one v5e in Jamba2-3B's 512-wide prefill program (26 mixer
layers; ``scripts/scan_form_cost.py``, my chip run, PR 32): the program
takes 22.5 ms with the kernel (0.14 ms a layer) and 30.6 ms with the
sequential scan (0.45 ms a layer: 512 steps of 0.9 us).  Two blocked XLA
forms were tried and are gone: an outer scan over 64-position blocks
with ``lax.associative_scan`` inside took 51.2 ms (each block's
``[64, 16, 5120]`` pair crosses HBM at every level) and one over
16-position blocks with the steps written out 61.2 ms; both lost to the
plain scan they were meant to beat.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan", "selective_scan_reference", "scan_route",
           "SCAN_FORMS"]

# positions one grid step of the kernel walks: its u / delta / y tiles
# are CHUNK x 8 x 128 float32 = 0.5 MB each, double-buffered
PALLAS_CHUNK = 128
_TILE = 8 * 128                 # channels one program holds: one vreg a row

SCAN_FORMS = ("pallas_chunk", "sequential", "one_step")


def scan_route(width: int, d_inner: int, form: Optional[str] = None):
    """``(route, reason)`` of the recurrence over ``width`` positions of
    ``d_inner`` channels: static per compiled program.  ``form`` forces
    a route (tests, the chip timing script); None takes the measured
    default: the kernel where its tiling holds, else the sequential
    scan, with the reason it is not the kernel."""
    if form is not None:
        if form not in SCAN_FORMS:
            raise ValueError(f"scan form {form!r} not in {SCAN_FORMS}")
        return form, "forced"
    if width == 1:
        return "one_step", None
    from ..core.flags import flags
    if getattr(flags, "pallas_routing", "auto") == "never":
        return "sequential", "FLAGS_pallas_routing=never"
    if d_inner % _TILE:
        return "sequential", (
            f"d_inner {d_inner} is not a multiple of {_TILE} channels "
            f"(the kernel's 8 x 128 state tile)")
    return "pallas_chunk", None


# ------------------------------------------------------------- XLA forms
def selective_scan_reference(u, delta, A, B, C, h0):
    """Position by position: the definition."""
    def step(h, xs):
        u_t, d_t, b_t, c_t = xs                 # [b, D], [b, D], [b, N] x 2
        h = jnp.exp(d_t[:, None, :] * A) * h \
            + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.einsum("bnd,bn->bd", h, c_t)
    t_major = [jnp.moveaxis(a, 1, 0) for a in (u, delta, B, C)]
    h, ys = jax.lax.scan(step, h0, t_major)
    return jnp.moveaxis(ys, 0, 1), h


def _one_step(u, delta, A, B, C, h0):
    d = delta[:, 0]
    h = jnp.exp(d[:, None, :] * A) * h0 \
        + (d * u[:, 0])[:, None, :] * B[:, 0, :, None]
    return jnp.einsum("bnd,bn->bd", h, C[:, 0])[:, None], h


# ------------------------------------------------------------ the kernel
def _scan_kernel(b_ref, c_ref, u_ref, d_ref, a_ref, h0_ref, y_ref, h_ref,
                 *, chunk, n_state, width):
    """One ``[N, 8, 128]`` channel tile over ``chunk`` positions.  Grid
    ``(batch, channel tiles, chunks)``, the chunks innermost and in
    order: the output state block keeps its index along them, so it
    stays in VMEM and carries the state from chunk to chunk."""
    bi, wi = pl.program_id(0), pl.program_id(2)

    @pl.when(wi == 0)
    def _load():
        h_ref[...] = h0_ref[...]

    base = (bi * width + wi * chunk) * n_state
    a = [a_ref[n, 0] for n in range(n_state)]

    def step(t, hs):
        d = d_ref[0, t, 0]                          # [8, 128]
        du = d * u_ref[0, t, 0]
        row = base + t * n_state
        y = jnp.zeros_like(d)
        out = []
        for n in range(n_state):
            h = jnp.exp(d * a[n]) * hs[n] + du * b_ref[row + n]
            y = y + h * c_ref[row + n]
            out.append(h)
        y_ref[0, t, 0] = y
        return tuple(out)

    hs = jax.lax.fori_loop(
        0, chunk, step, tuple(h_ref[0, n, 0] for n in range(n_state)))
    for n in range(n_state):
        h_ref[0, n, 0] = hs[n]


def _pallas_chunk(u, delta, A, B, C, h0, interpret):
    b, w, d = u.shape
    n = A.shape[0]
    chunk = min(PALLAS_CHUNK, w)
    tiles = d // _TILE

    def tiled(a):                                   # [.., D] -> [.., D/1024, 8, 128]
        return a.reshape(*a.shape[:-1], tiles, 8, 128)

    seq = pl.BlockSpec((1, chunk, 1, 8, 128),
                       lambda bi, di, wi, *_: (bi, wi, di, 0, 0))
    state = pl.BlockSpec((1, n, 1, 8, 128),
                         lambda bi, di, wi, *_: (bi, 0, di, 0, 0))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, n_state=n, width=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, tiles, w // chunk),
            in_specs=[seq, seq,
                      pl.BlockSpec((n, 1, 8, 128),
                                   lambda bi, di, wi, *_: (0, di, 0, 0)),
                      state],
            out_specs=[seq, state]),
        out_shape=[jax.ShapeDtypeStruct((b, w, tiles, 8, 128), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, tiles, 8, 128), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="selective_scan",
        interpret=interpret,
    )(B.reshape(-1), C.reshape(-1), tiled(u), tiled(delta), tiled(A),
      tiled(h0))
    return y.reshape(b, w, d), h.reshape(b, n, d)


# ------------------------------------------------------------- the entry
def selective_scan(u, delta, A, B, C, h0, form: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """The recurrence over a chunk by :func:`scan_route`'s form.  A
    width the kernel's chunk does not divide is padded with ``delta =
    0`` positions (which leave the state alone) and cut again."""
    b, w, d = u.shape
    route, _ = scan_route(w, d, form)
    args = [a.astype(jnp.float32) for a in (u, delta, A, B, C, h0)]
    if route == "one_step":
        if w != 1:
            raise ValueError(f"one_step over {w} positions")
        return _one_step(*args)
    if route == "sequential":
        return selective_scan_reference(*args)
    block = min(PALLAS_CHUNK, w)
    pad = -w % block
    if pad:
        for i in (0, 1, 3, 4):
            args[i] = jnp.pad(args[i], ((0, 0), (0, pad), (0, 0)))
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    y, h = _pallas_chunk(*args, interpret)
    return y[:, :w], h
