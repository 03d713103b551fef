"""Compile-surface entry-point registration (graftprog, analysis v4).

graftprog (:mod:`.compile_surface`) enumerates every compile unit —
``jax.jit``, ``shard_map``, ``pallas_call``, the jax.export AOT paths —
reachable from the program's REGISTERED entry points, and classifies
each unit's compile-key space.  Entry points are registered three ways,
all import-free (the analysis only ever reads source):

  * **in-source marker** — a module-level tuple of local names::

        __compile_surface_roots__ = ("EngineCore",
                                     "build_tp_decode_program")

    A name may be a function (that function roots the walk) or a class
    (every method roots the walk).  This is the form the serving stack
    uses (serving/engine.py, serving/tp.py, serving/aot.py): zero imports,
    zero runtime cost, provably no behavior change.

  * **decorator marker** — ``@compile_surface_root`` (a no-op identity
    function defined here, recognized purely by name in the AST) for
    code that prefers the decorator form.

  * **built-in table** — :data:`DEFAULT_ENTRY_POINTS` below registers
    roots by fully-qualified dotted name for modules the serving stack
    does not own textually (the pallas kernels' public entry functions).
    :func:`register_entry_point` extends the table at runtime (tests,
    downstream embedders).

The registration table participates in the parse-cache key
(:func:`entry_point_fingerprint`, mixed into walker cache versioning
alongside :func:`..signatures.table_fingerprint`): editing the entry
set invalidates cached analysis inputs the same way editing the
analysis package itself does.
"""

from __future__ import annotations

import hashlib
from typing import List, Tuple

__all__ = ["ROOTS_DUNDER", "MARKER_NAMES", "DEFAULT_ENTRY_POINTS",
           "compile_surface_root", "register_entry_point",
           "registered_entry_points", "entry_point_fingerprint"]

# module-level tuple-of-names marker recognized in any scanned module
ROOTS_DUNDER = "__compile_surface_roots__"

# decorator names (leaf of the dotted decorator) recognized as markers
MARKER_NAMES = {"compile_surface_root"}

# fully-qualified roots for modules registered centrally rather than
# textually: the pallas kernels' public entry functions (ISSUE 16 —
# "the pallas kernels" are themselves registered entry points; their
# private kernel bodies and custom-vjp halves are then reached through
# the project call graph / name-reference edges)
DEFAULT_ENTRY_POINTS: Tuple[str, ...] = (
    "paddle_tpu.kernels.decode_attention.decode_attention",
    "paddle_tpu.kernels.decode_attention.decode_attention_auto",
    "paddle_tpu.kernels.decode_attention.decode_attention_reference",
    "paddle_tpu.kernels.flash_attention.flash_attention",
    "paddle_tpu.kernels.flash_attention.flash_attention_varlen",
    "paddle_tpu.kernels.flash_attention.flash_attention_with_lse",
    "paddle_tpu.kernels.fused_norm.fused_rms_norm_pallas",
    "paddle_tpu.kernels.fused_norm.fused_layer_norm_pallas",
    "paddle_tpu.kernels.fused_adamw.fused_adamw_update",
    "paddle_tpu.kernels.decode_block.decode_block_attn",
    "paddle_tpu.kernels.decode_block.decode_block_mlp",
    "paddle_tpu.kernels.decode_block.decode_block_layer",
    "paddle_tpu.kernels.decode_block.decode_block_reference",
    "paddle_tpu.kernels.decode_block_tp.ring_entry_matmul",
    "paddle_tpu.kernels.decode_block_tp.ring_exit_matmul",
    "paddle_tpu.kernels.decode_block_tp.decode_block_attn_tp",
    "paddle_tpu.kernels.decode_block_tp.tp_fused_block_layer",
    # the jit/_export_compat AOT surface: direction 2's exporter lowers
    # through these, so their compile units belong on the manifest
    "paddle_tpu.jit.save",
    "paddle_tpu.jit.load",
    "paddle_tpu.jit.save_program",
    "paddle_tpu.jit.load_program",
    "paddle_tpu.jit.to_static",
    "paddle_tpu.jit.StaticFunction",
)

_EXTRA_ENTRY_POINTS: List[str] = []


def compile_surface_root(obj):
    """No-op identity marker: ``@compile_surface_root`` registers the
    decorated function/class as a compile-surface entry point.  The
    analysis recognizes the NAME in the AST; at runtime this must cost
    nothing and change nothing."""
    return obj


def register_entry_point(qname: str) -> None:
    """Register a fully-qualified dotted root (``pkg.mod.fn`` or
    ``pkg.mod.Cls``) in addition to :data:`DEFAULT_ENTRY_POINTS`."""
    if qname not in _EXTRA_ENTRY_POINTS:
        _EXTRA_ENTRY_POINTS.append(qname)


def registered_entry_points() -> Tuple[str, ...]:
    return DEFAULT_ENTRY_POINTS + tuple(_EXTRA_ENTRY_POINTS)


def entry_point_fingerprint() -> str:
    """Stable content hash of the entry-point registration table — part
    of the walker's parse-cache version, so a changed table (edited
    defaults, runtime registrations) never serves stale analysis state."""
    payload = "|".join((ROOTS_DUNDER,
                        ",".join(sorted(MARKER_NAMES)),
                        ",".join(registered_entry_points())))
    return hashlib.sha1(payload.encode("utf-8")).hexdigest()
