"""The AOT save/load paths' ``jax.export`` import point.

Thin re-export of the four public ``jax.export`` symbols jit.save /
save_program use; everything in this package resolves them through
here.
"""

from __future__ import annotations

from jax.export import SymbolicScope, deserialize, export, symbolic_shape

__all__ = ["export", "deserialize", "symbolic_shape", "SymbolicScope"]
