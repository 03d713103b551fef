"""The distributed stack's ``shard_map`` / ``axis_size`` import point.

Thin re-exports of the jax surface this repo is installed against
(pyproject.toml pins it): top-level ``jax.shard_map`` with
``check_vma=`` / ``axis_names=``, and ``jax.lax.axis_size``.  Every call
site in the package imports through here.
"""

from __future__ import annotations

from jax import shard_map
from jax.lax import axis_size

__all__ = ["shard_map", "axis_size"]
