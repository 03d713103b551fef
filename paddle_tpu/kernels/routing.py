"""Empirical Pallas-vs-XLA kernel routing.

The Pallas tier's thesis is "beats XLA where it matters" — so the default
path must be the MEASURED winner per kernel and shape, not a blanket flag
(round-3 verdict Weak #1: two wired-in defaults picked the slower kernel).
This module holds the on-chip measurements and the per-shape decision
rules derived from them.

Measurements: one sweep on a TPU v5e taken on 2026-08-01, BEFORE PR 1,
through a remote-execution path that no longer exists (scan-chained
timing at iters=100; a ~3.4 ms/iter dispatch floor of that path drowned
sub-ms kernels at iters=20).  The sweep scripts are gone with it and
nothing since has re-timed these kernels: ROADMAP S1 re-measures the
table on the chip builders have now.  speedup = xla_ms / pallas_ms:

  flash_attn fwd/bwd  s1024: 0.97/0.94   s2048: 2.05/2.32
                      s4096: 2.30/2.35   s8192: 40x (dense OOM-adjacent)
  decode_attn (bk1024) kv4096: 1.06   kv8192: 0.99   kv16384: 1.00
    (the COPYING kernel, head-major operands, every row streamed: since
    PR 29 decode takes the in-place kernel, which reads live rows only,
    so this row and the 6144 rule below are due a re-measure)
  fused_adamw (br8192) 8M: 1.00 (exact tie)
  layer_norm   2048x1024: 0.98  8192x4096: 0.90  32768x2048: 0.93
  rms_norm     2048x1024: 0.98  8192x4096: 0.88  32768x2048: 0.83
                4096x8192: 0.78

Decision rules (the table above, compressed):
  - flash attention: Pallas iff seq >= 2048 (crossover between 1024 and
    2048; the win grows with seq as the dense path's S^2 materialisation
    bites).
  - decode attention: Pallas iff cache length <= 6144 (wins at 4096,
    statistical tie beyond — the tie-break goes to XLA per the "default
    must be >= 1.0x" rule).
  - norms: XLA always (fusion into neighbours beats the standalone
    kernel at every measured shape).  Kernels stay available explicitly.
  - fused AdamW: XLA (exact tie at the best tile; the fused kernel stays
    as the opt-in FusedAdamW class).

``FLAGS_pallas_routing``: "auto" (this table), "always" (every
flag-enabled kernel forced on where legal), "never" (all Pallas off).
The per-kernel boolean flags (use_pallas_attention, use_pallas_norm)
remain hard off-switches on top.
"""

from __future__ import annotations

from typing import Optional

import jax

from ..core.flags import flags

__all__ = ["use_pallas", "partition_refusal"]

# shape-keyed measured speedups (xla_ms / pallas_ms), kept as data so
# tests can assert the rules agree with the evidence
MEASURED = {
    ("flash_attention", 1024): 0.95,
    ("flash_attention", 2048): 2.05,
    ("flash_attention", 4096): 2.30,
    ("flash_attention", 8192): 40.5,
    ("decode_attention", 4096): 1.06,
    ("decode_attention", 8192): 0.99,
    ("decode_attention", 16384): 1.00,
    ("layer_norm", (8192, 4096)): 0.90,
    ("rms_norm", (8192, 4096)): 0.88,
    ("fused_adamw", 8 * 1024 * 1024): 1.00,
}


def _rule(kernel: str, f: dict) -> bool:
    if kernel == "flash_attention":
        return min(f.get("seq_q", 0), f.get("seq_k", 0)) >= 2048
    if kernel == "decode_attention":
        return f.get("kv_len", 0) <= 6144
    if kernel == "decode_block":
        # fused decode block (kernels/decode_block.py): not timed on a
        # chip yet (it first compiled on one in PR 21) — the path is
        # opt-in (the engine's fused_decode flag) and inherits
        # decode_attention's win region (pallas <= 6144, statistical
        # tie beyond -> composed XLA path) until ROADMAP S1/S3 measure
        # it; shape/mesh legality — incl. the tp > 1 per-shard plan of
        # the sharded variant (kernels/decode_block_tp.py) — is checked
        # separately by decode_block.fusion_legal(tp=...) before this
        # table is consulted.
        return _rule("decode_attention", f)
    if kernel in ("layer_norm", "rms_norm"):
        return False
    if kernel == "fused_adamw":
        return False
    return False


def partition_refusal() -> Optional[str]:
    """Why a Mosaic kernel traced HERE could not be lowered, or None.
    XLA cannot partition a Mosaic custom call, so under a mesh every
    axis larger than one must be manual (the kernel inside a
    ``shard_map`` over it).  The mesh is the one in scope
    (``jax.set_mesh``, or a ``shard_map`` body's own); a route that
    asked anyway would die at lowering with the compiler's words,
    quoted here so the refusal is static and named."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = [a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t != jax.sharding.AxisType.Manual and mesh.shape[a] > 1]
    if not auto:
        return None
    return (f"mosaic: mesh axes {auto} are partitioned by XLA here "
            f"('Mosaic kernels cannot be automatically partitioned. "
            f"Please wrap the call in a shard_map.')")


def use_pallas(kernel: str, **features) -> bool:
    """Should ``kernel`` take the Pallas path for these (static, trace-time)
    shape features?  Consults FLAGS_pallas_routing, then where the call
    is being traced (:func:`partition_refusal`), then the measured
    per-shape rules."""
    mode = getattr(flags, "pallas_routing", "auto")
    if mode == "never":
        return False
    if mode == "always":
        return True
    if partition_refusal() is not None:
        return False
    return _rule(kernel, features)
