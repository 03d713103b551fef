"""kernels: the share of the serving programs' device time that sits
under a named scope (PR 36).  100 x (1 - time of the operations whose
``op_name`` holds no part of ``paddle_tpu.obs.parts.PARTS`` / time of
all operations) over the traced slice's ``jit_decode`` and
``jit_prefill`` programs together, each table from the engine's
``program.parts`` span of THAT compile (``lib/parts.py``).  What is left
is data movement XLA put between parts; an earlier line holds both
programs' whole tables and the three largest operations under no scope.
Nothing where the run has no device trace or the engine recorded no
table (a tree from before the scopes)."""

from benchmarks.lib import parts
from benchmarks.metrics.prefill_chunk_ms import PREFILL_MODULE_PREFIX


def read(run):
    rows = []
    for prefix in (run.get("decode_module_prefix"), PREFILL_MODULE_PREFIX):
        row = parts.serve_row(run, prefix)
        if row is not None:
            parts.log_row(run, "scope_coverage.serve", prefix, row)
            rows.append(row)
    return parts.coverage_percent(rows) if rows else None
