"""kernels: what attention costs in one prefill chunk (PR 36): device
time of the operations under the scopes ``attention`` and ``kv_append``
per ``jit_prefill`` program, the mean over the traced slice's chunks of
every width (``lib/parts.by_part`` pairs each program event with the
table of its own compile); an earlier line holds the whole table.
Nothing where the run has no device trace, the slice no chunk, or the
engine recorded no table."""

from benchmarks.lib import parts
from benchmarks.metrics.prefill_chunk_ms import PREFILL_MODULE_PREFIX


def read(run):
    row = parts.serve_row(run, PREFILL_MODULE_PREFIX)
    if row is None:
        return None
    parts.log_row(run, "prefill_attention_ms", PREFILL_MODULE_PREFIX, row)
    return parts.part_ms(row, "attention", "kv_append")
