"""kernels: what attention costs in one decode step (PR 36): device time
of the operations under the scopes ``attention`` (projections, rotary,
the decode-attention kernel, which also appends where it reads the slab
in place, the branch's residual add) and ``kv_append`` (the XLA append,
where the kernel cannot) per ``jit_decode`` program of the traced slice
(``lib/parts.by_part``); an earlier line holds the program's whole
table.  Nothing where the run has no device trace or the engine
recorded no table."""

from benchmarks.lib import parts


def read(run):
    row = parts.serve_row(run, run.get("decode_module_prefix"))
    if row is None:
        return None
    parts.log_row(run, "decode_attention_ms", run["decode_module_prefix"],
                  row)
    return parts.part_ms(row, "attention", "kv_append")
