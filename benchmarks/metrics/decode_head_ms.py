"""kernels: what the head and the program's tail cost in one decode
step (PR 36): device time of the operations under the scopes ``head``
(the vocabulary matmul) and ``sampling`` (finite check, argmax or
``sample_rows``, positions, counters riding the readback) per
``jit_decode`` program of the traced slice (``lib/parts.by_part``); an
earlier line holds the program's whole table.  Nothing where the run has
no device trace or the engine recorded no table."""

from benchmarks.lib import parts


def read(run):
    row = parts.serve_row(run, run.get("decode_module_prefix"))
    if row is None:
        return None
    parts.log_row(run, "decode_head_ms", run["decode_module_prefix"], row)
    return parts.part_ms(row, "head", "sampling")
