"""Operations and bytes ONE prefill chunk needs, from shapes alone:
arithmetic on a builder's ``facts`` like ``lib/flops_bytes.py``, for the
program that file does not cover.

A chunk of ``width`` tokens whose request already holds ``rows_before``
cached positions.  FLOPs: 2 per parameter that multiplies activations
per token, the head apart: ONE row of logits is required of a prefill
chunk, whatever the program computes (``head_params``: the head's
share of ``matmul_params``, 0 where a builder does not say); plus
causal attention's scores and value sums, each query against the rows
held before the chunk and those of the chunk up to itself.  Bytes:
every weight once, the request's recurrent state read and written
(``state_bytes_per_slot``, 0 where a builder does not say), its cached
rows read and the chunk's rows written.
"""

from benchmarks.lib.flops_bytes import BYTES, kv_row_bytes


def chunk_flops(facts: dict, width: int, rows_before: int) -> float:
    head = facts.get("head_params", 0)
    attn_width = facts["heads"] * facts["head_dim"]
    seen = width * rows_before + width * (width + 1) / 2.0
    return 2.0 * (facts["matmul_params"] - head) * width + 2.0 * head \
        + 4.0 * facts["layers"] * attn_width * seen


def chunk_bytes(facts: dict, width: int, rows_before: int) -> float:
    return facts["matmul_params"] * BYTES[facts["dtype"]] \
        + 2.0 * facts.get("state_bytes_per_slot", 0) \
        + (rows_before + width) * kv_row_bytes(facts)
