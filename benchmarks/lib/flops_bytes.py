"""Operations and bytes a step needs, from shapes alone.

A builder (``benchmarks/builders/<family>.py``) turns a configuration
file into ``facts``: parameter counts split by how they are used, and
the widths attention needs.  Everything here is arithmetic on those
facts, so a later PR cannot move a roofline share by changing the
program.

``matmul_params`` are the parameters that multiply activations (every
token pays 2 FLOPs per such parameter forward, 4 backward).
``lookup_params`` are tables that are only indexed (an untied input
embedding, learned positions): they cost no FLOPs, and a decode step
reads one row per slot, not the table.
"""

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def total_params(facts: dict) -> int:
    return facts["matmul_params"] + facts["lookup_params"]


def kv_row_bytes(facts: dict) -> int:
    """Bytes of K and V one cached position holds over all layers."""
    return (2 * facts["layers"] * facts["kv_heads"] * facts["head_dim"]
            * BYTES[facts["dtype"]])


def train_flops_per_token(facts: dict, seq: int,
                          convention: str = "required") -> float:
    """Forward + backward FLOPs one trained token needs.  Recomputed
    operations (remat) never count.

    ``required``: 6 per matmul parameter, plus causal attention's
    scores and value sums, 6 * layers * width * seq (half of the full
    square: a causal kernel need not touch the masked half).  This is
    what ``train_roofline_share`` divides.

    ``6n12les``: the repo's older MFU arithmetic, (6N + 12 L E S) with N
    counting EVERY parameter, lookup tables included, and attention as
    the full square (``bench.py``, ``BASELINE.md``).  Printed beside the
    first so old numbers stay comparable; it reads higher."""
    width = facts["heads"] * facts["head_dim"]
    if convention == "required":
        return 6.0 * facts["matmul_params"] \
            + 6.0 * facts["layers"] * width * seq
    if convention == "6n12les":
        return 6.0 * total_params(facts) \
            + 12.0 * facts["layers"] * width * seq
    raise ValueError(f"unknown convention {convention!r}")


def decode_step_flops(facts: dict, active: float, live_rows: float) -> float:
    """FLOPs of one decode step: every matmul parameter once per active
    slot, plus one query against each live cached row (scores and value
    sums, 4 * width per row per layer)."""
    width = facts["heads"] * facts["head_dim"]
    return 2.0 * facts["matmul_params"] * active \
        + 4.0 * facts["layers"] * width * live_rows


def decode_step_bytes(facts: dict, active: float, live_rows: float) -> float:
    """Bytes one decode step must move: every matmul weight read once
    (the batch amortizes it), the live K and V rows of the active slots
    read once, and one new row written per active slot.  Copied from
    ``bench.decode_bw_util``'s byte model, with the live rows counted
    and not assumed."""
    return facts["matmul_params"] * BYTES[facts["dtype"]] \
        + (live_rows + active) * kv_row_bytes(facts)


def least_time_s(flops: float, byts: float, peaks: dict, chips: int = 1):
    """The least time the chips could take, and which bound sets it."""
    t_flops = flops / (chips * peaks["bf16_flops"])
    t_bytes = byts / (chips * peaks["hbm_bytes_per_s"])
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "hbm")
