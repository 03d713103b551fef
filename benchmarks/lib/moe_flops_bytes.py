"""Operations and bytes a decode step and a prefill chunk of a model
with SPARSE experts need: arithmetic on a builder's ``facts`` plus the
experts the program TOUCHED, which the program counts
(``experts_touched`` on the ``serving.step`` and ``prefill_chunk``
spans: experts that got at least one live row, summed over the expert
layers).

``lib/flops_bytes.decode_step_bytes`` reads "every matmul weight once"
and ``lib/prefill_flops_bytes.chunk_flops`` takes "2 per matmul
parameter per token": with 256 experts of which a token uses 8, both
count several times what a step must do (11.1 GB where about 7 are
touched), and a share of that would read over 100%.  Here:

* bytes: the DENSE weights once (attention, dense MLPs, shared experts,
  routers, head: ``dense_params``), ``experts_touched`` x ONE expert's
  weights (``expert_params``), the live latent rows read and the fresh
  ones written (``cache_row_width`` values a layer a position: the
  ``latent_row_width`` of the row in whole 128-lane tiles);
* FLOPs: 2 per dense parameter and per parameter of the
  ``experts_per_token`` experts a layer a token runs, per token; a
  prefill chunk needs ONE row of logits (``head_params``); attention as
  the programs compute it, ABSORBED in a decode step and in a chunk:
  scores ``latent_row_width`` wide and values ``kv_lora_rank`` wide,
  per head per (query, seen row) pair.  The absorbed query and the
  output's ``W_uv`` product cost a token what ``W_ukv`` would
  (``2 x kv_lora_rank x heads x (nope + v)``), which ``dense_params``
  already holds.
"""

from benchmarks.lib.flops_bytes import BYTES


def latent_row_bytes(facts: dict) -> int:
    """Bytes of latent rows one cached position holds over all layers,
    as the cache holds them (``cache_row_width``: the row in whole lane
    tiles, which is what a read of it moves)."""
    return facts["layers"] * facts["cache_row_width"] \
        * BYTES[facts["dtype"]]


def experts_cap(facts: dict, tokens: float) -> float:
    """The most experts ``tokens`` live tokens can touch over the
    expert layers."""
    return min(facts["expert_layers"] * facts["experts"],
               facts["expert_layers"] * facts["experts_per_token"] * tokens)


def _token_params(facts: dict) -> float:
    """Matmul parameters one token passes, the head apart."""
    return facts["dense_params"] - facts["head_params"] \
        + facts["expert_layers"] * facts["experts_per_token"] \
        * facts["expert_params"]


def touched_expert_bytes(facts: dict, experts_touched: float) -> float:
    return experts_touched * facts["expert_params"] * BYTES[facts["dtype"]]


def decode_step_bytes(facts: dict, active: float, live_rows: float,
                      experts_touched: float) -> float:
    return facts["dense_params"] * BYTES[facts["dtype"]] \
        + touched_expert_bytes(facts, experts_touched) \
        + (live_rows + active) * latent_row_bytes(facts)


def _absorbed_pair_flops(facts: dict) -> float:
    """One (query token, seen row) pair of one layer, all heads."""
    return 2.0 * facts["heads"] * (facts["latent_row_width"]
                                   + facts["kv_lora_rank"])


def decode_step_flops(facts: dict, active: float, live_rows: float) -> float:
    return 2.0 * (_token_params(facts) + facts["head_params"]) * active \
        + facts["layers"] * _absorbed_pair_flops(facts) * live_rows


def chunk_bytes(facts: dict, width: int, rows_before: int,
                experts_touched: float) -> float:
    return facts["dense_params"] * BYTES[facts["dtype"]] \
        + touched_expert_bytes(facts, experts_touched) \
        + (rows_before + width) * latent_row_bytes(facts)


def chunk_flops(facts: dict, width: int, rows_before: int) -> float:
    seen = width * rows_before + width * (width + 1) / 2.0
    return 2.0 * _token_params(facts) * width + 2.0 * facts["head_params"] \
        + facts["layers"] * _absorbed_pair_flops(facts) * seen


def share(least_s: float, took_s: float, what: str) -> float:
    """``100 x least / took``.  Over 100% the operations or bytes are
    counted too high or the time leaves out part of the work: an error,
    never clipped."""
    value = 100.0 * least_s / took_s
    if value > 100.0:
        raise ValueError(f"{what}: least time {1e3 * least_s:.3f} ms over "
                         f"the {1e3 * took_s:.3f} ms it took = "
                         f"{value:.1f}%: a count is too high or the time "
                         f"leaves work out")
    return value
