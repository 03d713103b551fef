"""The phases of chip_smoke.py that tier-1's window has no room for.

tests/test_chip_smoke.py rehearses the train and serve phases ahead of
the window's end; this file, sorted behind it, rehearses the rest at
tiny sizes: the kernel phase, and the four-chip phases on the
8-virtual-device CPU mesh — ``tensor_parallel=4`` serving (composed and
fused) and GPTHybridTrainer (mp2·pp2, mp4).  A minute of a four-chip run
is charged as four, so its logic is proven here first.
"""

import chip_smoke as cs
from paddle_tpu.models import gpt_tiny

from test_chip_smoke import TRAFFIC, no_captured_constants


def test_serve_tp_phase():
    with no_captured_constants():
        row = cs.serve_tp_phase(gpt_tiny(), 4, decode_steps=3, **TRAFFIC)
    for path in ("tp_fused", "tp_fused_block"):
        leg = row[path]
        assert leg["decode_path"] == path
        assert leg["trace_counts"] == {"decode": 1, "verify": 0,
                                       "prefill": 2, "gather": 1,
                                       "scatter": 1}
        # sharded for real: four devices, a quarter of each array on each
        for where in leg["devices"].values():
            assert where["devices"] == [0, 1, 2, 3]
            assert where["shard"] != where["shape"]
    assert row["block_vs_composed_rel_err"] <= cs.F32_LOGIT_TOL


def test_hybrid_train_phase():
    for degrees in ({"dp_degree": 1, "mp_degree": 2, "pp_degree": 2},
                    {"dp_degree": 1, "mp_degree": 4, "pp_degree": 1}):
        row = cs.hybrid_train_phase(gpt_tiny(), degrees, batch=4)
        assert row["loss"][-1] < row["loss"][0]
        assert row["devices"]["block_weights"]["devices"] == [0, 1, 2, 3]


def test_kernels_phase():
    """Plumbing only — tests/test_pallas_kernels.py owns the kernels'
    numerics: every kernel is reached, judged and counted."""
    with no_captured_constants():
        row = cs.kernels_phase(heads=2, head_dim=64, seq=128, slots=2,
                               rows=32, dtype="float32")
    assert sorted(row["rel_err"]) == [
        "decode_attention", "decode_attention_chunk", "flash_attention",
        "flash_attention_varlen", "fused_adamw", "fused_layer_norm",
        "fused_rms_norm"]
    # 2 x (fwd, dQ, dK/dV) flash + 2 decode + 2 x (fwd, bwd) norms + adamw
    assert row["pallas_calls"] == row["interpreted"] == 13
