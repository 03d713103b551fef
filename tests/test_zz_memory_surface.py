"""Runtime/static consistency gate for graftmem (ISSUE 19).

graftmem (tools/analysis/memory.py) statically derives the serving
plane's byte footprint — pool-slab formulas from the constructor AST,
declared row-state/staging legs, VMEM working sets from integer mirrors
of the Pallas plans.  This test closes the loop from the OTHER side: it
warms a CPU-smoke engine per config leg (tp=1 and tp=2) and measures
the live device state from array shapes/dtypes (``.nbytes`` — no
accelerator needed), then asserts:

  * pool slabs match the manifest's formulas EXACTLY (byte-for-byte,
    both legs — the capacity manifest's per-block ladder is only
    trustworthy if the formulas are exact);
  * staging (the single-slot prefill cache) matches its declared
    formula EXACTLY;
  * the persistent row-state + staging estimate matches the measured
    footprint within a stated 5% tolerance (the declared legs include
    lazily-uploaded sampling/mask vectors a fresh engine has not
    materialized yet — the static side is the UPPER bound);
  * the plan mirrors are line-for-line faithful: over every reference
    tiling, mirror output equals live plan output exactly (tilings AND
    refusal strings), so plan drift cannot silently de-sync the static
    VMEM check.

zz-prefixed for the same reason as test_zz_compile_surface: the tp=2
leg drives shard_map on the 8-device CPU mesh — sort after the
jaxlib-0.4 dispatch-race window conftest documents.
"""

import os

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import ServingEngine

ENGINE_PLANE = "paddle_tpu.serving.engine.EngineCore"
KV_POOL = "paddle_tpu.serving.kv_pool.KVPool"
BLOCK_POOL = "paddle_tpu.serving.kv_pool.BlockPool"

NUM_SLOTS = 4
MAX_SEQ = 64
BLOCK_LEN = 16
# the static side is an upper bound over lazily-materialized row state
# (_sampling_dev/_mask_dev upload on first use) — tolerance, stated
ROW_STATE_TOL = 0.05

# the capacity environment of the smoke engine below (gpt_tiny: vocab
# 256, hidden 64, 2 layers, 4 heads, head_dim 16, float32)
TINY_ENV = {
    "num_slots": NUM_SLOTS, "max_seq": MAX_SEQ, "num_layers": 2,
    "kv_heads": 4, "head_dim": 16, "num_heads": 4, "hidden": 64,
    "vocab_size": 256, "ffn": 256, "itemsize": 4,
    "block_len": BLOCK_LEN,
    "num_blocks": NUM_SLOTS * (MAX_SEQ // BLOCK_LEN),
    "blocks_per_row": MAX_SEQ // BLOCK_LEN,
    # recurrent state a slot holds beside its KV rows: none for GPT
    "state_bytes_per_slot": 0,
    # the pool's V slabs: one per K slab (0 where a position holds one
    # row kind, a latent row)
    "v_slabs": 2,
}


@pytest.fixture(scope="module")
def manifest():
    """The statically-derived capacity manifest, built through the same
    library entry point the CLI's ``--memory`` uses."""
    from paddle_tpu.tools.analysis import build_memory_manifest_for_paths
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    scope = [os.path.join(root, p)
             for p in ("paddle_tpu", "scripts")]
    m = build_memory_manifest_for_paths(scope, root=root)
    assert ENGINE_PLANE in m["planes"], sorted(m["planes"])
    return m


def _eval(formula, env=TINY_ENV):
    from paddle_tpu.tools.analysis import eval_formula
    return eval_formula(formula, env)


def _fresh_engine(**engine_kw):
    paddle_tpu.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    eng = ServingEngine(model, num_slots=NUM_SLOTS, max_seq=MAX_SEQ,
                        min_bucket=8, prefill_chunk=16,
                        block_len=BLOCK_LEN, **engine_kw)
    # warm it: real traffic so every persistent buffer exists
    rs = np.random.RandomState(7)
    rids = [eng.submit(rs.randint(0, 256, (L,)), max_new_tokens=3)
            for L in (3, 17)]
    eng.run_until_complete(200)
    assert all(eng.result(r).finished for r in rids)
    return eng, model


def _measured_pool_bytes(pool):
    return sum(a.nbytes for a in pool.ks) \
        + sum(a.nbytes for a in pool.vs if a is not None) \
        + pool.seq_pos.nbytes


def _measured_block_bytes(bp):
    return sum(a.nbytes for a in bp.bks) + sum(a.nbytes for a in bp.bvs)


def _measured_staging(model):
    """One in-flight prefill's staging: its KV rows and, where the
    model carries one, its recurrent state."""
    import jax
    cache = model.init_cache(1, MAX_SEQ)
    rows = sum(a.nbytes for layer in cache for a in layer[:2]
               if a is not None)
    if hasattr(model, "init_state"):
        rows += sum(leaf.nbytes for leaf in
                    jax.tree_util.tree_leaves(model.init_state(1)))
    return rows


def _check_pools_exact(manifest, eng, model, leg):
    kv_formula = manifest["pools"][KV_POOL]["formula"]
    bp_formula = manifest["pools"][BLOCK_POOL]["formula"]
    measured_kv = _measured_pool_bytes(eng.core.pool)
    measured_bp = _measured_block_bytes(eng.core.block_pool)
    assert measured_kv == _eval(kv_formula), (
        f"[{leg}] KVPool: measured {measured_kv} B != static "
        f"{_eval(kv_formula)} B from '{kv_formula}'")
    assert measured_bp == _eval(bp_formula), (
        f"[{leg}] BlockPool: measured {measured_bp} B != static "
        f"{_eval(bp_formula)} B from '{bp_formula}'")
    plane = manifest["planes"][ENGINE_PLANE]
    staging = plane["staging"]["formula"]
    assert staging and _measured_staging(model) == _eval(staging), (
        f"[{leg}] staging: measured {_measured_staging(model)} B != "
        f"static {_eval(staging)} B from '{staging}'")


def test_leg_tp1_pools_match_static_exactly(manifest):
    eng, model = _fresh_engine()
    _check_pools_exact(manifest, eng, model, "tp1")


def test_leg_tp2_pools_match_static_exactly(manifest):
    """Sharded slabs: ``.nbytes`` is the GLOBAL logical size, which is
    exactly what the capacity formula accounts — sharding changes the
    per-chip share, never the total."""
    eng, model = _fresh_engine(tensor_parallel=2)
    _check_pools_exact(manifest, eng, model, "tp2")


def _family(name):
    from paddle_tpu.models import (LlamaForCausalLM, OuroForCausalLM,
                                   llama_tiny, ouro_tiny)
    return {"gpt": lambda: GPTForCausalLM(gpt_tiny()),
            "llama": lambda: LlamaForCausalLM(llama_tiny()),
            "ouro": lambda: OuroForCausalLM(ouro_tiny())}[name]()


@pytest.mark.parametrize("family, planes, model_kv_heads",
                         [("gpt", 2, 4), ("llama", 2, 2), ("ouro", 9, 4)])
def test_static_bytes_equal_the_live_pool_for_every_served_family(
        manifest, family, planes, model_kv_heads):
    """The manifest's formulas speak of the POOL's fields: its slabs
    (``num_layers``) and the heads one slab holds (``kv_heads``).  For
    a looped model they are 1 and planes x heads (kv_pool.
    cache_geometry); their product is planes x the model's kv heads in
    every family, so pool, block pool and staging stay exact."""
    paddle_tpu.seed(0)
    model = _family(family)
    model.eval()
    eng = ServingEngine(model, num_slots=NUM_SLOTS, max_seq=MAX_SEQ,
                        min_bucket=8, block_len=BLOCK_LEN)
    try:
        pool, bp = eng.core.pool, eng.core.block_pool
        assert pool.planes == planes
        env = {**TINY_ENV, "num_layers": pool.num_layers,
               "v_slabs": pool.num_layers,
               "kv_heads": pool.ks[0].shape[2],
               "head_dim": model.cfg.head_dim,
               "vocab_size": model.cfg.vocab_size}
        assert env["num_layers"] * env["kv_heads"] \
            == planes * model_kv_heads
        pools, plane = manifest["pools"], manifest["planes"][ENGINE_PLANE]
        assert _measured_pool_bytes(pool) \
            == _eval(pools[KV_POOL]["formula"], env)
        assert _measured_block_bytes(bp) \
            == _eval(pools[BLOCK_POOL]["formula"], env)
        assert _measured_staging(model) \
            == _eval(plane["staging"]["formula"], env)
        # a cached row: 2 x planes x kv_heads x head_dim x itemsize
        row = 2 * planes * model_kv_heads * model.cfg.head_dim * 4
        assert _measured_staging(model) == MAX_SEQ * row
    finally:
        eng.close()


def test_recurrent_state_is_counted_exactly(manifest):
    """A model that carries a recurrent state beside its KV rows
    (models/jamba.py): the pool's state arrays equal the declared
    ``row_state.recurrent_state`` leg, a prefill's staging its KV rows
    plus one slot's state, and the KV slabs the pool's own formula at 2
    planes of 1 head, byte for byte."""
    import jax
    from paddle_tpu.models import JambaForCausalLM, jamba_tiny
    paddle_tpu.seed(0)
    model = JambaForCausalLM(jamba_tiny())
    model.eval()
    eng = ServingEngine(model, num_slots=NUM_SLOTS, max_seq=MAX_SEQ,
                        min_bucket=8, prefill_chunk=16,
                        enable_prefix_cache=False)
    try:
        eng.serve_batch([np.arange(3, 40) % 128], max_new_tokens=3)
        pool = eng.core.pool
        # 2 Mamba layers x (8 x 128 float32 of state + 3 x 128 float32
        # of convolution window)
        per_slot = 2 * (8 * 128 * 4 + 3 * 128 * 4)
        assert pool.state_bytes_per_slot == per_slot
        env = {**TINY_ENV, "num_layers": pool.num_layers,
               "v_slabs": pool.num_layers,
               "kv_heads": pool.ks[0].shape[2],
               "head_dim": model.cfg.head_dim,
               "vocab_size": model.cfg.vocab_size,
               "state_bytes_per_slot": per_slot}
        assert (env["num_layers"], env["kv_heads"]) == (2, 1)
        plane = manifest["planes"][ENGINE_PLANE]
        state_arrays = jax.tree_util.tree_leaves(pool.state)
        assert len(state_arrays) == 4
        assert sum(a.nbytes for a in state_arrays) == _eval(
            plane["row_state"]["recurrent_state"]["formula"], env) \
            == NUM_SLOTS * per_slot
        assert _measured_pool_bytes(pool) \
            == _eval(manifest["pools"][KV_POOL]["formula"], env)
        assert _measured_staging(model) \
            == _eval(plane["staging"]["formula"], env) \
            == 2 * 2 * MAX_SEQ * 16 * 4 + per_slot
    finally:
        eng.close()


def test_latent_row_is_counted_exactly(manifest):
    """A model whose cached position is ONE latent row
    (models/deepseek_v3.py, ``cache_row_kinds`` 1): the pool makes no V
    slabs (``v_slabs`` 0), and the pool's formula, a prefill's staging
    and ``row_bytes`` are 3 layers x 1 head x 128 values (the 32 + 8 of
    the row in one lane tile) x 4 B a position, byte for byte: not
    twice that."""
    from paddle_tpu.models import DeepseekV3ForCausalLM, deepseek_v3_tiny
    paddle_tpu.seed(0)
    model = DeepseekV3ForCausalLM(deepseek_v3_tiny())
    model.eval()
    eng = ServingEngine(model, num_slots=NUM_SLOTS, max_seq=MAX_SEQ,
                        min_bucket=8, prefill_chunk=16,
                        enable_prefix_cache=False)
    try:
        eng.serve_batch([np.arange(3, 40) % 128], max_new_tokens=3)
        pool = eng.core.pool
        assert pool.row_kinds == 1 and pool.vs == [None] * 3
        assert pool.row_bytes == 3 * 128 * 4
        env = {**TINY_ENV, "num_layers": 3, "v_slabs": 0, "kv_heads": 1,
               "head_dim": model.cfg.cache_row_width,
               "vocab_size": model.cfg.vocab_size}
        plane = manifest["planes"][ENGINE_PLANE]
        assert _measured_pool_bytes(pool) \
            == _eval(manifest["pools"][KV_POOL]["formula"], env) \
            == NUM_SLOTS * MAX_SEQ * pool.row_bytes + 4 * NUM_SLOTS
        assert _measured_staging(model) \
            == _eval(plane["staging"]["formula"], env) \
            == MAX_SEQ * pool.row_bytes
    finally:
        eng.close()


def test_row_state_estimate_within_tolerance(manifest):
    """The declared row-state legs bound the measured persistent
    non-pool device state within the stated tolerance.  Static must be
    >= measured (it includes the lazily-uploaded vectors) and close."""
    eng, model = _fresh_engine()
    plane = manifest["planes"][ENGINE_PLANE]
    static = _eval(plane["staging"]["formula"]) + sum(
        _eval(r["formula"]) for r in plane["row_state"].values())
    measured = (_measured_staging(model) + eng.core._last_tok.nbytes
                + eng.core._keys.nbytes)
    for attr in ("_sampling_dev", "_mask_dev"):
        dev = getattr(eng.core, attr, None)
        if dev is None:
            continue
        parts = dev if isinstance(dev, (tuple, list)) else [dev]
        measured += sum(int(p.nbytes) for p in parts)
    assert static >= measured, (static, measured)
    assert (static - measured) / static <= ROW_STATE_TOL, (
        f"row-state estimate {static} B vs measured {measured} B — "
        f"off by more than {ROW_STATE_TOL:.0%}")


def test_plan_mirrors_are_faithful():
    """The static VMEM check is only as good as its mirrors: over every
    reference tiling, mirror output must equal the LIVE plan's output
    exactly — the chosen tiles, the working-set legs, and (at a
    deliberately impossible budget) the refusal strings."""
    from paddle_tpu.kernels.decode_block import plan_decode_block
    from paddle_tpu.kernels.decode_block_tp import plan_decode_block_tp
    from paddle_tpu.tools.analysis import PLAN_MIRRORS, REFERENCE_TILINGS
    live = {"plan_decode_block": plan_decode_block,
            "plan_decode_block_tp": plan_decode_block_tp}
    assert set(PLAN_MIRRORS) == set(live)
    for t in REFERENCE_TILINGS:
        got = PLAN_MIRRORS[t["plan"]](**t["kwargs"])
        want = live[t["plan"]](**t["kwargs"])
        assert got == want, (t["name"], got, want)
        # refusal path: both sides must refuse identically
        got_r = PLAN_MIRRORS[t["plan"]](vmem_budget=64 * 1024,
                                        **t["kwargs"])
        want_r = live[t["plan"]](vmem_budget=64 * 1024, **t["kwargs"])
        assert got_r == want_r, (t["name"], got_r, want_r)


def test_manifest_vmem_all_green(manifest):
    """Acceptance pin: every ``plan_decode_block{,_tp}`` tiling in-tree
    passes the static VMEM check against the budget the kernels
    declare."""
    vmem = manifest["vmem"]
    assert vmem["all_ok"], vmem
    assert {"plan_decode_block", "plan_decode_block_tp"} <= \
        set(vmem["plans"])
    for name, plan in vmem["plans"].items():
        assert plan["tilings"], f"no reference tilings ran for {name}"
        for row in plan["tilings"]:
            assert row["ok"], row
            assert all(v <= plan["budget"]
                       for v in row["working_set"].values()), row
