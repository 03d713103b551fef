"""Tensor-parallel serving (serving/tp.py + kernels/collective_matmul).

The load-bearing contracts:

  * TOKEN-FOR-TOKEN parity between a tp=1 engine and tp in {2, 4, 8}
    engines on mixed-length workloads, greedy AND seeded sampling, GPT
    (MHA, learned positions, tied head) and Llama (GQA, rotary, SwiGLU,
    untied head) — the TP decode is the same arithmetic re-partitioned,
    so exact equality is the bar;
  * the fused compute-collective primitives (ring-decomposed
    allgather_matmul / matmul_reduce_scatter) match their serialized
    collective forms and the dense single-device reference;
  * the compile-count pin survives the mesh: {chunk} + pow2 buckets +
    ONE decode + ONE gather + ONE scatter per plane, at any tp;
  * the fallback matrix: the Pallas decode-block leg under TP is
    legality-gated (ISSUE 12 — ``tp_fused_block`` engages at legal
    shapes, tests/test_zz_decode_block_tp.py holds its parity matrix);
    an unsupported shape (num_slots not divisible) falls back to the
    composed GSPMD decode and KEEPS SERVING with parity.

zz-prefixed for the same reason as test_zz_decode_block /
test_zz_bench_projection: this file drives shard_map + ppermute rings on
the 8-device CPU mesh, and the jaxlib-0.4 dispatch-race window conftest
documents makes early-alphabet placement of distributed files
reproducibly fragile — sort after the window.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM,
                               gpt_tiny, llama_tiny)
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.tp import build_serving_mesh

LENGTHS = (5, 11, 3, 17, 30)
NEW = 6


def _prompts(seed=0, lengths=LENGTHS, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (L,)) for L in lengths]


def _fresh(maker, seed=0):
    """Deterministic model build: TP engines shard the weights in
    place, so every engine gets its own identically-initialized model."""
    paddle_tpu.seed(seed)
    m = maker()
    m.eval()
    return m


def _serve(model, tp, sampling=None, **kw):
    eng = ServingEngine(model, num_slots=4, tensor_parallel=tp, **kw)
    outs = eng.serve_batch(_prompts(), max_new_tokens=NEW,
                           sampling=sampling, max_steps=2000)
    assert all(o.finished for o in outs)
    return [o.tokens for o in outs], eng


SAMPLED = SamplingParams(do_sample=True, temperature=0.9, top_k=12,
                         top_p=0.85, seed=7)


# -------------------------------------------- collective-matmul kernels

def test_collective_matmul_parity():
    """Ring-overlapped == serialized collective == dense reference, for
    both the entry (allgather@dot) and exit (dot@reduce-scatter)
    primitives, on a real 4-device mesh."""
    from paddle_tpu.distributed._jax_compat import shard_map
    from paddle_tpu.kernels.collective_matmul import (
        allgather_matmul, matmul_reduce_scatter)
    tp = 4
    mesh = build_serving_mesh(tp)
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(8, 16), jnp.float32)       # [B, K]
    w_col = jnp.asarray(rs.randn(16, 12), jnp.float32)  # K x N (col-sh)
    w_row = jnp.asarray(rs.randn(16, 12), jnp.float32)  # K (row-sh) x N

    def ag(overlap):
        def body(xs, w):
            return allgather_matmul(xs, w, "mp", tp, overlap=overlap)
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("mp", None), P(None, "mp")),
            out_specs=P(None, "mp"), check_vma=False))(x, w_col)

    # the ring only MOVES bits (ppermute + dynamic_update_slice): its
    # output is bit-equal to the same [B/tp, K] @ [K, N/tp] dots run
    # with no collective at all
    dot = jax.jit(jnp.matmul)
    b, n = x.shape[0] // tp, w_col.shape[1] // tp
    hops = np.block([[np.asarray(dot(x[s * b:(s + 1) * b],
                                     w_col[:, d * n:(d + 1) * n]))
                      for d in range(tp)] for s in range(tp)])
    ring = np.asarray(ag(True))
    np.testing.assert_array_equal(ring, hops)
    # against the serialized form and the dense reference the bound is
    # the one the exit half uses below: they run one [B, K] dot where
    # the ring runs tp [B/tp, K] dots, and XLA:CPU orders a dot's
    # accumulation by its operand shape (9.5e-7 apart on jax 0.9.0 —
    # every row still contracts the full K locally in all three)
    dense = x @ w_col
    for other in (ag(False), dense):
        np.testing.assert_allclose(ring, np.asarray(other),
                                   rtol=1e-5, atol=1e-5)

    def rs_(overlap):
        def body(xs, w):
            return matmul_reduce_scatter(xs, w, "mp", tp,
                                         overlap=overlap)
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, "mp"), P("mp", None)),
            out_specs=P("mp", None), check_vma=False))(x, w_row)

    dense2 = x @ w_row
    np.testing.assert_allclose(np.asarray(rs_(True)),
                               np.asarray(dense2), rtol=1e-5, atol=1e-5)
    # ring chain vs psum tree reduce in different orders: allclose, not
    # bit-equal, is the contract between the two collective forms
    np.testing.assert_allclose(np.asarray(rs_(True)),
                               np.asarray(rs_(False)),
                               rtol=1e-5, atol=1e-5)


def test_build_serving_mesh_validation():
    with pytest.raises(ValueError, match="tensor_parallel"):
        build_serving_mesh(0)
    with pytest.raises(ValueError, match="devices"):
        build_serving_mesh(len(jax.devices()) + 1)


# ------------------------------------------------------- GPT parity

def test_gpt_tp_greedy_parity():
    base, e1 = _serve(_fresh(lambda: GPTForCausalLM(gpt_tiny())), 1)
    assert e1.decode_path == "unfused"
    for tp in (2, 4):
        toks, eng = _serve(
            _fresh(lambda: GPTForCausalLM(gpt_tiny())), tp)
        assert eng.decode_path == "tp_fused"
        assert eng.tp_fusion_reason is None
        assert toks == base
        assert eng.tensor_parallel == tp


def test_gpt_tp8_fused_parity():
    """Degree 8 — the deepest ring the 8-device mesh allows (7 ppermute
    hops per fused collective): the tp_fused program itself, not the
    GSPMD fallback, must hold token parity.  gpt_tiny has 4 heads, so
    this uses an 8-head tiny config with num_slots=8 (both must tile
    the mesh for the fused path to engage)."""
    from paddle_tpu.models import GPTConfig
    mk = lambda: GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=8,
        max_seq_len=128))

    def serve(tp):
        m = _fresh(mk)
        eng = ServingEngine(m, num_slots=8, tensor_parallel=tp)
        outs = eng.serve_batch(_prompts(), max_new_tokens=NEW,
                               max_steps=2000)
        assert all(o.finished for o in outs)
        return [o.tokens for o in outs], eng

    base, _ = serve(1)
    toks, eng = serve(8)
    assert eng.decode_path == "tp_fused"
    assert eng.tp_fusion_reason is None
    assert toks == base


def test_gpt_tp4_seeded_sampling_parity():
    base, _ = _serve(_fresh(lambda: GPTForCausalLM(gpt_tiny())), 1,
                     sampling=SAMPLED)
    toks, eng = _serve(_fresh(lambda: GPTForCausalLM(gpt_tiny())), 4,
                       sampling=SAMPLED)
    assert eng.decode_path == "tp_fused"
    assert toks == base


def test_gpt_tp2_gspmd_fallback_parity():
    """collective_fusion=False: the composed decode runs as a
    GSPMD-partitioned program over the mesh — same tokens, explicit
    fallback reason."""
    base, _ = _serve(_fresh(lambda: GPTForCausalLM(gpt_tiny())), 1)
    toks, eng = _serve(_fresh(lambda: GPTForCausalLM(gpt_tiny())), 2,
                       collective_fusion=False)
    assert eng.decode_path == "unfused"
    assert "collective_fusion" in eng.tp_fusion_reason
    assert toks == base


# ------------------------------------------------------ Llama parity

def test_llama_tp2_parity_greedy_and_sampled():
    mk = lambda: LlamaForCausalLM(llama_tiny())
    base_g, _ = _serve(_fresh(mk), 1)
    base_s, _ = _serve(_fresh(mk), 1, sampling=SAMPLED)
    toks_g, eng = _serve(_fresh(mk), 2)
    assert eng.decode_path == "tp_fused"     # GQA: kv_heads=2 tiles tp=2
    assert toks_g == base_g
    toks_s, _ = _serve(_fresh(mk), 2, sampling=SAMPLED)
    assert toks_s == base_s


def test_llama_tp4_rejects_on_kv_heads():
    """kv_heads=2 cannot partition over 4 devices: the slot slabs shard
    on the kv-head axis, so construction is a loud error, not silent
    replication — and it fires BEFORE the model is resharded, so a
    caller that catches and retries at tp=1 gets an untouched
    single-device model."""
    m = _fresh(lambda: LlamaForCausalLM(llama_tiny()))
    before = m.lm_head.weight.sharding
    with pytest.raises(ValueError, match="kv_heads"):
        ServingEngine(m, num_slots=4, tensor_parallel=4)
    assert m.lm_head.weight.sharding == before
    # ...and the untouched model still serves single-chip
    outs = ServingEngine(m, num_slots=2).serve_batch(
        _prompts(lengths=(4,)), max_new_tokens=2)
    assert outs[0].finished


# ----------------------------------------------- fallback matrix / pin

def test_pallas_fused_decode_conditional_under_tp():
    """fused_decode=True on a TP mesh (ISSUE 12): the hard
    "tensor_parallel" refusal is gone — at a legal shape the resolve
    chain ACCEPTS and the engine decodes through the sharded Pallas
    block (``tp_fused_block``) with token parity; an ILLEGAL shape
    (kv-heads not tiling the mesh is checked at construction, so probe
    the resolver directly) refuses with the real legality reason and
    the engine keeps serving on the next rung."""
    from paddle_tpu.kernels.decode_block import resolve_fused_decode
    m = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    ok, reason = resolve_fused_decode(m, batch=4, kv_len=128, tp=2)
    assert (ok, reason) == (True, None)
    toks, eng = _serve(m, 2, fused_decode=True)
    assert eng.decode_path == "tp_fused_block"
    assert eng.decode_fallback_reason is None
    base, _ = _serve(_fresh(lambda: GPTForCausalLM(gpt_tiny())), 1)
    assert toks == base
    # illegal: batch 3 cannot slot-shard over 2 devices — refusal names
    # the real check, and the engine's chain lands on the composed
    # compute-collective program... which ALSO refuses at num_slots=3,
    # so the GSPMD decode serves (the chain's last rung)
    ok, reason = resolve_fused_decode(m, batch=3, kv_len=128, tp=2)
    assert not ok and "batch 3" in reason
    m2 = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    eng2 = ServingEngine(m2, num_slots=3, tensor_parallel=2,
                         fused_decode=True)
    assert eng2.decode_path == "unfused"
    assert "batch 3" in eng2.decode_fallback_reason
    outs = eng2.serve_batch(_prompts(lengths=(4, 9)), max_new_tokens=4)
    assert all(o.finished for o in outs)


def test_tp_unsupported_shape_falls_back_and_serves():
    """num_slots=3 does not tile tp=2 — the fused program needs the
    residual stream slot-sharded, so the engine falls back to the
    composed GSPMD decode with an explicit reason and still serves."""
    m = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    eng = ServingEngine(m, num_slots=3, tensor_parallel=2)
    assert eng.decode_path == "unfused"
    assert "num_slots" in eng.tp_fusion_reason
    outs = eng.serve_batch(_prompts(lengths=(4, 9)), max_new_tokens=4)
    assert all(o.finished for o in outs)


def test_compile_count_pin_under_tp():
    """The mesh must not change the compiled-program SET: mixed lengths
    + cache hits + chunked prefill at tp=4 still lower {chunk} + pow2
    tails, ONE decode, ONE block gather, ONE block scatter."""
    m = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    eng = ServingEngine(m, num_slots=4, min_bucket=8, prefill_chunk=16,
                        block_len=16, tensor_parallel=4)
    prompts = _prompts(1, (3, 9, 17, 33, 50))
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    eng.run_until_complete(500)
    rids.append(eng.submit(prompts[-1].copy(), max_new_tokens=3))
    eng.run_until_complete(100)
    assert all(eng.result(r).finished for r in rids)
    assert eng.result(rids[-1]).prefix_hit_tokens == 48
    core = eng.core
    assert core.trace_counts["decode"] == 1
    assert core.trace_counts["prefill"] == 2       # 16 (chunk) + 8
    assert core.block_pool.trace_counts == {"gather": 1, "scatter": 1}


# -------------------------------------------------- telemetry / layout

def test_tp_metrics_and_sharded_plane():
    m = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    eng = ServingEngine(m, num_slots=4, tensor_parallel=2)
    outs = eng.serve_batch(_prompts(lengths=(4, 9)), max_new_tokens=4)
    assert all(o.finished for o in outs)
    snap = eng.registry.snapshot()
    assert snap["serving.tp_degree"] == 2
    # every TP decode step's dispatch + readback phases carry its fused
    # entry/exit collectives: the phase histograms are the evidence
    for phase in ("decode_dispatch", "readback"):
        h = snap[f"serving.phase.{phase}_s"]
        assert h["count"] > 0 and h["sum"] > 0, phase
    # the degree is an engine-lifetime constant: the warmup->reset->
    # measure flow must not zero it (nothing re-publishes it per step)
    eng.metrics.reset()
    assert eng.registry.snapshot()["serving.tp_degree"] == 2
    # the device plane is genuinely sharded: slabs on the kv-head axis
    # (a spec may drop trailing unsharded dims; compare the sharded ones)
    assert tuple(eng.core.pool.ks[0].sharding.spec)[:3] == \
        (None, None, "mp")
    assert tuple(eng.core.block_pool.bks[0].sharding.spec)[:3] == \
        (None, None, "mp")
    # single-chip engines report degree 1 and time the same phases, so
    # the two registries compare phase against phase
    m1 = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    e1 = ServingEngine(m1, num_slots=2)
    e1.serve_batch(_prompts(lengths=(4,)), max_new_tokens=2)
    snap1 = e1.registry.snapshot()
    assert snap1["serving.tp_degree"] == 1
    assert snap1["serving.phase.decode_dispatch_s"]["count"] > 0
    assert snap1["serving.phase.readback_s"]["count"] > 0


def test_multichip_serving_smoke_artifacts(tmp_path):
    """Tier-1 artifact smoke (mirrors test_chaos_smoke_artifacts): the
    multi-chip serving CI script end-to-end on the virtual-device mesh —
    per-degree parity verdict + the scraped tp gauge and decode
    phase histograms."""
    import importlib.util
    import json
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "multichip_serving_smoke",
        os.path.join(repo, "scripts", "multichip_serving_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "artifacts")
    assert mod.main(["--out", out, "--degrees", "1,2,4",
                     "--requests", "4", "--new", "4"]) == 0
    with open(os.path.join(out, "serving_tp.json")) as f:
        v = json.load(f)
    assert v["ok"]
    # ISSUE 12: both modes run — composed (tp_fused at tp > 1) and
    # fused (the sharded Pallas block, tp_fused_block), with CROSS-mode
    # token parity against the composed tp=1 baseline
    assert [(r["mode"], r["tp"]) for r in v["rows"]] == \
        [("composed", 1), ("composed", 2), ("composed", 4),
         ("fused", 1), ("fused", 2), ("fused", 4)]
    for r in v["rows"]:
        assert r["parity_vs_tp1"] and r["drained"] and r["path_ok"]
        if r["tp"] > 1:
            assert r["plane_sharded"]
            assert r["decode_path"] == ("tp_fused_block"
                                        if r["mode"] == "fused"
                                        else "tp_fused")
        for phase in ("decode_dispatch_s", "readback_s"):
            assert r[phase]["count"] > 0
    prom = open(os.path.join(out, "metrics.prom")).read()
    assert "serving_tp_degree" in prom
    assert "serving_phase_decode_dispatch_s" in prom
    assert "serving_phase_readback_s" in prom
