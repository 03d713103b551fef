"""Sharded decode-block megakernel (kernels/decode_block_tp.py +
ISSUE 12 engine wiring).

The load-bearing contracts:

  * the shared ring schedule (``collective_matmul.ring_schedule``) is
    THE bookkeeping for both the XLA and the in-kernel rings — unit
    tested directly so the two lowerings cannot drift;
  * KERNEL parity: ``tp_fused_block_layer`` under shard_map at
    tp in {2, 4} matches ``decode_block_reference`` (the tp=1 oracle)
    elementwise on GPT-style (LayerNorm + biases + GeLU) and
    Llama-style (RMSNorm + GQA + rotary + SwiGLU) layers, at ragged
    ``seq_pos`` including empty (0) and full (== S) slots;
  * ENGINE parity: with ``tensor_parallel in {2, 4}`` and
    ``fused_decode=True`` the engine resolves ``tp_fused_block``
    (``decode_fallback_reason is None``) and serves token-for-token
    with the tp=1 fused engine, the tp=1 composed engine AND the tp>1
    composed engine — greedy and seeded, GPT and Llama GQA;
  * the refusal matrix is REAL legality now (kv_heads/batch/ffn tiling,
    VMEM budget), not a blanket "tensor_parallel" string, and every
    refusal keeps serving on the next rung of the chain;
  * the compile pin holds: {chunk} + buckets + ONE decode at any tp,
    fused or not.

zz-prefixed per the jaxlib-0.4 dispatch-race precedent
(tests/conftest.py): this file drives shard_map + ppermute + Pallas
interpret kernels on the 8-device CPU mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu
from paddle_tpu.distributed._jax_compat import shard_map
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM,
                               gpt_tiny, llama_tiny)
from paddle_tpu.serving import SamplingParams, ServingEngine

LENGTHS = (5, 11, 3, 17, 30)
NEW = 6
SAMPLED = SamplingParams(do_sample=True, temperature=0.9, top_k=12,
                         top_p=0.85, seed=7)


def _prompts(seed=0, lengths=LENGTHS, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (L,)) for L in lengths]


def _fresh(maker, seed=0):
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


# ------------------------------------------------- shared ring schedule

def test_ring_schedule_shared_bookkeeping():
    """The perm table is the forward ring, the entry sources visit
    every origin exactly once per device, and the exit chunks walk
    d-1, d-2, ..., d so the final hop lands on the device's own chunk —
    for every degree the 8-device mesh can host.  This object is what
    both ``collective_matmul`` and ``decode_block_tp`` unroll, so the
    invariants here pin BOTH lowerings."""
    from paddle_tpu.kernels.collective_matmul import ring_schedule
    for tp in (1, 2, 3, 4, 8):
        ring = ring_schedule(tp)
        assert ring.perm == [(d, (d + 1) % tp) for d in range(tp)]
        for idx in range(tp):
            srcs = [ring.entry_src(idx, h) for h in range(tp)]
            assert sorted(srcs) == list(range(tp))   # every shard once
            assert srcs[0] == idx                    # own shard first
            chunks = [ring.exit_chunk(idx, h) for h in range(tp)]
            assert sorted(chunks) == list(range(tp))
            assert chunks[-1] == idx                 # own chunk last
    with pytest.raises(ValueError, match="tp >= 1"):
        ring_schedule(0)


def test_collective_matmul_still_matches_after_refactor():
    """The XLA rings on the shared schedule still equal the dense
    reference (regression for the ring_schedule factor-out)."""
    from paddle_tpu.kernels.collective_matmul import (
        allgather_matmul, matmul_reduce_scatter)
    from paddle_tpu.serving.tp import build_serving_mesh
    tp = 4
    mesh = build_serving_mesh(tp)
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(8, 16), jnp.float32)
    w = jnp.asarray(rs.randn(16, 12), jnp.float32)

    def ag(xs, ws):
        return allgather_matmul(xs, ws, "mp", tp)

    def rs_(xs, ws):
        return matmul_reduce_scatter(xs, ws, "mp", tp)

    ya = jax.jit(shard_map(ag, mesh=mesh,
                           in_specs=(P("mp", None), P(None, "mp")),
                           out_specs=P(None, "mp"),
                           check_vma=False))(x, w)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(x @ w),
                               rtol=1e-6, atol=1e-6)
    yr = jax.jit(shard_map(rs_, mesh=mesh,
                           in_specs=(P(None, "mp"), P("mp", None)),
                           out_specs=P("mp", None),
                           check_vma=False))(x, w)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------- kernel-level parity

def _layer_case(tp, gated, use_rope, norm, bias, pos_list):
    """Run one layer through the sharded Pallas block under shard_map
    and through ``decode_block_reference``; return max-abs diffs."""
    from paddle_tpu.kernels.decode_block import (decode_block_reference,
                                                 plan_decode_block)
    from paddle_tpu.kernels.decode_block_tp import tp_fused_block_layer
    mesh = Mesh(np.array(jax.devices()[:tp]), ("mp",))
    B, S = len(pos_list), 32
    KH = max(tp, 2)
    DH, H = 8, 2 * max(tp, 2)
    FF = 24 * tp
    D = H * DH
    rs = np.random.RandomState(0)
    A = lambda *sh: jnp.asarray(rs.randn(*sh), jnp.float32) * 0.1
    x = A(B, 1, D)
    k_slab, v_slab = A(B, S, KH, DH), A(B, S, KH, DH)
    pos = jnp.asarray(pos_list, jnp.int32)
    n1w, n2w = A(D) + 1, A(D) + 1
    n1b = A(D) if norm == "layer" else None
    n2b = A(D) if norm == "layer" else None
    wq, wk, wv = A(D, H * DH), A(D, KH * DH), A(D, KH * DH)
    bq = A(H * DH) if bias else None
    bkv = A(KH * DH) if bias else None
    bv = A(KH * DH) if bias else None
    wo, w1, w2 = A(H * DH, D), A(D, FF), A(FF, D)
    bo = A(D) if bias else None
    b1 = A(FF) if bias else None
    b2 = A(D) if bias else None
    wg = A(D, FF) if gated else None
    if use_rope:
        t = np.random.RandomState(1).rand(B, DH // 2).astype(np.float32)
        cos = jnp.asarray(np.concatenate([np.cos(t), np.cos(t)], -1))
        sin = jnp.asarray(np.concatenate([np.sin(t), np.sin(t)], -1))
    else:
        cos = sin = None
    act = "swiglu" if gated else "gelu_tanh"
    ref, kr, vr = decode_block_reference(
        x, k_slab, v_slab, pos, kv_heads=KH, head_dim=DH, norm=norm,
        eps1=1e-5, eps2=1e-5, norm1_w=n1w, norm1_b=n1b, wq=wq, wk=wk,
        wv=wv, bq=bq, bkv=bkv, bv=bv, wo=wo, bo=bo, norm2_w=n2w,
        norm2_b=n2b, w1=w1, b1=b1, w2=w2, b2=b2, w_gate=wg, act=act,
        rope_cos=cos, rope_sin=sin)
    # the tp_decode_weights bundle layout: per-device head-aligned
    # [q_d | k_d | v_d] QKV columns, [gate_d | up_d] MLP columns
    h_l, kh_l, f_l = H // tp, KH // tp, FF // tp
    qs, kvs = h_l * DH, kh_l * DH
    parts, bparts, mparts, mbparts = [], [], [], []
    for d in range(tp):
        parts += [wq[:, d * qs:(d + 1) * qs],
                  wk[:, d * kvs:(d + 1) * kvs],
                  wv[:, d * kvs:(d + 1) * kvs]]
        if bias:
            bparts += [bq[d * qs:(d + 1) * qs],
                       bkv[d * kvs:(d + 1) * kvs],
                       bv[d * kvs:(d + 1) * kvs]]
        if gated:
            mparts += [wg[:, d * f_l:(d + 1) * f_l],
                       w1[:, d * f_l:(d + 1) * f_l]]
        else:
            mparts += [w1[:, d * f_l:(d + 1) * f_l]]
            if bias:
                mbparts += [b1[d * f_l:(d + 1) * f_l]]
    blk = {"n1w": n1w, "n1b": n1b,
           "wqkv": jnp.concatenate(parts, 1),
           "bqkv": jnp.concatenate(bparts) if bias else None,
           "wo": wo, "bo": bo, "n2w": n2w, "n2b": n2b,
           "wup": jnp.concatenate(mparts, 1),
           "bup": jnp.concatenate(mbparts)
           if (bias and not gated) else None,
           "wdown": w2, "bdown": b2}
    arch = {"norm": norm, "eps": 1e-5, "act": act,
            "heads": H, "kv_heads": KH, "head_dim": DH}
    plan, why = plan_decode_block(
        max_seq=S, hidden=D, heads=H, kv_heads=KH, head_dim=DH, ffn=FF,
        batch=B, itemsize=4, gated=gated, tp=tp)
    assert plan is not None, why
    specs = {"n1w": P(), "n1b": P(), "wqkv": P(None, "mp"),
             "bqkv": P("mp"), "wo": P("mp", None), "bo": P(),
             "n2w": P(), "n2b": P(), "wup": P(None, "mp"),
             "bup": P("mp"), "wdown": P("mp", None), "bdown": P()}
    blk_specs = {k: (None if blk[k] is None else specs[k]) for k in blk}
    rope = (cos, sin) if use_rope else None

    def body(x_s, pk, pv, pos, blk_l):
        return tp_fused_block_layer(x_s, pk, pv, pos, blk_l, arch,
                                    rope, "mp", tp, plan)

    slab = P(None, None, "mp", None)
    f = shard_map(body, mesh=mesh,
                  in_specs=(P("mp", None), slab, slab, P(), blk_specs),
                  out_specs=(P("mp", None), slab, slab),
                  check_vma=False)
    y, k2, v2 = jax.jit(f)(x[:, 0], k_slab, v_slab, pos, blk)
    return (np.abs(np.asarray(y) - np.asarray(ref[:, 0])).max(),
            np.abs(np.asarray(k2) - np.asarray(kr)).max(),
            np.abs(np.asarray(v2) - np.asarray(vr)).max())


@pytest.mark.parametrize("tp", [2, 4])
def test_kernel_parity_gpt_style(tp):
    """LayerNorm + biases + GeLU layer, ragged seq_pos with an EMPTY
    slot (0) and a FULL slot (== S: last-row overwrite lifecycle)."""
    dy, dk, dv = _layer_case(tp, gated=False, use_rope=False,
                             norm="layer", bias=True,
                             pos_list=[0, 3, 7, 32])
    assert dy < 2e-5 and dk < 1e-6 and dv < 1e-6, (dy, dk, dv)


@pytest.mark.parametrize("tp", [2, 4])
def test_kernel_parity_llama_style(tp):
    """RMSNorm + GQA + rotary + SwiGLU layer (the bundle's fused
    [gate|up] columns), same ragged lifecycle positions."""
    dy, dk, dv = _layer_case(tp, gated=True, use_rope=True, norm="rms",
                             bias=False, pos_list=[0, 3, 7, 32])
    assert dy < 2e-5 and dk < 1e-6 and dv < 1e-6, (dy, dk, dv)


# ------------------------------------------------ plan / refusal matrix

def test_plan_tp_budget_shrinks_then_refuses():
    """The per-shard plan shrinks the kv tile and the ring tiles under
    a tightening budget, and refuses with a 'vmem:' reason when even
    the minimum tiles bust it."""
    from paddle_tpu.kernels.decode_block import plan_decode_block
    kw = dict(max_seq=2048, hidden=1024, heads=16, kv_heads=4,
              head_dim=64, ffn=4096, batch=8, itemsize=4, tp=4)
    full, why = plan_decode_block(**kw)
    assert full is not None, why
    small, why = plan_decode_block(vmem_budget=600 * 1024, **kw)
    assert small is not None, why
    assert small["block_k"] <= full["block_k"]
    assert small["block_up"] <= full["block_up"]
    assert small["vmem_entry"] <= 600 * 1024
    assert small["vmem_exit"] <= 600 * 1024
    tiny, reason = plan_decode_block(vmem_budget=16 * 1024, **kw)
    assert tiny is None and "vmem:" in reason


def test_fusion_legal_tp_refusal_matrix():
    """Every divisibility gate names itself — these strings are the
    docs/serving.md fallback-matrix rows for the conditional
    tensor_parallel entry."""
    from paddle_tpu.kernels.decode_block import fusion_legal
    base = dict(max_seq=128, hidden=64, heads=4, kv_heads=2,
                head_dim=16, ffn=128, batch=4, dtype=jnp.float32)
    ok, reason = fusion_legal(tp=2, **base)
    assert ok and reason is None
    ok, reason = fusion_legal(tp=4, **base)
    assert not ok and "kv_heads 2" in reason
    ok, reason = fusion_legal(tp=2, **dict(base, batch=3))
    assert not ok and "batch 3" in reason
    ok, reason = fusion_legal(tp=2, **dict(base, ffn=129))
    assert not ok and "ffn 129" in reason


def test_resolve_chain_tp_legs():
    """resolve_fused_decode(tp=...): model-surface and routing legs on
    top of the legality — a model without the TP bundle refuses with
    the bundle reason; FLAGS_pallas_routing=never still wins."""
    from paddle_tpu.core.flags import flags
    from paddle_tpu.kernels.decode_block import resolve_fused_decode
    m = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    ok, reason = resolve_fused_decode(m, batch=4, kv_len=128, tp=2)
    assert ok and reason is None

    class NoBundle:
        fused_decode_supported = m.fused_decode_supported
        fused_decode_step = m.fused_decode_step
    ok, reason = resolve_fused_decode(NoBundle(), batch=4, kv_len=128,
                                      tp=2)
    assert not ok and "tp_decode_weights" in reason
    # a model with no fused surface at all says so, at any degree
    ok, reason = resolve_fused_decode(object(), batch=4, kv_len=64)
    assert not ok and "fused_decode_step" in reason
    old = flags.pallas_routing
    flags.pallas_routing = "never"
    try:
        ok, reason = resolve_fused_decode(m, batch=4, kv_len=128, tp=2)
        assert not ok and reason == "FLAGS_pallas_routing=never"
    finally:
        flags.pallas_routing = old


def test_collective_fusion_off_refuses_block_with_reason():
    """collective_fusion=False forces serialized collectives — the
    sharded block's rings ARE fused collectives, so the engine refuses
    it with an explicit reason and keeps serving (GSPMD rung)."""
    m = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    toks, eng = _serve(m, 2, fused_decode=True, collective_fusion=False)
    assert eng.decode_path == "unfused"
    assert "collective_fusion" in eng.decode_fallback_reason
    base, _ = _serve(_fresh(lambda: GPTForCausalLM(gpt_tiny())), 1)
    assert toks == base


# ------------------------------------------------- engine parity matrix

def test_gpt_engine_parity_matrix():
    """GPT at tp in {2, 4}: the sharded block engages
    (decode_fallback_reason None) and matches the tp=1 composed, tp=1
    fused AND tp>1 composed engines token-for-token, greedy."""
    mk = lambda: GPTForCausalLM(gpt_tiny())
    base, _ = _serve(_fresh(mk), 1)
    base_f, e1f = _serve(_fresh(mk), 1, fused_decode=True)
    assert e1f.decode_path == "fused" and base_f == base
    for tp in (2, 4):
        comp, ec = _serve(_fresh(mk), tp)
        assert ec.decode_path == "tp_fused" and comp == base
        toks, eng = _serve(_fresh(mk), tp, fused_decode=True)
        assert eng.decode_path == "tp_fused_block"
        assert eng.decode_fallback_reason is None
        assert eng.tp_fusion_reason is None
        assert toks == base


def test_gpt_engine_seeded_sampling_parity():
    mk = lambda: GPTForCausalLM(gpt_tiny())
    base, _ = _serve(_fresh(mk), 1, sampling=SAMPLED)
    toks, eng = _serve(_fresh(mk), 4, sampling=SAMPLED,
                       fused_decode=True)
    assert eng.decode_path == "tp_fused_block"
    assert toks == base


def test_llama_gqa_engine_parity():
    """Llama GQA (2 kv heads -> tp=2 is the deepest legal mesh):
    greedy + seeded through the sharded block."""
    mk = lambda: LlamaForCausalLM(llama_tiny())
    base_g, _ = _serve(_fresh(mk), 1)
    base_s, _ = _serve(_fresh(mk), 1, sampling=SAMPLED)
    toks_g, eng = _serve(_fresh(mk), 2, fused_decode=True)
    assert eng.decode_path == "tp_fused_block"
    assert eng.decode_fallback_reason is None
    assert toks_g == base_g
    toks_s, _ = _serve(_fresh(mk), 2, sampling=SAMPLED,
                       fused_decode=True)
    assert toks_s == base_s


def test_compile_pin_tp_fused_block():
    """The sharded Pallas block must not change the compiled-program
    SET: mixed lengths + cache hits + chunked prefill at tp=2 with the
    fused path still lower {chunk} + pow2 tails, ONE decode, ONE block
    gather, ONE block scatter."""
    m = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    eng = ServingEngine(m, num_slots=4, min_bucket=8, prefill_chunk=16,
                        block_len=16, tensor_parallel=2,
                        fused_decode=True)
    assert eng.decode_path == "tp_fused_block"
    prompts = _prompts(1, (3, 9, 17, 33, 50))
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    eng.run_until_complete(500)
    rids.append(eng.submit(prompts[-1].copy(), max_new_tokens=3))
    eng.run_until_complete(100)
    assert all(eng.result(r).finished for r in rids)
    core = eng.core
    assert core.trace_counts["decode"] == 1
    assert core.trace_counts["prefill"] == 2       # 16 (chunk) + 8
    assert core.block_pool.trace_counts == {"gather": 1, "scatter": 1}


def test_obs_event_carries_tp_dimension():
    """The decode_block obs event gains the mesh degree (it says which
    path the decode phases' histograms timed), and fused TP steps feed
    serving.phase.decode_dispatch_s like every other path."""
    m = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    eng = ServingEngine(m, num_slots=2, tensor_parallel=2,
                        fused_decode=True)
    eng.serve_batch(_prompts(lengths=(4, 9)), max_new_tokens=3)
    evs = eng.core.metrics.tracer.events("decode_block")
    assert len(evs) == 1
    attrs = evs[0][3]
    assert attrs["active"] is True
    assert attrs["tp"] == 2
    assert attrs["reason"] == ""
    assert eng.registry.get("serving.phase.decode_dispatch_s").count > 0
    # a composed tp engine still reports active=False at its degree
    m2 = _fresh(lambda: GPTForCausalLM(gpt_tiny()))
    e2 = ServingEngine(m2, num_slots=2, tensor_parallel=2)
    e2.serve_batch(_prompts(lengths=(4,)), max_new_tokens=2)
    evs2 = e2.core.metrics.tracer.events("decode_block")
    attrs2 = evs2[0][3]
    assert attrs2["active"] is False
    assert attrs2["tp"] == 2
    assert e2.registry.get("serving.phase.decode_dispatch_s").count > 0
