"""Fused decode-block megakernel (kernels/decode_block.py) — ISSUE 7.

The load-bearing contracts:
  * kernel parity: the Pallas pair (attention block + proj/MLP block)
    matches the composed-op reference numerically at fp32 AND bf16, GQA
    included, over ragged per-slot ``seq_pos`` including empty (pos=0)
    and full (pos=S) slots — and the in-kernel KV append lands exactly
    where ``append_kv`` would put it;
  * VMEM planning: ``plan_decode_block`` shrinks tiles under a budget
    and REFUSES (with a reason) when the irreducible residents cannot
    fit, which ``fusion_legal``/the engine surface as the fallback;
  * engine e2e: with ``fused_decode=True`` the engine is token-for-token
    identical to the unfused path for greedy and seeded sampling on GPT
    and Llama (GQA) f32 configs, the program set stays {chunk} + buckets
    + ONE decode, and the obs event/histogram mark the fused path.

Every kernel call here runs under ``interpret=True`` (the CPU default),
so the whole contract — including the manual DMA append and the aliased
slab update — is exercised on every tier-1 CPU run.

Named ``test_zz_*`` ON PURPOSE (same reason as test_zz_bench_projection):
this container's jaxlib-0.4 pin has the timing-dependent CPU crasher
conftest.py documents, and ``test_decode_block.py``'s natural sort
position — immediately before ``test_dist_*`` — reproducibly segfaulted
``test_dist_checkpoint`` by inserting heavy Pallas-interpret work right
before the fragile distributed window.  Sorting last keeps that window's
order byte-identical to the pre-PR suite.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.decode_block import (decode_block_layer,
                                             decode_block_reference,
                                             decode_block_route,
                                             fusion_legal,
                                             plan_decode_block)
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM, gpt_tiny)
from paddle_tpu.models.llama import llama_tiny
from paddle_tpu.serving import SamplingParams, ServingEngine, bucket_length


# ------------------------------------------------------ kernel-level parity

def _gpt_layer_weights(rs, d, ffn, dtype):
    A = lambda *s: jnp.asarray(rs.randn(*s), dtype) * 0.08
    return dict(norm="layer", eps1=1e-5, eps2=1e-5,
                norm1_w=A(d) + 1, norm1_b=A(d),
                wq=A(d, d), wk=A(d, d), wv=A(d, d),
                bq=A(d), bkv=A(d), bv=A(d),
                wo=A(d, d), bo=A(d),
                norm2_w=A(d) + 1, norm2_b=A(d),
                w1=A(d, ffn), b1=A(ffn), w2=A(ffn, d), b2=A(d),
                act="gelu_tanh")


def _llama_layer_weights(rs, d, h, kh, dh, ffn, dtype):
    A = lambda *s: jnp.asarray(rs.randn(*s), dtype) * 0.08
    return dict(norm="rms", eps1=1e-5, eps2=1e-5,
                norm1_w=A(d) + 1, norm1_b=None,
                wq=A(d, h * dh), wk=A(d, kh * dh), wv=A(d, kh * dh),
                bq=None, bkv=None, bv=None,
                wo=A(h * dh, d), bo=None,
                norm2_w=A(d) + 1, norm2_b=None,
                w1=A(d, ffn), b1=None, w2=A(ffn, d), b2=None,
                w_gate=A(d, ffn))


def _run_both(x, k, v, pos, kv_heads, head_dim, kw):
    y, k2, v2 = decode_block_layer(x, k, v, pos, kv_heads=kv_heads,
                                   head_dim=head_dim, **kw)
    yr, k2r, v2r = decode_block_reference(x, k, v, pos, kv_heads=kv_heads,
                                          head_dim=head_dim, **kw)
    return (y, k2, v2), (yr, k2r, v2r)


def test_parity_fp32_gpt_shape_ragged_pos():
    """LayerNorm + biases + gelu_tanh (the GPT block wiring), MHA, over
    ragged positions including an EMPTY slot (pos=0: attends only its
    ride-along token) and a FULL slot (pos=S: overwrites the last row,
    exactly dynamic_update_slice's clamp)."""
    rs = np.random.RandomState(0)
    B, S, H, Dh = 4, 64, 4, 16
    D = H * Dh
    x = jnp.asarray(rs.randn(B, 1, D), jnp.float32) * 0.1
    k = jnp.asarray(rs.randn(B, S, H, Dh), jnp.float32) * 0.1
    v = jnp.asarray(rs.randn(B, S, H, Dh), jnp.float32) * 0.1
    pos = jnp.asarray([0, 17, 63, 64], jnp.int32)   # empty..full
    kw = _gpt_layer_weights(rs, D, 4 * D, jnp.float32)
    (y, k2, v2), (yr, k2r, v2r) = _run_both(x, k, v, pos, H, Dh, kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(k2), np.asarray(k2r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v2r),
                               rtol=2e-5, atol=2e-5)


def test_parity_bf16_gqa_rope():
    """bf16 storage, GQA (2 q heads per kv head), rotary in matrix form,
    SwiGLU — the Llama wiring.  Both sides accumulate in f32 and store
    the appended K/V in bf16, so the slabs match EXACTLY and the
    activation matches to bf16 resolution."""
    rs = np.random.RandomState(1)
    B, S, H, KH, Dh = 3, 32, 4, 2, 16
    D, F = H * Dh, 176
    dt = jnp.bfloat16
    x = jnp.asarray(rs.randn(B, 1, D), dt) * 0.1
    k = jnp.asarray(rs.randn(B, S, KH, Dh), dt) * 0.1
    v = jnp.asarray(rs.randn(B, S, KH, Dh), dt) * 0.1
    pos = jnp.asarray([0, 9, 31], jnp.int32)
    ang = rs.rand(B, Dh // 2).astype(np.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    kw = _llama_layer_weights(rs, D, H, KH, Dh, F, dt)
    kw.update(rope_cos=cos, rope_sin=sin)
    (y, k2, v2), (yr, k2r, v2r) = _run_both(x, k, v, pos, KH, Dh, kw)
    np.testing.assert_array_equal(np.asarray(k2).view(np.uint16),
                                  np.asarray(k2r).view(np.uint16))
    np.testing.assert_array_equal(np.asarray(v2).view(np.uint16),
                                  np.asarray(v2r).view(np.uint16))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_parity_mixed_biases_and_scalar_pos():
    """Each bias is INDEPENDENTLY optional (bq+bo set, bkv/bv/b1/b2
    None must neither crash nor silently zero the set ones), and a
    0-d ``seq_pos`` — the single-request ``decode_step`` cache shape —
    broadcasts to the per-slot vector."""
    rs = np.random.RandomState(7)
    B, S, H, KH, Dh, F = 2, 32, 4, 2, 16, 64
    D = H * Dh
    A = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32) * 0.08
    kw = dict(norm="layer", eps1=1e-5, eps2=1e-5,
              norm1_w=A(D) + 1, norm1_b=A(D),
              wq=A(D, H * Dh), wk=A(D, KH * Dh), wv=A(D, KH * Dh),
              bq=A(H * Dh), bkv=None, bv=None,
              wo=A(H * Dh, D), bo=A(D),
              norm2_w=A(D) + 1, norm2_b=A(D),
              w1=A(D, F), b1=None, w2=A(F, D), b2=A(D))
    x = A(B, 1, D)
    k = A(B, S, KH, Dh)
    v = A(B, S, KH, Dh)
    pos = jnp.asarray([3, 17], jnp.int32)
    (y, k2, v2), (yr, k2r, v2r) = _run_both(x, k, v, pos, KH, Dh, kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(k2), np.asarray(k2r),
                               rtol=2e-5, atol=2e-5)
    # scalar seq_pos == uniform vector seq_pos
    ys, ks, vs = decode_block_layer(x, k, v, jnp.asarray(5, jnp.int32),
                                    kv_heads=KH, head_dim=Dh, **kw)
    yv, kvv, vv = decode_block_layer(x, k, v, jnp.full((B,), 5, jnp.int32),
                                     kv_heads=KH, head_dim=Dh, **kw)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(yv))
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(kvv))


def test_kv_append_lands_at_slot_position():
    """The in-kernel DMA writes each slot's fresh K/V row at exactly
    ``min(pos, S-1)`` and touches nothing else."""
    rs = np.random.RandomState(2)
    B, S, KH, Dh = 3, 16, 2, 16
    H, D = 2, 32
    x = jnp.asarray(rs.randn(B, 1, D), jnp.float32) * 0.1
    k0 = jnp.asarray(rs.randn(B, S, KH, Dh), jnp.float32)
    v0 = jnp.asarray(rs.randn(B, S, KH, Dh), jnp.float32)
    pos = jnp.asarray([0, 5, 16], jnp.int32)
    kw = _llama_layer_weights(rs, D, H, KH, Dh, 64, jnp.float32)
    (y, k2, v2), (yr, k2r, v2r) = _run_both(x, k0, v0, pos, KH, Dh, kw)
    for b, p in enumerate([0, 5, 15]):                # 16 clamps to 15
        assert not np.allclose(np.asarray(k2)[b, p], np.asarray(k0)[b, p])
        untouched = np.delete(np.asarray(k2)[b], p, axis=0)
        np.testing.assert_array_equal(
            untouched, np.delete(np.asarray(k0)[b], p, axis=0))
    np.testing.assert_allclose(np.asarray(k2), np.asarray(k2r),
                               rtol=1e-6, atol=1e-6)


def test_block_k_tiling_matches_untiled():
    """Forcing a small streaming tile (block_k) changes the loop
    schedule, never the result."""
    rs = np.random.RandomState(3)
    B, S, KH, Dh, H = 2, 64, 2, 16, 2
    D = H * Dh
    x = jnp.asarray(rs.randn(B, 1, D), jnp.float32) * 0.1
    k = jnp.asarray(rs.randn(B, S, KH, Dh), jnp.float32) * 0.1
    v = jnp.asarray(rs.randn(B, S, KH, Dh), jnp.float32) * 0.1
    pos = jnp.asarray([33, 64], jnp.int32)
    kw = _llama_layer_weights(rs, D, H, KH, Dh, 64, jnp.float32)
    y_a, k_a, _ = decode_block_layer(x, k, v, pos, kv_heads=KH,
                                     head_dim=Dh, block_k=8, **kw)
    y_b, k_b, _ = decode_block_layer(x, k, v, pos, kv_heads=KH,
                                     head_dim=Dh, block_k=64, **kw)
    np.testing.assert_allclose(np.asarray(y_a), np.asarray(y_b),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(k_a), np.asarray(k_b))


# ------------------------------------------------- VMEM planning / legality

def test_plan_shrinks_tiles_under_budget():
    base = dict(max_seq=8192, hidden=1024, heads=8, kv_heads=8,
                head_dim=128, ffn=4096, batch=8, itemsize=2)
    roomy, why = plan_decode_block(vmem_budget=12 << 20, **base)
    tight, why2 = plan_decode_block(vmem_budget=5 << 20, **base)
    assert why is None and why2 is None
    assert tight["block_k"] < roomy["block_k"] or \
        tight["block_f"] < roomy["block_f"]
    assert tight["vmem_attn"] <= 5 << 20
    assert tight["vmem_mlp"] <= 5 << 20


def test_plan_refuses_when_residents_cannot_fit():
    plan, why = plan_decode_block(
        max_seq=8192, hidden=4096, heads=32, kv_heads=32, head_dim=128,
        ffn=16384, batch=8, itemsize=2, vmem_budget=1 << 20)
    assert plan is None and "vmem" in why
    ok, reason = fusion_legal(
        max_seq=8192, hidden=4096, heads=32, kv_heads=32, head_dim=128,
        ffn=16384, batch=8, dtype="bfloat16", vmem_budget=1 << 20)
    assert not ok and "vmem" in reason


def test_fusion_legal_shape_and_dtype_refusals():
    base = dict(max_seq=64, hidden=64, heads=4, kv_heads=2, head_dim=16,
                ffn=176, batch=2)
    ok, _ = fusion_legal(dtype="float32", gated=True, **base)
    assert ok
    ok, reason = fusion_legal(dtype="float16", **base)
    assert not ok and "float16" in reason
    ok, reason = fusion_legal(max_seq=64, hidden=64, heads=3, kv_heads=2,
                              head_dim=16, ffn=176, batch=2,
                              dtype="float32")
    assert not ok


def test_route_respects_pallas_never_flag():
    from paddle_tpu.core.flags import flags
    old = flags.pallas_routing
    try:
        flags.pallas_routing = "never"
        ok, reason = decode_block_route(64)
        assert not ok and "never" in reason
        flags.pallas_routing = "auto"
        ok, reason = decode_block_route(64)
        assert ok and reason is None
    finally:
        flags.pallas_routing = old


# --------------------------------------------------------- engine e2e parity

@pytest.fixture(scope="module")
def gpt():
    with jax.default_prng_impl("rbg"):
        return GPTForCausalLM(gpt_tiny())


@pytest.fixture(scope="module")
def llama():
    with jax.default_prng_impl("rbg"):
        return LlamaForCausalLM(llama_tiny())


def _serve(model, fused, sampled, lengths=(5, 11, 3), n_new=8):
    rs = np.random.RandomState(3)
    eng = ServingEngine(model, num_slots=3, max_seq=64, min_bucket=8,
                        fused_decode=fused)
    hs = []
    for i, L in enumerate(lengths):
        sp = SamplingParams(do_sample=True, temperature=0.9, top_k=40,
                            seed=7 + i) if sampled else None
        hs.append(eng.submit(rs.randint(0, 256, (L,)),
                             max_new_tokens=n_new, sampling=sp))
    eng.run_until_complete(max_steps=300)
    toks = {h: list(eng.result(h).tokens) for h in hs}
    return toks, eng


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_engine_parity_gpt(gpt, sampled):
    a, ea = _serve(gpt, False, sampled)
    b, eb = _serve(gpt, True, sampled)
    assert ea.core.decode_path == "unfused"
    assert eb.core.decode_path == "fused"
    assert eb.core.decode_fallback_reason is None
    assert a == b


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_engine_parity_llama_gqa(llama, sampled):
    a, ea = _serve(llama, False, sampled)
    b, eb = _serve(llama, True, sampled)
    assert eb.core.decode_path == "fused"
    assert a == b


def test_engine_fallback_keeps_serving(gpt):
    """A model the kernel cannot fuse (fp16) still serves: the engine
    resolves to the unfused path, records the reason, and the output
    matches the flag-off run token-for-token (it IS the same program)."""
    with jax.default_prng_impl("rbg"):
        m16 = GPTForCausalLM(gpt_tiny(dtype="float16"))
    m16.to(dtype="float16")
    a, ea = _serve(m16, False, False, lengths=(5, 9), n_new=6)
    b, eb = _serve(m16, True, False, lengths=(5, 9), n_new=6)
    assert eb.core.decode_path == "unfused"
    assert "float16" in eb.core.decode_fallback_reason
    assert a == b


# ------------------------------------------------- compile-count / telemetry

def test_compile_count_pins_one_decode_with_fused_path(gpt):
    """The fused flag must not change the program set: {chunk} + pow2
    buckets + ONE decode (the single-compiled-program discipline the
    whole engine is built around)."""
    lengths = (3, 5, 8, 9, 13, 17, 20, 31, 6, 11)
    buckets = {bucket_length(L, 8, 64) for L in lengths}
    rs = np.random.RandomState(6)
    eng = ServingEngine(gpt, num_slots=3, max_seq=64, min_bucket=8,
                        fused_decode=True)
    rids = [eng.submit(rs.randint(0, 256, (L,)),
                       max_new_tokens=3 + (i % 3))
            for i, L in enumerate(lengths)]
    eng.run_until_complete(500)
    assert all(eng.result(r).finished for r in rids)
    assert eng.core.decode_path == "fused"
    assert eng.core.trace_counts["decode"] == 1
    assert eng.core.trace_counts["prefill"] == len(buckets)


def test_obs_event_and_histogram_mark_fused_path(gpt):
    toks, eng = _serve(gpt, True, False)
    evs = eng.core.metrics.tracer.events("decode_block")
    assert len(evs) == 1
    attrs = evs[0][3]
    assert attrs["active"] is True and attrs["reason"] == ""
    # the fused block streams the slab in place by construction
    assert attrs["attention_route"] == "slab_in_place"
    assert attrs["attention_reason"] == ""
    # ... and appends the fresh row inside its attention kernel
    assert attrs["kv_append"] == "in_kernel"
    assert attrs["kv_append_reason"] == ""
    assert eng.core.kv_append() == ("in_kernel", None)
    # the fused dispatch is timed by the decode phase's histogram, the
    # event says which path that was
    assert eng.registry.get("serving.phase.decode_dispatch_s").count > 0
    # unfused engine: event says inactive, the same phase is timed
    toks2, eng2 = _serve(gpt, False, False)
    evs2 = eng2.core.metrics.tracer.events("decode_block")
    assert len(evs2) == 1 and evs2[0][3]["active"] is False
    # gpt_tiny's 4 x 16 slab rows are no window Mosaic can address: the
    # route and its reason (the compiler's words) ride the same event
    assert evs2[0][3]["attention_route"] == "head_major_copy"
    assert "head_dim 16" in evs2[0][3]["attention_reason"]
    assert eng2.core.attention_route() == (
        "head_major_copy", evs2[0][3]["attention_reason"])
    # the copying kernel writes nothing: kv_cache.append_kv goes first,
    # for the route's own reason
    assert evs2[0][3]["kv_append"] == "xla_scatter"
    assert evs2[0][3]["kv_append_reason"] == \
        evs2[0][3]["attention_reason"]
    assert eng2.core.kv_append() == (
        "xla_scatter", evs2[0][3]["attention_reason"])
    assert eng2.registry.get("serving.phase.decode_dispatch_s").count > 0
