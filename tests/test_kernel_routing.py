"""Empirical Pallas-vs-XLA routing (round-3 VERDICT item 1: the default
path must be the measured winner per kernel and shape)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu
from paddle_tpu.core.flags import flags
from paddle_tpu.kernels.routing import MEASURED, use_pallas


def test_rules_agree_with_measurements():
    """Every measured row's routed choice must be the faster side (>= 1.0
    speedup for pallas-chosen rows, <= 1.02 for xla-chosen ones — ties go
    to XLA)."""
    for (kernel, shape), speedup in MEASURED.items():
        if kernel == "flash_attention":
            chosen = use_pallas(kernel, seq_q=shape, seq_k=shape)
        elif kernel == "decode_attention":
            chosen = use_pallas(kernel, kv_len=shape)
        elif kernel in ("layer_norm", "rms_norm"):
            chosen = use_pallas(kernel, rows=shape[0], h=shape[1])
        else:
            chosen = use_pallas(kernel, n=shape)
        if chosen:
            assert speedup >= 1.0, (kernel, shape, speedup)
        else:
            assert speedup <= 1.02, (kernel, shape, speedup)


def test_flash_seq_threshold():
    assert not use_pallas("flash_attention", seq_q=1024, seq_k=1024)
    assert use_pallas("flash_attention", seq_q=2048, seq_k=2048)
    assert use_pallas("flash_attention", seq_q=8192, seq_k=8192)


def test_decode_kv_threshold():
    assert use_pallas("decode_attention", kv_len=4096)
    assert not use_pallas("decode_attention", kv_len=8192)


def test_norms_route_to_xla():
    assert not use_pallas("layer_norm", rows=8192, h=4096)
    assert not use_pallas("rms_norm", rows=8192, h=4096)


def test_routing_mode_overrides():
    old = flags.pallas_routing
    try:
        flags.pallas_routing = "always"
        assert use_pallas("layer_norm", rows=8, h=128)
        flags.pallas_routing = "never"
        assert not use_pallas("flash_attention", seq_q=8192, seq_k=8192)
    finally:
        flags.pallas_routing = old


def test_decode_auto_reference_parity():
    """The dense routed fallback matches the kernel's semantics exactly
    (variable lengths + causal tail + GQA)."""
    from paddle_tpu.kernels.decode_attention import (
        decode_attention, decode_attention_reference)
    rs = np.random.RandomState(0)
    b, sq, h, kh, d, T = 2, 4, 8, 4, 32, 64
    q = jnp.asarray(rs.randn(b, sq, h, d), jnp.float32)
    kc = jnp.asarray(rs.randn(b, T, kh, d), jnp.float32)
    vc = jnp.asarray(rs.randn(b, T, kh, d), jnp.float32)
    lens = jnp.asarray([17, 64], jnp.int32)
    out_k = decode_attention(q, kc, vc, lens, interpret=True)
    out_r = decode_attention_reference(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_decode_auto_routes_long_cache_to_reference(monkeypatch):
    """On a non-CPU backend the auto wrapper must take the dense path for
    kv > 6144; on CPU it always uses the (interpreted) kernel."""
    import importlib
    da_mod = importlib.import_module("paddle_tpu.kernels.decode_attention")
    calls = []
    monkeypatch.setattr(
        da_mod, "decode_attention_reference",
        lambda *a, **k: calls.append("ref") or jnp.zeros((1, 1, 1, 1)))
    monkeypatch.setattr(
        da_mod, "decode_attention",
        lambda *a, **k: calls.append("kernel") or jnp.zeros((1, 1, 1, 1)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 1, 1, 32))
    kc = jnp.zeros((1, 8192, 1, 32))
    da_mod.decode_attention_auto(q, kc, kc, jnp.zeros((1,), jnp.int32))
    assert calls == ["ref"]
    kc_small = jnp.zeros((1, 4096, 1, 32))
    da_mod.decode_attention_auto(q, kc_small, kc_small,
                                 jnp.zeros((1,), jnp.int32))
    assert calls == ["ref", "kernel"]


def test_fused_adamw_large_tensor_block_cap():
    """Block auto-pick shrinks for very large tensors (the 64M 8192-row
    tile blew Mosaic scoped vmem on chip) but correctness is unchanged."""
    from paddle_tpu.kernels import fused_adamw_update
    rs = np.random.RandomState(1)
    n = 256 * 1024
    p = jnp.asarray(rs.randn(n), jnp.float32)
    g = jnp.asarray(rs.randn(n), jnp.float32)
    m = jnp.zeros(n)
    v = jnp.zeros(n)
    p2, m2, v2 = fused_adamw_update(p, g, m, v, 1, 1e-3, interpret=True)
    ref_m = 0.1 * g
    ref_v = 0.001 * g * g
    ref_p = p - 1e-3 * (ref_m / (1 - 0.9)
                        / (jnp.sqrt(ref_v / (1 - 0.999)) + 1e-8))
    np.testing.assert_allclose(np.asarray(p2), np.asarray(ref_p),
                               rtol=1e-6, atol=1e-6)


def test_norm_block_picker_vmem_cap():
    """The block's f32 working copy stays within 2 MiB: at 4 MiB (256
    rows of h=4096) the BACKWARD kernel's scoped VMEM is 22 MiB against
    Mosaic's 16 MiB limit and does not compile
    (tests/test_chip_compile.py compiles both at this shape)."""
    from paddle_tpu.kernels.fused_norm import _flatten_and_pick_block
    x = jnp.zeros((4096, 8192), jnp.bfloat16)
    _, block = _flatten_and_pick_block(x)
    assert block > 0
    assert block * 8192 * 4 <= 2 * 1024 * 1024
    x2 = jnp.zeros((8192, 4096), jnp.bfloat16)
    _, block2 = _flatten_and_pick_block(x2)
    assert block2 == 128


def test_route_refuses_where_xla_would_partition_the_kernel():
    """XLA cannot partition a Mosaic custom call: under a mesh axis
    larger than one that is not manual, every route answers no — with
    the compiler's words — and inside a shard_map over that axis it
    answers from the table again."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.kernels.routing import partition_refusal, use_pallas
    assert partition_refusal() is None
    assert use_pallas("flash_attention", seq_q=4096, seq_k=4096)
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    seen = {}

    def body(x):
        seen["manual"] = (partition_refusal(),
                          use_pallas("flash_attention", seq_q=4096,
                                     seq_k=4096))
        return x

    with jax.set_mesh(mesh):
        why = partition_refusal()
        assert "['mp']" in why and "cannot be automatically partitioned" in why
        assert not use_pallas("flash_attention", seq_q=4096, seq_k=4096)
        assert not use_pallas("decode_attention", kv_len=2048)
        jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("mp"),
                              out_specs=P("mp")))(jnp.zeros(4))
    assert seen["manual"] == (None, True)
    # a one-device mesh partitions nothing
    with jax.set_mesh(Mesh(np.array(jax.devices()[:1]), ("mp",))):
        assert partition_refusal() is None
