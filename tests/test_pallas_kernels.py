"""Pallas kernel tier tests (interpret mode on the CPU test platform —
same kernel code compiles on TPU).

Oracle pattern follows the reference's OpTest: kernel output vs reference
implementation, plus gradient checks against jax.grad of the reference
(SURVEY.md §4 — check_output/check_grad)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.kernels import (flash_attention, flash_attention_with_lse,
                                fused_adamw_update, fused_rms_norm_pallas)
from paddle_tpu.nn.functional.attention import sdpa_reference


def _qkv(b=2, s=128, h=2, d=64, kh=None, seed=0, dtype=np.float32):
    rs = np.random.RandomState(seed)
    kh = kh or h
    q = rs.randn(b, s, h, d).astype(dtype) * 0.5
    k = rs.randn(b, s, kh, d).astype(dtype) * 0.5
    v = rs.randn(b, s, kh, d).astype(dtype) * 0.5
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = sdpa_reference(q, k, v, is_causal=causal, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_uneven_blocks():
    # seq not a multiple of 128 -> block-size fallback path
    q, k, v = _qkv(s=96)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = sdpa_reference(q, k, v, is_causal=True, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_gqa():
    q, k, v = _qkv(h=4, kh=2)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = sdpa_reference(q, k, v, is_causal=True, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grad(causal):
    q, k, v = _qkv(b=1, s=64, h=2, d=32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, is_causal=causal,
                                      training=False) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_flash_attention_lse():
    q, k, v = _qkv(b=1, s=64, h=2, d=32)
    out, lse = flash_attention_with_lse(q, k, v, causal=False,
                                        interpret=True)
    # lse must equal logsumexp of scaled logits
    d = q.shape[-1]
    logits = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k))
    logits = logits / np.sqrt(d)
    ref_lse = np.log(np.exp(logits).sum(-1))
    np.testing.assert_allclose(np.asarray(lse), ref_lse, rtol=1e-4,
                               atol=1e-5)


def test_fused_adamw_matches_reference():
    rs = np.random.RandomState(0)
    p = jnp.asarray(rs.randn(37, 19).astype(np.float32))  # odd size -> pad
    g = jnp.asarray(rs.randn(37, 19).astype(np.float32))
    m = jnp.asarray(rs.randn(37, 19).astype(np.float32) * 0.1)
    v = jnp.asarray(np.abs(rs.randn(37, 19)).astype(np.float32) * 0.01)
    lr, b1, b2, eps, wd, t = 1e-3, 0.9, 0.999, 1e-8, 0.01, 7

    new_p, new_m, new_v = fused_adamw_update(p, g, m, v, t, lr, b1, b2, eps,
                                             wd, interpret=True)
    # numpy reference (paddle adamw semantics: decoupled decay)
    rm = b1 * np.asarray(m) + (1 - b1) * np.asarray(g)
    rv = b2 * np.asarray(v) + (1 - b2) * np.asarray(g) ** 2
    mhat = rm / (1 - b1 ** t)
    vhat = rv / (1 - b2 ** t)
    rp = np.asarray(p) - lr * (mhat / (np.sqrt(vhat) + eps) + wd * np.asarray(p))
    np.testing.assert_allclose(np.asarray(new_p), rp, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_m), rm, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_v), rv, rtol=1e-6, atol=1e-6)


def test_fused_adamw_bf16_param():
    rs = np.random.RandomState(1)
    p = jnp.asarray(rs.randn(16, 128).astype(np.float32)).astype(jnp.bfloat16)
    g = jnp.asarray(rs.randn(16, 128).astype(np.float32)).astype(jnp.bfloat16)
    m = jnp.zeros((16, 128), jnp.float32)
    v = jnp.zeros((16, 128), jnp.float32)
    new_p, new_m, new_v = fused_adamw_update(p, g, m, v, 1, 1e-2,
                                             interpret=True)
    assert new_p.dtype == jnp.bfloat16
    assert new_m.dtype == jnp.float32
    assert np.isfinite(np.asarray(new_p, dtype=np.float32)).all()


def test_fused_rms_norm_forward_and_grad():
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(6, 5, 64).astype(np.float32))
    w = jnp.asarray(rs.randn(64).astype(np.float32))

    out = fused_rms_norm_pallas(x, w, 1e-5, interpret=True)

    def ref(x, w):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-5) * w

    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, w)),
                               rtol=1e-5, atol=1e-5)

    gp = jax.grad(lambda x, w: jnp.sum(
        fused_rms_norm_pallas(x, w, 1e-5, interpret=True) ** 2),
        argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x, w: jnp.sum(ref(x, w) ** 2), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gr[1]),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_jit_composes():
    q, k, v = _qkv(b=1, s=64, h=2, d=32)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True)

    out = f(q, k, v)
    ref = sdpa_reference(q, k, v, is_causal=True, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_adamw_optimizer_matches_adamw():
    import paddle_tpu
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.nn.functional_call import state

    paddle_tpu.seed(0)
    model = nn.Linear(16, 128)
    params, _ = state(model)
    rs = np.random.RandomState(0)
    grads = {k: jnp.asarray(rs.randn(*v.shape).astype(np.float32))
             for k, v in params.items()}

    o1 = opt.AdamW(learning_rate=1e-2, weight_decay=0.01)
    o2 = opt.FusedAdamW(learning_rate=1e-2, weight_decay=0.01)
    s1, s2 = o1.init(params), o2.init(params)
    p1, p2 = dict(params), dict(params)
    for _ in range(3):
        p1, s1 = o1.update(grads, s1, p1)
        p2, s2 = o2.update(grads, s2, p2)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


@pytest.mark.parametrize("sq,sk", [(64, 128), (32, 96)])
def test_flash_attention_causal_cross_length(sq, sk):
    # bottom-right-aligned causal mask for seq_q != seq_k must match the
    # sdpa_reference convention (ADVICE r1: mask was top-left aligned)
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(2, sq, 2, 64).astype(np.float32) * 0.5)
    k = jnp.asarray(rs.randn(2, sk, 2, 64).astype(np.float32) * 0.5)
    v = jnp.asarray(rs.randn(2, sk, 2, 64).astype(np.float32) * 0.5)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = sdpa_reference(q, k, v, is_causal=True, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_causal_cross_length_grad():
    rs = np.random.RandomState(8)
    q = jnp.asarray(rs.randn(1, 64, 2, 64).astype(np.float32) * 0.5)
    k = jnp.asarray(rs.randn(1, 128, 2, 64).astype(np.float32) * 0.5)
    v = jnp.asarray(rs.randn(1, 128, 2, 64).astype(np.float32) * 0.5)

    def f(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_fa = jax.grad(f(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f(lambda q, k, v: sdpa_reference(
        q, k, v, is_causal=True, training=False)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_with_lse_gqa():
    # kv heads < q heads must be repeated, not crash (ADVICE r1)
    q, k, v = _qkv(h=4, kh=2)
    out, lse = flash_attention_with_lse(q, k, v, causal=True, interpret=True)
    ref = sdpa_reference(q, k, v, is_causal=True, training=False)
    assert lse.shape == (q.shape[0], q.shape[2], q.shape[1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _decode_ref(q, k_cache, v_cache, seq_lens, causal_tail=True):
    b, sq, h, d = q.shape
    s_max = k_cache.shape[1]
    kh = k_cache.shape[2]
    if kh != h:
        k_cache = np.repeat(np.asarray(k_cache), h // kh, axis=2)
        v_cache = np.repeat(np.asarray(v_cache), h // kh, axis=2)
    qn = np.asarray(q, np.float32)
    kn = np.asarray(k_cache, np.float32)
    vn = np.asarray(v_cache, np.float32)
    out = np.zeros((b, sq, h, d), np.float32)
    for bi in range(b):
        L = int(seq_lens[bi])
        for hi in range(h):
            s = qn[bi, :, hi] @ kn[bi, :, hi].T / np.sqrt(d)  # [sq, s_max]
            mask = np.arange(s_max)[None, :] < L
            if causal_tail:
                mask = mask & (np.arange(s_max)[None, :] <=
                               L - sq + np.arange(sq)[:, None])
            s = np.where(mask, s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[bi, :, hi] = p @ vn[bi, :, hi]
    return out


def test_decode_attention_single_token():
    from paddle_tpu.kernels import decode_attention
    rs = np.random.RandomState(0)
    b, s_max, h, d = 3, 256, 4, 64
    q = jnp.asarray(rs.randn(b, 1, h, d).astype(np.float32) * 0.5)
    kc = jnp.asarray(rs.randn(b, s_max, h, d).astype(np.float32) * 0.5)
    vc = jnp.asarray(rs.randn(b, s_max, h, d).astype(np.float32) * 0.5)
    lens = jnp.asarray([17, 256, 130], jnp.int32)
    out = decode_attention(q, kc, vc, lens, block_k=128, interpret=True)
    ref = _decode_ref(q, kc, vc, np.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_decode_attention_chunked_tail_and_gqa():
    from paddle_tpu.kernels import decode_attention
    rs = np.random.RandomState(1)
    b, s_max, h, kh, d, sq = 2, 128, 4, 2, 64, 8
    q = jnp.asarray(rs.randn(b, sq, h, d).astype(np.float32) * 0.5)
    kc = jnp.asarray(rs.randn(b, s_max, kh, d).astype(np.float32) * 0.5)
    vc = jnp.asarray(rs.randn(b, s_max, kh, d).astype(np.float32) * 0.5)
    lens = jnp.asarray([40, 128], jnp.int32)
    out = decode_attention(q, kc, vc, lens, block_k=64, interpret=True)
    ref = _decode_ref(q, kc, vc, np.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_decode_attention_matches_model_cache_semantics():
    """Parity vs F.scaled_dot_product_attention with the per-query mask the
    models build for chunked prefill (gpt.py/llama.py decode path)."""
    from paddle_tpu.kernels import decode_attention
    rs = np.random.RandomState(2)
    b, s_max, h, d, sq = 2, 64, 2, 64, 4
    pos = 10                       # cache already holds 10 tokens
    q = jnp.asarray(rs.randn(b, sq, h, d).astype(np.float32) * 0.5)
    kc = jnp.asarray(rs.randn(b, s_max, h, d).astype(np.float32) * 0.5)
    vc = jnp.asarray(rs.randn(b, s_max, h, d).astype(np.float32) * 0.5)
    lens = jnp.full((b,), pos + sq, jnp.int32)
    out = decode_attention(q, kc, vc, lens, block_k=32, interpret=True)
    kpos = jnp.arange(s_max)
    qpos = pos + jnp.arange(sq)
    mask = (kpos[None, None, None, :] <= qpos[None, None, :, None])
    ref = sdpa_reference(q, kc, vc, attn_mask=mask, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _da():
    # ``paddle_tpu.kernels.decode_attention`` names the function the
    # package exports; the module is reached by its path
    import importlib
    return importlib.import_module("paddle_tpu.kernels.decode_attention")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("planes,plane", [(1, 0), (3, 1)])
@pytest.mark.parametrize("sq", [1, 4])
@pytest.mark.parametrize("h,kh", [(32, 32), (32, 8), (8, 2)])
def test_decode_attention_reads_the_slab_in_place(h, kh, sq, planes, plane,
                                                  dtype):
    """The in-place kernel against the dense reference: MHA and GQA (the
    cache never repeated), decode and a causal verify window, ragged
    lengths from an empty slot to a full one, the whole slab and a middle
    plane of a many-plane slab reached by a TRACED first head.  A plane
    of 2 heads is no whole sublane tile: that window keeps the copying
    kernel, and agrees too."""
    da = _da()
    rs = np.random.RandomState(hash((h, kh, sq, planes)) % 2**31)
    b, s_max, d = 4, 64, 128
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rs.randn(b, sq, h, d) * 0.5, dt)
    kc = jnp.asarray(rs.randn(b, s_max, planes * kh, d) * 0.5, dt)
    vc = jnp.asarray(rs.randn(b, s_max, planes * kh, d) * 0.5, dt)
    lens = jnp.asarray([0, sq, 37, s_max], jnp.int32)
    route, why = da.pallas_attention_route(q.shape, kc.shape, dt, kh)
    if planes > 1 and kh % 8:
        assert route == "head_major_copy" and "sublane" in why
    else:
        assert (route, why) == ("slab_in_place", None)
    out = jax.jit(lambda *a: da.decode_attention(
        *a[:4], block_k=32, interpret=True, head0=a[4], kv_heads=kh))(
        q, kc, vc, lens, jnp.asarray(plane * kh, jnp.int32))
    assert out.shape == q.shape and out.dtype == q.dtype
    f32 = lambda x: x.astype(jnp.float32)
    ref = da.decode_attention_reference(f32(q), f32(kc), f32(vc), lens,
                                        head0=plane * kh, kv_heads=kh)
    tol = 2e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(f32(out)), np.asarray(ref),
                               rtol=tol, atol=tol)
    assert not np.asarray(f32(out))[0].any()        # an empty slot reads 0


@pytest.mark.parametrize("sq,h,kh,d,why", [
    (1, 12, 12, 64, "head_dim 64"),         # h768: 12 x 64
    (1, 12, 12, 128, "12 kv heads"),
    (16, 32, 32, 128, "16 query tokens"),   # a prefill chunk: MXU work
    (8, 32, 8, 128, "past the"),            # 8 tokens x 4 query heads
])
def test_decode_attention_keeps_the_copying_kernel(sq, h, kh, d, why):
    """What the in-place kernel does not take, by shape and with the
    reason in words; the head-major kernel serves it (GQA as rows of one
    program, the cache not repeated) and agrees with the reference."""
    da = _da()
    rs = np.random.RandomState(sq + h)
    b, s_max = 2, 64
    q = jnp.asarray(rs.randn(b, sq, h, d).astype(np.float32) * 0.5)
    kc = jnp.asarray(rs.randn(b, s_max, kh, d).astype(np.float32) * 0.5)
    vc = jnp.asarray(rs.randn(b, s_max, kh, d).astype(np.float32) * 0.5)
    lens = jnp.asarray([sq + 3, s_max], jnp.int32)
    route, reason = da.pallas_attention_route(q.shape, kc.shape, kc.dtype)
    assert route == "head_major_copy" and why in reason, reason
    out = da.decode_attention(q, kc, vc, lens, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _decode_ref(q, kc, vc, np.asarray(lens)),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(da.decode_attention_reference(q, kc, vc, lens)),
        _decode_ref(q, kc, vc, np.asarray(lens)), rtol=2e-5, atol=2e-5)


_APPEND_SHAPES = {
    # name: (query heads, kv heads, planes of the slab, the plane)
    "mha32": (32, 32, 1, 0),            # gpt3-6.7b's slab, unwindowed
    "plane_head0_16": (16, 16, 3, 1),   # a middle plane, traced head0
    "gqa_kh8_rep2": (16, 8, 1, 0),
}


def _append_operands(shape, sq, dtype, s_max, b, seed=0):
    h, kh, planes, plane = _APPEND_SHAPES[shape]
    rs = np.random.RandomState(seed + 7 * sq + h + planes)
    dt, d = jnp.dtype(dtype), 128
    draw = lambda *dims: jnp.asarray(rs.randn(*dims) * 0.5, dt)
    return (draw(b, sq, h, d), draw(b, sq, kh, d), draw(b, sq, kh, d),
            draw(b, s_max, planes * kh, d), draw(b, s_max, planes * kh, d),
            plane * kh, kh)


def _check_append(da, ops, pos, sq, dtype, **kw):
    """``append_and_attend`` against ``append_kv`` (the slabs, bit for
    bit) and the dense reference over ITS slabs (the output)."""
    from paddle_tpu.models.kv_cache import append_kv, cache_lens
    q, kn, vn, kc, vc, head0, kh = ops
    b = q.shape[0]
    out, k2, v2 = jax.jit(lambda *a: da.append_and_attend(
        *a[:6], head0=a[6], kv_heads=kh, interpret=True, **kw))(
        q, kn, vn, kc, vc, pos, jnp.asarray(head0, jnp.int32))
    kw_, vw_ = append_kv(kc, vc, kn, vn, pos, head0)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    np.testing.assert_array_equal(f32(k2), f32(kw_))
    np.testing.assert_array_equal(f32(v2), f32(vw_))
    assert k2.dtype == kc.dtype and out.dtype == q.dtype
    outside = np.ones(kc.shape[2], bool)
    outside[head0:head0 + kh] = False
    np.testing.assert_array_equal(f32(k2)[:, :, outside],
                                  f32(kc)[:, :, outside])
    np.testing.assert_array_equal(f32(v2)[:, :, outside],
                                  f32(vc)[:, :, outside])
    ref = da.decode_attention_reference(
        q.astype(jnp.float32), kw_.astype(jnp.float32),
        vw_.astype(jnp.float32), cache_lens(pos, sq, b), head0=head0,
        kv_heads=kh, **kw)
    tol = 2e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(f32(out), np.asarray(ref), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pos", ["per_row", "scalar", "scalar_clamped"])
@pytest.mark.parametrize("sq", [1, 3, 8])
@pytest.mark.parametrize("shape", list(_APPEND_SHAPES))
def test_append_and_attend_writes_the_rows_in_the_kernel(shape, sq, pos,
                                                         dtype):
    """The appending kernel: the returned slabs equal ``append_kv``'s
    bit for bit (no head outside the window touched) and the output is
    the reference's over those slabs, for decode and a causal verify
    window, with slots parked at row 0, on either side of a streamed
    tile's edge, at the last start that fits, and past it (where the
    write clamps to ``S - sq`` as ``dynamic_update_slice`` does and the
    mask follows ``pos``), per row and as one scalar."""
    da = _da()
    s_max, t = 2 * da.SLAB_TILE_ROWS, da.SLAB_TILE_ROWS
    rows = [0, t - 1, t, t + 1, s_max - sq, s_max - sq + 1, s_max + 3]
    ops = _append_operands(shape, sq, dtype, s_max, len(rows))
    q, _, _, kc = ops[:4]
    assert da.decode_attention_route(q.shape, kc.shape, kc.dtype,
                                     ops[-1]) == ("slab_in_place", None)
    at = {"per_row": jnp.asarray(rows, jnp.int32),
          "scalar": jnp.asarray(t - 1, jnp.int32),
          "scalar_clamped": jnp.asarray(s_max - 1, jnp.int32)}[pos]
    _check_append(da, ops, at, sq, dtype)
    if pos == "per_row" and sq == 3:
        _check_append(da, ops, at, sq, dtype, causal_tail=False)
    text = str(jax.make_jaxpr(lambda *a: da.append_and_attend(
        *a, kv_heads=ops[-1], interpret=True))(*ops[:5], at))
    assert "pallas_call" in text
    assert "scatter" not in text and "dynamic_update_slice" not in text


@pytest.mark.parametrize("route", ["head_major_copy_prefill",
                                   "head_major_copy_h64", "xla_dense"])
def test_append_and_attend_keeps_the_xla_append(route, monkeypatch):
    """What the in-place kernel does not take still appends with
    ``append_kv`` ahead of its attention (a scatter per row, one
    ``dynamic_update_slice`` for a scalar ``pos``): a prefill chunk, a
    slab Mosaic cannot window, and ``FLAGS_pallas_routing=never``."""
    from paddle_tpu.core.flags import flags
    da = _da()
    b, s_max = 3, 64
    sq, h, d = {"head_major_copy_prefill": (16, 8, 128),
                "head_major_copy_h64": (1, 12, 64),
                "xla_dense": (1, 8, 128)}[route]
    if route == "xla_dense":
        monkeypatch.setattr(flags, "pallas_routing", "never")
    rs = np.random.RandomState(len(route))
    draw = lambda *dims: jnp.asarray(
        rs.randn(*dims).astype(np.float32) * 0.5)
    ops = (draw(b, sq, h, d), draw(b, sq, h, d), draw(b, sq, h, d),
           draw(b, s_max, h, d), draw(b, s_max, h, d), 0, h)
    got = da.decode_attention_route(ops[0].shape, ops[3].shape,
                                    jnp.float32)[0]
    assert route.startswith(got)
    for pos, op in ((jnp.asarray([0, 9, s_max - sq + 2], jnp.int32),
                     "scatter"),
                    (jnp.asarray(5, jnp.int32), "dynamic_update_slice")):
        _check_append(da, ops, pos, sq, "float32")
        text = str(jax.make_jaxpr(lambda *a: da.append_and_attend(
            *a, interpret=True))(*ops[:5], pos))
        assert op in text, (route, op)
        assert ("pallas_call" in text) == (route != "xla_dense")


def test_flash_attention_varlen_matches_dense_mask():
    """Segment-masked kernel == dense same-segment masking (packed varlen),
    fwd and grads."""
    from paddle_tpu.kernels import flash_attention_varlen
    rs = np.random.RandomState(11)
    b, s, h, d = 2, 128, 2, 64
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32) * 0.5)
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32) * 0.5)
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32) * 0.5)
    # two packs: [50, 78] and [30, 60, 38]
    seg = np.zeros((b, s), np.int32)
    seg[0, 50:] = 1
    seg[1, 30:90] = 1
    seg[1, 90:] = 2
    seg = jnp.asarray(seg)

    def dense(q, k, v, causal):
        mask = (seg[:, None, :, None] == seg[:, None, None, :])
        if causal:
            i = jnp.arange(s)
            mask = jnp.logical_and(mask, i[None, :] >= 0)
            mask = jnp.logical_and(
                mask, (i[None, None, None, :] <= i[None, None, :, None]))
        return sdpa_reference(q, k, v, attn_mask=mask, training=False)

    for causal in (False, True):
        out = flash_attention_varlen(q, k, v, seg, seg, causal=causal,
                                     interpret=True)
        ref = dense(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=str(causal))

    # grads
    g_k = jax.grad(lambda q, k, v: jnp.sum(flash_attention_varlen(
        q, k, v, seg, seg, causal=True, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda q, k, v: jnp.sum(dense(q, k, v, True) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_k, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attn_unpadded_padded_kernel_path_matches_dense():
    """The padded segment-id construction flash_attn_unpadded uses for its
    TPU kernel route == the dense cu_seqlens route (exercised directly via
    flash_attention_varlen since the CPU test backend gates the route)."""
    from paddle_tpu.nn.functional.attention import flash_attn_unpadded
    from paddle_tpu.kernels import flash_attention_varlen
    rs = np.random.RandomState(12)
    t, h, d = 100, 2, 64
    q = jnp.asarray(rs.randn(t, h, d).astype(np.float32) * 0.5)
    k = jnp.asarray(rs.randn(t, h, d).astype(np.float32) * 0.5)
    v = jnp.asarray(rs.randn(t, h, d).astype(np.float32) * 0.5)
    cu = jnp.asarray([0, 40, 100], jnp.int32)
    scale = 1.0 / np.sqrt(d)
    out_d, _ = flash_attn_unpadded(q, k, v, cu, cu, 60, 60, scale,
                                   causal=True)
    # replicate the route's padding + segment construction
    seg = jnp.cumsum(jnp.zeros(t, jnp.int32).at[cu[1:-1]].add(1))
    pad = (-t) % 128
    qp = jnp.pad(q, [(0, pad), (0, 0), (0, 0)])[None]
    kp = jnp.pad(k, [(0, pad), (0, 0), (0, 0)])[None]
    vp = jnp.pad(v, [(0, pad), (0, 0), (0, 0)])[None]
    sq = jnp.pad(seg, (0, pad), constant_values=-1)[None]
    sk_ = jnp.pad(seg, (0, pad), constant_values=-2)[None]
    out_k = flash_attention_varlen(qp, kp, vp, sq, sk_, causal=True,
                                   scale=scale, interpret=True)[0][:t]
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,d,kh,causal", [
    (64, 64, 2, True), (96, 64, 1, False), (192, 128, 2, True),
    (320, 128, 1, True), (128, 256, 2, False)])
def test_flash_attention_shape_sweep(s, d, kh, causal):
    """Random-shape sweep (odd block splits, GQA, both masks): kernel ==
    dense reference, fwd + grad, for every combination."""
    rs = np.random.RandomState(s + d)
    q = jnp.asarray(rs.randn(2, s, 2, d).astype(np.float32) * 0.4)
    k = jnp.asarray(rs.randn(2, s, kh, d).astype(np.float32) * 0.4)
    v = jnp.asarray(rs.randn(2, s, kh, d).astype(np.float32) * 0.4)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = sdpa_reference(q, k, v, is_causal=causal, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    g1 = jax.grad(lambda a: jnp.sum(flash_attention(
        a, k, v, causal=causal, interpret=True) ** 2))(q)
    g2 = jax.grad(lambda a: jnp.sum(sdpa_reference(
        a, k, v, is_causal=causal, training=False) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=3e-4, atol=3e-4)


def test_fused_layer_norm_fwd_bwd_matches_reference():
    """Fused LayerNorm kernel (interpret mode on CPU): forward + both
    weight grads match the XLA reference to fp32 precision."""
    import numpy as np
    from paddle_tpu.kernels import fused_layer_norm_pallas
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(6, 128).astype(np.float32))
    w = jnp.asarray(rs.randn(128).astype(np.float32))
    b = jnp.asarray(rs.randn(128).astype(np.float32))

    def ref(x, w, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b

    out = fused_layer_norm_pallas(x, w, b, 1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, w, b)),
                               rtol=1e-5, atol=1e-5)

    def loss_p(x, w, b):
        return jnp.sum(fused_layer_norm_pallas(x, w, b, 1e-5) ** 2)

    def loss_r(x, w, b):
        return jnp.sum(ref(x, w, b) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)


def test_fused_norms_multi_block_grid():
    """rows > 256 forces nblk > 1: cross-block dw/db accumulation and the
    per-block mu/rstd index maps must hold for i > 0 (both kernels), and
    mixed weight/bias dtypes keep their own grad dtypes."""
    import numpy as np
    from paddle_tpu.kernels import (fused_layer_norm_pallas,
                                    fused_rms_norm_pallas)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(512, 128).astype(np.float32))
    w = jnp.asarray(rs.randn(128).astype(np.float32))
    b = jnp.asarray(rs.randn(128).astype(np.float32))

    def lref(x, w, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b

    np.testing.assert_allclose(
        np.asarray(fused_layer_norm_pallas(x, w, b, 1e-5)),
        np.asarray(lref(x, w, b)), rtol=1e-5, atol=1e-5)
    gp = jax.grad(lambda *a: jnp.sum(
        fused_layer_norm_pallas(*a, 1e-5) ** 2), argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(lambda *a: jnp.sum(lref(*a) ** 2),
                  argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-4)

    def rref(x, w):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * w

    np.testing.assert_allclose(
        np.asarray(fused_rms_norm_pallas(x, w, 1e-6)),
        np.asarray(rref(x, w)), rtol=1e-5, atol=1e-5)
    grp = jax.grad(lambda *a: jnp.sum(
        fused_rms_norm_pallas(*a, 1e-6) ** 2), argnums=(0, 1))(x, w)
    grr = jax.grad(lambda *a: jnp.sum(rref(*a) ** 2),
                   argnums=(0, 1))(x, w)
    for a, c in zip(grp, grr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-4)


def test_layer_norm_flag_routing(monkeypatch):
    """The routing gate really reaches the fused kernel when on 'TPU'
    (backend shim + recorder kernel), matches the XLA form, and the flag
    disables it."""
    import paddle_tpu
    import paddle_tpu.kernels as K
    import paddle_tpu.nn.functional as F
    from paddle_tpu.nn.functional import norm as norm_mod
    import numpy as np

    x = jnp.asarray(np.random.RandomState(0).randn(8, 128)
                    .astype(np.float32))
    w = jnp.asarray(np.random.RandomState(1).randn(128).astype(np.float32))
    b = jnp.asarray(np.random.RandomState(2).randn(128).astype(np.float32))

    calls = []
    real = K.fused_layer_norm_pallas

    def recorder(x, w, b, eps, interpret=None):
        calls.append(1)
        return real(x, w, b, eps, interpret=True)   # CPU-safe

    monkeypatch.setattr(norm_mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(K, "fused_layer_norm_pallas", recorder)

    # empirical routing (r4 sweep): norms default to XLA even on TPU
    out_default = F.layer_norm(x, 128, w, b)
    assert not calls, "auto routing should pick XLA for norms"

    paddle_tpu.set_flags({"FLAGS_pallas_routing": "always"})
    try:
        out_fused = F.layer_norm(x, 128, w, b)
        assert calls, "routing gate never reached the fused kernel"
        # the boolean flag stays a hard off-switch on top of routing
        paddle_tpu.set_flags({"FLAGS_use_pallas_norm": False})
        out_xla = F.layer_norm(x, 128, w, b)
        assert len(calls) == 1
    finally:
        paddle_tpu.set_flags({"FLAGS_pallas_routing": "auto",
                              "FLAGS_use_pallas_norm": True})
    np.testing.assert_allclose(np.asarray(out_fused),
                               np.asarray(out_xla), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_fused),
                               np.asarray(out_default), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention on bf16 inputs (PR 35): operands reach the MXU as given
# ---------------------------------------------------------------------------

# bf16 keeps 8 significant bits: rounding to nearest moves a value by at
# most U = 2^-8 of its magnitude.  The bounds below count roundings; no
# factor in them was fitted to a run.
U = 2.0 ** -8


def _bf16_case(name):
    """``(kernel, reference, (q, k, v))`` of one bf16 case: both take
    ``(q, k, v)`` in any dtype, layout [B, S, H, D]."""
    from paddle_tpu.kernels import flash_attention_varlen
    sq, sk, h, kh, causal, blocks = {
        "dense": (128, 128, 2, 2, False, {}),
        "causal": (128, 128, 2, 2, True, {}),
        "gqa": (128, 128, 4, 2, True, {}),
        "cross_length": (64, 128, 2, 2, True, {}),
        "uneven_blocks": (96, 96, 2, 2, True, {}),
        # several tiles each way: the online softmax rescales across k
        # tiles and the causal grid skips the tiles above the diagonal
        "tiled": (256, 256, 2, 2, True, {"block_q": 64, "block_k": 128}),
        "varlen": (128, 128, 2, 2, True, {"block_q": 64, "block_k": 64}),
    }[name]
    rs = np.random.RandomState(len(name))
    q, k, v = (jnp.asarray(rs.randn(2, s, n, 64) * 0.5, jnp.bfloat16)
               for s, n in ((sq, h), (sk, kh), (sk, kh)))
    if name != "varlen":
        return (lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, interpret=True, **blocks),
                lambda q, k, v: sdpa_reference(
                    q, k, v, is_causal=causal, training=False),
                (q, k, v))
    seg = np.zeros((2, sq), np.int32)   # two packs: [50, 78], [30, 60, 38]
    seg[0, 50:] = 1
    seg[1, 30:90] = 1
    seg[1, 90:] = 2
    seg = jnp.asarray(seg)
    i = jnp.arange(sq)
    mask = (seg[:, None, :, None] == seg[:, None, None, :]) \
        & (i[None, None, None, :] <= i[None, None, :, None])
    return (lambda q, k, v: flash_attention_varlen(
                q, k, v, seg, seg, causal=True, interpret=True, **blocks),
            lambda q, k, v: sdpa_reference(
                q, k, v, attn_mask=mask, training=False),
            (q, k, v))


_BF16_CASES = ["dense", "causal", "gqa", "cross_length", "uneven_blocks",
               "tiled", "varlen"]


@pytest.mark.parametrize("case", _BF16_CASES)
def test_flash_attention_bf16_forward(case):
    """bf16 in, bf16 out.  Against the float32 reference on float32 copies
    of the same inputs the kernel commits two roundings, each at most U
    of the largest value a row mixes: P before ``P V`` (the scores and the
    denominator are float32) and the result.  ``sdpa_reference`` on the
    bf16 inputs commits the same two, so the pair may differ by four."""
    kernel, reference, (q, k, v) = _bf16_case(case)
    out = kernel(q, k, v)
    assert out.dtype == jnp.bfloat16
    out = np.asarray(out, np.float32)
    vmax = float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    exact = reference(*(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_allclose(out, np.asarray(exact), rtol=0,
                               atol=2 * U * vmax)
    same = reference(q, k, v)
    assert same.dtype == jnp.bfloat16
    np.testing.assert_allclose(out, np.asarray(same, np.float32), rtol=0,
                               atol=4 * U * vmax)


@pytest.mark.parametrize("case", _BF16_CASES)
def test_flash_attention_bf16_grad(case):
    """Gradients of ``sum(out * w)`` in bf16.  Roundings on the way to a
    gradient: the saved output (inside ``delta``), dO, P or dS before
    their matmuls, the result: four, each at most U of the largest term
    the gradient sums, taken as the gradient's own largest magnitude;
    the bf16 reference's backward commits as many of its own."""
    kernel, reference, (q, k, v) = _bf16_case(case)
    rs = np.random.RandomState(1)
    w = jnp.asarray(rs.randn(*q.shape[:3], 64), jnp.bfloat16)

    def grads(fn, dtype):
        args = [x.astype(dtype) for x in (q, k, v)]
        return jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)),
            argnums=(0, 1, 2))(*args)

    got = grads(kernel, jnp.bfloat16)
    exact = grads(reference, jnp.float32)
    same = grads(reference, jnp.bfloat16)
    for g, e, s, name in zip(got, exact, same, "qkv"):
        assert g.dtype == jnp.bfloat16
        g = np.asarray(g, np.float32)
        scale = float(jnp.max(jnp.abs(e)))
        np.testing.assert_allclose(g, np.asarray(e), rtol=0,
                                   atol=4 * U * scale,
                                   err_msg=f"d{name} against float32")
        np.testing.assert_allclose(g, np.asarray(s, np.float32), rtol=0,
                                   atol=8 * U * scale,
                                   err_msg=f"d{name} against bf16")


def _pallas_dots(fn, *args):
    """``(lhs dtype, rhs dtype, result dtype)`` of every ``dot_general``
    inside the ``pallas_call`` bodies of ``fn``'s jaxpr, by kernel name."""
    found = {}

    def walk(jaxpr, kernel):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and kernel:
                found.setdefault(kernel, []).append(
                    tuple(str(x.aval.dtype) for x in
                          (*eqn.invars, eqn.outvars[0])))
            inner = kernel
            if eqn.primitive.name == "pallas_call":
                inner = eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_attention_dots_take_the_inputs_dtype(dtype):
    """All nine matmuls of the three kernels take their operands in the
    inputs' dtype (P and dS rounded to it) and accumulate in float32:
    bf16 inputs cost one MXU pass a product, float32 inputs behave as
    they always did."""
    q, k, v = (x.astype(dtype) for x in _qkv(b=1, s=128, h=2, d=64))
    dots = _pallas_dots(
        jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True).astype(jnp.float32)),
            argnums=(0, 1, 2)), q, k, v)
    assert {name: len(d) for name, d in dots.items()} == {
        "flash_attention_fwd": 2, "flash_attention_bwd_dq": 3,
        "flash_attention_bwd_dkv": 4}
    for name, kernel_dots in dots.items():
        assert set(kernel_dots) == {(dtype, dtype, "float32")}, name


# ---------------------------------------------------------------------------
# flash_attention_plan: the tiles, from the shapes and the dtype
# ---------------------------------------------------------------------------

def _plan(*args, **kw):
    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    return fa, fa.flash_attention_plan(*args, **kw)


@pytest.mark.parametrize("sq,sk", [
    (4096, 4096), (2048, 2048), (96, 96), (64, 128), (32, 96), (320, 320),
    (192, 192), (2048, 8192), (3000, 3000)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_attention_plan_tiles_divide_and_count(sq, sk, dtype):
    """Every kernel's tile divides both sequences, and the computing
    steps equal a brute-force count of the tiles that hold at least one
    (query, key) pair the bottom-right-aligned causal mask admits."""
    _, plan = _plan(sq, sk, 128, dtype, causal=True)
    _, dense = _plan(sq, sk, 128, dtype, causal=False)
    assert set(plan) == {"fwd", "bwd_dq", "bwd_dkv"}
    off = sk - sq
    for kernel, p in plan.items():
        bq, bk = p["block_q"], p["block_k"]
        assert sq % bq == 0 and sk % bk == 0, (kernel, p)
        assert p["operand_dtype"] == dtype
        nq, nk = sq // bq, sk // bk
        assert p["grid_steps"] == nq * nk == dense[kernel]["grid_steps"]
        assert dense[kernel]["computing_steps"] == nq * nk
        brute = sum(1 for qi in range(nq) for ki in range(nk)
                    if ki * bk <= qi * bq + bq - 1 + off)
        assert p["computing_steps"] == brute, (kernel, p)


@pytest.mark.parametrize("head_dim", [64, 128, 256])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_attention_plan_fits_vmem_budget(head_dim, dtype):
    """At long sequences every kernel's grid step stays inside the budget
    the kernels hand Mosaic as their scoped-VMEM limit, and the tile
    shrinks rather than the budget stretching when the budget is small."""
    fa, plan = _plan(8192, 8192, head_dim, dtype, causal=True)
    for kernel, p in plan.items():
        assert p["vmem_bytes"] <= fa.VMEM_BUDGET, (kernel, p)
        assert p["vmem_bytes"] == fa._vmem_bytes(
            kernel, p["block_q"], p["block_k"], head_dim,
            jnp.dtype(dtype).itemsize)
    whole = fa.VMEM_BUDGET
    try:
        fa.VMEM_BUDGET = 4 * 1024 * 1024
        small = fa.flash_attention_plan(8192, 8192, head_dim, dtype, True)
    finally:
        fa.VMEM_BUDGET = whole
    for kernel, p in small.items():
        assert p["vmem_bytes"] <= 4 * 1024 * 1024, (kernel, p)
        assert p["block_q"] * p["block_k"] \
            < plan[kernel]["block_q"] * plan[kernel]["block_k"]


def test_flash_attention_explicit_blocks_override_the_plan():
    """``block_q=`` / ``block_k=`` reach all three kernels (clamped to a
    divisor); left out, the plan's tiles do."""
    fa, plan = _plan(256, 256, 64, "float32", causal=True)
    assert fa._tiles(256, 256, 64, jnp.float32, 64, 128) == fa._Tiles(
        (64, 128), (64, 128), (64, 128))
    assert fa._tiles(96, 96, 64, jnp.float32, 64, None).fwd == (32, 96)
    assert fa._tiles(256, 256, 64, jnp.float32) == fa._Tiles(*(
        (plan[k]["block_q"], plan[k]["block_k"])
        for k in ("fwd", "bwd_dq", "bwd_dkv")))


def test_flash_attention_cost_script_prints_no_time_off_chip(capsys):
    """``scripts/flash_attention_cost.py`` without a TPU: the plan and
    the causal FLOPs of each kernel, never a time."""
    import json
    from scripts import flash_attention_cost as C
    assert C.main(["--shapes", "64x4096x128,16x2048x128",
                   "--dtypes", "bfloat16"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert lines[0].startswith("platform=cpu")
    rows = [json.loads(line) for line in lines[1:]]
    assert [r["shape"] for r in rows] == [[64, 4096, 128], [16, 2048, 128]]
    for row in rows:
        assert set(row) == {"shape", "dtype", "plan", "causal_gflop"}
        assert "ms" not in json.dumps(row)
        assert set(row["plan"]) == set(C.KERNELS)
    # forward: 2 matmuls x 2 x 64 x 4096^2 x 128 / 2 (ISSUE 35: 275 GFLOP)
    assert rows[0]["causal_gflop"] == {"fwd": 274.88, "bwd_dq": 412.32,
                                       "bwd_dkv": 549.76}
    # the cell's shape takes 1024 x 1024 in every kernel; at 2048 the
    # backward pair takes 512 x 512 (PERF.md section 6, PR 35)
    tiles = [{k: (p["block_q"], p["block_k"]) for k, p in r["plan"].items()}
             for r in rows]
    assert tiles[0] == dict.fromkeys(C.KERNELS, (1024, 1024))
    assert tiles[1] == {"fwd": (1024, 1024), "bwd_dq": (512, 512),
                        "bwd_dkv": (512, 512)}
