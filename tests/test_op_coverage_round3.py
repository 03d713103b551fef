"""Oracles for the round-3 OP_COVERAGE additions (torch CPU and scipy are
the references, same pattern as the reference's test_*_op.py suites)."""

import numpy as np
import pytest

import torch

import jax
import jax.numpy as jnp

import paddle_tpu as P
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

rs = np.random.RandomState(0)


# ---------------------------------------------------------------- tensor ops

def test_unfold_matches_torch():
    x = rs.randn(2, 3, 10).astype(np.float32)
    mine = np.asarray(P.unfold(x, 2, 4, 2))
    ref = torch.tensor(x).unfold(2, 4, 2).numpy()
    np.testing.assert_allclose(mine, ref, atol=1e-6)


def test_as_strided_matches_numpy():
    x = rs.randn(24).astype(np.float32)
    mine = np.asarray(P.as_strided(x, (3, 4), (8, 2), offset=1))
    ref = np.lib.stride_tricks.as_strided(
        x[1:], shape=(3, 4), strides=(8 * 4, 2 * 4))
    np.testing.assert_allclose(mine, ref)


def test_polar_and_complex_predicates():
    mag = np.abs(rs.randn(3, 4)).astype(np.float32)
    ang = rs.randn(3, 4).astype(np.float32)
    mine = np.asarray(P.polar(mag, ang))
    ref = mag * np.exp(1j * ang)
    np.testing.assert_allclose(mine, ref, atol=1e-5)
    assert P.is_complex(mine) and not P.is_complex(mag)
    assert P.is_floating_point(mag) and not P.is_integer(mag)
    assert P.is_integer(np.arange(3))
    assert bool(np.asarray(P.isreal(np.asarray([1 + 0j, 1j]))[0]))


def test_tolist_roundtrip():
    x = np.arange(6).reshape(2, 3)
    assert P.tolist(jnp.asarray(x)) == x.tolist()


def test_geometric_distribution():
    x = np.zeros(20000, np.float32)
    s = np.asarray(P.geometric_(x, 0.25))
    assert s.min() >= 1
    assert abs(s.mean() - 4.0) < 0.15   # E[Geom(p)] = 1/p


# -------------------------------------------------------------------- linalg

def test_matrix_exp_vs_scipy():
    import scipy.linalg as sl
    a = rs.randn(4, 4).astype(np.float32) * 0.3
    np.testing.assert_allclose(np.asarray(P.linalg.matrix_exp(a)),
                               sl.expm(a), rtol=1e-4, atol=1e-5)


def test_lu_unpack_reconstructs():
    a = rs.randn(5, 5).astype(np.float32)
    lu_packed, piv = P.linalg.lu(a)
    pm, lm, um = P.linalg.lu_unpack(lu_packed, piv)
    recon = np.asarray(pm) @ np.asarray(lm) @ np.asarray(um)
    np.testing.assert_allclose(recon, a, rtol=1e-4, atol=1e-5)


def test_ormqr_vs_torch():
    a = rs.randn(5, 3).astype(np.float32)
    other = rs.randn(5, 4).astype(np.float32)
    ta = torch.tensor(a)
    h, tau = torch.geqrf(ta)
    ref = torch.ormqr(h, tau, torch.tensor(other)).numpy()
    mine = np.asarray(P.linalg.ormqr(h.numpy(), tau.numpy(), other))
    np.testing.assert_allclose(mine, ref, rtol=1e-4, atol=1e-5)


def test_svd_lowrank_reconstructs_lowrank_matrix():
    u = rs.randn(10, 3).astype(np.float32)
    v = rs.randn(3, 8).astype(np.float32)
    a = u @ v                       # exactly rank 3
    U, s, V = P.linalg.svd_lowrank(a, q=3)
    recon = np.asarray(U) @ np.diag(np.asarray(s)) @ np.asarray(V).T
    np.testing.assert_allclose(recon, a, rtol=1e-3, atol=1e-3)


# ----------------------------------------------------------------------- fft

def test_hermitian_fft_family_vs_scipy():
    import scipy.fft as sf
    x = (rs.randn(4, 6) + 1j * rs.randn(4, 6))
    y = rs.randn(4, 6)
    for norm in ("backward", "ortho", "forward"):
        np.testing.assert_allclose(np.asarray(P.fft.hfftn(x, norm=norm)),
                                   sf.hfftn(x, norm=norm), atol=1e-4)
        np.testing.assert_allclose(np.asarray(P.fft.ihfftn(y, norm=norm)),
                                   sf.ihfftn(y, norm=norm), atol=1e-6)
        np.testing.assert_allclose(np.asarray(P.fft.hfft2(x, norm=norm)),
                                   sf.hfft2(x, norm=norm), atol=1e-4)
        np.testing.assert_allclose(np.asarray(P.fft.ihfft2(y, norm=norm)),
                                   sf.ihfft2(y, norm=norm), atol=1e-6)


# -------------------------------------------------------------------- losses

def test_multi_margin_loss_vs_torch():
    x = rs.randn(6, 5).astype(np.float32)
    y = rs.randint(0, 5, (6,))
    for p, m, red in [(1, 1.0, "mean"), (2, 0.7, "sum"), (1, 1.0, "none")]:
        mine = np.asarray(F.multi_margin_loss(x, y, p=p, margin=m,
                                              reduction=red))
        ref = torch.nn.functional.multi_margin_loss(
            torch.tensor(x), torch.tensor(y), p=p, margin=m,
            reduction=red).numpy()
        np.testing.assert_allclose(mine, ref, atol=1e-6)


def test_triplet_with_distance_vs_torch():
    a, pos, neg = [rs.randn(4, 8).astype(np.float32) for _ in range(3)]
    mine = np.asarray(F.triplet_margin_with_distance_loss(
        a, pos, neg, margin=0.6, swap=True))
    ref = torch.nn.functional.triplet_margin_with_distance_loss(
        torch.tensor(a), torch.tensor(pos), torch.tensor(neg),
        margin=0.6, swap=True).numpy()
    np.testing.assert_allclose(mine, ref, atol=1e-6)


def test_adaptive_log_softmax_vs_torch():
    torch.manual_seed(0)
    D, C = 16, 20
    tl = torch.nn.AdaptiveLogSoftmaxWithLoss(D, C, cutoffs=[5, 12],
                                             div_value=2.0)
    x = torch.randn(10, D)
    y = torch.randint(0, C, (10,))
    tout = tl(x, y)
    hw = tl.head.weight.detach().numpy().T
    tails = [(seq[0].weight.detach().numpy().T,
              seq[1].weight.detach().numpy().T) for seq in tl.tail]
    out, loss = F.adaptive_log_softmax_with_loss(
        x.numpy(), y.numpy(), hw, tails, cutoffs=[5, 12, C])
    np.testing.assert_allclose(np.asarray(out),
                               tout.output.detach().numpy(), atol=1e-5)
    np.testing.assert_allclose(float(loss), float(tout.loss.detach()),
                               atol=1e-5)


def test_adaptive_log_softmax_layer_normalized():
    paddle_seed = P.seed(3)
    layer = nn.AdaptiveLogSoftmaxWithLoss(8, 30, cutoffs=[6, 14])
    x = jnp.asarray(rs.randn(5, 8).astype(np.float32))
    lp = layer.log_prob(x)
    # rows are proper log-distributions over all 30 classes
    np.testing.assert_allclose(
        np.asarray(jax.scipy.special.logsumexp(lp, axis=-1)),
        np.zeros(5), atol=1e-5)
    y = jnp.asarray(rs.randint(0, 30, (5,)))
    out, loss = layer(x, y)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(jnp.take_along_axis(lp, y[:, None], 1)[:, 0]),
        atol=1e-5)
    assert np.asarray(layer.predict(x)).shape == (5,)


def test_margin_cross_entropy_reduces_to_ce():
    logits = np.clip(rs.randn(5, 7).astype(np.float32), -0.9, 0.9)
    lbl = rs.randint(0, 7, (5,))
    mine = float(F.margin_cross_entropy(logits, lbl, margin1=1.0,
                                        margin2=0.0, margin3=0.0,
                                        scale=4.0))
    ref = torch.nn.functional.cross_entropy(
        torch.tensor(logits) * 4.0, torch.tensor(lbl)).item()
    assert abs(mine - ref) < 1e-5


def test_margin_cross_entropy_margin_increases_loss():
    logits = np.clip(rs.randn(6, 9).astype(np.float32), -0.9, 0.9)
    lbl = rs.randint(0, 9, (6,))
    base = float(F.margin_cross_entropy(logits, lbl, margin2=0.0))
    with_m = float(F.margin_cross_entropy(logits, lbl, margin2=0.5))
    assert with_m > base


def test_hsigmoid_loss_trains():
    x = jnp.asarray(rs.randn(16, 8).astype(np.float32))
    y = jnp.asarray(rs.randint(0, 10, (16,)))
    w = jnp.asarray(rs.randn(9, 8).astype(np.float32) * 0.1)

    @jax.jit
    def loss_fn(w):
        return jnp.mean(F.hsigmoid_loss(x, y, 10, w))

    g = jax.grad(loss_fn)
    lr = 0.5
    l0 = float(loss_fn(w))
    for _ in range(30):
        w = w - lr * g(w)
    l1 = float(loss_fn(w))
    assert np.isfinite(l0) and l1 < l0 * 0.7, (l0, l1)


def test_class_center_sample_keeps_positives():
    lbl = np.array([3, 7, 3, 1, 19])
    rl, sc = F.class_center_sample(lbl, 20, 8)
    sc, rl = np.asarray(sc), np.asarray(rl)
    assert len(sc) == 8
    for orig, remap in zip(lbl, rl):
        assert sc[remap] == orig


def test_sparse_attention_matches_dense_mask():
    B, H, S, D = 1, 2, 6, 4
    q, k, v = [rs.randn(B, H, S, D).astype(np.float32) for _ in range(3)]
    cols, counts = [], []
    for i in range(S):
        cs = list(range(max(0, i - 1), min(S, i + 2)))
        cols.extend(cs)
        counts.append(len(cs))
    off = np.tile(np.cumsum([0] + counts), (B, H, 1))
    colsa = np.tile(np.array(cols), (B, H, 1))
    out = np.asarray(F.sparse_attention(q, k, v, off, colsa))
    mask = np.zeros((S, S), bool)
    for i in range(S):
        mask[i, max(0, i - 1):min(S, i + 2)] = True
    sc = np.einsum("bhsd,bhtd->bhst", q, k) / np.sqrt(D)
    sc = np.where(mask, sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhst,bhtd->bhsd", p, v)
    np.testing.assert_allclose(out, ref, atol=1e-5)


# --------------------------------------------------------- pooling / unpool

@pytest.mark.parametrize("shape,n,k,s,p", [
    ((2, 3, 8, 8), 2, 2, 2, 0), ((2, 3, 9, 9), 2, 3, 2, 1),
    ((2, 3, 10), 1, 3, 2, 1), ((1, 2, 4, 6, 6), 3, 2, 2, 0)])
def test_max_pool_mask_and_unpool_vs_torch(shape, n, k, s, p):
    x = rs.randn(*shape).astype(np.float32)
    fn = {1: F.max_pool1d, 2: F.max_pool2d, 3: F.max_pool3d}[n]
    tfn = {1: torch.nn.functional.max_pool1d,
           2: torch.nn.functional.max_pool2d,
           3: torch.nn.functional.max_pool3d}[n]
    o, m = fn(x, k, s, p, return_mask=True)
    to, tm = tfn(torch.tensor(x), k, s, p, return_indices=True)
    np.testing.assert_allclose(np.asarray(o), to.numpy(), atol=1e-6)
    assert np.array_equal(np.asarray(m), tm.numpy())
    ufn = {1: F.max_unpool1d, 2: F.max_unpool2d, 3: F.max_unpool3d}[n]
    tufn = {1: torch.nn.functional.max_unpool1d,
            2: torch.nn.functional.max_unpool2d,
            3: torch.nn.functional.max_unpool3d}[n]
    osz = list(shape[2:])
    u = ufn(np.asarray(o), np.asarray(m), k, s, p, output_size=osz)
    tu = tufn(to, tm, k, s, p, output_size=osz)
    np.testing.assert_allclose(np.asarray(u), tu.numpy(), atol=1e-6)


def test_max_unpool_layers():
    x = rs.randn(2, 3, 8, 8).astype(np.float32)
    o, m = F.max_pool2d(x, 2, 2, 0, return_mask=True)
    layer = nn.MaxUnPool2D(2, stride=2)
    u = layer(np.asarray(o), np.asarray(m))
    assert u.shape == x.shape


# ------------------------------------------------------------------- layers

def test_softmax2d_and_circular_pad_vs_torch():
    x = rs.randn(2, 3, 4, 5).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(nn.Softmax2D()(x)),
        torch.nn.Softmax2d()(torch.tensor(x)).numpy(), atol=1e-6)
    pad = nn.CircularPad2D([1, 1, 2, 2])
    ref = torch.nn.functional.pad(torch.tensor(x), (1, 1, 2, 2),
                                  mode="circular").numpy()
    np.testing.assert_allclose(np.asarray(pad(x)), ref, atol=1e-6)


def test_pairwise_distance_layer_vs_torch():
    a = rs.randn(5, 8).astype(np.float32)
    b = rs.randn(5, 8).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(nn.PairwiseDistance(p=2.0)(a, b)),
        torch.nn.PairwiseDistance(p=2.0)(torch.tensor(a),
                                         torch.tensor(b)).numpy(),
        atol=1e-5)


def test_unflatten_layer():
    x = rs.randn(4, 6).astype(np.float32)
    out = nn.Unflatten(1, (2, 3))(x)
    assert out.shape == (4, 2, 3)
    np.testing.assert_allclose(np.asarray(out), x.reshape(4, 2, 3))


def test_spectral_norm_layer_sigma():
    w = rs.randn(6, 10).astype(np.float32)
    sn = nn.SpectralNorm(w.shape, dim=0, power_iters=50)
    out = np.asarray(sn(w))
    # after normalization the top singular value is ~1
    assert abs(np.linalg.svd(out, compute_uv=False)[0] - 1.0) < 1e-3


def test_gumbel_softmax_layer_hard_onehot():
    P.seed(0)
    x = jnp.asarray(rs.randn(5, 7).astype(np.float32))
    with P.rng_context(jax.random.PRNGKey(0)):
        out = nn.GumbelSoftmax(hard=True)(x)
    o = np.asarray(out)
    np.testing.assert_allclose(o.sum(-1), np.ones(5), atol=1e-6)
    assert ((o == 0) | (o == 1)).all()


def test_loss_layer_wrappers_match_functionals():
    x = rs.randn(6, 4).astype(np.float32)
    y = (rs.rand(6, 4) > 0.5).astype(np.float32) * 2 - 1
    np.testing.assert_allclose(
        float(nn.SoftMarginLoss()(x, y)),
        float(F.soft_margin_loss(x, y)), atol=1e-6)
    lbl = rs.randint(0, 4, (6,))
    np.testing.assert_allclose(
        float(nn.MultiMarginLoss(margin=0.8)(x, lbl)),
        float(F.multi_margin_loss(x, lbl, margin=0.8)), atol=1e-6)
    var = np.abs(rs.randn(6, 4)).astype(np.float32) + 0.1
    tgt = rs.randn(6, 4).astype(np.float32)
    np.testing.assert_allclose(
        float(nn.GaussianNLLLoss()(x, tgt, var)),
        float(F.gaussian_nll_loss(x, tgt, var)), atol=1e-6)
    rate = np.abs(rs.randn(6, 4)).astype(np.float32)
    np.testing.assert_allclose(
        float(nn.PoissonNLLLoss()(x, rate)),
        float(F.poisson_nll_loss(x, rate)), atol=1e-6)


def test_hsigmoid_layer_forward():
    P.seed(1)
    layer = nn.HSigmoidLoss(8, 10)
    x = jnp.asarray(rs.randn(4, 8).astype(np.float32))
    y = jnp.asarray(rs.randint(0, 10, (4,)))
    out = layer(x, y)
    assert out.shape == (4, 1)      # reference: per-sample cost, no reduce
    assert np.isfinite(np.asarray(out)).all()


def test_beam_search_decoder_beats_greedy():
    """beam_size=1 == greedy argmax decode; larger beams score >= greedy."""
    P.seed(0)
    cell = nn.SimpleRNNCell(8, 8)
    proj_w = jnp.asarray(rs.randn(8, 12).astype(np.float32))
    emb = jnp.asarray(rs.randn(12, 8).astype(np.float32) * 0.5)

    def embedding_fn(tok):
        return emb[tok]

    def output_fn(h):
        return h @ proj_w

    B = 2
    h0 = jnp.asarray(rs.randn(B, 8).astype(np.float32))

    dec1 = nn.BeamSearchDecoder(cell, start_token=0, end_token=11,
                                beam_size=1, embedding_fn=embedding_fn,
                                output_fn=output_fn)
    seq1, sc1 = dec1.decode(h0, max_steps=5)

    # greedy oracle in plain python
    import numpy as _np
    tok = _np.zeros(B, _np.int32)
    state = h0
    gseq, gscore = [], _np.zeros(B)
    for _ in range(5):
        out, state = cell(embedding_fn(jnp.asarray(tok)), state)
        logp = _np.asarray(jax.nn.log_softmax(output_fn(out), axis=-1))
        nxt = logp.argmax(-1)
        gscore += logp[_np.arange(B), nxt]
        tok = nxt.astype(_np.int32)
        gseq.append(tok.copy())
    gseq = _np.stack(gseq, -1)
    assert _np.array_equal(_np.asarray(seq1)[:, 0, :], gseq)
    np.testing.assert_allclose(_np.asarray(sc1)[:, 0], gscore, atol=1e-4)

    dec4 = nn.BeamSearchDecoder(cell, start_token=0, end_token=11,
                                beam_size=4, embedding_fn=embedding_fn,
                                output_fn=output_fn)
    _, sc4 = dec4.decode(h0, max_steps=5)
    assert (_np.asarray(sc4)[:, 0] >= _np.asarray(sc1)[:, 0] - 1e-5).all()


# ------------------------------------------------------------ top-level API

def test_summary_counts_params():
    m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    r = P.summary(m)
    assert r["total_params"] == 4 * 8 + 8 + 8 * 2 + 2


def test_ormqr_batched():
    a = rs.randn(2, 5, 3).astype(np.float32)
    other = rs.randn(2, 5, 4).astype(np.float32)
    h = np.stack([torch.geqrf(torch.tensor(ai))[0].numpy() for ai in a])
    tau = np.stack([torch.geqrf(torch.tensor(ai))[1].numpy() for ai in a])
    ref = np.stack([torch.ormqr(torch.tensor(h[i]), torch.tensor(tau[i]),
                                torch.tensor(other[i])).numpy()
                    for i in range(2)])
    mine = np.asarray(P.linalg.ormqr(h, tau, other))
    np.testing.assert_allclose(mine, ref, rtol=1e-4, atol=1e-5)


def test_max_pool_ceil_mode_with_mask():
    x = rs.randn(1, 1, 6, 6).astype(np.float32)
    o, m = F.max_pool2d(x, 3, 2, 0, return_mask=True, ceil_mode=True)
    to, tm = torch.nn.functional.max_pool2d(
        torch.tensor(x), 3, 2, 0, ceil_mode=True, return_indices=True)
    np.testing.assert_allclose(np.asarray(o), to.numpy(), atol=1e-6)
    assert np.array_equal(np.asarray(m), tm.numpy())


def test_class_center_sample_fresh_negatives_and_overflow():
    lbl = np.array([1, 2])
    a = np.asarray(F.class_center_sample(lbl, 50, 10)[1])
    b = np.asarray(F.class_center_sample(lbl, 50, 10)[1])
    assert not np.array_equal(a, b)   # fresh negatives per call
    with pytest.raises(ValueError, match="distinct classes"):
        F.class_center_sample(np.arange(6), 20, 4)


def test_static_mode_flags():
    assert P.in_dynamic_mode()
    P.enable_static()
    try:
        assert not P.in_dynamic_mode()
    finally:
        P.disable_static()
    assert P.in_dynamic_mode()


def test_set_grad_enabled_context():
    with P.set_grad_enabled(False):
        assert not P.is_grad_enabled()
    assert P.is_grad_enabled()
