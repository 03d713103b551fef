"""Sparse 3D convolution on voxelized point clouds.

Reference analog: the paddle.sparse.nn workflow (SubmConv3D/BatchNorm/
ReLU stacks over SparseCooTensor voxels — the sparse ResNet pattern used
for point-cloud perception).  TPU-native: sparse activations are BCOO
(indices [nnz,4], values [nnz,C]); the conv rulebook is static-shape
sort+searchsorted with one masked MXU matmul per kernel offset
(paddle_tpu/sparse/nn.py).

Run:

    env JAX_PLATFORMS=cpu \
        python examples/train_sparse_pointcloud.py --steps 120

Task: classify which octant of the volume a noisy point cluster occupies
(8 classes).  A sparse conv stack + global readout learns it from ~1%
occupancy — the dense volume is never materialized in the hot path.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_cloud(rs, side, cls, n_pts, feat):
    """Points clustered in octant ``cls`` with noisy features."""
    import numpy as np
    half = side // 2
    oz, oy, ox = (cls >> 2) & 1, (cls >> 1) & 1, cls & 1
    dense = np.zeros((1, side, side, side, feat), np.float32)
    for _ in range(n_pts):
        d = rs.randint(0, half) + oz * half
        h = rs.randint(0, half) + oy * half
        w = rs.randint(0, half) + ox * half
        dense[0, d, h, w] = rs.randn(feat) * 0.3 + 1.0
    return dense


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--side", type=int, default=8)
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import sparse as jsparse
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.sparse import nn as snn

    rs = np.random.RandomState(0)
    FEAT, CLASSES, N = 4, 8, 32
    # ALL clouds in ONE sparse tensor: the batch index is the first
    # sparse coordinate, so a single conv processes every cloud (one
    # compile, one rulebook) — the TPU-native batching for sparse data
    dense = np.zeros((N, args.side, args.side, args.side, FEAT), np.float32)
    labels = []
    for i in range(N):
        cls = i % CLASSES
        dense[i] = make_cloud(rs, args.side, cls, n_pts=12, feat=FEAT)[0]
        labels.append(cls)
    x = jsparse.BCOO.fromdense(jnp.asarray(dense), n_dense=1)
    labels = jnp.asarray(labels)
    occupancy = x.nse / dense[..., 0].size
    print(f"{N} clouds in one sparse tensor, nnz={x.nse}, "
          f"occupancy {occupancy:.1%}")

    paddle.seed(0)
    conv1 = snn.SubmConv3D(FEAT, 16, 3)
    bn = snn.BatchNorm(16)
    conv2 = snn.SubmConv3D(16, 16, 3)
    head = jnp.asarray(rs.randn(16 + 3, CLASSES) * 0.1, jnp.float32)

    def logits(params):
        w1, b1, g, b, w2, b2, hw = params
        y = snn.functional.subm_conv3d(x, w1, b1)
        v = jnp.maximum(y.data, 0)
        v = (v - v.mean(0)) * jax.lax.rsqrt(v.var(0) + 1e-5) * g + b
        y2 = snn.functional.subm_conv3d(
            jsparse.BCOO((v, y.indices), shape=y.shape), w2, b2)
        v2 = jnp.maximum(y2.data, 0)
        # per-cloud readout: segment means over the batch coordinate
        seg = x.indices[:, 0]
        cnt = jnp.maximum(
            jax.ops.segment_sum(jnp.ones_like(seg, jnp.float32), seg,
                                num_segments=N), 1.0)[:, None]
        feat = jax.ops.segment_sum(v2, seg, num_segments=N) / cnt
        pos = jax.ops.segment_sum(
            x.indices[:, 1:].astype(jnp.float32), seg,
            num_segments=N) / cnt / args.side
        return jnp.concatenate([feat, pos], axis=1) @ hw

    def loss_fn(params):
        return jnp.mean(F.cross_entropy(logits(params), labels))

    params = (conv1.weight, conv1.bias, bn.weight, bn.bias,
              conv2.weight, conv2.bias, head)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    first = None
    for step in range(args.steps):
        loss, g = grad_fn(params)
        params = jax.tree.map(lambda p, gg: p - 0.3 * gg, params, g)
        first = float(loss) if first is None else first
        if step % 10 == 0:
            print(f"step {step}: loss {float(loss):.4f}")
    print(f"loss {first:.4f} -> {float(loss):.4f}")
    assert float(loss) < first * 0.5, "sparse conv failed to learn"

    acc = float((jnp.argmax(logits(params), axis=1) == labels).mean())
    print(f"train accuracy {acc:.2f}")
    assert acc >= 0.75, acc
    print("SPARSE_POINTCLOUD_OK")


if __name__ == "__main__":
    main()
