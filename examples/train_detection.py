"""Single-scale YOLO-style detector: train with yolo_loss, deploy with
yolo_box + matrix_nms.

Reference analog: the yolov3_loss / yolo_box / matrix_nms op family
(paddle/vision/ops.py) that PaddleDetection-style pipelines build on:
a conv backbone emits one [A*(5+C), H, W] head trained against the
lattice loss, then the SAME head is decoded into pixel boxes and
soft-suppressed — the full detection train->infer chain on one device.

Run:

    env JAX_PLATFORMS=cpu \
        python examples/train_detection.py --steps 150

The synthetic task: each 32x32 image carries one axis-aligned bright
square (class = bright vs dark), the gt box is its bounding box.  A
detector that localizes must beat the prior (boxes at the right cells
with the right class), which the final assert checks through the full
decode + NMS path — not just the loss curve.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_batch(rs, n, img=32, lo=6, hi=12):
    """Images with one square each; returns (imgs, gt_box, gt_label)."""
    import numpy as np
    imgs = 0.05 * rs.randn(n, 3, img, img).astype("float32")
    gt_box = np.zeros((n, 1, 4), "float32")       # (cx, cy, w, h) normalized
    gt_label = np.zeros((n, 1), "int64")
    for i in range(n):
        w = rs.randint(lo, hi)
        h = rs.randint(lo, hi)
        x0 = rs.randint(0, img - w)
        y0 = rs.randint(0, img - h)
        cls = rs.randint(0, 2)
        val = 1.0 if cls else -1.0
        imgs[i, :, y0:y0 + h, x0:x0 + w] += val
        gt_box[i, 0] = [(x0 + w / 2) / img, (y0 + h / 2) / img,
                        w / img, h / img]
        gt_label[i, 0] = cls
    return imgs, gt_box, gt_label


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--img", type=int, default=32)
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.nn import functional_call, state
    from paddle_tpu.vision import ops as V

    anchors = [10, 10]                 # one anchor, ~ the square scale
    mask = [0]
    nclass = 2
    ds = 8                             # 32 -> 4x4 grid
    rs = np.random.RandomState(0)

    backbone = nn.Sequential(
        nn.Conv2D(3, 16, 3, stride=2, padding=1), nn.ReLU(),
        nn.Conv2D(16, 32, 3, stride=2, padding=1), nn.ReLU(),
        nn.Conv2D(32, 32, 3, stride=2, padding=1), nn.ReLU(),
        nn.Conv2D(32, len(mask) * (5 + nclass), 1),
    )
    params, bufs = state(backbone)
    optimizer = opt.Adam(learning_rate=3e-3)
    ost = optimizer.init(params)

    imgs, gt_box, gt_label = make_batch(rs, args.batch, args.img)
    imgs = jnp.asarray(imgs)
    gt_box_j = jnp.asarray(gt_box)
    gt_label_j = jnp.asarray(gt_label)

    @jax.jit
    def step(p, os_):
        def loss_fn(p):
            head, _ = functional_call(backbone, p, bufs, (imgs,))
            # label smoothing is 1/class_num (kernel semantics): with 2
            # classes both targets become 0.5 — degenerate, so off here
            per = V.yolo_loss(head, gt_box_j, gt_label_j, anchors, mask,
                              nclass, ignore_thresh=0.7,
                              downsample_ratio=ds, use_label_smooth=False)
            return per.mean()
        loss, grads = jax.value_and_grad(loss_fn)(p)
        newp, nos = optimizer.update(grads, os_, p)
        return newp, nos, loss

    first = None
    for it in range(args.steps):
        params, ost, loss = step(params, ost)
        if first is None:
            first = float(loss)
        if it % 25 == 0:
            print(f"step {it:4d} loss {float(loss):.3f}")
    print(f"loss {first:.3f} -> {float(loss):.3f}")
    assert float(loss) < 0.4 * first, "detector failed to learn"

    # ---- inference: decode the trained head, soft-suppress, score ------
    head, _ = functional_call(backbone, params, bufs, (imgs,))
    img_size = jnp.broadcast_to(
        jnp.asarray([args.img, args.img], jnp.float32), (args.batch, 2))
    boxes, scores = V.yolo_box(head, img_size, anchors, nclass,
                               conf_thresh=0.3, downsample_ratio=ds)
    dets, rois = V.matrix_nms(boxes, jnp.moveaxis(scores, 1, 2),
                              score_threshold=0.2, post_threshold=0.1,
                              nms_top_k=10, keep_top_k=1,
                              background_label=-1)
    dets = np.asarray(dets)
    rois = np.asarray(rois)
    hits = cls_hits = 0
    off = 0
    for i in range(args.batch):
        if rois[i] == 0:
            continue
        cls, score, x1, y1, x2, y2 = dets[off]
        gx, gy = gt_box[i, 0, :2] * args.img
        cx, cy = (x1 + x2) / 2, (y1 + y2) / 2
        if abs(cx - gx) < 6 and abs(cy - gy) < 6:
            hits += 1
            if int(cls) == int(gt_label[i, 0]):
                cls_hits += 1
        off += rois[i]
    print(f"localized {hits}/{args.batch}, class-correct {cls_hits}")
    assert hits >= int(0.7 * args.batch), "decode+NMS chain missed the boxes"
    assert cls_hits >= int(0.6 * hits), "classes wrong through the chain"
    print("OK")


if __name__ == "__main__":
    main()
