"""Static-graph (Program/Executor) training — the reference's classic
`paddle.enable_static()` workflow, end to end.

Reference analog: the canonical static-mode script shape
(python/paddle/static/ usage: program_guard + static.data + static.nn
builders + optimizer.minimize + Executor.run with feed/fetch; SURVEY.md
§2.2 "static API").  TPU-native: the tape Executor.run replays compiles
forward + AD + the optimizer update into ONE jitted XLA program — see
paddle_tpu/static/program.py.

Run:

    env JAX_PLATFORMS=cpu \
        python examples/train_static.py --steps 60

The task is a small MNIST-shaped synthetic classification: a conv+bn+fc
net must separate 4 classes of blob images.  The script demonstrates the
full surface: startup init, train-program steps, moving-stat write-backs,
clone(for_test=True) evaluation, and static.save/load.
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_blobs(rs, n, n_classes, hw=12):
    """Class-dependent blob position + noise — conv-separable."""
    import numpy as np
    ys = rs.randint(0, n_classes, n)
    xs = rs.normal(0, 0.3, size=(n, 1, hw, hw)).astype("float32")
    for i, c in enumerate(ys):
        r, col = divmod(int(c), 2)
        xs[i, 0, 2 + 5 * r:6 + 5 * r, 2 + 5 * col:6 + 5 * col] += 1.5
    return xs, ys.reshape(-1, 1).astype("int64")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import static

    paddle.enable_static()
    main_prog = static.Program()
    startup = static.Program()
    main_prog.random_seed = 7

    with static.program_guard(main_prog, startup):
        x = static.data("x", [None, 1, 12, 12])
        y = static.data("y", [None, 1], "int64")
        h = static.nn.conv2d(x, num_filters=8, filter_size=3, act="relu")
        h = static.nn.batch_norm(h)
        logits = static.nn.fc(h, 4)
        loss = paddle.mean(F.cross_entropy(logits, y))
        paddle.optimizer.Adam(learning_rate=0.01).minimize(loss)
    test_prog = main_prog.clone(for_test=True)

    exe = static.Executor(paddle.CPUPlace())
    exe.run(startup)

    rs = np.random.RandomState(0)
    xs, ys = make_blobs(rs, 256, 4)
    first = last = None
    for step in range(args.steps):
        i = (step * args.batch) % (len(xs) - args.batch)
        lv, = exe.run(main_prog,
                      feed={"x": xs[i:i + args.batch], "y": ys[i:i + args.batch]},
                      fetch_list=[loss])
        first = lv if first is None else first
        last = lv
        if step % 20 == 0:
            print(f"step {step}: loss {float(lv):.4f}")
    print(f"train loss {float(first):.4f} -> {float(last):.4f}")
    assert float(last) < float(first) * 0.5, "static training failed to learn"

    # evaluation on the pruned inference clone (no label feed needed)
    out, = exe.run(test_prog, feed={"x": xs}, fetch_list=[logits])
    acc = float((out.argmax(1) == ys.ravel()).mean())
    print(f"eval accuracy {acc:.3f}")
    assert acc > 0.9, acc

    # save / reload the program state and re-verify
    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "static_model")
        static.save(main_prog, prefix)
        wname = next(n for n in main_prog.params if n.endswith(".w_0"))
        static.global_scope()._store[wname] = np.zeros_like(
            np.asarray(static.global_scope().find_var(wname).get_tensor()))
        static.load(main_prog, prefix)
        out2, = exe.run(test_prog, feed={"x": xs}, fetch_list=[logits])
        assert np.allclose(out, out2), "reload changed predictions"
    print("save/load roundtrip OK")
    paddle.disable_static()
    print("STATIC_EXAMPLE_OK")


if __name__ == "__main__":
    main()
