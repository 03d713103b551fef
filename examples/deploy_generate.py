"""Train -> quantize -> generate -> AOT-export: the deployment path.

Reference analog: train with paddle, convert with the inference/
quantization tooling, serve with paddle inference / fused decode ops.
Here the whole chain is TPU-native:

1. train a tiny GPT a few steps (jitted functional step),
2. swap every dense linear for int8 weight-only storage
   (``nn.quant.convert_to_weight_only`` — 2-4x less decode HBM traffic),
3. decode with ``model.generate`` — the WHOLE autoregressive KV-cache
   loop is one compiled ``lax.scan`` (greedy here; beam_search for
   search), and
4. ``jit.save_program`` the jitted generate: the serialized artifact
   reloads in any process and reproduces the tokens bit-for-bit.

Run:

    env JAX_PLATFORMS=cpu \
        python examples/deploy_generate.py --steps 30
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--prompt_len", type=int, default=8)
    ap.add_argument("--new_tokens", type=int, default=12)
    args = ap.parse_args()

    import functools

    import numpy as np
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    import paddle_tpu.nn.quant as Q
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit as pjit
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.nn.functional_call import functional_call, state

    paddle_tpu.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    params, buffers = state(model)
    o = opt.AdamW(learning_rate=3e-3)
    ostate = o.init(params)

    # a repeating token pattern the model can actually learn (length
    # stays inside gpt_tiny's 128 max positions)
    rs = np.random.RandomState(0)
    period = np.asarray(rs.randint(0, 256, 16))
    seq = np.tile(period, 7)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, os_, x, y):
        def loss_fn(p):
            out, _ = functional_call(model, p, buffers, (x,), train=True)
            logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
            return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))
        loss, g = jax.value_and_grad(loss_fn)(p)
        newp, nos = o.update(g, os_, p)
        return newp, nos, loss

    x = jnp.asarray(seq[None, :-1])
    y = jnp.asarray(seq[None, 1:])
    first = last = None
    for i in range(args.steps):
        params, ostate, loss = step(params, ostate, x, y)
        lv = float(loss)
        first = lv if first is None else first
        last = lv
    print(f"train loss {first:.3f} -> {last:.3f}")
    assert last < 0.5 * first, "did not learn the pattern"

    # push the trained params back into the Layer, then quantize weights
    model.set_state_dict({**params, **buffers})
    qmodel = Q.convert_to_weight_only(model, weight_dtype="int8")
    n_q = sum(1 for _, l in qmodel.named_sublayers()
              if type(l).__name__ == "WeightOnlyLinear")
    print(f"quantized {n_q} linears to int8 weight-only storage")

    prompt = jnp.asarray(seq[None, :args.prompt_len])
    gen = jax.jit(lambda ids: qmodel.generate(ids, args.new_tokens))
    out = np.asarray(gen(prompt))[0, args.prompt_len:]
    want = seq[args.prompt_len:args.prompt_len + args.new_tokens]
    acc = float((out == want).mean())
    print(f"generated continuation accuracy vs pattern: {acc:.2f}")
    assert acc > 0.7, (out, want)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "decode")
        pjit.save_program(gen, path, prompt)
        loaded = pjit.load_program(path)
        re_out = np.asarray(loaded.call(prompt))[0, args.prompt_len:]
        assert (re_out == out).all()
        size_kb = os.path.getsize(path + ".pdprog") / 1024
        print(f"AOT artifact reloaded, tokens bit-equal ({size_kb:.0f} KB)")


if __name__ == "__main__":
    main()
