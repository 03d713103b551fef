"""Controls for the comparison that decides ``correct``: the benchmark's
own run of a cell whose model is DeepSeek-V3-shaped, on a PROGRAM with
one fault.

    python3 scripts/wrong_builds.py --fault shared_expert_left_out \
        --workload joyai-llm-flash.serve-assist-4k --seed 1 --seconds 40 --trace 0

Every argument but ``--fault`` and ``--slow-step`` goes to
``benchmarks/run.py`` untouched: the same engine, traffic, plain
reference and comparison, the reference on the same weights; only the
program's model code is wrong.  A comparison that covers the faulty
layer prints ``correct: false`` (``tokens_near_reference_argmax``).
PERF.md section 6 (PR 34) holds what each fault read on the chip at the
cell's size and initializer, beside the sound program's readings: the
initializer of ``benchmarks/configs/joyai-llm-flash-d5.json`` was
chosen so that the 0.03 bar of ``benchmarks/lib/reference.py`` lies
between them.  ``scripts/served_reference_check.py --faults`` reads the
same faults on LOGITS.

``--fault none`` runs the cell as it is.  With every fault, an engine
step that takes over ``--slow-step`` seconds is printed as it ends with
the phases that took a tenth of it or more (an untraced run prints no
phase otherwise, and the engine's ring of spans has long turned over
when the window closes).
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def four_bits(x):
    """``x`` rounded to 4 significant bits, to nearest even: float8
    e4m3's significand, where bfloat16 keeps 8 (the nearest format
    below it), at ``x``'s own range.  By integer arithmetic on the bits:
    a convert to ``float8_e4m3fn`` and back ahead of the grouped matmul
    changed no reading on the chip by one digit (a pair of converts is
    precision XLA:TPU is allowed to keep; PERF.md section 6, PR 34)."""
    import jax
    import jax.numpy as jnp
    word = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    drop = jnp.finfo(x.dtype).nmant - 3
    bits = jax.lax.bitcast_convert_type(x, word)
    bits = bits + word((1 << (drop - 1)) - 1) + ((bits >> drop) & word(1))
    bits = bits & word(~((1 << drop) - 1) & (2 ** (8 * x.dtype.itemsize) - 1))
    return jax.lax.bitcast_convert_type(bits, x.dtype)


def shared_expert_left_out(model, patch):
    for layer in model.model.layers:
        if layer.sparse:
            patch(layer.mlp, "n_shared", 0)


def routed_inputs_to_4_bits(model, patch):
    """The activation operand of the ROUTED experts' matmuls alone: what
    a comparison that forgives a flipped near-tie (ONE wrong expert of
    eight) cannot see."""
    from paddle_tpu.distributed import moe_dropless
    grouped = moe_dropless.grouped_matmul
    patch(moe_dropless, "grouped_matmul",
          lambda lhs, rhs, sizes: grouped(four_bits(lhs), rhs, sizes))


def mlp_inputs_to_4_bits(model, patch):
    """That of every matmul of the gated MLPs (dense, shared, routed)
    and of ``o_proj``: the layers' branches, nothing of the head."""
    from paddle_tpu.distributed import moe_dropless
    from paddle_tpu.models import deepseek_v3
    routed_inputs_to_4_bits(model, patch)
    wide = moe_dropless._wide
    for module in (moe_dropless, deepseek_v3):
        patch(module, "_wide", lambda layer, x: wide(layer, four_bits(x)))


def matmul_inputs_to_4_bits(model, patch):
    """That of EVERY weight matmul: the above, the attention's
    projections and the head: the program as a float8-activation build
    would run it."""
    from paddle_tpu.nn.layers.common import Linear
    mlp_inputs_to_4_bits(model, patch)
    forward = Linear.forward
    patch(Linear, "forward", lambda self, x: forward(self, four_bits(x)))


def routed_scale_left_out(model, patch):
    for layer in model.model.layers:
        if layer.sparse:
            patch(layer.mlp, "routed_scale", 1.0)


FAULTS = {f.__name__: f for f in (
    shared_expert_left_out, routed_scale_left_out, routed_inputs_to_4_bits,
    mlp_inputs_to_4_bits, matmul_inputs_to_4_bits)}
FAULTS["none"] = lambda model, patch: None


def watch_slow_steps(threshold: float, log=print) -> None:
    """Print every ``serving.step`` span longer than ``threshold``
    seconds as it ends, with its ``step.<phase>`` children of a tenth of
    it or more."""
    from paddle_tpu.obs import Tracer
    end_span, phases = Tracer.end_span, []

    def watched(self, span, t=None):
        end_span(self, span, t)
        if span is None:
            return
        if span.name.startswith("step."):
            phases.append((span.name, span.duration))
        elif span.name == "serving.step":
            if span.duration > threshold:
                long = [(n, round(d, 3)) for n, d in phases
                        if d >= span.duration / 10]
                log(f"slow step: {span.duration:.3f}s "
                    f"{dict(span.attrs or {})} phases {long}")
            del phases[:]

    Tracer.end_span = watched


def install(fault: str, patch=setattr) -> None:
    """Every ``DeepseekV3ForCausalLM`` built from here on has
    ``fault``."""
    from paddle_tpu.models import DeepseekV3ForCausalLM
    init = DeepseekV3ForCausalLM.__init__

    def faulty(self, *a, **kw):
        init(self, *a, **kw)
        FAULTS[fault](self, patch)

    patch(DeepseekV3ForCausalLM, "__init__", faulty)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--slow-step", type=float, default=0.2)
    args, rest = ap.parse_known_args(argv)

    from benchmarks import run as R
    install(args.fault)
    watch_slow_steps(args.slow_step, R.log)
    R.log(f"wrong build: {args.fault}")
    return R.main(rest)


if __name__ == "__main__":
    sys.exit(main())
