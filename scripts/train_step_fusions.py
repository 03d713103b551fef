"""What the chip's compiler makes of a training step's parameter updates
and weight-gradient matmuls, from any host, without a chip:

    python3 scripts/train_step_fusions.py --workload mistral-7b.train-4k [--optimizer FusedAdamW]

Builds the cell's step as ``benchmarks/drivers/train_job.py`` does (its
configuration and traffic files, ``functional_call`` + cross-entropy +
the optimizer with ``multi_precision``, arguments donated) over ABSTRACT
weights and optimizer state (``jax.eval_shape``: nothing of the 11 GB is
made), compiles it for one v5e chip with libtpu's compile-only client
(``get_topology_desc``) and reads the optimized program's text.  Prints
one row per fusion that writes an updated parameter or optimizer-state
array, or that computes a weight gradient (a fusion of the backward
pass whose result has a matrix parameter's shape: a ``convolution``, or
the embedding's scatter-add): name, kind,
result shapes, whether its body holds a ``convolution``, and the
compiler's ``iteration_bounds`` and ``estimated_cycles`` for it.  Then
the two counts that say whether ``Optimizer.update``'s per-leaf barrier
engaged, ``update_fusions_with_matmul`` (update fusions whose body holds
a ``convolution``: 15 before PR 33, 0 since) and ``grad_barriers``
(``optimization_barrier`` equations in the step's jaxpr: one per gradient
leaf; those ``jax.checkpoint`` adds when it is LOWERED are not in the
jaxpr and not counted), the program's ``code`` / ``temp`` / ``alias``
bytes and the sum of ``estimated_cycles``.  With ``--trace DIR`` (a traced
benchmark run of the SAME tree: the trace names operations as this
compile does) it also sums the traced device time by part of the model
and phase, from the scopes the step carries: the compiled text's table
(``paddle_tpu.obs.parts.program_parts``) over the trace's leaf events
(``benchmarks/lib/parts.by_part``), and the benchmark's four training
metrics of it (``recomputed_forward_ms``, ``head_loss_ms``,
``optimizer_ms``, ``scope_coverage.train``).

With ``--steps N`` it needs a TPU and RUNS the same step instead: model,
corpus and optimizer state from ``--seed`` as the benchmark makes them,
then N steps, each closed by ``block_until_ready``; prints every loss
(to compare a same-seed pair of two trees digit by digit), the median
step time and the peak memory.  ``--optimizer FusedAdamW --steps 12`` is
the hearing ROADMAP D3 owed ``kernels/fused_adamw.py``.

A compile is no chip run: cycles are the compiler's estimate, not a time
(PERF.md section 6, PR 33, holds both side by side).  ``jax.default_backend``
reports ``"tpu"`` while the step is traced, HERE ONLY, so that attention
routes to the flash kernel as on the chip (the dense path asks for 19.6 GB
at 2 x 4096 and the compile fails).
"""

import argparse
import contextlib
import functools
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@contextlib.contextmanager
def _traced_as_tpu():
    """Code that asks ``jax.default_backend()`` takes its chip branch."""
    import jax
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = real


def describe_v5e():
    """The detached v5e topology libtpu compiles for (raises where this
    installation cannot describe one)."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def _donated_step(model, mcfg, mix: dict, optimizer: str):
    """``train_job``'s step over ``model``: ``(optimizer, step)``."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.meta_parallel.mp_layers import \
        parallel_cross_entropy
    from paddle_tpu.nn.functional_call import functional_call

    def loss_of(p, x, y):
        out, _ = functional_call(model, p, {}, (x,), train=True)
        return jnp.mean(parallel_cross_entropy(out, y))

    o = getattr(opt, optimizer)(learning_rate=mix["learning_rate"],
                                multi_precision=mcfg.dtype != "float32")

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, os_, x, y):
        loss, g = jax.value_and_grad(loss_of)(p, x, y)
        newp, nos = o.update(g, os_, p)
        return newp, nos, loss

    return o, step


def abstract_train_step(builder, cfg: dict, mix: dict,
                        optimizer: str = "AdamW"):
    """``train_job``'s donated step for ``cfg`` under ``mix`` and its
    arguments as shapes: ``(step, (params, opt_state, x, y))``."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.random import rng_context
    from paddle_tpu.nn.functional_call import state

    mcfg = builder.model_config(cfg, mix["seq"])
    made = []

    def make(key):
        with rng_context(key):
            model = builder.model_class()(mcfg)
        if mcfg.dtype != "float32":
            model.to(dtype=mcfg.dtype)
        made.append(model)
        return state(model)

    params, buffers = jax.eval_shape(make, jax.random.key(0))
    if buffers:
        raise ValueError(f"the model holds buffers {sorted(buffers)}: "
                         f"train_job closes over them as constants, which "
                         f"shapes cannot stand in for")
    o, step = _donated_step(made[0], mcfg, mix, optimizer)
    ids = jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32)
    return step, (params, jax.eval_shape(o.init, params), ids, ids)


def run_steps(builder, cfg: dict, mix: dict, optimizer: str, seed: int,
              steps: int) -> dict:
    """ON A CHIP: the cell's model, corpus and optimizer state made from
    ``seed`` as ``train_job`` makes them, ``steps`` steps from there,
    each closed by ``block_until_ready``: every loss, the median step
    time after the first two, the device's peak memory."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import stats, traffic
    from benchmarks.lib.build import build_model
    from paddle_tpu.nn.functional_call import state
    model, mcfg = build_model(builder, cfg, seed, max_seq_len=mix["seq"])
    params, _ = state(model)
    data = traffic.corpus(mix, seed, mcfg.vocab_size)
    o, step = _donated_step(model, mcfg, mix, optimizer)
    ostate = o.init(params)
    losses, closed_ms = [], []
    for k in range(steps):
        batch = data[k % len(data)]
        x, y = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
        t0 = time.perf_counter()
        params, ostate, loss = step(params, ostate, x, y)
        loss.block_until_ready()
        closed_ms.append(1e3 * (time.perf_counter() - t0))
        losses.append(loss)
    dev = jax.devices()[0]
    return {"optimizer": optimizer, "seed": seed, "device": dev.device_kind,
            "losses": [repr(float(v)) for v in jax.device_get(losses)],
            "first_step_ms": closed_ms[0],
            "step_ms_median": stats.median(closed_ms[2:]),
            "memory_peak_bytes": int((dev.memory_stats() or {})
                                     .get("peak_bytes_in_use", 0))}


def compile_for_chip(step, args, device):
    """Trace jitted ``step`` and compile it for ``device`` (one of a
    described topology's); ``(traced, compiled)``."""
    import jax
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(device)
    placed = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), args)
    with _traced_as_tpu():
        traced = step.trace(*placed)
        lowered = traced.lower(lowering_platforms=("tpu",))
    return traced, lowered.compile()


def count_primitive(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in ``jaxpr`` and every jaxpr its
    equations hold (``pjit``, ``checkpoint``, ``scan`` ...)."""
    import jax
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += count_primitive(sub, name)
    return n


_INSTR = re.compile(r"^\s+(ROOT )?%(\S+) = ")
_ARRAY = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
_THROUGH = ("get-tuple-element", "bitcast", "copy")


def _closing(text: str, depth: int = 0) -> int:
    """Index of the ``)`` that brings ``depth`` open parentheses (plus
    those ``text`` opens) back to none."""
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return i
    raise ValueError(f"unbalanced parentheses in {text[:80]!r}")


def _split_instruction(rest: str):
    """``(type, opcode, operand names, attributes)`` of the text after
    an instruction's `` = ``; a tuple type is parenthesised."""
    if rest.startswith("("):
        end = _closing(rest)
        typ, rest = rest[:end + 1], rest[end + 2:]
    else:
        typ, _, rest = rest.partition(" ")
    opcode, _, rest = rest.partition("(")
    end = _closing(rest, 1)
    return typ, opcode, re.findall(r"%([^\s,)]+)", rest[:end]), rest[end:]


def parse_entry(hlo: str):
    """The optimized module's text -> ``(instructions of ENTRY by name,
    name of ENTRY's root, names of the computations that hold a
    convolution)``; an instruction is ``{"type", "opcode", "operands",
    "attrs"}``."""
    with_conv, entry, root, current, in_entry = set(), {}, None, None, False
    for line in hlo.splitlines():
        if line.startswith(("%", "ENTRY ")):
            in_entry = line.startswith("ENTRY ")
            current = line.split()[1 if in_entry else 0].lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        typ, opcode, operands, attrs = _split_instruction(line[m.end():])
        if opcode == "convolution":
            with_conv.add(current)
        if in_entry:
            entry[m.group(2)] = {"type": typ, "opcode": opcode,
                                 "operands": operands, "attrs": attrs}
            if m.group(1):
                root = m.group(2)
    return entry, root, with_conv


def fusion_report(hlo: str, update_outputs, matrix_shapes) -> dict:
    """The rows and counts of this script for one compiled step.
    ``update_outputs``: the indices, among the step's flat outputs, of the
    updated parameters and optimizer-state arrays; ``matrix_shapes``: the
    dimension tuples of the parameters of two or more axes."""
    entry, root, with_conv = parse_entry(hlo)
    writers = set()
    results = entry[root]["operands"] if entry[root]["opcode"] == "tuple" \
        else [root]
    for i in update_outputs:
        name = results[i]
        while entry[name]["opcode"] in _THROUGH:
            name = entry[name]["operands"][0]
        writers.add(name)
    wanted = {",".join(map(str, s)) for s in matrix_shapes}
    rows, cycles = [], 0
    for name, ins in entry.items():
        cyc = re.search(r'"estimated_cycles":"(\d+)"', ins["attrs"])
        cycles += int(cyc.group(1)) if cyc else 0
        calls = re.search(r"calls=%(\S+?),", ins["attrs"])
        conv = ins["opcode"] == "fusion" and calls.group(1) in with_conv
        shapes = _ARRAY.findall(ins["type"])
        # the embedding's gradient is a scatter-add, not a convolution
        grad = ins["opcode"] == "fusion" and "transpose(" in ins["attrs"] \
            and name not in writers \
            and any(dims in wanted for _, dims in shapes)
        if ins["opcode"] != "fusion" or (name not in writers and not grad):
            continue
        bounds = re.search(r'"iteration_bounds":\[([^\]]*)\]', ins["attrs"])
        rows.append({
            "name": name,
            "kind": re.search(r"kind=(\w+)", ins["attrs"]).group(1),
            "outputs": [f"{t}[{d}]" for t, d in shapes],
            "writes_update": name in writers, "convolution": conv,
            "iteration_bounds": [int(b) for b in re.findall(
                r"\d+", bounds.group(1))] if bounds else None,
            "estimated_cycles": int(cyc.group(1)) if cyc else None})
    return {
        "fusions": rows,
        "update_fusions": sum(r["writes_update"] for r in rows),
        "update_fusions_with_matmul": sum(
            r["writes_update"] and r["convolution"] for r in rows),
        "weight_gradient_fusions": sum(
            r["convolution"] and not r["writes_update"] for r in rows),
        "estimated_cycles": cycles}


def parts_of_trace(trace_path: str, table: dict, prefix: str) -> dict:
    """A traced run's device time inside the programs named ``prefix``
    by part and phase of ``table`` (the compiled step's
    ``program_parts``), and the benchmark's training metrics of it.  A
    traced name the compiled program lacks (the trace is another
    tree's) raises."""
    from benchmarks.lib import parts, xplane
    path = trace_path if os.path.isfile(trace_path) \
        else xplane.find_xplane(trace_path)
    row = parts.by_part(xplane.load(path), prefix, table)
    return {"programs": row["programs"], "mean_ms": row["mean_ms"],
            "ops_ms": row["ops_ms"],
            "parts": {f"{part}.{phase}": round(ms, 3)
                      for (part, phase), ms in row["parts"].items()},
            "largest_unscoped": row["unscoped"],
            "recomputed_forward_ms": parts.part_ms(row, phase="recomputed"),
            "head_loss_ms": parts.part_ms(row, "head", "loss"),
            "optimizer_ms": parts.part_ms(row, "optimizer"),
            "scope_coverage.train": parts.coverage_percent([row])}


def train_step_report(builder, cfg: dict, mix: dict, optimizer: str,
                      device) -> dict:
    """Compile ``cfg``'s step under ``mix`` for ``device`` and report;
    ``parts`` is the step's table ``{operation: (part, phase)}``."""
    import jax
    from paddle_tpu.obs.parts import program_parts
    step, args = abstract_train_step(builder, cfg, mix, optimizer)
    t0 = time.perf_counter()
    traced, compiled = compile_for_chip(step, args, device)
    compile_s = time.perf_counter() - t0
    params, ostate = args[0], args[1]
    # the step returns (new params, new state, loss): the arrays of the
    # first two, the step counter aside, are what an update writes
    leaves = jax.tree.leaves((params, ostate))
    text = compiled.as_text()
    rep = fusion_report(
        text, [i for i, leaf in enumerate(leaves) if leaf.ndim],
        {p.shape for p in jax.tree.leaves(params) if p.ndim >= 2})
    mem = compiled.memory_analysis()
    rep.update(parts=program_parts(text),
               grad_barriers=count_primitive(traced.jaxpr.jaxpr,
                                             "optimization_barrier"),
               optimizer=optimizer, compile_s=round(compile_s, 1),
               gradient_leaves=len(jax.tree.leaves(params)),
               code_bytes=mem.generated_code_size_in_bytes,
               temp_bytes=mem.temp_size_in_bytes,
               alias_bytes=mem.alias_size_in_bytes,
               argument_bytes=mem.argument_size_in_bytes,
               output_bytes=mem.output_size_in_bytes)
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mistral-7b.train-4k",
                    help="a training cell of BENCHMARK.json")
    ap.add_argument("--optimizer", default="AdamW",
                    help="a class of paddle_tpu.optimizer")
    ap.add_argument("--steps", type=int, default=0,
                    help="needs a TPU: RUN this many steps from --seed "
                         "and print their losses and the step's time, "
                         "instead of compiling for a described chip")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace", help="a traced benchmark run of THIS tree's "
                    "step (a trace directory or its .xplane.pb): print its "
                    "device time by part of the step as well")
    args = ap.parse_args(argv)

    from benchmarks import run as R
    files = R.Files(os.path.join(ROOT, "BENCHMARK.json"))
    cell = files.entry("workloads", args.workload)
    cfg = files.json(os.path.join(
        ROOT, files.entry("configs", cell["config"])["file"]))
    mix = files.json(files.find(f"traffic/{cell['traffic']}.json"))
    builder = files.module(f"builders/{cfg['builder']}.py")
    if args.steps:
        R.demand_tpu(cell["chips"])
        print(json.dumps({"workload": args.workload, **run_steps(
            builder, cfg, mix, args.optimizer, args.seed, args.steps)}))
        return 0
    rep = train_step_report(builder, cfg, mix, args.optimizer,
                            describe_v5e().devices[0])
    for row in rep.pop("fusions"):
        print(json.dumps(row))
    table = rep.pop("parts")
    print(json.dumps({"workload": args.workload, **rep}))
    if args.trace:
        print(json.dumps(parts_of_trace(args.trace, table,
                                        mix["step_module_prefix"])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
