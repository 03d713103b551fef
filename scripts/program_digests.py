"""Are two trees' programs the same programs?  sha256 of what each cell's
jitted programs LOWER to, with no debug information:

    python3 scripts/program_digests.py [--tree .checkout/parent] [--cells a,b] [--layers N]

For every serving cell of ``BENCHMARK.json``: the cell's model from its
configuration file and an engine with the cell's ``engine`` arguments,
then the StableHLO (``.lower(...).as_text()``) of ``jit_prefill`` at the
widest chunk the cell's longest prompt reaches and of ``jit_decode``,
over the engine's own operands.  The programs are taken where the engine
is about to dispatch them and the dispatch is abandoned: nothing is
compiled or run.  For every training cell: the step
``scripts/train_step_fusions.py`` composes as ``train_job`` does, over
shapes, its StableHLO and its OPTIMIZED text (compile-only client for a
v5e) with instruction names canonical and no metadata.

"No debug information" has to reach inside the Pallas kernels: a
``tpu_custom_call`` carries its Mosaic module as serialized MLIR WITH
locations (file, line and the name stack, so a scope around a kernel's
caller, a moved line or another checkout path changes the bytes).  Each
such body is parsed and re-printed without them before the text is
hashed.

``--tree`` reads another checkout (its ``paddle_tpu``, ``benchmarks``,
``scripts`` and ``BENCHMARK.json``): run once per tree and compare the
lines.  On a host without a TPU ``jax.default_backend`` reports
``"tpu"`` while the programs are traced, HERE ONLY, so that every route
is the chip's.  ``--layers N`` cuts every model to its first N layers: a
full-size engine holds the cell's slabs (12 GB for ``gpt3-6.7b-d8``), so
here on a CPU the depth is cut and the widths and the engine's geometry
kept; on a chip the cell is built whole.
"""

import argparse
import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

_BODY = re.compile(r'(\\22|")body\1: \1([A-Za-z0-9+/=]+)\1')
_DEPTH_KEYS = ("num_layers", "num_hidden_layers")


class _Taken(BaseException):
    """Raised out of a dispatch once its program is in hand (a
    ``BaseException``: the engine's fault handling lets it through)."""


def strip_kernel_locations(text: str) -> str:
    """``text`` with every Mosaic kernel body replaced by the sha256 of
    its MLIR printed without debug information."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    tpu.register_dialect(ctx)

    def digest(m):
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(2)))
            asm = module.operation.get_asm(enable_debug_info=False)
        q = m.group(1)
        return f"{q}body{q}: {q}sha256:" \
               f"{hashlib.sha256(asm.encode()).hexdigest()}{q}"
    return _BODY.sub(digest, text)


def sha(text: str) -> str:
    return hashlib.sha256(strip_kernel_locations(text).encode()).hexdigest()


class _Lowered:
    """Stands where the engine keeps a program: called with the
    dispatch's operands it lowers the real program over them, keeps the
    digest and abandons the dispatch."""

    def __init__(self, program, into: dict, name: str):
        self.program, self.into, self.name = program, into, name

    def __call__(self, *args):
        lowered = self.program.func.trace(
            *self.program.args, *args).lower(lowering_platforms=("tpu",))
        self.into[self.name] = sha(lowered.as_text())
        raise _Taken


def serving_digests(files, cell: dict, layers) -> dict:
    import numpy as np
    from benchmarks.lib.build import build_model
    from paddle_tpu.serving import ServingEngine
    cfg = dict(files.json(os.path.join(
        files.base, files.entry("configs", cell["config"])["file"])))
    if layers:
        for k in _DEPTH_KEYS:
            if k in cfg:
                cfg[k] = min(cfg[k], layers)
    mix = files.json(files.find(f"traffic/{cell['traffic']}.json"))
    builder = files.module(f"builders/{cfg['builder']}.py")
    model, mcfg = build_model(builder, cfg, 1)
    eng = ServingEngine(model, **cfg.get("engine", {}))
    core, out = eng.core, {}
    core._prefill_fn = _Lowered(core._build_prefill_fn(), out, "jit_prefill")
    core._decode_fn = _Lowered(core._build_decode_fn(), out, "jit_decode")
    longest = mix["prompt_len"]["max"]
    widths = [w for _, w, _ in core.scheduler.chunk_plan(
        0, longest, core.prefill_chunk)]
    eng.submit(np.ones((longest,), np.int32), max_new_tokens=2)
    for dispatch in (eng.step, core._decode_dispatch):
        try:
            dispatch()
        except _Taken:
            pass
    return {"prefill_width": widths[0], "layers": layers or "all",
            "decode_path": eng.decode_path, **out}


def training_digests(root: str, files, cell: dict) -> dict:
    from jax._src.lib import _jax
    from scripts import train_step_fusions as T
    cfg = files.json(os.path.join(
        files.base, files.entry("configs", cell["config"])["file"]))
    mix = files.json(files.find(f"traffic/{cell['traffic']}.json"))
    builder = files.module(f"builders/{cfg['builder']}.py")
    step, args = T.abstract_train_step(builder, cfg, mix, "AdamW")
    traced, compiled = T.compile_for_chip(step, args,
                                          T.describe_v5e().devices[0])
    with T._traced_as_tpu():
        lowered = traced.lower(lowering_platforms=("tpu",))
    options = _jax.HloPrintOptions.canonical()
    options.print_metadata = False
    module, = compiled._executable.xla_executable.hlo_modules()
    return {"jit_step": sha(lowered.as_text()),
            "jit_step_optimized": sha(module.to_string(options))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to read")
    ap.add_argument("--cells", help="comma-separated cell names "
                                    "(default: every cell)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut every serving model to its first N layers")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.tree)
    sys.path.insert(0, root)
    import jax
    from benchmarks import run as R
    files = R.Files(os.path.join(root, "BENCHMARK.json"))
    wanted = args.cells.split(",") if args.cells else None
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        jax.default_backend = lambda: "tpu"
    for cell in files.manifest["workloads"]:
        if wanted and cell["name"] not in wanted:
            continue
        mix = files.json(files.find(f"traffic/{cell['traffic']}.json"))
        row = serving_digests(files, cell, args.layers) \
            if mix["kind"] == "open_loop" \
            else training_digests(root, files, cell)
        print(json.dumps({"cell": cell["name"], "tree": root,
                          "on_tpu": on_tpu, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
