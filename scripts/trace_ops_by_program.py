"""Where a program's device time goes, by operation, from a traced
benchmark run's ``.xplane.pb``:

    python3 scripts/trace_ops_by_program.py benchmarks/out/trace/<cell> --programs jit_prefill,jit_decode

For each program name prefix: the events of device 0's ``XLA Modules``
line with that prefix, and the ``XLA Ops`` events that fall inside them,
summed by name (with ``--group``: by KIND, the name without its number
and the result's type, so that 26 layers' copies of one fusion are one
row) and divided by the number of programs: milliseconds per program
run, largest first.  LEAF events only (``benchmarks/lib/parts.leaves``,
the rule the part readers use): a ``while``'s body operations are on
the line inside the loop's own event, and the loop is left out, so the
rows add up to the program.  With ``--parts``: the same time by PART of
the model and phase (``paddle_tpu/obs/parts.py``), each operation's
part read off the ``op_name`` the trace file itself carries for it (the
``tf_op`` stat of the event's metadata), so any trace of a tree with the
scopes will do, a serving run's or a training run's, and no span or
second compile is needed.  Reads a file; needs no chip.
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


_KIND = re.compile(r"^(%[A-Za-z_\-]+)[.\d]* = (\(?[a-z0-9]+\[[0-9,]*\])")


def kind_of(name: str) -> str:
    """``%fusion.12 = f32[512,8192]{...} fusion(...)`` -> ``%fusion
    f32[512,8192]``: what the copies of one operation share."""
    m = _KIND.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def ops_by_program(trace: dict, prefix: str,
                   key=lambda name: name) -> dict:
    """``{"programs": n, "mean_ms": t, "ops": [[name, ms per program,
    calls per program], ...]}`` for the programs named ``prefix`` on
    device 0, their leaf operations summed under ``key(name)``."""
    from benchmarks.lib import parts
    programs = parts.program_events(trace, prefix)
    if not programs:
        return {"programs": 0, "mean_ms": None, "ops": []}
    total, calls = {}, {}
    for _, _, events in programs:
        for name, _, dur in events:
            name = key(name)
            total[name] = total.get(name, 0.0) + dur
            calls[name] = calls.get(name, 0) + 1
    n = len(programs)
    return {"programs": n,
            "mean_ms": sum(e - s for s, e, _ in programs) / n / 1e6,
            "ops": [[name, ns / n / 1e6, calls[name] / n] for name, ns
                    in sorted(total.items(), key=lambda kv: -kv[1])]}


def print_parts(path: str, trace: dict, prefix: str) -> None:
    """The program's time by part and phase, and its three largest
    operations under no scope."""
    from benchmarks.lib import parts
    row = parts.by_part(trace, prefix, parts.file_tables(path))
    print(json.dumps({"program": prefix, "programs": row["programs"],
                      "mean_ms": row["mean_ms"], "ops_ms": row["ops_ms"],
                      "unscoped_ms": row["unscoped_ms"]}))
    for (part, phase), ms in row["parts"].items():
        print(f"  {ms:9.4f} ms  {part}.{phase}")
    for name, ms in row["unscoped"]:
        print(f"  {ms:9.4f} ms  under no scope: {name}")


def main(argv=None) -> int:
    from benchmarks.lib import xplane
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="a trace directory, or the "
                                     ".xplane.pb itself")
    ap.add_argument("--programs", default="jit_prefill,jit_decode")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--width", type=int, default=110)
    ap.add_argument("--group", action="store_true",
                    help="sum the copies of one operation under its kind")
    ap.add_argument("--parts", action="store_true",
                    help="sum by part of the model instead, from the "
                         "op_names in the trace file")
    args = ap.parse_args(argv)
    path = args.trace_dir if os.path.isfile(args.trace_dir) \
        else xplane.find_xplane(args.trace_dir)
    trace = xplane.load(path)
    for prefix in args.programs.split(","):
        if args.parts:
            print_parts(path, trace, prefix)
            continue
        row = ops_by_program(trace, prefix,
                             key=kind_of if args.group else str)
        print(json.dumps({"program": prefix, "programs": row["programs"],
                          "mean_ms": row["mean_ms"]}))
        for name, ms, calls in row["ops"][:args.top]:
            print(f"  {ms:9.4f} ms  x{calls:6.1f}  {name[:args.width]}")
        rest = sum(ms for _, ms, _ in row["ops"][args.top:])
        print(f"  {rest:9.4f} ms  the other "
              f"{max(len(row['ops']) - args.top, 0)} operations")
    return 0


if __name__ == "__main__":
    sys.exit(main())
