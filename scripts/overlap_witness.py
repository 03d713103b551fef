"""One decode program in flight, witnessed in a cell's own traced run:

    python3 scripts/overlap_witness.py --workload gpt3-6.7b.serve-chat --seed 1 --seconds 40 --trace 1

Every argument goes to ``benchmarks/run.py`` untouched: the same engine,
traffic, window and result line.  Behind the line it prints what the
benchmark has no reader for, from the run's own ``serving.step`` spans
(``--trace 1`` keeps them): ``overlap`` as the engine resolved it, the
share of the window's decoding steps (``active_slots > 0``) whose decode
program was dispatched ahead of the previous one's read
(``decode_ahead``), the tokens dropped as overruns, and the largest
``experts_touched`` over what the span's own ``active_slots`` can reach.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def witness(result: dict, overlap) -> dict:
    t0, t1 = result["window"]
    steps = [a for name, start, _, a in result["spans"] or ()
             if name == "serving.step" and t0 <= start < t1]
    decoding = [a for a in steps if a.get("active_slots", 0) > 0]
    facts = result.get("facts") or {}
    over_cap = None
    if "expert_params" in facts:
        from benchmarks.lib import moe_flops_bytes
        over_cap = max((a["experts_touched"] / moe_flops_bytes.experts_cap(
            facts, a["active_slots"]) for a in decoding), default=None)
    return {
        "overlap": overlap[0], "overlap_reason": overlap[1] or "",
        "steps": len(steps), "decoding_steps": len(decoding),
        "decode_ahead_share": sum(a["decode_ahead"] for a in decoding)
        / len(decoding) if decoding else None,
        "overrun_tokens": sum(a["overrun_tokens"] for a in steps),
        "experts_touched_over_cap_max": over_cap,
    }


def main(argv=None) -> int:
    from benchmarks import run as R
    from benchmarks.drivers import open_loop
    seen = {}
    build, run = open_loop.build, open_loop.run

    def build_and_look(ctx):
        built = build(ctx)
        seen["overlap"] = built[2].core.overlap()
        return built

    def run_and_keep(ctx):
        seen["result"] = run(ctx)
        return seen["result"]

    open_loop.build, open_loop.run = build_and_look, run_and_keep
    rc = R.main(argv)
    if seen.get("result", {}).get("spans") is None:
        print("overlap_witness: no spans (run with --trace 1)")
        return rc or 1
    print("overlap_witness: " + json.dumps(
        witness(seen["result"], seen["overlap"]), sort_keys=True))
    return rc


if __name__ == "__main__":
    sys.exit(main())
