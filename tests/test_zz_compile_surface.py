"""Runtime/static consistency gate for graftprog (ISSUE 16).

graftprog (tools/analysis/compile_surface.py) statically enumerates the
serving engine's compile surface and pins it on the program manifest:
``{chunk} + O(log2) prefill buckets + ONE decode + 1 gather + 1
scatter`` per device plane.  This test closes the loop from the OTHER
side: it runs a warm CPU-smoke engine per config leg — tp=1 composed,
tp=1 fused, tp=2 composed — and asserts the trace counters the engine
actually ticked are a SUBSET of what the manifest enumerates, with the
static upper bounds respected.  Manifest drift (a new counter the
analysis missed, a bound the runtime exceeded) fails loudly with the
offending program named.

zz-prefixed for the same reason as test_zz_decode_block /
test_zz_tp_serving: the tp=2 leg drives shard_map on the 8-device CPU
mesh, and the jaxlib-0.4 dispatch-race window conftest documents makes
early-alphabet placement of distributed work reproducibly fragile —
sort after the window.
"""

import math

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import ServingEngine

ENGINE_PLANE = "paddle_tpu.serving.engine.EngineCore"
MAX_SEQ = 64
MIN_BUCKET = 8
# chunk program + pow2 bucket tails: the static "O(log2) shape buckets"
# bound, made concrete for this config
MAX_PREFILL = int(math.log2(MAX_SEQ // MIN_BUCKET)) + 2


@pytest.fixture(scope="module")
def engine_plane():
    """The statically-derived EngineCore counter plane, built through
    the same library entry point the CLI's ``--manifest`` uses."""
    from paddle_tpu.tools.analysis import build_manifest_for_paths
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    scope = [os.path.join(root, p)
             for p in ("paddle_tpu", "scripts")]
    manifest = build_manifest_for_paths(scope, root=root)
    assert ENGINE_PLANE in manifest["planes"], (
        f"manifest lost the EngineCore plane; planes="
        f"{sorted(manifest['planes'])}")
    return manifest["planes"][ENGINE_PLANE]


def _fresh_gpt(seed=0):
    paddle_tpu.seed(seed)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _run_leg(**engine_kw):
    """Warm CPU smoke: mixed-length prompts, then a resubmitted copy so
    the prefix cache exercises the gather AND scatter programs."""
    eng = ServingEngine(_fresh_gpt(), num_slots=4, max_seq=MAX_SEQ,
                        min_bucket=MIN_BUCKET, prefill_chunk=16,
                        block_len=16, **engine_kw)
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 256, (L,)) for L in (3, 9, 17, 50)]
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    eng.run_until_complete(500)
    rids.append(eng.submit(prompts[-1].copy(), max_new_tokens=3))
    eng.run_until_complete(100)
    assert all(eng.result(r).finished for r in rids)
    observed = dict(eng.core.trace_counts)
    observed.update(eng.core.block_pool.trace_counts)
    return eng, observed


def _check_against_plane(plane, observed, leg):
    # every counter the runtime ticked must be a program set the static
    # analysis enumerated — a missing counter IS manifest drift
    for counter, count in sorted(observed.items()):
        if count <= 0:
            continue
        assert counter in plane, (
            f"[{leg}] runtime traced '{counter}' x{count} but the "
            f"manifest has no such program on the {ENGINE_PLANE} plane "
            f"(static analysis missed a compile unit); manifest "
            f"counters: {sorted(plane)}")
    # and the static upper bounds hold: ONE decode / verify / gather /
    # scatter
    for counter in ("decode", "verify", "gather", "scatter"):
        entry = plane[counter]
        assert entry["upper_bound"] == "1", (
            f"[{leg}] manifest bound for '{counter}' is "
            f"{entry['upper_bound']!r}, expected '1' "
            f"(programs: {entry['programs']})")
        assert observed.get(counter, 0) <= 1, (
            f"[{leg}] runtime compiled {observed[counter]} '{counter}' "
            f"programs, exceeding the static bound of 1 for "
            f"{entry['programs']}")
    assert plane["prefill"]["key_space"] == "bucketed", (
        f"[{leg}] prefill key space drifted: {plane['prefill']}")
    assert 0 < observed.get("prefill", 0) <= MAX_PREFILL, (
        f"[{leg}] prefill traced {observed.get('prefill')} times, "
        f"outside (0, {MAX_PREFILL}] for programs "
        f"{plane['prefill']['programs']}")
    # at least one decode step actually ran — a zero here means the leg
    # did not exercise the plane and the subset check proved nothing
    assert observed.get("decode", 0) == 1, (
        f"[{leg}] expected exactly one decode trace, got "
        f"{observed.get('decode')}")


def test_plane_is_the_pinned_program_set(engine_plane):
    """The static side of the pin: the EngineCore plane holds exactly
    the five counters, with ONE-program bounds on
    decode/verify/gather/scatter and a bucketed prefill."""
    assert set(engine_plane) == {"prefill", "decode", "verify",
                                 "gather", "scatter"}, (
        f"plane counters drifted: {sorted(engine_plane)}")
    # both decode VARIANTS (composed + fused) share one holder — the
    # manifest proves at most one compiles per process; same for the
    # verify variants (composed + tp shard_map)
    assert engine_plane["decode"]["holders"] == ["_decode_fn"]
    assert engine_plane["verify"]["holders"] == ["_verify_fn"]
    assert engine_plane["verify"]["upper_bound"] == "1"


def test_leg_tp1_composed(engine_plane):
    eng, observed = _run_leg(fused_decode=False)
    assert eng.core.decode_path == "unfused"
    _check_against_plane(engine_plane, observed, "tp1-composed")
    assert observed["gather"] == 1 and observed["scatter"] == 1


def test_leg_tp1_fused(engine_plane):
    eng, observed = _run_leg(fused_decode=True)
    assert eng.core.decode_path == "fused"
    _check_against_plane(engine_plane, observed, "tp1-fused")


def test_leg_tp2_composed(engine_plane):
    eng, observed = _run_leg(tensor_parallel=2)
    _check_against_plane(engine_plane, observed, "tp2-composed")
    assert observed["gather"] == 1 and observed["scatter"] == 1


def test_leg_tp1_spec(engine_plane):
    """Speculation on (ISSUE 18): a cyclic prompt guarantees the n-gram
    table proposes, so the verify program dispatches — and still traces
    exactly ONCE alongside the one decode (steps where nothing was
    proposed fall back to it)."""
    eng, observed = _run_leg(spec_k=3)
    assert eng.core.spec_on and eng.spec_fallback_reason is None
    r = eng.submit(np.tile([5, 6, 7, 8], 8), max_new_tokens=8)
    eng.run_until_complete(100)
    assert eng.result(r).finished
    observed = dict(eng.core.trace_counts)
    observed.update(eng.core.block_pool.trace_counts)
    _check_against_plane(engine_plane, observed, "tp1-spec")
    assert observed["verify"] == 1, (
        f"expected exactly one verify trace, got {observed.get('verify')}")
    assert eng.metrics.snapshot()["spec_draft_tokens"] > 0
