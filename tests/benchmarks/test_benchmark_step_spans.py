"""The three readers of the engine's step spans (``step_host_ms``,
``prefill_stall_ms``, ``readback_return_ms``) on a hand-made ``run``:
spans as the serving driver hands them over and a three-step trace of
plain tuples; the value, the cases that give nothing, and that steps
outside each filter are left out.  Then the seam itself on the CPU: a
real engine's spans through the two span readers, and its annotations on
the host plane of a real profiler trace."""

import os

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.lib import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FILES = bench_run.Files(os.path.join(ROOT, "BENCHMARK.json"))
MS = 1e-3


def reader(name):
    return FILES.module(f"metrics/{name}.py").read


def step(idx, start, phases, **counts):
    """One ``serving.step`` span and its children as the driver's
    ``(name, start, end, attrs)`` tuples; ``phases`` is ``[(name,
    milliseconds)]`` laid end to end from ``start``."""
    out, t = [], start
    for name, ms in phases:
        out.append((f"step.{name}", t, t + ms * MS, {"step": idx}))
        t += ms * MS
    counts = {"admitted": 0, "prefill_tokens": 0, "prefills_completed": 0,
              "active_slots": 0, "live_kv_rows": 0, "new_tokens": 0,
              "queue_depth": 0, **counts}
    out.append(("serving.step", start, t, {"step": idx, **counts}))
    return out


DECODE = [("admission", 0.25), ("prefill", 0.25), ("decode_dispatch", 1.0),
          ("readback", 40.0), ("harvest", 0.5), ("bookkeeping", 0.5)]


def make_run(spans, **kw):
    log = []
    return {"spans": spans, "window": (10.0, 20.0), "log": log.append,
            "lines": log, **kw}


# ------------------------------------------------------------ step_host_ms

def test_step_host_ms_is_the_step_less_its_readback():
    slow = [(n, 3 * ms if n != "readback" else ms) for n, ms in DECODE]
    spans = (step(0, 10.0, DECODE, active_slots=4)
             + step(1, 10.1, slow, active_slots=4)
             + step(2, 10.2, DECODE, active_slots=5))
    run = make_run(spans)
    # 2.5 ms of host work in steps 0 and 2, 7.5 in step 1: the median
    assert reader("step_host_ms")(run) == pytest.approx(2.5)
    assert "3 pure decode steps" in run["lines"][0]


def test_step_host_ms_leaves_out_what_is_not_a_pure_decode_step():
    prefilling = [("admission", 1.0), ("prefill", 20.0),
                  ("first_token_readback", 30.0)] + DECODE[2:]
    spans = (step(0, 10.0, DECODE, active_slots=4)
             # prefilled: not a pure decode step
             + step(1, 10.1, prefilling, active_slots=4, prefill_tokens=300,
                    prefills_completed=1)
             # nothing decoding: no readback
             + step(2, 10.2, [("admission", 9.0), ("bookkeeping", 9.0)])
             # outside the window
             + step(3, 30.0, [(n, 5 * ms) for n, ms in DECODE],
                    active_slots=4))
    assert reader("step_host_ms")(make_run(spans)) == pytest.approx(2.5)


@pytest.mark.parametrize("spans", [
    None, [],
    # the parent's spans: phases without counts on the step
    [("serving.step", 10.0, 10.05, {"step": 0}),
     ("step.readback", 10.0, 10.04, {"step": 0})],
    # only prefilling steps
    step(0, 10.0, DECODE, active_slots=2, prefill_tokens=8),
])
def test_step_host_ms_gives_nothing_without_its_input(spans):
    assert reader("step_host_ms")(make_run(spans)) is None


# -------------------------------------------------------- prefill_stall_ms

def stalled(ms):
    return [("admission", 1.0), ("prefill", ms - 1.0 - 5.0),
            ("first_token_readback", 5.0)] + DECODE[2:]


def test_prefill_stall_ms_is_the_wait_ahead_of_the_decode_dispatch():
    spans = (step(0, 10.0, stalled(20.0), active_slots=4,
                  prefill_tokens=200, prefills_completed=1)
             + step(1, 10.2, stalled(30.0), active_slots=5,
                    prefill_tokens=500, prefills_completed=2)
             + step(2, 10.4, stalled(70.0), active_slots=3,
                    prefill_tokens=900, prefills_completed=1)
             + step(3, 10.6, DECODE, active_slots=4))
    run = make_run(spans)
    assert reader("prefill_stall_ms")(run) == pytest.approx(30.0)
    assert "3 of 4 decoding steps also prefilled (75.0%)" in run["lines"][0]
    assert "p95 70.000 ms" in run["lines"][0]


def test_prefill_stall_ms_leaves_out_steps_that_stalled_nobody():
    spans = (step(0, 10.0, stalled(20.0), active_slots=4,
                  prefill_tokens=200, prefills_completed=1)
             # every slot at the dispatch was prefilled in this very step
             + step(1, 10.2, stalled(90.0), active_slots=2,
                    prefill_tokens=800, prefills_completed=2)
             # a chunk ran but nothing decoded in the step
             + step(2, 10.4, [("admission", 1.0), ("prefill", 50.0),
                              ("bookkeeping", 1.0)], prefill_tokens=64)
             # outside the window
             + step(3, 25.0, stalled(99.0), active_slots=4,
                    prefill_tokens=200, prefills_completed=1))
    assert reader("prefill_stall_ms")(make_run(spans)) == pytest.approx(20.0)


@pytest.mark.parametrize("spans", [
    None, [],
    [("serving.step", 10.0, 10.05, {"step": 0}),
     ("step.decode_dispatch", 10.01, 10.02, {"step": 0})],
    step(0, 10.0, DECODE, active_slots=4),
])
def test_prefill_stall_ms_gives_nothing_without_its_input(spans):
    assert reader("prefill_stall_ms")(make_run(spans)) is None


# ------------------------------------------------------ readback_return_ms

def traced_step(t, program_ms, late_us, name="jit_decode(123)"):
    """Host annotations and the device program of one step starting at
    ``t`` ns: the program starts 1 ms in, and the readback returns
    ``late_us`` after it ends."""
    p0 = t + 1_000_000
    p1 = p0 + int(program_ms * 1e6)
    r1 = p1 + late_us * 1000
    host = [("serving.step", t, r1 + 500_000 - t),
            ("step.decode_dispatch", t + 300_000, 600_000),
            ("step.readback", t + 900_000, r1 - t - 900_000),
            ("ReadSyncFlag", p1, late_us * 500)]
    return host, [(name, p0, p1 - p0)]


def make_trace(steps, window):
    host, modules = [], []
    for h, m in steps:
        host += h
        modules += m
    return make_run(None, trace={"host": host, "devices": {
        0: {"ops": [], "modules": modules}}}, trace_window_ns=window,
        decode_module_prefix="jit_decode")


def test_readback_return_ms_is_readback_end_less_program_end():
    run = make_trace([traced_step(0, 45.0, 1500),
                      traced_step(60_000_000, 45.0, 2500),
                      traced_step(120_000_000, 46.0, 9000)],
                     (0, 200_000_000))
    assert reader("readback_return_ms")(run) == pytest.approx(2.5)
    assert "3 decode steps in the slice" in run["lines"][0]
    assert "p95 9.000 ms" in run["lines"][0]


def test_readback_return_ms_leaves_out_steps_it_cannot_pair():
    no_readback = traced_step(60_000_000, 45.0, 7000)
    no_readback = ([e for e in no_readback[0] if e[0] != "step.readback"],
                   no_readback[1])
    run = make_trace([traced_step(0, 45.0, 1500),
                      no_readback,
                      # a prefill program, no decode program in the step
                      traced_step(120_000_000, 45.0, 8000, "jit_prefill(9)"),
                      # cut by the slice's end
                      traced_step(180_000_000, 45.0, 9000)],
                     (0, 200_000_000))
    assert reader("readback_return_ms")(run) == pytest.approx(1.5)


def test_readback_return_ms_pairs_a_step_with_its_own_program_under_skew():
    """What the chip showed (PR 26): the device plane's clock ran about
    a millisecond ahead, so every program starts BEFORE its step and the
    NEXT step's program starts inside it.  The program is the one the
    readback waited for; the line says how far the clocks disagree."""
    steps, skew = [], 1_700_000
    for k, late_us in enumerate((2400, 2300, 2500)):
        host, (prog,) = traced_step(k * 48_000_000, 45.0, late_us)
        steps.append((host, [(prog[0], prog[1] - skew, prog[2])]))
    run = make_trace(steps, (-10 ** 6, 200_000_000))
    # every program lies 1.7 ms earlier: it starts 0.7 ms before its step
    assert steps[1][1][0][1] < steps[1][0][0][1]
    assert steps[2][1][0][1] < steps[1][0][0][1] + steps[1][0][0][2]
    assert reader("readback_return_ms")(run) == pytest.approx(2.4 + 1.7)
    said = run["lines"][0]
    assert "program start less dispatch start median -1.000 ms" in said
    # 0.7 ms from dispatch to program, 2.4 ms back: whatever the skew
    assert "the program's device time median 3.100 ms" in said


def test_readback_return_ms_gives_nothing_without_its_input():
    read = reader("readback_return_ms")
    assert read(make_run(None)) is None                 # untraced
    one = traced_step(0, 45.0, 1500)
    # the CPU rehearsal: annotations, no device plane
    assert read(make_run(None, trace={"host": one[0], "devices": {}},
                         trace_window_ns=(0, 10 ** 9),
                         decode_module_prefix="jit_decode")) is None
    # the parent: a device plane and serving.step, no step.readback
    parent = ([e for e in one[0] if not e[0].startswith("step.")], one[1])
    assert read(make_trace([parent], (0, 10 ** 9))) is None
    # another program's name
    assert read(make_trace([traced_step(0, 45.0, 1500, "jit_other")],
                           (0, 10 ** 9))) is None


# ------------------------------------------------- the seam, on the CPU

@pytest.fixture(scope="module")
def engine_run(tmp_path_factory):
    """A tiny engine served under a real profiler trace, handed over the
    way ``drivers/open_loop.py`` does."""
    import time

    import jax

    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.obs import Tracer
    from paddle_tpu.serving import ServingEngine

    with jax.default_prng_impl("rbg"):
        model = GPTForCausalLM(gpt_tiny())
    eng = ServingEngine(model, num_slots=2, min_bucket=8,
                        record_events=True, tracer=Tracer(max_spans=1 << 14))
    rs = np.random.RandomState(5)
    try:
        eng.serve_batch([rs.randint(0, 256, (6,))], max_new_tokens=2)
        eng.metrics.reset()
        t0 = time.perf_counter()
        tracing = xplane.TraceSlice(str(tmp_path_factory.mktemp("trace")))
        tracing.start()
        eng.submit(rs.randint(0, 256, (5,)), max_new_tokens=12)
        for k in range(40):
            if k == 3:
                eng.submit(rs.randint(0, 256, (9,)), max_new_tokens=6)
            if not eng.step():
                break
        tracing.stop()
        spans = [(s.name, s.start, s.end, dict(s.attrs or {}))
                 for s in eng.tracer.spans()]
    finally:
        eng.close()
    log = []
    return {"spans": spans, "window": (t0, time.perf_counter()),
            "log": log.append, "lines": log,
            "trace": xplane.load(xplane.find_xplane(tracing.trace_dir))}


def test_span_readers_take_a_real_engines_spans(engine_run):
    """Counts only: the values are CPU times and are not looked at."""
    assert reader("step_host_ms")(engine_run) > 0
    assert reader("prefill_stall_ms")(engine_run) > 0
    said = " ".join(engine_run["lines"])
    assert "pure decode steps" in said
    assert "1 of " in said and "decoding steps also prefilled" in said


def test_step_phases_are_annotations_on_the_profilers_host_plane(engine_run):
    host = engine_run["trace"]["host"]
    steps = sorted((s, s + d) for n, s, d in host if n == "serving.step")
    assert len(steps) >= 10
    for phase in ("admission", "prefill", "first_token_readback",
                  "decode_dispatch", "readback", "harvest", "bookkeeping"):
        found = [(s, s + d) for n, s, d in host if n == f"step.{phase}"]
        assert found, phase
        # on the trace's clock every phase lies inside a serving.step
        for s, e in found:
            assert any(a <= s and e <= b for a, b in steps), phase
    # request-lane spans are facts after the event: never annotations
    assert not any(n in ("queued", "prefill", "decode", "request")
                   for n, _, _ in host)
