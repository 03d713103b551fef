"""Fault-injection framework (SURVEY §5 failure/elastic row): declared
faults exercise the repo's own recovery machinery — check_numerics
catches injected NaNs, the launcher's restart path absorbs an injected
exit, and checkpoint corruption is detected at load."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu.framework import fault
from paddle_tpu.framework.fault import Fault, FaultInjected, FaultPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_exception_fault_fires_at_exact_step_once():
    plan = FaultPlan([Fault(step=3, kind="exception")])
    run = fault.wrap(lambda x: x + 1, plan, rank=0)
    out = []
    for i in range(6):
        try:
            out.append(run(i))
        except FaultInjected:
            out.append("FAULT")
    assert out == [1, 2, 3, "FAULT", 5, 6]  # once=True: fires exactly once


def test_rank_and_restart_filters():
    plan = FaultPlan([Fault(step=0, kind="exception", rank=1)])
    ok = fault.wrap(lambda: "fine", plan, rank=0)
    assert ok() == "fine"                      # other rank: no fault
    plan2 = FaultPlan([Fault(step=0, kind="exception", restart=0)])
    os.environ["PADDLE_RESTART_COUNT"] = "1"
    try:
        survived = fault.wrap(lambda: "fine", plan2, rank=0)
        assert survived() == "fine"            # later incarnation: no fault
    finally:
        os.environ.pop("PADDLE_RESTART_COUNT")


def test_spec_parsing_roundtrip():
    plan = FaultPlan.parse(
        "step=3,kind=exit,rank=1,code=7;step=5,kind=nan,restart=any;"
        "step=2,kind=slow,seconds=0.5,once=false")
    assert len(plan.faults) == 3
    assert plan.faults[0].code == 7 and plan.faults[0].rank == 1
    assert plan.faults[1].restart is None
    assert plan.faults[2].seconds == 0.5 and not plan.faults[2].once
    assert FaultPlan.parse("").faults == []
    with pytest.raises(ValueError, match="step="):
        FaultPlan.parse("kind=exit")
    with pytest.raises(ValueError, match="kind"):
        FaultPlan.parse("step=1,kind=meteor")


def test_nan_fault_is_caught_by_check_numerics():
    from paddle_tpu.framework.debug import check_tree_numerics

    plan = FaultPlan([Fault(step=2, kind="nan")])

    def step(x):
        return {"loss": jnp.sum(x ** 2), "count": jnp.asarray(3)}

    run = fault.wrap(step, plan, rank=0)
    x = jnp.ones((4,))
    for i in range(2):
        check_tree_numerics(run(x))            # clean steps pass
    poisoned = run(x)
    assert np.isnan(float(poisoned["loss"]))
    assert int(poisoned["count"]) == 3         # non-float leaves untouched
    with pytest.raises(Exception, match="(?i)nan"):
        check_tree_numerics(poisoned)


def test_slow_fault_injects_latency():
    plan = FaultPlan([Fault(step=1, kind="slow", seconds=0.4)])
    run = fault.wrap(lambda: None, plan, rank=0)
    t0 = time.perf_counter()
    run()
    fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    run()
    slow = time.perf_counter() - t0
    assert slow >= 0.35 and fast < 0.2


def test_corrupt_checkpoint_is_detected_at_load(tmp_path):
    path = str(tmp_path / "model.pdparams")
    paddle_tpu.save({"w": jnp.arange(8.0), "b": jnp.zeros((2,))}, path)
    clean = paddle_tpu.load(path)
    np.testing.assert_allclose(np.asarray(clean["w"]), np.arange(8.0))
    fault.corrupt_file(path, offset=16, nbytes=64)
    try:
        loaded = paddle_tpu.load(path)
    except Exception:
        return  # corruption detected at load — the desired outcome
    # if load survived the flip it must at least not silently return the
    # original payload (separate assert OUTSIDE any raises-block so a
    # silent round-trip is a real failure, not a caught AssertionError)
    w = np.asarray(loaded["w"], np.float64)
    assert not np.array_equal(w, np.arange(8.0)), \
        "corrupted checkpoint silently round-tripped"


def test_hang_fault_through_heartbeat_detector(tmp_path):
    """kind=hang re-execs a beatless sleep; the launcher's stale-heartbeat
    detector kills and restarts, and the restart=0 gate lets the retry
    finish — the declarative form of the hang_runner scenario."""
    runner = os.path.join(REPO, "tests", "runners", "fault_runner.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PADDLE_TPU_REPO"] = REPO
    env["PADDLE_FAULT_SPEC"] = "step=1,kind=hang,seconds=600"
    log_dir = str(tmp_path / "log")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--log_dir", log_dir,
         "--heartbeat_timeout", "2", "--max_restart", "1", runner],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=150)
    assert r.returncode == 0, (r.stdout[-300:], r.stderr[-500:])
    assert "heartbeat stale" in r.stderr
    logs = open(os.path.join(log_dir, "workerlog.0")).read()
    assert "FAULT_RUNNER_OK restart=1" in logs


def test_exit_fault_through_launcher_restart(tmp_path):
    """Incarnation 0 dies via the declared exit fault at step 2; the
    launcher restarts; restart=0 gating lets incarnation 1 finish."""
    runner = os.path.join(REPO, "tests", "runners", "fault_runner.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PADDLE_TPU_REPO"] = REPO
    env["PADDLE_FAULT_SPEC"] = "step=2,kind=exit,code=3"
    log_dir = str(tmp_path / "log")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--log_dir", log_dir,
         "--max_restart", "1", runner],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout[-300:], r.stderr[-500:])
    logs = open(os.path.join(log_dir, "workerlog.0")).read()
    assert "FAULT_RUNNER_OK restart=1" in logs


def test_startup_wedge_detected_without_any_heartbeat(tmp_path):
    """A worker that wedges BEFORE its first heartbeat (the import/
    backend-init failure mode) trips the startup grace and restarts."""
    runner = tmp_path / "wedge_runner.py"
    runner.write_text(
        "import os, sys, time\n"
        "sys.path.insert(0, os.environ['PADDLE_TPU_REPO'])\n"
        "from paddle_tpu.distributed import env\n"
        "if int(os.environ.get('PADDLE_RESTART_COUNT', 0)) == 0:\n"
        "    time.sleep(600)   # wedged before _start_heartbeat\n"
        "env._start_heartbeat(interval=0.2)\n"
        "print('WEDGE_RUNNER_OK')\n")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PADDLE_TPU_REPO"] = REPO
    log_dir = str(tmp_path / "log")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--log_dir", log_dir,
         # margins sized for a saturated CI box (full suite + chip bench
         # in parallel): a 1s timeout flaked when the restarted worker's
         # interpreter startup itself exceeded the beat budget
         "--heartbeat_timeout", "3", "--heartbeat_startup_grace", "9",
         "--max_restart", "1", str(runner)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, (r.stdout[-300:], r.stderr[-500:])
    assert "heartbeat stale" in r.stderr
    logs = open(os.path.join(log_dir, "workerlog.0")).read()
    assert "WEDGE_RUNNER_OK" in logs
