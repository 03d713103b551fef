"""The examples/ scripts are living documentation — run each end-to-end
at tiny settings so they cannot rot (subprocess, scrubbed TPU plugin,
8-device CPU mesh)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=600):
    env = {k: v for k, v in os.environ.items()}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/paddle_tpu_jax_cache")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, (script, r.stdout[-800:], r.stderr[-800:])
    # the fleet examples log through the rank-tagged logger (stderr)
    return r.stdout + r.stderr


def test_example_deploy_generate():
    out = _run("deploy_generate.py", "--steps", "60")
    assert "quantized" in out
    assert "AOT artifact reloaded, tokens bit-equal" in out


def test_example_train_gnn():
    out = _run("train_gnn.py", "--steps", "25", "--nodes", "128",
               "--edges", "1024", "--hidden", "32")
    assert "train accuracy" in out
    assert "sampled-subgraph forward" in out


def test_example_train_gpt_hybrid():
    out = _run("train_gpt_hybrid.py", "--dp", "1", "--mp", "2", "--pp", "2",
               "--steps", "3", "--batch", "4", "--seq", "32")
    assert "loss" in out.lower(), out[-400:]


def test_example_train_llama_semi_auto():
    out = _run("train_llama_semi_auto.py", "--dp", "2", "--mp", "2",
               "--steps", "3", "--batch", "4", "--seq", "32")
    assert "loss" in out.lower(), out[-400:]


def test_example_train_moe_ep():
    out = _run("train_moe_ep.py", "--ep", "2", "--pp", "2", "--sharding",
               "1", "--steps", "2", "--batch", "4", "--seq", "16")
    assert "OK: expert-parallel MoE trained" in out, out[-400:]


def test_example_train_static():
    out = _run("train_static.py", "--steps", "60")
    assert "STATIC_EXAMPLE_OK" in out


def test_example_train_sparse_pointcloud():
    out = _run("train_sparse_pointcloud.py", "--steps", "120")
    assert "SPARSE_POINTCLOUD_OK" in out


def test_example_infer_export():
    out = _run("infer_export.py")
    low = out.lower()
    assert "export" in low or "predict" in low or "ok" in low, out[-400:]


def test_example_train_detection():
    out = _run("train_detection.py", "--steps", "150")
    # the example enforces its own localization/class thresholds
    assert "localized" in out
    assert "OK" in out
