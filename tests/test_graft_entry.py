"""The driver's own gates, exercised in CI: dryrun_multichip compiles and
runs the FULL hybrid train step on virtual meshes — including 16 devices
(dp2 x mp2 x pp2 x sharding2), one size beyond the suite's standard
8-device mesh, so topology construction generalizes past the default."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# the 16-device variant re-runs the same legs on a wider virtual mesh
# (~33 s); 8 is the fast sibling that stays in tier-1's window
@pytest.mark.parametrize("n", [8, pytest.param(16, marks=pytest.mark.slow)])
def test_dryrun_multichip(n):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/paddle_tpu_jax_cache")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(%d)\n"
        "print('DRYRUN_OK', %d)\n" % (REPO, n, n))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-800:])
    assert f"DRYRUN_OK {n}" in r.stdout
