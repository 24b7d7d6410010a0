"""Structural multi-chip scaling: the dp x sp training step compiles and
executes on meshes LARGER than the 8-device suite default.

The test sandbox has no accelerator (real chips are reached only through
``chip_smoke.py --chips 4``), so this is the structural scaling artifact: the same `dryrun_multichip` entry the
driver uses — full train step, real dp x sp shardings, halo-exchange +
psum collectives — provisions 16-, 32- and 64-device virtual CPU meshes in
subprocesses and runs a finite step.  Catches anything that hard-codes the
8-device topology (mesh construction, shard divisibility, collective axis
sizes).
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow


@pytest.mark.parametrize("n_devices", [16, 32, 64])
def test_dryrun_scales_to_larger_meshes(n_devices):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; g.dryrun_multichip({n_devices})"],
        env=env, capture_output=True, text=True, timeout=1200,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "[dryrun] mesh" in proc.stdout and "ok" in proc.stdout, proc.stdout

