"""chip_smoke.py's contract, as far as a machine without a chip can show it:
no accelerator -> non-zero exit, fast, and never a result line; and the
spawning parent stays off JAX (one process per chip)."""

import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.mark.parametrize("alone", [False, True],
                         ids=["no_tpu", "script_alone"])
def test_fails_fast_and_prints_no_result(tmp_path, alone):
    """With JAX held to the CPU the probe child finds no TPU; in a
    directory holding the script and nothing else of the repo there is
    nothing to drive.  Either way: exit != 0, no ``"ok": true``."""
    script = SMOKE
    if alone:
        script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode not in (0, 3), out.stdout + out.stderr
    assert '"ok"' not in out.stdout
    assert time.monotonic() - t0 < 60


def test_parent_never_imports_jax():
    """A parent that has touched JAX holds the chip and its children then
    fail or hang: importing the script (everything but the hidden
    ``--mesh-worker`` child body) must leave JAX unloaded."""
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "bad = [m for m in ('jax', 'jaxlib', 'can_tpu') "
            "if m in sys.modules]; assert not bad, bad" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_attention_worker_rehearses_on_the_cpu():
    """The fused prefill attention leg's child (``--attention-worker``)
    with JAX held to the CPU: the kernel interpreted, at the kernel's own
    blocks, against ``prefill_causal``; its three lines are what
    ``phase_attention`` looks for."""
    out = subprocess.run([sys.executable, SMOKE, "--attention-worker"],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = out.stdout.splitlines()
    assert any(l.startswith("[runtime] {") for l in lines)
    assert any(l.startswith("[attention] kernel INTERPRETED, platform cpu")
               for l in lines)
    assert lines[-1] == "ATTENTION OK"


def test_experts_worker_rehearses_on_the_cpu():
    """The skipping experts leg's child (``--experts-worker``) with JAX held
    to the CPU: the kernel interpreted at GLM's widths against
    ``_share_apply_batched``; its lines are what ``phase_experts`` looks
    for."""
    out = subprocess.run([sys.executable, SMOKE, "--experts-worker"],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = out.stdout.splitlines()
    assert any(l.startswith("[runtime] {") for l in lines)
    assert any(l.startswith("[experts] kernel INTERPRETED, platform cpu")
               for l in lines)
    assert lines[-1] == "EXPERTS OK"
