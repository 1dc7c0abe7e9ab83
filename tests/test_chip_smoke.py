"""chip_smoke.py off the chip: it must fail, quickly, and say why.

The pass itself is proven on a TPU through the chip tool (CHANGES.md
records each run); here only the contract that needs no chip is checked.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def test_parent_imports_neither_jax_nor_the_package():
    """A parent that touched jax would hold the chip its child needs."""
    probe = (
        "import sys, chip_smoke; "
        "bad = [m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'production_stack_tpu'))]; "
        "sys.exit(repr(bad) if bad else 0)"
    )
    done = subprocess.run(
        [sys.executable, "-c", probe], cwd=REPO, capture_output=True,
        text=True, timeout=60,
    )
    assert done.returncode == 0, done.stderr


@pytest.mark.skipif(
    bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")),
    reason="an accelerator is attached: the smoke would run for real",
)
def test_without_a_chip_it_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # must not be inherited
    done = subprocess.run(
        [sys.executable, SMOKE], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=180,
    )
    assert done.returncode != 0
    assert "NO ACCELERATOR" in done.stderr
    assert '"ok"' not in done.stdout


def test_beside_nothing_else_of_the_repo_it_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(SMOKE, "rb").read())
    done = subprocess.run(
        [sys.executable, str(alone)], cwd=tmp_path, capture_output=True,
        text=True, timeout=180,
    )
    assert done.returncode != 0
    assert "not beside chip_smoke.py" in done.stderr
    assert '"ok"' not in done.stdout
