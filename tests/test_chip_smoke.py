"""``chip_smoke.py``: its refusal without a TPU, and its phases rehearsed on
the CPU at a small size (kernels interpreted, four virtual devices for the
multi-chip phase).  The script itself runs on the chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _run(cwd, *argv, env_extra=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _says_ok(stdout: str) -> bool:
    return '"ok"' in stdout


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_without_the_chip_or_the_repo(tmp_path, where):
    """No TPU (or no repo beside the script): non-zero exit, no result."""
    if where == "alone":
        shutil.copy(SMOKE, tmp_path / SMOKE.name)
        proc = _run(tmp_path, SMOKE.name)
    else:
        proc = _run(ROOT, str(SMOKE))
    assert proc.returncode != 0, proc.stdout
    assert not _says_ok(proc.stdout), proc.stdout


def test_chip_smoke_one_chip_phases_on_cpu():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    report = chip_smoke.run_one_chip(1500, n_general=64, n_pair=8,
                                     n_onesided=16, n_trivial=4, per_lane=2,
                                     n_hybrid=16)
    # per-lane samples at both epochs, plus the update's touched queries
    assert report["oracle_checked"] >= 16
    assert report["epoch1_changed"] >= 1


def test_chip_smoke_four_chip_phases_on_virtual_devices():
    code = ("import json, chip_smoke; "
            "print(json.dumps(chip_smoke.run_four_chips(600, n_general=64, "
            "n_pair=8, n_onesided=16, n_trivial=4)))")
    proc = _run(ROOT, "-c", code, env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["oracle_checked"] == 8
