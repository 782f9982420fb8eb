"""`chip_smoke.py` on the CPU: every phase function at a tiny size (the
kernel in interpret mode, the device check skipped), the four-chip sweep on
four virtual CPU devices, and the script's refusal to run without a TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

# J=1500 rows, 512 CPUs, 200 ticks: small enough for the CPU, busy enough
# that omfs and backfill_cr both evict and spill
TINY = dict(n_jobs=1500, cpu_total=512, horizon=200, pass_depth=16)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PHASES = {
    "reference": lambda m: m.run_reference(),
    "tick_scan": lambda m: m.run_tick_scan(**TINY),
    "kernel": lambda m: m.run_kernel(
        sizes=(TINY["n_jobs"],), cpu_total=TINY["cpu_total"],
        horizon=TINY["horizon"], pass_depth=TINY["pass_depth"],
        interpret=True, kernel_sizes=(100, 1000)),
    "sweep": lambda m: m.run_sweep(n_jobs=300, cpu_total=256, horizon=40,
                                   pass_depth=16),
    "stream": lambda m: m.run_stream(
        capacity=TINY["n_jobs"], cpu_total=TINY["cpu_total"],
        horizon=TINY["horizon"], segment_len=50,
        pass_depth=TINY["pass_depth"]),
}


@pytest.mark.parametrize("name", sorted(PHASES))
def test_phase_passes_at_tiny_size(smoke, name, capsys):
    PHASES[name](smoke)
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines and all(ln.startswith(f"phase={name}") for ln in lines)
    assert all("compile_s=" in ln and "run_s=" in ln for ln in lines)


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def _ok_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    try:
        return bool(lines) and json.loads(lines[-1]).get("ok") is True
    except ValueError:
        return False


def test_refuses_without_tpu():
    proc = _run([str(SCRIPT)], ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not _ok_line(proc.stdout)


def test_refuses_outside_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    proc = _run([SCRIPT.name], tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert not _ok_line(proc.stdout)


def test_sharded_sweep_on_four_virtual_devices():
    """The ``--chips 4`` phase: sharded cells equal one-device cells and
    the batch tables lie on four devices."""
    body = ("import chip_smoke; chip_smoke.run_sharded_sweep("
            "4, n_jobs=300, cpu_total=256, horizon=30, pass_depth=16)")
    proc = _run(["-c", body], ROOT,
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    assert "phase=sweep/devices=4" in out and "devices=4" in out
    assert "phase=sweep/devices=1" in out
