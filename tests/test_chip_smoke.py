"""chip_smoke.py rehearsed on the CPU at a tiny scale: the same served
phases and host-reference checks it runs on the chip, with the TPU check
lifted by the caller. The script itself must refuse to run without a TPU
and without the repository around it."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SMOKE = REPO / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_refuses_without_tpu(smoke, capsys):
    # this process runs on CPU: the device check comes first
    assert smoke.main(["--scale", "6"]) == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU found" in err


def test_one_chip_phases_on_cpu(smoke, capsys, monkeypatch, tmp_path):
    # a set JAX_COMPILATION_CACHE_DIR keeps the script from placing a
    # cache of its own in the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main(["--scale", "9", "--bfs", "12", "--sssp", "4"],
                      require_tpu=False) == 0
    out = capsys.readouterr().out
    assert _last_json(out) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    for phase in ("bucketed", "continuous"):
        assert f"[{phase}] 12 BFS + 4 SSSP answered and checked" in out
    assert "[pallas] 12 BFS answered, equal to the reference" in out


def _run(code: str, cwd, **env):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, capture_output=True,
        text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_four_chip_mesh_phase_on_cpu_devices(tmp_path):
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import chip_smoke\n"
            "sys.exit(chip_smoke.main(['--chips', '4', '--scale', '9', "
            "'--bfs', '8', '--batch', '4'], require_tpu=False))\n")
    proc = _run(code, tmp_path,
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert _last_json(proc.stdout)["device"]["count"] == 4
    for ex in ("combined", "allgather"):
        for ov in (False, True):
            assert f"[{ex} overlap={ov}] 4 BFS answered and checked" \
                in proc.stdout


def test_fails_outside_the_checkout(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    code = ("import sys, chip_smoke\n"
            "sys.exit(chip_smoke.main(['--scale', '6'], "
            "require_tpu=False))\n")
    proc = _run(code, tmp_path,
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
