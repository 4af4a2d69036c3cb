"""chip_smoke.py's control flow on the CPU: it refuses to report success
without a TPU or without the repository, and its phases run and pass
their own checks at a tiny size."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run_script(path: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(path), *args], env=env,
                          capture_output=True, text=True, timeout=120)


def _reported_ok(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return bool(json.loads(lines[-1]).get("ok"))
    except (ValueError, AttributeError):
        return False


@pytest.mark.parametrize("args", [(), ("--four-chips",)])
def test_refuses_without_tpu(args):
    res = _run_script(ROOT / "chip_smoke.py", *args)
    assert res.returncode != 0
    assert not _reported_ok(res.stdout)
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_refuses_outside_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", alone)
    res = _run_script(alone)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_phases_pass_their_checks_at_tiny_size(capsys):
    from repro.configs import get_config

    meter = chip_smoke.CompileMeter()
    cpu = jax.devices("cpu")[0]
    swarm = chip_smoke.swarm_phase(meter, cpu, cpu, n=24, chunks=16)
    assert swarm["t_round_s"] > swarm["t_warm_s"] > 0
    cfg = get_config("qwen3-1.7b", reduced=True)
    out = chip_smoke.train_phase(meter, cfg, published_layers=28,
                                 steps=3, batch=2, seq=16)
    assert len(out["losses"]) == 3 and len(out["step_s"]) == 3
    text = capsys.readouterr().out
    assert "byte-identical" in text and "compile" in text
    assert '"ok"' not in text


def test_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check(False, "a check that fails")
