"""chip_smoke.py off the chip: it refuses a CPU backend, and each of its
phases passes its own check at a small size (the CPU rehearsal of the
chip run, through the same phase functions and entry points)."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("phase,kwargs", [
    ("forest", dict(n=1 << 12, m=1 << 10, seed=0)),
    ("pool", dict(tenants=16, lo=16, hi=64, draws=1 << 8, update_every=4,
                  seed=0)),
    ("serve", dict(arch="qwen1.5-0.5b", reduced=True, slots=2, requests=3,
                   prompt_len=4, max_new=3, seed=0)),
    ("sharded", dict(n=1 << 12, m=1 << 10, seed=0)),
])
def test_phase_passes_at_small_size(smoke, capsys, phase, kwargs):
    getattr(smoke, f"phase_{phase}")(**kwargs)
    out = capsys.readouterr().out.strip().splitlines()
    assert out[-1].startswith(f"phase={phase} ")
    assert out[-1].endswith("check=pass")
