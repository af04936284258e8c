"""chip_smoke.py: its phases at a tiny size on the CPU, and its refusal
to report a result without a TPU or without the rest of the repo."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.configs.smoke import smoke_config

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_query_phase_tiny(chip_smoke):
    out = chip_smoke.query_phase(sf=0.002, target_bytes=4 << 20,
                                 platform="cpu")
    assert set(out["platforms"]) == {"cpu"}
    for q in chip_smoke.QUERIES:
        assert out["stats"][(2, q)]["compiles"] == 0
        assert out["stats"][(1, q)]["rows"] == out["stats"][(2, q)]["rows"]


def test_model_phase_tiny(chip_smoke):
    out = chip_smoke.model_phase(smoke_config("smollm-135m"), batch=2,
                                 prompt_len=16, new_tokens=4)
    assert out["tokens"].shape == (2, 4)
    assert out["max_abs_diff"] <= 1e-3 * max(out["max_abs_logit"], 1.0)


def _run(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    r = _run(SCRIPT.parent, SCRIPT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_refuses_without_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    r = _run(tmp_path, alone)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
