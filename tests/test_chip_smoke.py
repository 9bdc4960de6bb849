"""chip_smoke.py's phases on CPU at ``.reduced()`` widths (Pallas kernels
interpreted), and its refusal to run anywhere but on a TPU.

The phases are the script's own functions; only the sizes shrink.  The
device phase and the script as a whole must fail here: there is no CPU
fallback.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.configs import get_config

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg(smoke):
    return get_config(smoke.ARCH).reduced()


@pytest.fixture(scope="module")
def fitted(smoke, cfg):
    """fit_depth with no memory limit: (cfg at MAX_LAYERS, compiled step)."""
    return smoke.fit_depth(cfg, 1 << 62, seq=16, batch=2)


@pytest.fixture(scope="module")
def trained(smoke, fitted):
    cfg, step = fitted
    return smoke.phase_train(cfg, 0, step=step, seq=16, batch=2, steps=2)


def test_device_phase_refuses_cpu(smoke):
    with pytest.raises(SystemExit, match="needs a TPU"):
        smoke.phase_device(1)


def test_fit_depth_keeps_the_deepest_that_fits(smoke, fitted):
    c, compiled = fitted
    assert c.n_layers == smoke.MAX_LAYERS
    assert smoke._step_bytes(compiled) > 0


def test_train_phase(smoke, cfg, trained):
    assert len(trained["losses"]) == 2
    assert trained["h"].shape == (32, cfg.d_model)
    assert trained["head"].shape == (cfg.vocab_size, cfg.d_model)


def test_sampler_phase(smoke, cfg, trained):
    out = smoke.phase_sampler(cfg, trained, 0, t=8, m=4)
    assert out["logq_err"] <= smoke.LOGQ_ATOL
    assert out["impl"] == "chunked"  # no compiled kernels off the chip


def test_serve_phase(smoke, cfg, trained):
    assert smoke.phase_serve(cfg, trained, requests=4)["requests"] == 8


def test_sharded_phase_on_a_one_device_mesh(smoke, cfg):
    out = smoke.phase_sharded(cfg, 0, chips=1, seq=16, batch=2, steps=2)
    assert len(out["losses"]) == 2


@pytest.mark.parametrize("alone", [False, True], ids=["repo", "alone"])
def test_script_fails_without_a_tpu(tmp_path, alone):
    """Run as the driver runs it: off the chip, and in a directory holding
    chip_smoke.py and nothing else of the repo, it exits non-zero and
    prints no result line."""
    script = ROOT / "chip_smoke.py"
    cwd = ROOT
    if alone:
        script = pathlib.Path(shutil.copy(script, tmp_path))
        cwd = tmp_path
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    proc = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
