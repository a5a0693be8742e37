"""``chip_smoke.py`` at CPU size.

Only ``main()`` insists on a TPU; these tests drive its phase functions with
the reduced model config and tiny decision sizes, and check that ``main()``
refuses to run without a chip or without the repository beside it.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_phase_on_reduced_config(smoke, tmp_path, monkeypatch):
    monkeypatch.setattr(smoke, "RESULTS", tmp_path)
    info = smoke.served_phase(full_config=False, serve_requests=2, profile_requests=8)
    assert info["d_model"] == 64 and info["dtype"] == "float32"
    assert info["profile_requests"] == 8
    assert (tmp_path / f"PROFILE_{smoke.ARCH}.json").is_file()
    assert info["param_bytes"] > 0


def test_decision_phase_at_tiny_sizes(smoke):
    info = smoke.decision_phase(grid=16, samples=4, clients=8, mf_clients=1_000)
    assert info["analytic_rows"] == 256
    assert info["analytic_max_rel_err"] <= smoke.ANALYTIC_TOL
    assert info["euler_entries"] >= 30
    assert info["euler_max_rel_err"] <= smoke.EULER_TOL
    assert info["cluster_client_epochs"] == 8 * 180


def test_four_chip_phase_refuses_one_device(smoke):
    with pytest.raises(smoke.SmokeFailure, match="need 4 devices"):
        smoke.four_chip_phase()


def test_main_refuses_cpu_and_prints_no_result(smoke, capsys):
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_main_refuses_a_directory_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
