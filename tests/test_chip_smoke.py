"""``chip_smoke.py`` at tiny sizes on the CPU.

The smoke's phases take their sizes, so the same checks that gate the
chip run (kernel results against ``kernels/ref.py``; service status,
ledger and results against independent paths) run here with Pallas in
interpret mode.  The entry point itself must refuse the CPU.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_phase_matches_ref(smoke):
    out = smoke.kernel_phase(
        n_sites=3, n_tx=300, n_items=40, n_cand=70, n_pts=300, dim=3, k=5, seed=0,
        require_mosaic=False,
    )
    assert set(out) == {
        "support_count", "support_count_prune", "kmeans_assign",
        "support_count_sites", "support_count_prune_sites", "kmeans_assign_sites",
    }
    assert all(v["compile_s"] >= 0 and v["run_s"] >= 0 for v in out.values())


def test_kernel_phase_demands_mosaic_off_tpu(smoke):
    """On the CPU the kernels are interpreted, so the chip's
    ``tpu_custom_call`` check must fire."""
    with pytest.raises(smoke.SmokeError, match="tpu_custom_call"):
        smoke.kernel_phase(
            n_sites=2, n_tx=64, n_items=16, n_cand=8, n_pts=64, dim=2, k=3, seed=0,
        )


def test_service_phase_checks_pass(smoke):
    out = smoke.service_phase(
        n_sites=4, n_tx=2000, n_items=24, n_pts=3000, dim=3, k=3, minsup=0.05,
        k_local=4, n_components=5, seed=0, clock=smoke.CompileClock(),
    )
    led = out["ledger"]
    assert led["failures"] == 0 and led["fused_fallbacks"] == 0
    assert led["fused_requests"] >= 2
    assert led["device_dispatches"] < led["exec_groups"]
    assert {"service/data", "service/wave1", "service/reference"} <= set(out["times"])


def test_check_assign_accepts_ties_only(smoke):
    x = np.array([[0.0, 0.0], [1.0, 0.0], [3.0, 0.0]], np.float32)
    c = np.array([[0.0, 0.0], [2.0, 0.0], [4.0, 0.0]], np.float32)
    want = np.array([0, 0, 1], np.int32)  # point 1 is tied between centres 0 and 1
    d = np.array([0.0, 1.0, 1.0], np.float32)
    tied = np.array([0, 1, 1], np.int32)
    assert smoke._check_assign(x, c, tied, d, want, d, "tie") == 1
    with pytest.raises(smoke.SmokeError, match="without a tie"):
        smoke._check_assign(x, c, np.array([2, 0, 1], np.int32), d, want, d, "wrong")
    with pytest.raises(smoke.SmokeError, match="min_d2"):
        smoke._check_assign(x, c, want, d + 1.0, want, d, "far")


def test_check_clustering_accepts_near_ties_only(smoke):
    x = np.array([[0.0, 0.0], [1.0, 0.0], [3.0, 0.0], [1.1, 0.0]], np.float32)
    c_a = np.array([[0.0, 0.0], [2.0, 0.0]], np.float32)
    c_b = c_a + np.array([0.0, 1e-5], np.float32)  # drifted along the bisector
    want = np.array([0, 0, 1, 1], np.int32)  # settled; point 1 sits on the bisector
    assert smoke._check_clustering(x, want, c_a, want, c_b, "same")[0] == 0
    assert smoke._check_clustering(x, np.array([0, 1, 1, 1]), c_a, want, c_b, "tie")[0] == 1
    with pytest.raises(smoke.SmokeError, match="without a near-tie"):
        smoke._check_clustering(x, np.array([1, 0, 1, 1]), c_a, want, c_b, "wrong")
    # a reference whose next Lloyd step would still move point 3 (relative
    # gap 0.4 / 2.02) tolerates differences up to twice that gap, no further
    unsettled = np.array([0, 0, 1, 0], np.int32)
    n, tol = smoke._check_clustering(x, want, c_a, unsettled, c_b, "unsettled")
    assert n == 1 and tol == pytest.approx(2 * 0.4 / 2.02, rel=1e-4)
    with pytest.raises(smoke.SmokeError, match="without a near-tie"):
        smoke._check_clustering(x, np.array([0, 0, 0, 0]), c_a, unsettled, c_b, "far")


def test_main_refuses_the_cpu(smoke, monkeypatch, capsys):
    import repro.launch.mesh as mesh

    monkeypatch.setattr(mesh, "enable_compile_cache", lambda: "(off in tests)")
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out


def test_script_alone_fails_without_a_result(tmp_path):
    """Copied into a directory that holds nothing else of the repo, the
    script exits non-zero and prints no result line."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_placement(tmp_path, env_dir):
    """Entry points keep the compile cache in ``$JAX_COMPILATION_CACHE_DIR``
    when it is set, else in ``.jax_cache/`` at the checkout root.  Checked
    in a child process so this one never turns the cache on."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src")
    want = ROOT / ".jax_cache"
    if env_dir is not None:
        want = tmp_path / env_dir
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    code = (
        "import jax\n"
        "from repro.launch.mesh import enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=120, check=True)
    got, min_secs = p.stdout.split()
    assert Path(got) == want
    assert float(min_secs) < 1.0
