"""Bring-up on the chip, rehearsed on the CPU.

``chip_smoke.py`` refuses to run without a TPU, so these tests call its
phases directly at a reduced size on the virtual CPU mesh.  Around it:
``bench.py`` and ``chip_smoke.py`` exit non-zero without a TPU, spawned
workers are pinned to the CPU from their first import, and the compile
cache goes where the operator or the repo says.
"""

import os
import shutil
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(n_nodes=96, n_classes=12, n_tasks=20_000, beats=4, churn=8)


def _run(args, cwd=REPO, timeout=240, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    full = {k: v for k, v in full.items() if v is not None}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_refuses_the_cpu(script):
    p = _run([script])
    assert p.returncode != 0, p.stdout
    assert p.stdout == "", p.stdout          # no record, no device number
    assert "needs a TPU" in p.stderr or "needs a tpu" in p.stderr, \
        p.stderr[-400:]


def test_smoke_alone_fails(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = _run(["chip_smoke.py"], cwd=tmp_path, PYTHONPATH=None)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout, p.stdout


def test_heartbeat_phase_matches_oracle():
    rec = chip_smoke.heartbeat_phase("cpu", **SMALL)
    assert rec["engine"] == "DeltaScheduler"
    assert all(rec["parity"].values()) and len(rec["parity"]) == 3
    assert rec["budget_parity"] is True
    assert rec["stats"]["delta_beats"] == SMALL["beats"], rec["stats"]


def test_continuity_phase_matches_oracle():
    rng = np.random.default_rng(3)
    totals = rng.integers(400, 12800, size=(40, 8)).astype(np.int32)
    avail = (totals * 0.6).astype(np.int32)
    reqs = rng.integers(0, 400, size=(8, 8)).astype(np.int32)
    counts = rng.multinomial(5000, np.full(8, 1 / 8)).astype(np.int32)
    rec = chip_smoke.continuity_phase(
        (totals, avail, np.ones(40, bool), reqs, counts))
    assert rec["parity"] is True and rec["tasks"] == 5000


def test_four_chip_phase_on_virtual_devices():
    rec = chip_smoke.four_chip_phase("cpu", 4, **SMALL)
    assert rec["oracle_parity_beats"] == [0, 2, 4]
    assert rec["modes"]["flat"]["mesh"] == [1, 4]
    assert rec["modes"]["two_level"]["mesh"] == [2, 2]
    for mode in rec["modes"].values():
        assert mode["shards"] == 4 and len(set(mode["devices"])) == 4


def test_live_phase_reaches_the_device_beat():
    rec = chip_smoke.live_phase("cpu", n_tasks=400, extra_nodes=2,
                                wave=200, timeout_s=120)
    assert rec["results_ok"] and rec["actor_ok"]
    assert rec["device_engines"] >= 1 and rec["device_beats"] >= 1


def test_spawned_worker_is_pinned_to_the_cpu(tmp_path):
    """The driver imports jax at top level, and spawn re-imports the
    driver's __main__ in the child before worker_main runs; a platform
    the child cannot use must not reach its jax."""
    driver = tmp_path / "driver.py"
    driver.write_text(textwrap.dedent("""
        import jax
        import ray_tpu

        @ray_tpu.remote
        def backend():
            import jax
            return jax.default_backend()

        if __name__ == "__main__":
            ray_tpu.init(resources={"CPU": 1}, num_workers=1)
            try:
                print("BACKEND", ray_tpu.get(backend.remote(), timeout=90))
            finally:
                ray_tpu.shutdown()
    """))
    p = _run([str(driver)], cwd=tmp_path, JAX_PLATFORMS="no_such_chip",
             PYTHONPATH=REPO)
    assert p.returncode == 0, p.stderr[-1500:]
    assert "BACKEND cpu" in p.stdout, p.stdout


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_repo(monkeypatch, cache_dir_config):
    from ray_tpu.util.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = enable_compile_cache()
    assert got == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_before_jax_is_imported():
    """A process that has not imported jax yet gets the variable, and
    jax picks the directory up when it is imported."""
    p = _run(["-c", "import sys; "
              "from ray_tpu.util.compile_cache import enable_compile_cache; "
              "d = enable_compile_cache(); assert 'jax' not in sys.modules; "
              "import jax; "
              "print(d == jax.config.jax_compilation_cache_dir, d)"],
             JAX_COMPILATION_CACHE_DIR=None)
    assert p.returncode == 0, p.stderr[-800:]
    assert p.stdout.split() == ["True", os.path.join(REPO, ".jax_cache")]


def test_compile_cache_leaves_the_operators_dir(monkeypatch,
                                                cache_dir_config):
    from ray_tpu.util.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/operator/cache")
    assert enable_compile_cache() == "/operator/cache"
    assert jax.config.jax_compilation_cache_dir is None
