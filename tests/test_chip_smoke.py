"""chip_smoke.py: it refuses to report without a TPU, and its two phases
(train, SIGKILL, resume) drive the training entry point correctly. The
phases run here at the smoke size on the CPU, which only this test asks for:
the script itself always demands a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
SMOKE_ARGS = ["--arch", "dlrm-rm1", "--smoke", "--batch", "8",
              "--pool-backend", "pmem"]


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(tmp_path):
    return {**os.environ, "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
            "PYTHONPATH": os.path.join(REPO, "src")}


def test_refuses_without_tpu(tmp_path):
    r = subprocess.run([sys.executable, SCRIPT], env=_cpu_env(tmp_path),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "found no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=_cpu_env(tmp_path), capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout == ""


def test_phases_kill_and_resume(tmp_path):
    smoke = _load_script()
    r = smoke.run_phases(SMOKE_ARGS, str(tmp_path / "ckpt"),
                         _cpu_env(tmp_path), platform="cpu")
    a, b = r["A"], r["B"]
    assert a["killed"] and a["step"] == smoke.KILL_AT_STEP
    assert a["compile_s"] > 0
    assert 1 <= b["resumed_at"] <= smoke.KILL_AT_STEP + 1
    assert b["rc"] == 0 and b["final_loss"] == b["final_loss"]


def test_phase_failure_is_an_error(tmp_path):
    """A trainer that cannot start fails the phase; nothing is caught."""
    smoke = _load_script()
    try:
        smoke.run_phases(["--arch", "no-such-arch"], str(tmp_path / "ckpt"),
                         _cpu_env(tmp_path), platform="cpu")
    except smoke.SmokeError as e:
        assert "phase A" in str(e)
    else:
        raise AssertionError("a failed phase was not reported")


def test_compile_cache_dir(monkeypatch, tmp_path):
    import jax

    from repro.utils import compile_cache as cc
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    assert cc.use_compile_cache() == str(tmp_path)
    monkeypatch.delenv(cc.ENV)
    before = jax.config.jax_compilation_cache_dir
    try:
        want = os.path.join(REPO, ".jax_cache")
        assert cc.use_compile_cache() == want
        assert os.environ[cc.ENV] == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
