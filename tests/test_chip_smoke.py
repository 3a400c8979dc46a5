"""chip_smoke.py off the chip: it refuses to run without a TPU or outside a
checkout, and its engine phase holds on CPU at a tiny size (interpret
mode), so the script cannot rot between chip runs."""
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _has_json_line(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return True
        except ValueError:
            continue
    return False


def test_fails_without_tpu():
    out = _run(REPO, SMOKE)
    assert out.returncode == 1, out.stderr
    assert "no TPU" in out.stderr
    assert not _has_json_line(out.stdout)


def test_fails_outside_checkout(tmp_path):
    script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(tmp_path, str(script))
    assert out.returncode == 2, out.stderr
    assert not _has_json_line(out.stdout)


def test_engine_phase_holds_on_cpu(capsys):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    chip_smoke.phase_fused_round(chip_smoke.CompileClock(), P=4, K=20, L=3,
                                 batch=4, M=300, iters=3,
                                 expect=("pallas", True))
    out = capsys.readouterr().out
    for privacy in ("none", "hybrid", "iid_dp"):
        assert f"[a] {privacy}" in out


def test_compile_cache_dir(monkeypatch):
    """An entry point's cache goes where JAX_COMPILATION_CACHE_DIR says,
    else to one fixed, gitignored path in the checkout."""
    import jax
    import repro
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert repro.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert repro.use_compile_cache() == repro.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == repro.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert repro.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
