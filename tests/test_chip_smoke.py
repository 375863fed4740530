"""``chip_smoke.py`` refuses to run without a TPU, and the compile-cache
helper the entry points share places the cache where it says."""
import os
import shutil
import subprocess
import sys

REPO = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(REPO, "src")


def _run(argv, cwd, **env):
    return subprocess.run([sys.executable] + argv, cwd=cwd,
                          env=dict(os.environ, **env), capture_output=True,
                          text=True, timeout=120)


def test_chip_smoke_refuses_the_cpu():
    for argv in (["chip_smoke.py"], ["chip_smoke.py", "--chips", "4"]):
        out = _run(argv, REPO, JAX_PLATFORMS="cpu")
        assert out.returncode != 0, out.stdout
        assert '"ok": true' not in out.stdout, out.stdout
        assert "no TPU" in out.stderr, out.stderr[-2000:]


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], str(tmp_path), JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


_CACHE_PROBE = """
import jax
from repro.launch.compile_cache import CACHE_DIR, CHECKOUT, use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print(CACHE_DIR == CHECKOUT + "/.jax_cache")
"""


def test_compile_cache_dir_follows_the_environment(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = SRC
    unset = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                           capture_output=True, text=True, timeout=120)
    assert unset.returncode == 0, unset.stderr[-2000:]
    helper, config, fixed = unset.stdout.split()
    assert helper == config
    assert os.path.realpath(helper) == os.path.realpath(
        os.path.join(REPO, ".jax_cache"))
    assert fixed == "True"

    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    given = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                           capture_output=True, text=True, timeout=120)
    assert given.returncode == 0, given.stderr[-2000:]
    assert given.stdout.split()[:2] == [str(tmp_path), str(tmp_path)]
