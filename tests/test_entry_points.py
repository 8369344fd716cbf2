"""Entry-point plumbing for chip hosts: where the persistent compilation
cache goes (repro/utils/compile_cache.py), and that a launcher which spawns
training children never initialises a JAX backend itself — a chip belongs
to one process, so a parent holding it would starve its children."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.utils.compile_cache import REPO_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_is_left_to_jax(monkeypatch, cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_repo_cache_dir_without_env(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert REPO_CACHE_DIR == REPO / ".jax_cache"
    assert use_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    # a fixed path: a second call picks the same directory
    assert use_compile_cache() == str(REPO_CACHE_DIR)


def test_kill_resume_parent_initialises_no_backend():
    """scripts/kill_resume_smoke.py imports repro (and so jax) before it
    spawns its fl_train children; the import must not bring up a backend."""
    code = ("import sys; sys.path[:0] = ['scripts', 'src']\n"
            "import kill_resume_smoke\n"
            "from jax._src import xla_bridge\n"
            "assert 'jax' in sys.modules\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
