"""Where the process's compile cache goes (``repro.xla_env.configure``)."""
import os

from repro import xla_env


def test_cache_dir_is_fixed_inside_checkout(monkeypatch, tmp_path):
    """Unset, the cache lands at one absolute path inside the checkout,
    whatever the working directory."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    xla_env.configure()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    got = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert got == xla_env.CACHE_DIR == os.path.join(root, ".cache", "jax")


def test_caller_cache_dir_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    xla_env.configure()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
