"""Persistent-compile-cache wiring (utils/compile_cache.py).

The cache itself is jax's; what this framework owns is WHERE it lives,
and the rule is decided from outside the program: a set
``JAX_COMPILATION_CACHE_DIR`` wins and nothing in code overrides it;
unset, every process of the checkout shares ``<repo>/.xla_cache`` —
never ``$PIO_HOME``, a temp name, a pid or a time (the directory is part
of the cache key: a cache that moves never hits).
"""

import os
import subprocess
import sys

import jax
import pytest

from incubator_predictionio_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_enable_state(monkeypatch):
    monkeypatch.setattr(compile_cache, "_enabled", False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PIO_COMPILE_CACHE", raising=False)
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_compilation_cache_dir
    # jax opens its file cache once per process: drop a handle an earlier
    # test file of this worker may have opened, and ours afterwards
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    cc.reset_cache()


def test_env_var_wins_and_nothing_overrides_it(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set → that directory, applied through
    jax.config (jax IS imported here, so the env alone would be too
    late). Neither PIO_HOME nor a path in PIO_COMPILE_CACHE (the removed
    redirect) moves it."""
    user_dir = str(tmp_path / "user")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", user_dir)
    monkeypatch.setenv("PIO_HOME", str(tmp_path / "home"))
    monkeypatch.setenv("PIO_COMPILE_CACHE", str(tmp_path / "redirect"))
    compile_cache.enable()
    assert compile_cache.cache_dir() == user_dir
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == user_dir
    assert jax.config.jax_compilation_cache_dir == user_dir
    assert os.path.isdir(user_dir)
    assert not (tmp_path / "redirect").exists()
    assert not (tmp_path / "home").exists()


def test_unset_uses_the_fixed_in_checkout_directory(monkeypatch):
    """No env var → <repo>/.xla_cache, exported so child processes
    resolve the same directory."""
    want = os.path.join(REPO, ".xla_cache")
    assert compile_cache.cache_dir() == want
    compile_cache.enable()
    assert jax.config.jax_compilation_cache_dir == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".xla_cache/" in f.read().split()


def test_default_does_not_depend_on_pio_home_pid_or_time(
        tmp_path, monkeypatch):
    """Two processes with different PIO_HOMEs (and pids, and start
    times) resolve one directory — the property a warm second
    `pio train` depends on."""
    code = ("from incubator_predictionio_tpu.utils import compile_cache;"
            "print(compile_cache.cache_dir())")
    seen = set()
    for home in ("a", "b"):
        env = {**os.environ, "PIO_HOME": str(tmp_path / home),
               "PYTHONPATH": REPO}
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, check=True)
        seen.add(out.stdout.strip())
    monkeypatch.setenv("PIO_HOME", str(tmp_path / "c"))
    seen.add(compile_cache.cache_dir())
    assert seen == {os.path.join(REPO, ".xla_cache")}


def test_off_disables(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_COMPILE_CACHE", "off")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_compilation_cache_dir
    compile_cache.enable()
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "cache").exists()
    assert compile_cache._enabled is False


def test_enable_is_idempotent_and_never_repoints(tmp_path, monkeypatch):
    """There is no cache_dir argument any more: once enabled, a later
    call — even under a changed environment — leaves the directory
    where the first one put it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "a"))
    compile_cache.enable()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "b"))
    compile_cache.enable()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "a")
    assert not (tmp_path / "b").exists()
    with pytest.raises(TypeError):
        compile_cache.enable(str(tmp_path / "c"))


def test_persistent_cache_round_trip_counts_a_hit(tmp_path, monkeypatch):
    """A compiled program lands in the resolved directory and is read
    back after the in-memory executable cache is cleared (the
    cross-process story, driven in-process via jax.clear_caches); the
    obs bridge counts the hit."""
    import numpy as np

    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    compile_cache.enable()
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        hits = obs_metrics.REGISTRY.get("pio_compile_cache_hits_total")
        before = hits.value
        f = jax.jit(lambda a: a * 2 + 1)
        np.asarray(f(jax.numpy.ones(16)))
        entries = list((tmp_path / "cache").iterdir())
        assert entries, "no persistent cache entry written"
        jax.clear_caches()
        np.asarray(f(jax.numpy.ones(16)))  # served from the entry
        assert hits.value > before
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", old_min)
