"""Persistent compile cache: version keying, placement precedence, counters.

The version-keyed leaf is the load-bearing piece (workloads/
compile_cache.py): a foreign-jaxlib cache entry segfaults on
deserialize, so the keying is what makes a shared cache volume (and the
test suite's subprocess-exported cache) safe at all. Placement comes
from outside the program: an exported JAX_COMPILATION_CACHE_DIR, then
the flag / DSTACK_TPU_COMPILE_CACHE, then a fixed path in the checkout.
"""

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import jaxlib
import pytest

from dstack_tpu.workloads import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    """enable() mutates process-global jax config; put the suite's
    shared-cache settings back so later test files keep retrieving."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_enabled = compile_cache._enabled_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    with compile_cache._lock:
        compile_cache._enabled_dir = prev_enabled


@pytest.fixture
def no_cache_env(monkeypatch):
    """The suite itself runs under an exported JAX_COMPILATION_CACHE_DIR
    (tests/conftest.py); rules 2 and 3 are observable only without it."""
    monkeypatch.delenv(compile_cache.JAX_ENV_VAR, raising=False)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)


def test_cache_dir_is_version_and_backend_keyed(tmp_path):
    leaf = compile_cache.cache_dir_for(str(tmp_path))
    assert leaf.startswith(str(tmp_path))
    tail = leaf[len(str(tmp_path)) + 1:]
    # One path segment carrying all three key components: a jax OR
    # jaxlib bump (or a backend switch) must land in a DIFFERENT leaf.
    assert "/" not in tail
    assert f"jax{jax.__version__}" in tail
    assert f"jaxlib{jaxlib.__version__}" in tail
    assert tail.endswith(f"-{compile_cache.backend_name()}")
    # Explicit backend overrides detection (server-side keying for a
    # worker pool whose backend the caller knows).
    assert compile_cache.cache_dir_for(str(tmp_path), "tpu").endswith("-tpu")


def test_exported_jax_dir_wins_over_flag_and_env(tmp_path, monkeypatch,
                                                 restore_cache_config):
    """Rule 1: with JAX_COMPILATION_CACHE_DIR exported, neither the flag
    nor DSTACK_TPU_COMPILE_CACHE points JAX anywhere else — no
    jax.config.update of the cache dir happens at all."""
    raw = str(tmp_path / "raw")
    monkeypatch.setenv(compile_cache.JAX_ENV_VAR, raw)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "managed"))
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    got = compile_cache.enable(str(tmp_path / "flag"))
    assert got == raw == compile_cache.enabled_dir()
    assert jax.config.jax_compilation_cache_dir == prev_dir
    assert jax.config.jax_persistent_cache_min_compile_time_secs == prev_min
    assert not (tmp_path / "flag").exists()
    assert not (tmp_path / "managed").exists()


def test_flag_then_env_select_a_version_keyed_leaf(tmp_path, monkeypatch,
                                                   no_cache_env,
                                                   restore_cache_config):
    """Rule 2: the flag, else DSTACK_TPU_COMPILE_CACHE, names the BASE;
    the leaf under it is created, reported and handed to JAX."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "managed"))
    leaf = compile_cache.enable(str(tmp_path / "flag"))
    assert leaf == compile_cache.cache_dir_for(str(tmp_path / "flag"))
    assert os.path.isdir(leaf)
    assert compile_cache.enabled_dir() == leaf
    assert jax.config.jax_compilation_cache_dir == leaf
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    leaf = compile_cache.enable()
    assert leaf == compile_cache.cache_dir_for(str(tmp_path / "managed"))
    assert jax.config.jax_compilation_cache_dir == leaf


def test_default_is_a_fixed_path_under_the_checkout(no_cache_env,
                                                    restore_cache_config):
    """Rule 3: neither variable nor flag — the cache still exists, at a
    path that depends on nothing but where the package lives (the path
    is part of the cache key: a temp name, pid or time never hits)."""
    assert compile_cache.DEFAULT_BASE == str(REPO / ".jax-compile-cache")
    first = compile_cache.enable()
    assert first == compile_cache.cache_dir_for(compile_cache.DEFAULT_BASE)
    assert compile_cache.enable("") == first
    assert jax.config.jax_compilation_cache_dir == first
    # git must never see it.
    assert ".jax-compile-cache/" in (REPO / ".gitignore").read_text().split()


def test_counters_move_on_build_not_on_dispatch():
    compile_cache.install_counters()
    # A closure over a fresh object is a novel jit callable: guaranteed
    # in-memory cache miss, so the first call BUILDS (the persistent
    # cache may serve the executable — that still counts as a build).
    salt = jnp.asarray(3.0)
    fn = jax.jit(lambda x: x * salt + 1)
    arg = jnp.arange(7, dtype=jnp.float32)
    before = compile_cache.snapshot()
    fn(arg).block_until_ready()
    mid = compile_cache.snapshot()
    assert mid["compiles"] == before["compiles"] + 1
    assert mid["compile_seconds"] > before["compile_seconds"]
    # Second call with the same shapes: in-memory jit dispatch hit —
    # NO counter movement. This is the exact property the warmup
    # readiness contract rests on ("zero compiles after /readyz").
    fn(arg).block_until_ready()
    after = compile_cache.snapshot()
    assert after["compiles"] == mid["compiles"]
    assert after["compile_seconds"] == mid["compile_seconds"]
