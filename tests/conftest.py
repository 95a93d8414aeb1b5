import os

# The suite runs on a virtual 8-device CPU platform, so sharding tests
# exercise real multi-device code paths without an accelerator. Both
# variables must be in the environment before the first `import jax`.
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA:CPU compiles a large module in several parts at once. On this jaxlib
# that killed a worker in five whole runs out of five (PR 37: a segmentation
# fault or an abort inside the compile, or inside the persistent cache's
# `executable.serialize()` / its read-back, always at a `tiny-laguna` program,
# the suite's largest: a dense block and four expert blocks in one body; a
# different test each time, never alone) and in none with one part.
if "xla_cpu_parallel_codegen_split_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_parallel_codegen_split_count=1"
    ).strip()
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache. Most of the suite's wall time is XLA
# recompiling the same tiny-model programs: each make_*() call produces
# a fresh jitted closure, so JAX's in-memory cache never dedupes across
# engines or test files — the on-disk cache keys on the HLO itself and
# does (~40% off a cold full run, far more on re-runs). The suite uses
# the same place every other process of this checkout does
# (compile_cache.DEFAULT_BASE, version- and backend-keyed), and exports
# it as JAX_COMPILATION_CACHE_DIR so that subprocess children (server
# boots, device-count drills, orchestrated jobs) retrieve instead of
# recompiling and no engine constructed in-process re-points it.
# Set JAX_COMPILATION_CACHE_DIR yourself to relocate or pre-empt this.
# 0.2s floor, for this process and its children alike (not
# compile_cache.enable()'s 0: caching every trivial test program would
# churn disk for nothing; not JAX's 1s: most tiny-model programs build
# faster than that, and every server boot would rebuild them).
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.2")

import jax

from dstack_tpu.workloads import compile_cache

if not os.environ.get(compile_cache.JAX_ENV_VAR):
    os.environ[compile_cache.JAX_ENV_VAR] = compile_cache.cache_dir_for(
        compile_cache.DEFAULT_BASE
    )
    # jax read its environment at import, before the line above.
    jax.config.update(
        "jax_compilation_cache_dir", os.environ[compile_cache.JAX_ENV_VAR]
    )

import asyncio
import inspect

import pytest


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal async test support (pytest-asyncio is not in the image)."""
    func = pyfuncitem.function
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(func(**kwargs))
        return True
    return None


def free_port() -> int:
    """Kernel-assigned free TCP port (shared by the subprocess-server
    tests; bind-to-0 keeps the pick race as narrow as it can be)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_in_device_subprocess(source: str, *, device_count: int = 2,
                             timeout: float = 420.0):
    """Run a Python snippet in a fresh interpreter pinned to a virtual
    CPU platform with exactly `device_count` devices.

    XLA fixes the host-platform device count at first jax import, so
    tests that need a specific mesh extent (rather than this process's
    8) must run in a subprocess with the flag in the environment. Used
    by the sharded-serving bit-exactness tests and the disaggregation
    drill smoke. Returns the CompletedProcess; callers usually
    `json.loads` the snippet's stdout.
    """
    import pathlib
    import subprocess
    import sys

    repo = str(pathlib.Path(__file__).resolve().parents[1])
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={device_count}"
    )
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo, env.get("PYTHONPATH")) if p
    )
    # The child inherits JAX_COMPILATION_CACHE_DIR (set above): it runs
    # the same jaxlib, so the heavyweight subprocess drills (disagg,
    # sharded bit-exactness) retrieve their programs instead of
    # recompiling them every run.
    return subprocess.run(
        [sys.executable, "-c", source], env=env, cwd=repo,
        capture_output=True, text=True, timeout=timeout,
    )
