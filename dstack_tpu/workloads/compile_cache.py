"""Persistent XLA compile cache + compile-event counters.

The scale-from-zero cold-start budget (docs/guides/serving-tuning.md,
"cold start") is dominated by XLA compiling the engine's jitted program
set on first boot. JAX's persistent compilation cache keys entries on
the HLO, so a repeat boot of the same model retrieves executables from
disk instead of recompiling — IF the cache directory survives and does
not move (the directory is part of the key).

WHERE THE CACHE LIVES is decided from outside the program, in this order
(`enable`):

1. `JAX_COMPILATION_CACHE_DIR` is exported: JAX itself reads it. That
   directory is used as configured and nothing here points JAX anywhere
   else — not a flag, not the other variable.
2. `--compile-cache-dir` / `DSTACK_TPU_COMPILE_CACHE` (the orchestrator
   exports the latter for a run with a durable volume,
   process_running_jobs.py): a version-keyed leaf under that base.
3. Neither: a version-keyed leaf under `.jax-compile-cache/` at the root
   of the checkout this package was imported from (git-ignored). Always
   the same path for the same checkout — never a temp name, pid or time —
   so every process of one machine (trainer, server, tests, chip_smoke.py
   children) shares it.

VERSION KEYING IS LOAD-BEARING for the directories this module names:
the serialized executables are jaxlib- and backend-specific, and
deserializing a foreign entry does not fail cleanly — it segfaults
(observed on the PR 14 subprocess drills). `cache_dir_for` therefore
nests every cache under a ``jax<ver>-jaxlib<ver>-<backend>`` leaf, so
one shared volume can serve heterogeneous workers: a version bump lands
in a fresh leaf instead of poisoning the old one.

Counters ride JAX's monitoring seam and power the warmup-gated
readiness contract (`ServingEngine.warmup`): `/jax/core/compile/
backend_compile_duration` fires once per program BUILD — fresh compile
or persistent-cache retrieval — and never on an in-memory jit dispatch
hit, so "zero compile events after /readyz" is exactly the property
"the first request re-traces nothing". The cache_hits/cache_misses
events split builds into disk retrievals vs real XLA compiles.
"""

import os
import threading
from typing import Dict, Optional

ENV_VAR = "DSTACK_TPU_COMPILE_CACHE"
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# Rule 3's base: <checkout>/.jax-compile-cache (this file sits at
# <checkout>/dstack_tpu/workloads/compile_cache.py).
DEFAULT_BASE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax-compile-cache",
)

# Monitoring event names. backend_compile_duration fires for fresh
# compiles AND persistent-cache retrievals; the hit/miss events only
# fire when the persistent cache is enabled.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
# compile_seconds accumulates the reported durations: time actually
# spent inside backend compilation (disk retrieval counts its own, much
# smaller, duration). It is the denominator that makes cache wins
# measurable — wall-clock warmup spans conflate it with Python tracing
# and lowering, which a warm cache cannot remove.
_counts = {
    "compiles": 0, "cache_hits": 0, "cache_misses": 0,
    "compile_seconds": 0.0,
}
_installed = False
_enabled_dir: Optional[str] = None


def backend_name() -> str:
    """The platform token that keys the cache dir. Prefer the pinned
    JAX_PLATFORMS (orchestrated runs always set it) so keying never has
    to initialize the backend; fall back to asking JAX."""
    pinned = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if pinned:
        return pinned
    import jax

    return jax.default_backend()


def cache_dir_for(base: str, backend: Optional[str] = None) -> str:
    """`base`/jax<ver>-jaxlib<ver>-<backend>: the version+backend-keyed
    leaf a process may actually read executables from."""
    import jax
    import jaxlib

    return os.path.join(
        base,
        f"jax{jax.__version__}-jaxlib{jaxlib.__version__}"
        f"-{backend or backend_name()}",
    )


def _on_event(event: str, **kwargs) -> None:
    if event == _HIT_EVENT:
        with _lock:
            _counts["cache_hits"] += 1
    elif event == _MISS_EVENT:
        with _lock:
            _counts["cache_misses"] += 1


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        with _lock:
            _counts["compiles"] += 1
            _counts["compile_seconds"] += duration


def install_counters() -> None:
    """Register the monitoring listeners once per process. Idempotent;
    cheap enough to call from every engine constructor."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax._src import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def enable(base_dir: Optional[str] = None) -> str:
    """Turn the persistent compilation cache on where the module
    docstring's precedence puts it, install the counters, and return
    the directory in use. `base_dir` is the caller's flag value (rule 2;
    empty/None means "not given").

    Under rules 2 and 3 min_compile_time is forced to 0 so even the tiny
    programs (table-row setters, block copies) cache — a warm boot must
    retrieve the WHOLE program set or the first request still pays a
    compile. Under rule 1 the user's JAX configuration is left exactly
    as exported."""
    global _enabled_dir
    install_counters()
    d = os.environ.get(JAX_ENV_VAR)
    if not d:
        import jax

        d = cache_dir_for(base_dir or os.environ.get(ENV_VAR) or DEFAULT_BASE)
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _lock:
        _enabled_dir = d
    return d


def enabled_dir() -> Optional[str]:
    """The cache directory `enable` last put in use, or None when it was
    never called in this process."""
    with _lock:
        return _enabled_dir


def compile_count() -> int:
    """Programs BUILT so far in this process (fresh compile or
    persistent-cache retrieval — both mean the in-memory jit cache
    missed). The warmup readiness assert is `compile_count()` not
    moving across a post-ready request."""
    with _lock:
        return _counts["compiles"]


def snapshot() -> Dict[str, float]:
    with _lock:
        return dict(_counts)
