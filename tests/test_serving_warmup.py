"""Warmup-gated readiness: the zero-post-ready-compile contract.

`ServingEngine.warmup()` must pre-build every jitted program the
scheduler can dispatch — measured here not by inspecting the program
set but by the observable the readiness gate actually promises: after
warmup, a mixed traffic burst moves the process-wide compile counter by
exactly zero. The counter (workloads/compile_cache.py) fires once per
XLA program BUILD (fresh compile or persistent-cache retrieval) and
never on an in-memory jit dispatch hit, so "zero" means the burst
re-traced nothing — including the tiny weak-type-strip and host-convert
programs that historically leaked around naive warmups.
"""

import jax
import pytest

from dstack_tpu.workloads import compile_cache
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.serving import EngineBusyError, ServingEngine
from dstack_tpu.workloads.transformer import init_params

CFG = PRESETS["tiny"].with_(remat=False)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _drain(q):
    out = []
    while True:
        tok = q.get(timeout=120)
        if tok is None:
            return out
        out.append(tok)


def _burst(engine):
    """Mixed post-warmup traffic: prompt lengths landing in different
    prefill buckets, more requests than slots (queueing + slot reuse)."""
    prompts = [
        [5, 7, 11],                                # bucket 4
        list(range(2, 15)),                        # bucket 16, two chunks
        [3] * 9,                                   # bucket 16 (pad 9 -> 16)
        [2, 3, 5, 7],                              # bucket 4, exact
    ]
    queues = [engine.submit(p, max_new_tokens=5) for p in prompts]
    for q in queues:
        assert len(_drain(q)) == 5


def test_warmup_then_burst_compiles_nothing(params):
    # COMMITTED params, as a checkpoint restore hands them out (init_params
    # returns uncommitted arrays): every program then returns committed
    # state, and an engine whose state started uncommitted re-built the
    # program warmup ran first on the first live dispatch.
    engine = ServingEngine(
        CFG, jax.device_put(params, jax.devices()[0]), slots=2, max_len=64,
        prefill_chunk_tokens=16, kv_block_size=8,
    )
    try:
        stats = engine.stats()
        assert stats["warmup_done"] is False
        assert stats["warmup_seconds"] is None
        result = engine.warmup()
        assert result["programs"] > 0
        assert result["seconds"] > 0
        # Builds happened (fresh or retrieved — either way the burst
        # below would have paid them without warmup).
        assert result["compiles"] > 0
        before = compile_cache.compile_count()
        _burst(engine)
        assert compile_cache.compile_count() == before, (
            "post-warmup traffic built XLA programs the warmup missed"
        )
        stats = engine.stats()
        assert stats["warmup_done"] is True
        assert stats["warmup_seconds"] == pytest.approx(
            result["seconds"], abs=0.01
        )
        assert stats["warmup_programs"] == result["programs"]
        assert stats["compile_seconds_total"] > 0
        # Drained == idle again: warmup is legal after traffic ends,
        # and on a warmed engine it re-invokes in-memory-cached
        # programs — near-free, and still zero fresh builds.
        again = engine.warmup()
        assert again["programs"] == result["programs"]
        assert compile_cache.compile_count() == before
    finally:
        engine.close()


@pytest.mark.slow
def test_warmup_covers_speculative_ladder(params):
    """A spec engine's reachable set includes the draft/verify program
    ladder for every draft length; the burst runs real spec rounds."""
    engine = ServingEngine(
        CFG, params, slots=2, max_len=64, prefill_chunk_tokens=16,
        kv_block_size=8, spec_enable=True, spec_max_draft=2,
    )
    try:
        result = engine.warmup()
        assert result["programs"] > 0
        before = compile_cache.compile_count()
        _burst(engine)
        assert compile_cache.compile_count() == before
    finally:
        engine.close()


def test_warmup_requires_idle_engine(params):
    """Warmup invokes the real donated-state programs, so it must refuse
    to race in-flight work (the server calls it before serving). The
    warmup-after-drain legality rides the warmed engine in
    test_warmup_then_burst_compiles_nothing, where the re-run is free."""
    engine = ServingEngine(CFG, params, slots=1, max_len=64)
    try:
        q = engine.submit([5, 7, 11], max_new_tokens=30)
        # Its own type: a caller must be able to tell this refusal from
        # a program that failed to build (also a RuntimeError).
        with pytest.raises(EngineBusyError, match="idle"):
            engine.warmup()
        _drain(q)
    finally:
        engine.close()
