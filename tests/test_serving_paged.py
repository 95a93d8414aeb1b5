"""Paged KV + prefix sharing: exactness, CoW isolation, leak checks.

The chunked/paged path must be bit-identical to the dense `generate()`
reference at temperature 0 — for prompt lengths that are NOT multiples
of the chunk or block size, with the prefix cache both cold and hot —
and the block pool must drain to zero when requests end for any reason.
These are the invariants that make paging an optimization rather than a
semantics change.
"""

import threading

import jax
import jax.numpy as jnp
import pytest

from dstack_tpu.server.metrics_registry import METRICS
from dstack_tpu.utils.flight_recorder import LOOP_CHILDREN, LOOP_PHASES
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.generate import generate
from dstack_tpu.workloads.serving import ServingEngine, prometheus_metrics
from dstack_tpu.workloads.transformer import init_params

CFG = PRESETS["tiny"].with_(remat=False)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _drain(q):
    out = []
    while True:
        tok = q.get(timeout=60)
        if isinstance(tok, BaseException):
            raise tok
        if tok is None:
            return out
        out.append(tok)


def _reference(params, prompt, n):
    toks = generate(
        CFG, params, jnp.asarray([prompt], dtype=jnp.int32),
        max_new_tokens=n, temperature=0.0,
    )
    return [int(t) for t in toks[0]]


def _prompt(seed, n):
    return [(i * 37 + seed * 13 + 5) % 100 + 1 for i in range(n)]


def test_chunked_paged_temp0_exactness_at_awkward_lengths(params):
    """Lengths 5 / 27 / 33 with chunk=16, block=8: none is a multiple of
    chunk or block size, 27 and 33 straddle chunk boundaries, 33 crosses
    a block boundary mid-chunk. All must match the dense reference."""
    engine = ServingEngine(CFG, params, slots=4, max_len=96,
                           prefill_chunk_tokens=16, kv_block_size=8)
    try:
        for seed, n in ((1, 5), (2, 27), (3, 33)):
            p = _prompt(seed, n)
            q = engine.submit(p, max_new_tokens=8)
            assert _drain(q) == _reference(params, p, 8), f"len={n}"
    finally:
        engine.close()


def test_prefix_hit_skips_cached_compute_and_stays_exact(params):
    """Two prompts sharing a 24-token prefix (3 full blocks at bs=8),
    run back to back: the second's prefill computes only its 2-token
    suffix (>=50%% compute drop — the acceptance bar), reuses 24 cached
    tokens, and its output is still bit-exact."""
    engine = ServingEngine(CFG, params, slots=4, max_len=96,
                           prefill_chunk_tokens=16, kv_block_size=8)
    try:
        prefix = _prompt(7, 24)
        p1, p2 = prefix + [3, 5], prefix + [11, 13]
        q = engine.submit(p1, max_new_tokens=6)
        assert _drain(q) == _reference(params, p1, 6)
        cold = engine.stats()["prefill_tokens_computed_total"]
        assert cold == len(p1)

        q = engine.submit(p2, max_new_tokens=6)
        assert _drain(q) == _reference(params, p2, 6)
        s = engine.stats()
        hit_cost = s["prefill_tokens_computed_total"] - cold
        assert hit_cost == 2, f"cache hit recomputed {hit_cost} tokens"
        assert s["prefix_cache_hits_total"] == 1
        assert s["prefix_tokens_reused_total"] == 24
    finally:
        engine.close()


def test_concurrent_streams_activating_mid_decode_stay_exact(params):
    """Regression for the activation-ordering bug: a prefill that
    finalizes goes live in the SAME chunk, so its decode-block growth
    must run after admissions — otherwise the chunk's writes past the
    last prompt block hit the pad sentinel, silently drop, and the next
    chunk attends to garbage. Four streams admitted while others decode
    must all match their dense references."""
    engine = ServingEngine(CFG, params, slots=4, max_len=96,
                           prefill_chunk_tokens=16, kv_block_size=8)
    try:
        prefix = _prompt(9, 20)
        prompts = [prefix + [s, s + 2] for s in (3, 20, 40, 60)]
        refs = [_reference(params, p, 8) for p in prompts]
        queues = [engine.submit(p, max_new_tokens=8) for p in prompts]
        for p, q, r in zip(prompts, queues, refs):
            assert _drain(q) == r, p
    finally:
        engine.close()


def test_prefix_sharers_writing_a_shared_tail_block_cow_isolate(params):
    """The sharpest sharing case: a sharer matches the retired request's
    cached PARTIAL-TAIL block and must then append its own KV into that
    very block — which the cache (and a concurrent sharer) still hold.
    The engine must copy-on-write before writing; both sharers and a
    re-run of the original prompt must stay bit-exact."""
    engine = ServingEngine(CFG, params, slots=4, max_len=96,
                           prefill_chunk_tokens=16, kv_block_size=8)
    try:
        p1 = _prompt(9, 22)  # 2 full blocks + 6-token tail in block 2
        ref1 = _reference(params, p1, 8)
        assert _drain(engine.submit(p1, max_new_tokens=8)) == ref1
        # Sharers extend p1 itself: match covers p1's full blocks AND its
        # cached tail (matched=22), so decode writes land in the shared
        # tail block.
        sharers = [p1 + [5, 9], p1 + [7, 3]]
        refs = [_reference(params, p, 8) for p in sharers]
        queues = [engine.submit(p, max_new_tokens=8) for p in sharers]
        for p, q, r in zip(sharers, queues, refs):
            assert _drain(q) == r, p
        s = engine.stats()
        assert s["kv_cow_copies_total"] >= 1, "shared tail never CoW'd"
        assert s["prefix_tokens_reused_total"] >= 44  # 22 per sharer
        # The cached entries were never corrupted by the sharers' writes:
        # the original prompt still reproduces exactly from cache.
        assert _drain(engine.submit(p1, max_new_tokens=8)) == ref1
    finally:
        engine.close()


def test_clean_end_and_cache_off_returns_every_block(params):
    """With the prefix cache off, the pool must be empty after every
    request retires — over several rounds, including multi-chunk
    prompts, so refcount drift anywhere in the prefill/decode/retire
    path shows up as a nonzero residue."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           prefill_chunk_tokens=8, kv_block_size=8,
                           prefix_cache=False)
    try:
        for seed, n in ((1, 3), (2, 20), (3, 17)):
            q = engine.submit(_prompt(seed, n), max_new_tokens=6)
            assert len(_drain(q)) == 6
            assert engine.stats()["kv_blocks_in_use"] == 0, f"len={n}"
    finally:
        engine.close()


def test_cancel_mid_multichunk_prefill_returns_every_block(params):
    """Cancel landing between chunk boundaries of a 3-chunk prefill: the
    stream ends cleanly with no tokens and every allocated block goes
    back to the pool."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           prefill_chunk_tokens=8, kv_block_size=8,
                           prefix_cache=False)
    try:
        first_chunk_done = threading.Event()
        release = threading.Event()
        calls = []
        real_chunk_fn = engine._chunk_fn

        def gated_chunk_fn(n_padded):
            fn = real_chunk_fn(n_padded)

            def wrapped(*args):
                calls.append(n_padded)
                if len(calls) > 1:  # chunk 1 runs; later chunks gate
                    first_chunk_done.set()
                    assert release.wait(30)
                out = fn(*args)
                first_chunk_done.set()
                return out

            return wrapped

        engine._chunk_fn = gated_chunk_fn
        q = engine.submit(_prompt(4, 20), max_new_tokens=6)  # chunks 8+8+4
        assert first_chunk_done.wait(30)
        engine.cancel(q)  # lands after chunk 1, before the prefill ends
        release.set()
        assert _drain(q) == []  # clean end, zero tokens delivered
        engine._chunk_fn = real_chunk_fn
        # Pool fully drained, and the engine still serves.
        assert engine.stats()["kv_blocks_in_use"] == 0
        p = _prompt(5, 11)
        q = engine.submit(p, max_new_tokens=4)
        assert _drain(q) == _reference(params, p, 4)
        assert engine.stats()["kv_blocks_in_use"] == 0
    finally:
        engine.close()


def test_prometheus_metrics_matches_registry(params):
    """Every series the serving exposition emits is declared in the
    metrics registry with the declared type — the MET01 contract, pinned
    at runtime too so the native server's /metrics can never drift."""
    engine = ServingEngine(CFG, params, slots=2, max_len=32)
    try:
        q = engine.submit([5, 7, 11], max_new_tokens=3)
        _drain(q)
        text = prometheus_metrics(engine.stats())
    finally:
        engine.close()
    from dstack_tpu.server.metrics_registry import histogram_base

    seen = set()
    sampled = set()
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split()
            assert name in METRICS, f"undeclared series {name}"
            assert METRICS[name][0] == mtype, name
            # Serving series carry no labels, except the r12 attention
            # dispatch counter (path=pallas|lax_ragged), the loop's
            # per-phase seconds and the r13/r16 role-labeled latency
            # histograms — their samples are checked against the
            # declared label sets below.
            if name not in ("dstack_tpu_serving_attn_dispatch_total",
                            "dstack_tpu_serving_loop_phase_seconds_total",
                            "dstack_tpu_serving_ttft_seconds",
                            "dstack_tpu_serving_tpt_seconds",
                            "dstack_tpu_serving_kv_transfer_seconds",
                            "dstack_tpu_serving_kv_swap_in_seconds",
                            "dstack_tpu_serving_phase_seconds"):
                assert METRICS[name][1] == (), name
            seen.add(name)
        else:
            name, _, value = line.partition(" ")
            base = name.partition("{")[0]
            decl = histogram_base(base) or base
            assert decl in seen, f"sample before TYPE: {name}"
            if base == "dstack_tpu_serving_attn_dispatch_total":
                assert name in (
                    base + '{path="pallas"}', base + '{path="lax_ragged"}'
                ), name
            if base == "dstack_tpu_serving_loop_phase_seconds_total":
                phase = name[len(base) + len('{phase="'):-len('"}')]
                assert phase in LOOP_PHASES + LOOP_CHILDREN, name
            if base.startswith("dstack_tpu_serving_phase_seconds"):
                # r15 flight-recorder histograms: every sample carries
                # the declared (phase, role) pair.
                assert 'phase="' in name and 'role="unified"' in name, name
            if METRICS.get(decl, ("", ()))[1] == ("role",):
                # a unified engine's whole distribution is one role —
                # except TTFT, whose r20 cold_start split carries each
                # boot's first-ever delivery under its own role.
                if base.startswith("dstack_tpu_serving_ttft_seconds"):
                    assert ('role="unified"' in name
                            or 'role="cold_start"' in name), name
                else:
                    assert 'role="unified"' in name, name
            sampled.add(base)
            float(value)
    for expected in ("dstack_tpu_serving_kv_blocks_in_use",
                     "dstack_tpu_serving_prefix_cache_hits_total",
                     "dstack_tpu_serving_prefix_cache_misses_total",
                     "dstack_tpu_serving_prefill_chunks_total",
                     "dstack_tpu_serving_admitted_total"):
        assert expected in seen, expected
    # TTFT is a real histogram now: derived series, declared base.
    assert "dstack_tpu_serving_ttft_seconds" in seen
    # The default-on flight recorder must have fed the phase histograms
    # for the request served above — silence here would mean the r15
    # phase clock quietly stopped.
    assert "dstack_tpu_serving_phase_seconds" in seen
    for derived in ("dstack_tpu_serving_ttft_seconds_bucket",
                    "dstack_tpu_serving_ttft_seconds_sum",
                    "dstack_tpu_serving_ttft_seconds_count"):
        assert derived in sampled, derived
    # Speculation series render (at zero) even with speculation off, so
    # dashboards and the registry checker see one stable series set.
    assert "dstack_tpu_serving_spec_rounds_total" in seen
    assert "dstack_tpu_serving_spec_accept_rate_ewma" in seen


def test_spec_disabled_surface_is_inert(params):
    """A spec-off engine reports the speculation keys as zeros/False —
    scrapers get a stable schema — and rejects a KV budget smaller than
    the target pool with an actionable error (no drafter involved)."""
    engine = ServingEngine(CFG, params, slots=2, max_len=32)
    try:
        st = engine.stats()
        assert st["spec_enabled"] is False
        assert st["spec_rounds_total"] == 0
        assert st["spec_tokens_proposed_total"] == 0
        assert st["spec_accept_rate_ewma"] == 0.0
        pool = engine._pool_bytes_target
    finally:
        engine.close()
    with pytest.raises(ValueError, match="cannot fit the KV pool"):
        ServingEngine(CFG, params, slots=2, max_len=32,
                      kv_budget_bytes=pool - 1)


def test_ttft_histogram_tracks_deliveries(params):
    """Each admitted request's first token lands one TTFT observation;
    the stats snapshot carries the cumulative-bucket dict the exposition
    renders. On a warmup-less engine the first-ever delivery paid the
    jit trace+compile for its dispatch chain, so it lands in the
    role="cold_start" split, keeping the steady-state distribution
    clean (r20)."""
    engine = ServingEngine(CFG, params, slots=2, max_len=32)
    try:
        _drain(engine.submit([5, 7, 11], max_new_tokens=3))
        _drain(engine.submit([5, 7, 13], max_new_tokens=3))
        stats = engine.stats()
        hist = stats["ttft_hist"]
        cold = stats["ttft_cold_hist"]
    finally:
        engine.close()
    assert cold["count"] == 1
    assert hist["count"] == 1
    assert hist["sum"] > 0
    counts = [c for _, c in hist["buckets"]]
    assert counts == sorted(counts) and counts[-1] <= 1


@pytest.mark.parametrize("ring,traced", [(0, 0), (16, 5)])
def test_recorder_traces_every_request_or_none(params, ring, traced):
    """A recorder-on engine starts and finishes one trace a request, more
    requests than slots included; with `trace_ring=0` it keeps none (an
    overhead reading of a recorder that silently no-oped would be no
    reading)."""
    engine = ServingEngine(CFG, params, slots=2, max_len=32, trace_ring=ring)
    try:
        qs = [engine.submit(_prompt(s, 5), max_new_tokens=4) for s in range(5)]
        assert all(len(_drain(q)) == 4 for q in qs)
        trace = engine.stats()["trace"]
    finally:
        engine.close()
    assert trace["started_total"] == traced
    assert trace["finished_total"] == traced
