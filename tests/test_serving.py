"""Continuous-batching serving engine vs the one-shot generate loop."""

import time

import jax
import jax.numpy as jnp
import pytest

from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.generate import generate
from dstack_tpu.workloads.serving import ServingEngine
from dstack_tpu.workloads.transformer import init_params

CFG = PRESETS["tiny"].with_(remat=False)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _drain(q):
    out = []
    while True:
        tok = q.get(timeout=60)
        if tok is None:
            return out
        out.append(tok)


def _reference(params, prompt, n):
    toks = generate(
        CFG, params, jnp.asarray([prompt], dtype=jnp.int32),
        max_new_tokens=n, temperature=0.0,
    )
    return [int(t) for t in toks[0]]


def test_concurrent_requests_match_generate(params):
    engine = ServingEngine(CFG, params, slots=4, max_len=64)
    try:
        prompts = [[5, 7, 11], [13, 17, 19, 23, 29], [2, 3]]
        queues = [engine.submit(p, max_new_tokens=6) for p in prompts]
        outs = [_drain(q) for q in queues]
        for prompt, out in zip(prompts, outs):
            assert out == _reference(params, prompt, 6), (prompt, out)
    finally:
        engine.close()


def test_more_requests_than_slots(params):
    engine = ServingEngine(CFG, params, slots=2, max_len=64)
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
        queues = [engine.submit(p, max_new_tokens=4) for p in prompts]
        outs = [_drain(q) for q in queues]
        for prompt, out in zip(prompts, outs):
            assert len(out) == 4
            assert out == _reference(params, prompt, 4), (prompt, out)
    finally:
        engine.close()


def test_midflight_join(params):
    engine = ServingEngine(CFG, params, slots=4, max_len=96)
    try:
        q1 = engine.submit([5, 7, 11], max_new_tokens=24)
        # Let the first request get going, then join mid-decode.
        time.sleep(1.0)
        q2 = engine.submit([13, 17], max_new_tokens=5)
        out2 = _drain(q2)
        out1 = _drain(q1)
        assert out1 == _reference(params, [5, 7, 11], 24)
        assert out2 == _reference(params, [13, 17], 5)
    finally:
        engine.close()


def test_cache_full_retires_slot(params):
    """The cache-full guard in the decode step is unreachable through
    submit() (validation caps budget first) — exercise it directly with a
    hand-built over-budget PagedDecodeState."""
    from dstack_tpu.workloads.kv_blocks import (
        init_paged_state,
        make_chunk_prefill,
        make_paged_decode_step,
    )

    max_len, block = 12, 4
    # One slot whose table holds blocks 0..2; block 3 belongs to nobody.
    state = init_paged_state(CFG, 1, max_len, block, 4)
    i32, f32 = jnp.int32, jnp.float32
    state, _ = make_chunk_prefill(CFG, 4)(
        params, state, jnp.asarray(0, i32), jnp.arange(3, dtype=i32),
        jnp.asarray([[1, 2, 3, 0]], i32), jnp.asarray(3, i32),
        jnp.asarray(0, i32),
        jnp.asarray(100, i32),  # budget far beyond the cache
        jnp.asarray(0.0, f32), jnp.asarray(1.0, f32),
        jax.random.PRNGKey(0), jnp.asarray(True),
    )
    assert bool(state.active[0]) and int(state.lengths[0]) == 3
    step = make_paged_decode_step(CFG)
    rng = jax.random.PRNGKey(0)
    emitted = 0
    for _ in range(max_len + 5):
        state, toks, active = step(params, state, rng)
        emitted += int(toks[0, 0] >= 0)
        if not bool(active[0]):
            break
    assert not bool(active[0]), "slot must retire when the cache fills"
    # Writes never ran past the table: the last write landed at row
    # lengths-1 <= max_len-1, and the block outside it is untouched.
    assert int(state.lengths[0]) <= max_len
    assert not bool(jnp.any(state.k[:, 3])) and not bool(jnp.any(state.v[:, 3]))
    assert emitted >= 1


def test_submit_validates_budget(params):
    engine = ServingEngine(CFG, params, slots=1, max_len=16)
    try:
        with pytest.raises(ValueError):
            engine.submit([1, 2, 3], max_new_tokens=0)
        with pytest.raises(ValueError):
            engine.submit([1, 2, 3], max_new_tokens=-2)
    finally:
        engine.close()


def test_close_mid_generation_is_an_error_not_clean_end(params):
    """close() must not hand unfinished consumers the clean-end None —
    a truncated generation reading as complete is silent data loss."""
    engine = ServingEngine(CFG, params, slots=1, max_len=512)
    q = engine.submit([1, 2, 3], max_new_tokens=400)
    engine.close()
    tokens, sentinel = [], None
    while True:
        item = q.get(timeout=60)
        if item is None or isinstance(item, BaseException):
            sentinel = item
            break
        tokens.append(item)
    if len(tokens) < 400:  # truncated (the overwhelmingly likely case)
        assert isinstance(sentinel, BaseException), (
            "truncated generation was delivered as a clean end"
        )
    else:  # engine outran close(): complete output, clean end is correct
        assert sentinel is None


def test_submit_after_close_raises(params):
    engine = ServingEngine(CFG, params, slots=1, max_len=16)
    engine.close()
    with pytest.raises(RuntimeError):
        engine.submit([1, 2, 3], max_new_tokens=2)


def test_validation(params):
    engine = ServingEngine(CFG, params, slots=1, max_len=16)
    try:
        with pytest.raises(ValueError):
            engine.submit([], max_new_tokens=4)
        with pytest.raises(ValueError):
            engine.submit([1] * 10, max_new_tokens=10)
    finally:
        engine.close()


def _slow_decode(engine, delay):
    """Throttle the engine's decode chunks so slot occupancy is stable
    while a test asserts on admission behavior (real decode on the tiny
    model retires slots in milliseconds)."""
    orig = engine._step

    def slow(params, state, rng):
        time.sleep(delay)
        return orig(params, state, rng)

    engine._step = slow


def test_admission_control_sheds_overflow(params):
    """With max_pending bounded, submit() raises EngineOverloadedError
    (with a Retry-After estimate) instead of queueing unboundedly; stats()
    exposes the shed counter and queue depth for /metrics."""
    from dstack_tpu.workloads.serving import EngineOverloadedError

    engine = ServingEngine(CFG, params, slots=1, max_len=64, max_pending=1)
    _slow_decode(engine, 0.25)  # hold slot occupancy across the asserts
    try:
        qa = engine.submit([5, 7, 11], max_new_tokens=30)
        # Wait until A OCCUPIES the lone slot (first token arrives before
        # the jitted insert finishes compiling, so poll stats), ensuring B
        # deterministically parks in pending.
        first = qa.get(timeout=60)
        assert isinstance(first, int)
        deadline = time.monotonic() + 60
        while engine.stats()["active"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        qb = engine.submit([13, 17], max_new_tokens=30)
        deadline = time.monotonic() + 60
        while engine.stats()["pending"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(EngineOverloadedError) as e:
            engine.submit([2, 3], max_new_tokens=30)
        assert e.value.retry_after >= 1.0
        s = engine.stats()
        assert s["rejected_total"] == 1
        assert s["max_pending"] == 1
        # the accepted requests still complete correctly
        rest_a = [first] + _drain(qa)
        assert rest_a == _reference(params, [5, 7, 11], 30)
        assert _drain(qb) == _reference(params, [13, 17], 30)
    finally:
        engine.close()


def test_unbounded_engine_never_sheds(params):
    engine = ServingEngine(CFG, params, slots=1, max_len=64)  # max_pending=None
    try:
        queues = [engine.submit([i + 2, i + 3], max_new_tokens=3) for i in range(6)]
        for i, q in enumerate(queues):
            assert _drain(q) == _reference(params, [i + 2, i + 3], 3)
        assert engine.stats()["rejected_total"] == 0
    finally:
        engine.close()


def test_max_pending_zero_serves_but_never_queues(params):
    """Admission counts FREE SLOTS: max_pending=0 means 'no waiting', not
    'reject everything' — an idle engine must still serve up to `slots`
    concurrent requests."""
    from dstack_tpu.workloads.serving import EngineOverloadedError

    engine = ServingEngine(CFG, params, slots=2, max_len=64, max_pending=0)
    _slow_decode(engine, 0.25)  # hold both slots live across the asserts
    try:
        qa = engine.submit([5, 7, 11], max_new_tokens=20)
        qb = engine.submit([13, 17], max_new_tokens=20)
        # both admitted (2 free slots); once both are live, a third must shed
        assert isinstance(qa.get(timeout=60), int)
        assert isinstance(qb.get(timeout=60), int)
        deadline = time.monotonic() + 60
        while engine.stats()["active"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(EngineOverloadedError):
            engine.submit([2, 3], max_new_tokens=20)
        # after both retire, capacity is free again
        _drain(qa), _drain(qb)
        deadline = time.monotonic() + 60
        while engine.stats()["active"] and time.monotonic() < deadline:
            time.sleep(0.01)
        qc = engine.submit([2, 3], max_new_tokens=3)
        assert _drain(qc) == _reference(params, [2, 3], 3)
    finally:
        engine.close()


def test_per_request_temperature_in_one_batch(params):
    """A temperature=0 request must stay bit-identical to greedy decode
    even while sharing the batch with sampling requests (per-slot
    temperature, not an engine-wide mode)."""
    engine = ServingEngine(CFG, params, slots=4, max_len=64, temperature=0.8)
    try:
        # engine default (0.8): sampled
        q_hot = engine.submit([5, 7, 11], max_new_tokens=8)
        # explicit greedy override rides the same decode batch
        q_cold = engine.submit([5, 7, 11], max_new_tokens=8, temperature=0)
        hot = _drain(q_hot)
        cold = _drain(q_cold)
        assert cold == _reference(params, [5, 7, 11], 8)
        assert len(hot) == 8  # sampled stream still completes its budget
    finally:
        engine.close()


def test_submit_rejects_negative_temperature(params):
    engine = ServingEngine(CFG, params, slots=1, max_len=64)
    try:
        with pytest.raises(ValueError):
            engine.submit([1, 2], max_new_tokens=2, temperature=-0.5)
    finally:
        engine.close()


def test_top_p_near_zero_equals_greedy(params):
    """Nucleus sampling with top_p -> 0 keeps only the top token: even at
    a hot temperature the stream must equal greedy decode — a closed-form
    pin on the whole filter (sort, cumsum, scatter-back, strict <)."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64, temperature=1.0)
    try:
        q = engine.submit([5, 7, 11], max_new_tokens=8, top_p=1e-6)
        assert _drain(q) == _reference(params, [5, 7, 11], 8)
    finally:
        engine.close()


def test_submit_rejects_bad_top_p(params):
    engine = ServingEngine(CFG, params, slots=1, max_len=64)
    try:
        for bad in (0.0, -0.1, 1.5, float("nan")):
            with pytest.raises(ValueError):
                engine.submit([1, 2], max_new_tokens=2, top_p=bad)
    finally:
        engine.close()


def test_cancel_frees_the_slot(params):
    """cancel() retires an abandoned request at the next chunk boundary
    (client disconnects must not burn slot capacity for the rest of the
    budget): with ONE slot, a second request completes promptly after the
    first is cancelled mid-stream."""
    engine = ServingEngine(CFG, params, slots=1, max_len=64)
    _slow_decode(engine, 0.2)  # hold the slot so cancel is observable
    try:
        qa = engine.submit([5, 7, 11], max_new_tokens=40)
        assert isinstance(qa.get(timeout=60), int)  # A occupies the slot
        qb = engine.submit([13, 17], max_new_tokens=3)  # parks pending
        engine.cancel(qa)
        # A's consumer sees the clean end; B gets the slot and finishes.
        drained = _drain(qa)
        assert len(drained) < 39  # cancelled well before its budget
        assert _drain(qb) == _reference(params, [13, 17], 3)
        assert engine.stats()["active"] == 0
    finally:
        engine.close()


def test_cancel_pending_request(params):
    """Cancelling a request that never reached a slot ends its stream
    without occupying capacity."""
    engine = ServingEngine(CFG, params, slots=1, max_len=64)
    _slow_decode(engine, 0.2)
    try:
        qa = engine.submit([5, 7, 11], max_new_tokens=30)
        assert isinstance(qa.get(timeout=60), int)
        qb = engine.submit([13, 17], max_new_tokens=30)  # pending
        engine.cancel(qb)
        assert _drain(qb) == []  # ended with no tokens (first token never sampled)
        engine.cancel(qa)
        _drain(qa)
    finally:
        engine.close()


def test_nucleus_gate_ignores_retired_slots(params):
    """A completed top_p request must not leave the per-step nucleus
    filter armed for default traffic: retire keeps the old top_p in the
    PagedDecodeState row, so the gate (sampling._any_active_nucleus) may
    look only at ACTIVE slots."""
    from dstack_tpu.workloads.sampling import _any_active_nucleus

    engine = ServingEngine(CFG, params, slots=2, max_len=64)
    try:
        out = engine.submit([1, 2, 3], max_new_tokens=4,
                            temperature=0.8, top_p=0.5)
        _drain(out)
        state = engine.state
        # The regression state: no slot live, the stale 0.5 still in row 0.
        assert not bool(jnp.any(state.active))
        assert bool(jnp.any(state.top_p < 1.0))
        assert not bool(_any_active_nucleus(state)), (
            "stale top_p in a retired slot armed the nucleus branch"
        )
        # And a live nucleus slot must still arm it.
        armed = state._replace(
            active=state.active.at[0].set(True),
        )
        assert bool(_any_active_nucleus(armed))
        # Default traffic after the stale slot still matches greedy.
        out2 = engine.submit([1, 2, 3], max_new_tokens=4)
        assert _drain(out2) == _reference(params, [1, 2, 3], 4)[:4]
    finally:
        engine.close()


def test_one_token_completion_clears_cancel_race(params):
    """Every completion path must clear BOTH _inflight and _cancelled.

    Deterministic interleaving: _advance_prefills checks _cancelled at
    admission AND before each chunk dispatch, so blocking the chunk
    program and cancelling while blocked lands the cancel exactly in
    the window the leak needs — past both checks, before the reader
    thread's completion discards."""
    import threading

    engine = ServingEngine(CFG, params, slots=1, max_len=16)
    try:
        started, release = threading.Event(), threading.Event()
        real_chunk_fn = engine._chunk_fn

        def blocking_chunk_fn(n_padded):
            fn = real_chunk_fn(n_padded)

            def wrapped(*args):
                started.set()
                assert release.wait(30)
                return fn(*args)

            return wrapped

        engine._chunk_fn = blocking_chunk_fn
        out = engine.submit([1, 2], max_new_tokens=1)
        assert started.wait(30), "engine never admitted the request"
        engine.cancel(out)  # lands mid-admission: in _inflight, past the check
        release.set()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with engine._lock:
                if not engine._cancelled and not engine._inflight:
                    break
            time.sleep(0.02)
        with engine._lock:
            assert not engine._cancelled, "cancel-race leaked a queue entry"
            assert not engine._inflight
    finally:
        engine.close()


def test_greedy_top_p_does_not_arm_nucleus_branch(params):
    """{"temperature": 0, "top_p": 0.9} (a routine OpenAI-SDK combo) must
    not arm the per-step sort/cumsum: a greedy slot discards its sampled
    value, so only sampling slots may gate the filter."""
    from dstack_tpu.workloads.sampling import (
        _any_active_nucleus,
        _any_active_sampling,
    )

    engine = ServingEngine(CFG, params, slots=2, max_len=64)
    try:
        out = engine.submit([1, 2, 3], max_new_tokens=4,
                            temperature=0.0, top_p=0.9)
        toks = _drain(out)
        # Greedy output unchanged by the (unarmed) filter.
        assert toks == _reference(params, [1, 2, 3], 4)[:4]
        state = engine.state
        armed = state._replace(active=state.active.at[0].set(True))
        assert not bool(_any_active_nucleus(armed))
        assert not bool(_any_active_sampling(armed))
    finally:
        engine.close()


def test_cancelled_queued_requests_leave_the_backlog(params):
    """cancel() must purge a still-queued request immediately: dead
    entries counted in the admission backlog would shed new traffic
    below the real max_pending bound under cancel-heavy load."""
    engine = ServingEngine(CFG, params, slots=1, max_len=64, max_pending=2)
    try:
        hog = engine.submit([1, 2, 3], max_new_tokens=40)  # occupies the slot
        # Wait until the hog is IN the slot (not queued).
        deadline = time.monotonic() + 30
        while engine.stats()["active"] == 0:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        q1 = engine.submit([4, 5], max_new_tokens=4)
        q2 = engine.submit([6, 7], max_new_tokens=4)
        with pytest.raises(Exception):  # backlog full at max_pending=2
            engine.submit([8, 9], max_new_tokens=4)
        engine.cancel(q1)
        engine.cancel(q2)
        assert q1.get(timeout=5) is None  # purged = answered immediately
        assert q2.get(timeout=5) is None
        assert engine.stats()["pending"] == 0
        # The freed backlog admits new work right away.
        q3 = engine.submit([8, 9], max_new_tokens=4)
        engine.cancel(hog)
        assert len(_drain(q3)) == 4
    finally:
        engine.close()
