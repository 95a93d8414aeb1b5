"""Chunked-prefill scheduler: exactness, fairness, gauges.

tests/test_serving.py pins the engine's numerics and queue protocol; this
file pins the SCHEDULER — admission through budget-bounded prompt chunks
(`prefill_chunk_tokens`) dispatched ahead of each decode chunk, the
concurrent-prefill window capped by `max_prefills_per_chunk`, pow-2
chunk bucketing of the compile cache, and the TTFT/utilization gauges
the gateway and autoscaler read. Everything here runs on the tiny CPU
preset under `-m 'not slow'` so tier-1 catches scheduler regressions
without TPU hardware.
"""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.generate import generate
from dstack_tpu.utils.flight_recorder import (
    LOOP_CHILDREN,
    LOOP_PHASES,
    SLOW_CYCLE_SECONDS,
)
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


def _spy_chunks(engine, record):
    """Wrap engine._chunk_fn so `record(n_padded, engine)` runs at every
    chunk DISPATCH (the hook tests are told to patch)."""
    real = engine._chunk_fn

    def spying(n_padded):
        fn = real(n_padded)

        def wrapped(*args):
            record(n_padded, engine)
            return fn(*args)

        return wrapped

    engine._chunk_fn = spying


def test_admission_burst_token_exact_and_prefill_window_cap(params):
    """A 32-request greedy burst through the chunked scheduler yields
    outputs bit-identical to the sequential reference, while the
    concurrent-prefill window never exceeds `max_prefills_per_chunk`
    (the fairness knob: an admission burst must not starve decode
    cadence) and the window actually filled past one request (the point
    of admitting several prompts per boundary)."""
    engine = ServingEngine(CFG, params, slots=8, max_len=64,
                           max_prefills_per_chunk=3)
    window_sizes = []
    _spy_chunks(engine, lambda n, e: window_sizes.append(len(e._tasks)))
    try:
        base_prompts = [[5, 7, 11], [13, 17], [2, 3, 5, 7], [19, 23, 29]]
        refs = {tuple(p): _reference(params, p, 4) for p in base_prompts}
        prompts = [base_prompts[i % len(base_prompts)] for i in range(32)]
        queues = [engine.submit(p, max_new_tokens=4) for p in prompts]
        for p, q in zip(prompts, queues):
            assert _drain(q) == refs[tuple(p)], p
        assert window_sizes, "no prefill chunk ever dispatched"
        assert max(window_sizes) <= 3, (
            f"prefill window {max(window_sizes)} exceeded max_prefills_per_chunk"
        )
        assert max(window_sizes) > 1, (
            "a 32-request burst never filled the prefill window"
        )
        s = engine.stats()
        assert s["ttft_seconds_ewma"] > 0
        assert s["queue_wait_seconds_ewma"] > 0
        assert s["prefill_chunks_total"] >= 32
    finally:
        engine.close()


def test_chunked_prefill_splits_and_buckets(params):
    """A prompt longer than `prefill_chunk_tokens` is split across
    boundaries, each padded chunk drawn from the pow-2 bucket set (one
    compile per bucket, never per prompt length) — and the split output
    stays exact."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           prefill_chunk_tokens=16, kv_block_size=8)
    seen = []
    _spy_chunks(engine, lambda n, e: seen.append(n))
    try:
        short = [5, 7, 11]
        long = [(i * 29 + 3) % 50 + 1 for i in range(20)]
        q1 = engine.submit(short, max_new_tokens=4)
        q2 = engine.submit(long, max_new_tokens=4)
        assert _drain(q1) == _reference(params, short, 4)
        assert _drain(q2) == _reference(params, long, 4)
        assert set(seen) <= {8, 16}, seen  # pow-2 buckets capped at budget
        assert 16 in seen, "the 20-token prompt never used a full chunk"
        assert engine.stats()["prefill_chunks_total"] >= 3  # 1 + split-in-2
    finally:
        engine.close()


def test_stats_exposes_scheduler_gauges(params):
    """CI smoke (no TPU needed): the gauges the gateway /metrics and
    autoscaler consume exist and are coherent after one request — TTFT
    EWMA with its queue-wait/prefill breakdown, the decode/prefill/idle
    utilization split summing to ~1, the fairness knobs echoed, and the
    paged-KV pool counters."""
    engine = ServingEngine(CFG, params, slots=2, max_len=32,
                           max_prefills_per_chunk=2)
    try:
        q = engine.submit([5, 7, 11], max_new_tokens=4)
        assert len(_drain(q)) == 4
        s = engine.stats()
        for key in ("ttft_seconds_ewma", "queue_wait_seconds_ewma",
                    "prefill_seconds_ewma", "loop_cycles_total",
                    "loop_cycle_seconds_total", "decode_seconds_total",
                    "prefill_seconds_total", "idle_seconds_total",
                    "admitted_total", "ttft_seconds_sum",
                    "queue_wait_seconds_sum", "prefill_seconds_sum",
                    "kv_blocks_total", "kv_blocks_in_use",
                    "kv_blocks_cached", "prefix_cache_hits_total",
                    "prefix_cache_misses_total", "prefill_chunks_total",
                    "prefill_tokens_computed_total", "kv_block_size",
                    "prefill_chunk_tokens"):
            assert key in s, key
        assert s["max_prefills_per_chunk"] == 2
        assert s["admitted_total"] == 1
        assert s["ttft_seconds_sum"] >= s["prefill_seconds_sum"] > 0
        assert s["ttft_seconds_ewma"] > 0
        assert s["prefill_seconds_ewma"] > 0
        assert s["prefill_tokens_computed_total"] == 3
        assert s["prefill_chunks_total"] == 1
        # The loop accounts for all of its own time: the phases of its
        # cycles sum to the cycles, `wait` is everything else.
        in_cycles = sum(s[f"loop_{p}_seconds_total"]
                        for p in LOOP_PHASES if p != "wait")
        assert in_cycles == pytest.approx(
            s["loop_cycle_seconds_total"], abs=1e-9)
        assert s["loop_sync_seconds_total"] > 0  # at least one chunk ran
        assert not any(k.startswith("util_") for k in s)
    finally:
        engine.close()


def _settled_stats(engine):
    """stats() once the loop has gone back to waiting (the last cycle's
    counters are published when it ends, after the last token is out)."""
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        before = engine.stats()
        time.sleep(0.05)
        s = engine.stats()
        if (s["active"] == 0
                and s["loop_cycles_total"] == before["loop_cycles_total"]
                and s["loop_wait_seconds_total"]
                > before["loop_wait_seconds_total"]):
            return s
    raise AssertionError("the engine loop never went idle")


def test_loop_counters_account_for_the_loop(params):
    """The phase clock's counters on a live engine: phases sum to the
    cycles, children stay inside their parent, the step / slot-step /
    token counters are coherent with what consumers received, and the
    three older totals are the documented sums of phases."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           steps_per_sync=4)
    try:
        prompts = [[5, 7, 11], [3, 1, 4, 1, 5], [2, 7, 1, 8], [9, 9]]
        outs = [engine.submit(p, max_new_tokens=6) for p in prompts]
        delivered = sum(len(_drain(q)) for q in outs)
        assert delivered == 6 * len(prompts)
        s = _settled_stats(engine)
        phase = {p: s[f"loop_{p}_seconds_total"] for p in LOOP_PHASES}
        assert all(v >= 0 for v in phase.values())
        assert sum(phase.values()) - phase["wait"] == pytest.approx(
            s["loop_cycle_seconds_total"], abs=1e-9)
        children = {c: s[f"loop_{c.replace('/', '_')}_seconds_total"]
                    for c in LOOP_CHILDREN}
        assert all(v > 0 for v in children.values())
        # The shadow is the part of admit behind a decode launch and holds
        # the other children that ran there; those are disjoint.
        assert children["admit/shadow"] <= phase["admit"]
        assert sum(v for c, v in children.items() if c.startswith("admit/")) \
            - children["admit/shadow"] <= phase["admit"]
        # Delivery behind a launch is the part of fan_out the device does
        # not wait for; the settlements are the rest.
        assert children["fan_out/shadow"] < phase["fan_out"]
        # Every token but each request's first came out of a decode step.
        assert s["decode_tokens_total"] == delivered - len(prompts)
        assert s["decode_steps_total"] % 4 == 0
        assert (s["decode_tokens_total"] <= s["decode_slot_steps_total"]
                <= s["decode_steps_total"] * s["slots"])
        assert s["loop_cycles_total"] >= s["decode_steps_total"] // 4
        assert s["decode_seconds_total"] == pytest.approx(
            phase["dispatch"] + phase["sync"], abs=1e-5)
        assert s["idle_seconds_total"] == pytest.approx(
            phase["wait"], abs=1e-5)
        # admit + grow, plus the barrier of cycles with nothing live.
        lo = phase["admit"] + phase["grow"]
        assert lo - 1e-5 <= s["prefill_seconds_total"] <= (
            lo + phase["barrier"] + 1e-5)
        text = prometheus_metrics(s)
        assert 'dstack_tpu_serving_loop_phase_seconds_total{phase="sync"}' \
            in text
        assert f"dstack_tpu_serving_decode_tokens_total " \
            f"{s['decode_tokens_total']}" in text
    finally:
        engine.close()


def test_live_block_counters_match_the_hand_count(params):
    """decode_live_blocks_total / decode_table_columns_total: what the
    decode steps gave paged attention to walk over what the block table
    spans. With one step a launch every live slot emits one token a
    launch, so a request of P prompt tokens and N new ones is live at
    N - 1 launches with P, P + 1, ... P + N - 2 positions cached,
    however the two requests' steps interleave."""
    bs, max_len, slots = 8, 64, 3
    engine = ServingEngine(CFG, params, slots=slots, max_len=max_len,
                           kv_block_size=bs, steps_per_sync=1)
    try:
        asks = [(list(range(1, 12)), 14), (list(range(3, 33)), 9)]
        outs = [engine.submit(p, max_new_tokens=n) for p, n in asks]
        assert [len(_drain(q)) for q in outs] == [n for _, n in asks]
        s = _settled_stats(engine)
    finally:
        engine.close()
    by_hand = sum(
        -(-(len(p) + j) // bs) for p, n in asks for j in range(n - 1)
    )
    # 11..23 positions cached: 6 steps of 2 blocks, 7 of 3; 30..37: 3 of 4, 5 of 5.
    assert by_hand == 6 * 2 + 7 * 3 + 3 * 4 + 5 * 5
    assert s["decode_slot_steps_total"] == sum(n - 1 for _, n in asks)
    assert s["decode_steps_total"] < s["decode_slot_steps_total"]  # two at once
    assert s["decode_live_blocks_total"] == by_hand
    columns = s["decode_steps_total"] * slots * (max_len // bs)
    assert s["decode_table_columns_total"] == columns
    assert 0 < by_hand / columns < 1
    text = prometheus_metrics(s)
    assert f"dstack_tpu_serving_decode_live_blocks_total {by_hand}" in text
    assert f"dstack_tpu_serving_decode_table_columns_total {columns}" in text


def test_slow_cycle_is_kept_with_its_phases(params):
    """A cycle of SLOW_CYCLE_SECONDS or more is counted and kept whole:
    here the clock jumps two seconds inside a chunk launch."""
    engine = ServingEngine(CFG, params, slots=2, max_len=32)
    real_now = engine._clock._now
    jump = [0]
    engine._clock._now = lambda: real_now() + jump[0]

    def stall(n_padded, e):
        if not jump[0]:
            jump[0] = 2 * 10**9

    _spy_chunks(engine, stall)
    try:
        assert len(_drain(engine.submit([5, 7, 11], max_new_tokens=4))) == 4
        s = _settled_stats(engine)
        assert s["loop_slow_cycles_total"] == 1
        assert s["loop_slow_cycle_seconds_total"] >= SLOW_CYCLE_SECONDS
        (slow,) = s["loop_slow_cycles"]
        assert slow["seconds"] >= 2.0
        assert slow["phases"]["admit"] >= slow["phases"]["admit/chunk_launch"] \
            >= 2.0
        top = sum(v for k, v in slow["phases"].items() if "/" not in k)
        assert top == pytest.approx(slow["seconds"], abs=1e-9)
        assert {"t", "live", "tasks", "pending"} <= set(slow)
        assert slow["pending"] == 1  # the request that was about to stall
    finally:
        engine.close()


def test_engine_spans_land_in_a_profile(params, tmp_path):
    """Under jax.profiler the same marks are spans on the profiler's
    timeline: engine/cycle with its phases nested inside, chunk children
    carrying the request's id."""
    from jax.profiler import ProfileData

    engine = ServingEngine(CFG, params, slots=2, max_len=32)
    try:
        _drain(engine.submit([5, 7, 11], max_new_tokens=4))  # compile first
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            outs = [engine.submit([5, 7, 11 + i], max_new_tokens=4,
                                  request_id=700 + i) for i in range(3)]
            for q in outs:
                _drain(q)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.close()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            found = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats))
                     for e in line.events if e.name.startswith("engine/")]
            if found:
                assert plane.name == "/host:CPU"
                spans.append(found)
    (spans,) = spans  # every engine/ span is on one line: the loop thread's
    cycles = [e for e in spans if e[0] == "engine/cycle"]
    assert len(cycles) >= 2
    assert all({"n", "live", "tasks"} <= set(c[3]) for c in cycles)
    for name, lo, hi, _ in spans:
        if name in ("engine/cycle", "engine/wait"):
            continue
        assert any(c[1] <= lo and hi <= c[2] for c in cycles), name
    launches = [e for e in spans if e[0] == "engine/admit/chunk_launch"]
    assert {e[3]["request_id"] for e in launches} == {700, 701, 702}
    assert all(e[3]["tokens"] == 3 for e in launches)
    admits = [e for e in spans if e[0] == "engine/admit"]
    for _, lo, hi, _ in launches:
        assert any(a[1] <= lo and hi <= a[2] for a in admits)
    assert {"engine/sync", "engine/fan_out", "engine/barrier",
            "engine/admit/match", "engine/admit/chunk_args",
            "engine/fan_out/shadow"} <= {e[0] for e in spans}
    # A delivery behind a launch lies between that launch and its sync.
    syncs = sorted(e[1] for e in spans if e[0] == "engine/sync")
    launches = sorted(e[2] for e in spans if e[0] == "engine/dispatch")
    for _, lo, hi, _ in (e for e in spans if e[0] == "engine/fan_out/shadow"):
        launched = max(t for t in launches if t <= lo)
        assert min(t for t in syncs if t >= launched) >= hi


class _Gate:
    def __init__(self, pred, skip):
        self.pred, self.skip = pred, skip
        self.reached, self.go = threading.Event(), threading.Event()

    def release(self):
        self.go.set()


class _Timeline:
    """What the loop thread did, in order: ("mark", phase) at every phase
    mark, ("decode",) at every decode (or speculative verify) launch,
    ("chunk", slot, tokens, pos, in_shadow) at every prefill-chunk launch,
    ("barrier", slots whose first token is waited for) at every
    first-token barrier, ("settle", slots of the chunk, live slots) before
    and ("settled", live slots, tasks) after every settlement,
    ("deliver", slots whose tokens go out, {slot: tokens queued so far},
    behind a launch) before and ("delivered",) after every delivery.
    `gate(kind)` parks the loop at the next such event (the event is
    recorded, what it announces has not happened yet) until released."""

    def __init__(self, engine):
        self.engine = engine
        self.events = []
        self._gates = []
        clock = engine._clock
        real_mark, real_begin = clock.mark, clock.begin

        def mark(phase):
            self._note(("mark", phase))
            return real_mark(phase)

        def begin(phase, **kw):
            self._note(("mark", phase))
            return real_begin(phase, **kw)

        clock.mark, clock.begin = mark, begin
        for name in ("_step_base", "_step"):
            setattr(engine, name, self._launch(getattr(engine, name)))
        real_verify = engine._spec_verify_fn
        engine._spec_verify_fn = lambda k, lora=False: self._launch(
            real_verify(k, lora=lora))
        real_chunk = engine._chunk_fn

        def chunk_fn(n_padded, lora=False):
            fn = real_chunk(n_padded, lora=True) if lora else real_chunk(n_padded)

            def wrapped(*args):
                # (params, state, slot, table, tokens, n, pos, ...)
                self._note(("chunk", int(args[2]), int(args[5]),
                            int(args[6]), engine._chunk is not None))
                return fn(*args)

            return wrapped

        engine._chunk_fn = chunk_fn
        real_settle = engine._settle

        def settle(chunk):
            self._note(("settle", self._slots(chunk.live),
                        self._slots(engine._live)))
            real_settle(chunk)
            self._note(("settled", self._slots(engine._live),
                        len(engine._tasks)))

        engine._settle = settle
        real_hand_out = engine._hand_out

        def hand_out(chunk):
            self._note(("deliver", self._slots(chunk.live),
                        {slot: req.out.qsize()
                         for slot, req in enumerate(chunk.live)
                         if req is not None},
                        engine._chunk is not None))
            real_hand_out(chunk)
            self._note(("delivered",))

        engine._hand_out = hand_out
        real_wait = engine._wait_activations

        def wait_activations(tasks):
            self._note(("barrier", [t.slot for t in tasks]))
            real_wait(tasks)

        engine._wait_activations = wait_activations

    @staticmethod
    def _slots(reqs):
        return [slot for slot, req in enumerate(reqs) if req is not None]

    def _launch(self, fn):
        def launch(*args):
            self._note(("decode",))
            return fn(*args)

        return launch

    def _note(self, event):
        self.events.append(event)
        for gate in self._gates:
            if gate.pred(event):
                if gate.skip:
                    gate.skip -= 1
                    continue
                self._gates.remove(gate)
                gate.reached.set()
                assert gate.go.wait(60), "the test never released the loop"
                return

    def gate(self, pred, skip=0):
        if isinstance(pred, str):
            kind = pred

            def pred(event):
                return event[0] == kind

        gate = _Gate(pred, skip)
        self._gates.append(gate)
        return gate

    def kinds(self, *kinds):
        return [e for e in self.events if e[0] in kinds]


def _slot_of(engine, out):
    for slot, req in enumerate(engine._live):
        if req is not None and req.out is out:
            return slot
    for task in engine._tasks:
        if task.req.out is out:
            return task.slot
    raise AssertionError("the request holds no slot")


def _in_shadow_of_first_decode(engine, tl, submit_b):
    """Park the loop at its first decode launch (so request A is live and
    chunk N is about to go), submit B there, let go, and return the
    events from that launch to the settlement of chunk N+1, behind whose
    launch N's tokens go out."""
    gate = tl.gate("decode")
    assert gate.reached.wait(60), "no decode chunk was ever launched"
    start = len(tl.events) - 1
    qb = submit_b()
    settled = tl.gate("settled")
    gate.release()
    assert settled.reached.wait(60)
    slot_b = _slot_of(engine, qb)
    done = tl.gate("settled")
    settled.release()
    assert done.reached.wait(60)
    events = list(tl.events[start:])
    done.release()
    return qb, slot_b, events


def _check_pipelined_order(events, slot, n, first=True):
    """From the launch of N (`first`: the engine's first chunk, with no
    chunk before it to deliver): a chunk of `n` tokens for `slot` in N's
    shadow -> tables and key ahead -> sync N -> settle N -> the boundary's
    admit -> grow -> dispatch N+1 -> N's first-token barrier and N's tokens
    out, behind that launch -> shadow admit -> sync N+1 -> settle N+1.
    Returns the events (settle N, settled N, barrier N, deliver N,
    settle N+1)."""
    deliver = [("mark", "barrier"), ("barrier",), ("mark", "fan_out"),
               ("deliver",), ("delivered",)]
    order = [e if e[0] in ("mark", "chunk") else e[:1] for e in events]
    assert order == [
        ("decode",), *([] if first else deliver),
        ("mark", "admit"), ("chunk", slot, n, 0, True),
        ("mark", "grow"), ("mark", "sync"), ("mark", "fan_out"),
        ("settle",), ("settled",),
        ("mark", "admit"), ("mark", "grow"), ("mark", "dispatch"),
        ("decode",), *deliver,
        ("mark", "admit"), ("mark", "grow"), ("mark", "sync"),
        ("mark", "fan_out"), ("settle",), ("settled",),
    ], events
    settle_n, settle_n1 = [e for e in events if e[0] == "settle"]
    settled_n, _ = [e for e in events if e[0] == "settled"]
    barrier = [e for e in events if e[0] == "barrier"][-1]
    delivers = [e for e in events if e[0] == "deliver"]
    assert all(e[3] for e in delivers), "tokens went out, nothing launched"
    return settle_n, settled_n, barrier, delivers[-1], settle_n1


def _check_shadow_cycle(events, slot_b, n_b):
    """... and B's slot (free when N was launched, live from N's shadow
    on) is neither settled nor delivered with N, nor is its first token
    waited for by N's barrier: it is N+1's."""
    settle_n, settled_n, barrier, deliver, settle_n1 = \
        _check_pipelined_order(events, slot_b, n_b)
    assert slot_b not in settle_n[1] and slot_b in settle_n[2]
    assert slot_b in settled_n[1]                      # and not retired by it
    assert slot_b not in barrier[1] and slot_b not in deliver[1]
    assert slot_b in settle_n1[1]


def test_chunk_launches_in_the_decode_chunks_shadow(params):
    """A request waiting when decode chunk N is dispatched gets its chunk
    between N's launch and N's `device_get`; the slot that chunk flips
    live is neither settled nor delivered with N, and decodes from N+1 on
    with its first token already delivered. N's tokens go out behind the
    launch of N+1."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           steps_per_sync=2)
    tl = _Timeline(engine)
    try:
        a, b = [5, 7, 11], [13, 17, 19, 23]
        qa = engine.submit(a, max_new_tokens=12)
        qb, slot_b, events = _in_shadow_of_first_decode(
            engine, tl, lambda: engine.submit(b, max_new_tokens=5))
        _check_shadow_cycle(events, slot_b, len(b))
        assert _drain(qa) == _reference(params, a, 12)
        assert _drain(qb) == _reference(params, b, 5)
        # The first delivery B's slot is part of finds exactly its first
        # token queued: the barrier of N+1 waited for it, N's did not.
        first = next(e for e in tl.kinds("deliver") if slot_b in e[1])
        assert first[2][slot_b] == 1
        waited = [e[1] for e in tl.kinds("barrier") if e[1]]
        assert waited[-1] == [slot_b]  # by the barrier of N+1
        # Every chunk but the last was delivered behind a launch; the last
        # left nothing live, and went out at its settlement.
        behind = [e[3] for e in tl.kinds("deliver")]
        assert behind == [True] * (len(behind) - 1) + [False]
        s = _settled_stats(engine)
        assert 0 < s["loop_admit_shadow_seconds_total"] \
            <= s["loop_admit_seconds_total"]
        assert 0 < s["loop_fan_out_shadow_seconds_total"] \
            < s["loop_fan_out_seconds_total"]
        assert s["decode_tokens_total"] == 12 + 5 - 2
    finally:
        engine.close()


def test_arrival_behind_an_unspent_shadow_is_admitted_at_the_boundary(params):
    """The cycle's budget is spent at two points. A request that arrives
    after the shadow ran (here: as chunk N is settled) with the budget
    unspent gets its chunk at the boundary, before decode N+1 is
    dispatched, and decodes in N+1: it waits no longer than it did when
    all admission sat there."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           steps_per_sync=2)
    tl = _Timeline(engine)
    try:
        a, c = [5, 7, 11], [2, 3, 5, 7, 13]
        gate = tl.gate("settle")
        qa = engine.submit(a, max_new_tokens=12)
        assert gate.reached.wait(60)
        start = len(tl.events)
        qc = engine.submit(c, max_new_tokens=4)
        done = tl.gate("settle")
        gate.release()
        assert done.reached.wait(60)
        events = list(tl.events[start:])
        slot_c = _slot_of(engine, qc)
        done.release()
        order = [e for e in events if e[0] in ("decode", "chunk", "mark")]
        assert order[:5] == [
            ("mark", "admit"), ("chunk", slot_c, len(c), 0, False),
            ("mark", "grow"), ("mark", "dispatch"), ("decode",),
        ], events
        assert slot_c in events[-1][1]  # in the chunk launched right after
        assert not [e for e in order[5:] if e[0] == "chunk"]  # nothing left
        assert _drain(qa) == _reference(params, a, 12)
        assert _drain(qc) == _reference(params, c, 4)
    finally:
        engine.close()


def test_one_prefill_budget_a_cycle_spent_at_two_points(params):
    """prefill_chunk_tokens bounds what rides between two decode launches,
    shadow and boundary together: a 20-token prompt rides 8 + 8 + 4 in
    three shadows and the boundaries between launch nothing; a request
    arriving behind the 4 gets the 4 that are left at the boundary and
    its last 2 in the next shadow."""
    engine = ServingEngine(CFG, params, slots=3, max_len=64,
                           steps_per_sync=2, prefill_chunk_tokens=8,
                           kv_block_size=8, prefix_cache=False)
    tl = _Timeline(engine)
    try:
        a = [5, 7, 11]
        b = [(i * 29 + 3) % 50 + 1 for i in range(20)]
        c = [(i * 31 + 7) % 50 + 1 for i in range(6)]
        first_decode = tl.gate("decode")
        third_settle = tl.gate("settle", skip=2)
        qa = engine.submit(a, max_new_tokens=30)
        assert first_decode.reached.wait(60)
        qb = engine.submit(b, max_new_tokens=4)
        first_decode.release()
        assert third_settle.reached.wait(60)
        slot_b = _slot_of(engine, qb)
        qc = engine.submit(c, max_new_tokens=4)
        third_settle.release()
        assert _drain(qb) == _reference(params, b, 4)
        assert _drain(qc) == _reference(params, c, 4)
        assert _drain(qa) == _reference(params, a, 30)
        chunks = tl.kinds("chunk")[1:]  # but A's own, before anything decoded
        slot_c = chunks[-1][1]
        assert chunks == [
            ("chunk", slot_b, 8, 0, True), ("chunk", slot_b, 8, 8, True),
            ("chunk", slot_b, 4, 16, True), ("chunk", slot_c, 4, 0, False),
            ("chunk", slot_c, 2, 4, True),
        ]
        spent, windows = 0, []
        for event in tl.kinds("decode", "chunk")[1:]:
            if event[0] == "decode":
                windows.append(spent)
                spent = 0
            else:
                spent += event[2]
        assert max(windows) <= 8 and windows[1:5] == [8, 8, 8, 2], windows
    finally:
        engine.close()


@pytest.mark.parametrize("cancel_old", [False, True],
                         ids=["ends", "cancelled_as_it_ends"])
def test_shadow_admits_into_a_slot_sure_to_end_in_the_chunk(params, cancel_old):
    """Every slot taken, one of them within a chunk of its budget's end:
    the waiting request is admitted into THAT slot in the chunk's shadow
    (its chunk runs on the device behind the chunk that ends the slot).
    The chunk's settlement releases the old request's blocks and makes the
    slot the new request's on the host, BEFORE the old request's last
    tokens and clean end go out behind the next launch, in which the heir
    already decodes. A slot with budget to spare is never taken."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           steps_per_sync=2, prefix_cache=False)
    tl = _Timeline(engine)
    try:
        a, b, c = [5, 7, 11], [13, 17, 19, 23], [2, 3, 5, 7, 13]
        qa = engine.submit(a, max_new_tokens=5)    # first token + 2 chunks
        qb = engine.submit(b, max_new_tokens=30)
        # Park at the launch of the chunk that ends A: the second in which
        # both decode (B may have gone live a chunk after A).
        last = tl.gate(lambda e: e[0] == "decode" and any(
            s in engine._chunk.ending and r.out is qa
            for s, r in enumerate(engine._live) if r is not None))
        assert last.reached.wait(60), "A never came within a chunk of its end"
        slot_a = _slot_of(engine, qa)
        assert engine._chunk.ending == {slot_a}
        start = len(tl.events) - 1
        qc = engine.submit(c, max_new_tokens=6)
        if cancel_old:
            engine.cancel(qa)
        settled = tl.gate("settled")
        last.release()
        assert settled.reached.wait(60)
        with engine._lock:
            assert engine._live[slot_a].out is qc   # the heir took over
            assert not engine._admitting and not engine._settled.heirs
        # A's blocks went back with the settlement (the table is C's), and
        # its stream holds what earlier chunks delivered: the first token
        # and one chunk (and the clean end at once if nobody reads).
        assert qa.qsize() == 3 + cancel_old
        done = tl.gate("settled")
        settled.release()
        assert done.reached.wait(60)
        events = list(tl.events[start:])
        done.release()
        # C's chunk, into A's slot, behind the chunk that ends A; A's last
        # tokens go out behind the launch of the chunk C first decodes in.
        settle_n, settled_n, _, deliver, settle_n1 = _check_pipelined_order(
            events, slot_a, len(c), first=False)
        assert settle_n[1] == settle_n[2] == settled_n[1] == [0, 1]
        assert settle_n1[1] == [0, 1]
        if cancel_old:
            assert deliver[1] == [1 - slot_a]        # nothing of A's
            assert len(_drain(qa)) < 5               # its last chunk skipped
        else:
            assert deliver[1] == [0, 1] and deliver[2][slot_a] == 3
            assert _drain(qa) == _reference(params, a, 5)
        assert _drain(qc) == _reference(params, c, 6)
        assert _drain(qb) == _reference(params, b, 30)
        s = _settled_stats(engine)
        assert s["kv_blocks_in_use"] == 0            # A's blocks and C's
        with engine._lock:
            assert not engine._cancelled and not engine._inflight
    finally:
        engine.close()


def test_cancelled_while_a_chunk_runs_is_settled_without_delivery(params):
    """A request cancelled while chunk N runs, with budget to spare: N's
    settlement frees its slot and blocks and ends its stream; the delivery
    behind the next launch has nothing of it."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           steps_per_sync=2, prefix_cache=False)
    tl = _Timeline(engine)
    try:
        a, b = [5, 7, 11], [13, 17, 19, 23]
        both = tl.gate(lambda e: e[0] == "decode" and all(
            r is not None for r in engine._live))
        qa = engine.submit(a, max_new_tokens=30)
        qb = engine.submit(b, max_new_tokens=30)
        assert both.reached.wait(60)
        slot_a = _slot_of(engine, qa)
        engine.cancel(qa)
        settled = tl.gate("settled")
        both.release()
        assert settled.reached.wait(60)
        with engine._lock:
            assert engine._live[slot_a] is None
            assert engine._slot_tables[slot_a] is None
            assert qa not in engine._inflight | engine._cancelled
        queued = list(qa.queue)
        assert queued[-1] is None and None not in queued[:-1]
        delivered = tl.gate("delivered")
        settled.release()
        assert delivered.reached.wait(60)
        deliver = tl.kinds("deliver")[-1]
        assert deliver[1] == [1 - slot_a] and deliver[3]
        assert list(qa.queue) == queued              # nothing more of A's
        delivered.release()
        assert len(_drain(qa)) < 30
        assert _drain(qb) == _reference(params, b, 30)
        assert _settled_stats(engine)["kv_blocks_in_use"] == 0
    finally:
        engine.close()


def test_mixed_batch_greedy_streams_equal_the_reference(params):
    """Shared prefixes, a request finishing while others prefill, a cancel
    in mid-prefill, admission in shadows and at boundaries: every greedy
    stream is `forward`'s greedy continuation, token for token, and
    nothing leaks."""
    engine = ServingEngine(CFG, params, slots=4, max_len=96,
                           steps_per_sync=2, prefill_chunk_tokens=8,
                           kv_block_size=8)
    tl = _Timeline(engine)
    try:
        head = [(i * 17 + 5) % 90 + 1 for i in range(16)]
        r1, r2, r5 = head + [3, 1, 4], head + [1, 5, 9, 2], head + [6, 5]
        r3 = [(i * 29 + 3) % 90 + 1 for i in range(29)]
        rx = [(i * 13 + 11) % 90 + 1 for i in range(40)]
        first_decode = tl.gate("decode")
        q1 = engine.submit(r1, max_new_tokens=14)
        assert first_decode.reached.wait(60)
        q2 = engine.submit(r2, max_new_tokens=3)
        q3 = engine.submit(r3, max_new_tokens=4)
        qx = engine.submit(rx, max_new_tokens=6)
        mid_prefill = tl.gate(lambda e: e[0] == "chunk" and e[3] > 0 and any(
            t.slot == e[1] and t.req.out is qx for t in engine._tasks))
        first_decode.release()
        assert mid_prefill.reached.wait(60)
        engine.cancel(qx)
        mid_prefill.release()
        assert _drain(q2) == _reference(params, r2, 3)
        q5 = engine.submit(r5, max_new_tokens=5)
        assert _drain(q1) == _reference(params, r1, 14)
        assert _drain(q3) == _reference(params, r3, 4)
        assert _drain(q5) == _reference(params, r5, 5)
        assert _drain(qx) == []  # cancelled before its first token
        # r2 and r5 skipped the head's two blocks; r2 retired while r3 and
        # the cancelled prompt were still prefilling.
        s = _settled_stats(engine)
        assert s["prefix_tokens_reused_total"] >= 2 * 16
        shrank = [(before, after) for before, after in zip(
            tl.kinds("settle"), tl.kinds("settled"))
            if len(after[1]) < len(before[2]) and after[2] > 0]
        assert shrank, "no request finished while another was prefilling"
        assert any(e[4] for e in tl.kinds("chunk"))      # some in a shadow
        with engine._lock:
            assert not engine._cancelled and not engine._inflight
            assert not engine._admitting
        assert s["kv_blocks_in_use"] == s["kv_blocks_cached"]
    finally:
        engine.close()


def _spec_engine(params):
    return ServingEngine(CFG, params, slots=2, max_len=64,
                         prefill_chunk_tokens=16, kv_block_size=8,
                         spec_enable=True, spec_max_draft=3,
                         spec_draft_params=params, spec_draft_config=CFG)


def _lora_engine(params):
    from dstack_tpu.workloads.lora_serving import demo_adapter

    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           steps_per_sync=2, prefill_chunk_tokens=16,
                           kv_block_size=8, lora_max_adapters=1, lora_rank=4,
                           lora_targets=("wq", "wv"))
    engine.load_adapter("t1", demo_adapter(
        CFG, params, jax.random.PRNGKey(11), rank=4, targets=("wq", "wv")))
    return engine


def _plain_engine(params):
    return ServingEngine(CFG, params, slots=2, max_len=64, steps_per_sync=2,
                         prefill_chunk_tokens=16, kv_block_size=8)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("build, a_kw", [
    (_plain_engine, {}), (_spec_engine, {}), (_lora_engine, {"adapter": "t1"}),
], ids=["decode_chunks", "speculative", "lora"])
def test_streams_are_what_they_were_before_keys_were_split_ahead(
        params, build, a_kw, temperature):
    """One key of the chain a launch, in launch order, whatever the kind of
    launch: splitting the next launch's key in the last one's shadow draws
    the streams that splitting at the launch drew (the order before this
    loop delivered behind the launch; the same scenario on that tree gave
    these streams token for token). A is live and B arrives at the first
    decode launch, so B's chunk rides in a shadow, between two decode keys."""
    streams = []
    for ahead in (True, False):
        engine = build(params)
        if not ahead:
            engine._split_ahead = lambda: None   # every key at its launch
        tl = _Timeline(engine)
        try:
            first = tl.gate("decode")
            qa = engine.submit([5, 7, 11], max_new_tokens=12,
                               temperature=temperature, **a_kw)
            assert first.reached.wait(60)
            qb = engine.submit([13, 17, 19, 23], max_new_tokens=7,
                               temperature=temperature)
            first.release()
            streams.append((_drain(qa), _drain(qb)))
            assert (engine._key_ahead is not None) == ahead
        finally:
            engine.close()
    assert streams[0] == streams[1]
    out_a, out_b = streams[0]
    assert len(out_a) == 12 and len(out_b) == 7
    if temperature == 0.0:
        assert out_b == _reference(params, [13, 17, 19, 23], 7)
        if not a_kw:
            assert out_a == _reference(params, [5, 7, 11], 12)
    else:
        assert out_b != _reference(params, [13, 17, 19, 23], 7)


@pytest.mark.parametrize("build, a_kw", [
    (_spec_engine, {}), (_lora_engine, {"adapter": "t1"}),
], ids=["speculative", "lora"])
def test_every_engine_takes_the_same_order(params, build, a_kw):
    """One loop: behind a speculation round's verify launch, and behind a
    decode chunk that carries a LoRA bank, admission runs in the shadow
    like anywhere else; the slot it flips live is the next round's, and
    the adapter-free stream is the plain engine's."""
    engine = build(params)
    tl = _Timeline(engine)
    try:
        a, b = [5, 7, 11], [13, 17, 19, 23]
        qa = engine.submit(a, max_new_tokens=12, **a_kw)
        qb, slot_b, events = _in_shadow_of_first_decode(
            engine, tl, lambda: engine.submit(b, max_new_tokens=5))
        _check_shadow_cycle(events, slot_b, len(b))
        out_a = _drain(qa)
        assert len(out_a) == 12
        if not a_kw:
            assert out_a == _reference(params, a, 12)
        assert _drain(qb) == _reference(params, b, 5)
        s = _settled_stats(engine)
        # Every round proposed k drafts for each slot IT was launched
        # with, never for one that went live behind it.
        assert (s["spec_tokens_accepted_total"]
                + s["spec_tokens_rejected_total"]
                == s["spec_tokens_proposed_total"])
        assert s["decode_tokens_total"] == 12 + 5 - 2
    finally:
        engine.close()


def test_cancel_during_prefill_overlap_leaves_no_leak(params):
    """cancel() landing while a request's prefill chunk is in flight
    (popped from pending, not yet live) must end the stream cleanly,
    never activate the slot, return every KV block to the pool, and
    leave no entry behind in _inflight/_cancelled. prefix_cache=False so
    "returned" means literally zero blocks in use (with the cache on,
    the computed prefix is deliberately kept cached, not leaked)."""
    engine = ServingEngine(CFG, params, slots=2, max_len=64,
                           prefix_cache=False)
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
        out = engine.submit([1, 2, 3], max_new_tokens=5)
        assert started.wait(30), "engine never started the prefill"
        engine.cancel(out)  # lands mid-chunk: in _inflight, past the pop
        release.set()
        assert out.get(timeout=30) is None  # ended with zero tokens
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with engine._lock:
                if not engine._cancelled and not engine._inflight:
                    break
            time.sleep(0.02)
        with engine._lock:
            assert not engine._cancelled, "overlap cancel leaked an entry"
            assert not engine._inflight
            assert not engine._admitting
        assert engine.stats()["active"] == 0
        assert engine.stats()["kv_blocks_in_use"] == 0, (
            "cancelled mid-prefill request leaked pool blocks"
        )
        # The slot the cancelled request reserved is free for new work.
        engine._chunk_fn = real_chunk_fn
        q = engine.submit([5, 7, 11], max_new_tokens=3)
        assert _drain(q) == _reference(params, [5, 7, 11], 3)
    finally:
        engine.close()


def test_idle_resubmit_after_completion_is_not_shed(params):
    """Satellite regression (the stale-`free` race): with max_pending=0
    ("serve, never queue"), a client that sees its stream complete and
    immediately resubmits must be admitted — the loop frees the slot
    under the submit lock BEFORE delivering the clean end, so the
    admission snapshot can never show a phantom-occupied idle engine."""
    engine = ServingEngine(CFG, params, slots=1, max_len=32, max_pending=0)
    try:
        for i in range(5):  # each iteration: complete, then resubmit at once
            q = engine.submit([i + 2, i + 3], max_new_tokens=2)
            assert len(_drain(q)) == 2  # None received -> slot already free
    finally:
        engine.close()


def test_scheduler_knob_validation(params):
    with pytest.raises(ValueError):
        ServingEngine(CFG, params, slots=1, max_len=32,
                      max_prefills_per_chunk=0)
    with pytest.raises(ValueError):
        ServingEngine(CFG, params, slots=1, max_len=32,
                      prefill_chunk_tokens=0)
    with pytest.raises(ValueError):
        ServingEngine(CFG, params, slots=1, max_len=32, kv_block_size=0)
    with pytest.raises(ValueError, match="divide"):
        ServingEngine(CFG, params, slots=1, max_len=32, kv_block_size=12)
    with pytest.raises(ValueError, match="kv_pool_blocks"):
        ServingEngine(CFG, params, slots=1, max_len=32, kv_block_size=8,
                      kv_pool_blocks=2)
