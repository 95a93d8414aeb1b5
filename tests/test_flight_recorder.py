"""Flight recorder invariants under a frozen clock.

The recorder's contract is structural, so every test drives it with a
hand-stepped fake clock: phase durations must telescope exactly to the
total, the ring must overwrite oldest-first at capacity (index evicted
with the slot), a disabled recorder must retain nothing, and the
tail-capture threshold must be inclusive at the boundary.
"""

import pytest

from dstack_tpu.utils.flight_recorder import (
    LOOP_CHILDREN,
    LOOP_PHASES,
    PHASES,
    FlightRecorder,
    PhaseClock,
    RequestTrace,
    TailStore,
)

TP = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_phase_durations_telescope_to_total():
    clock = Clock()
    rec = FlightRecorder(capacity=4, clock=clock)
    tr = rec.begin(1, traceparent=TP, first_phase="queue_wait", t0=0.0)
    clock.t = 0.125
    tr.mark("prefill")
    clock.t = 0.5
    tr.mark("decode")
    clock.t = 1.75
    rec.finish(tr, "ok")
    d = tr.to_dict()
    assert d["status"] == "ok"
    assert d["total_seconds"] == 1.75
    assert [p["phase"] for p in d["phases"]] == [
        "queue_wait", "prefill", "decode",
    ]
    assert sum(p["duration_s"] for p in d["phases"]) == d["total_seconds"]
    # Offsets are starts relative to t0, consistent with durations.
    assert [p["start_s"] for p in d["phases"]] == [0.0, 0.125, 0.5]


def test_every_phase_name_is_canonical():
    # Engine mark sites use literals; pin them to the shared vocabulary.
    for phase in ("qos_admission", "adapter_acquire", "queue_wait",
                  "prefill", "kv_ship", "kv_adopt", "decode"):
        assert phase in PHASES


def test_ring_overwrites_oldest_and_evicts_index():
    clock = Clock()
    rec = FlightRecorder(capacity=2, clock=clock)
    t1 = rec.begin("a", t0=0.0)
    t2 = rec.begin("b", t0=0.0)
    rec.finish(t1, "ok")
    rec.finish(t2, "ok")
    assert rec.get("a") is not None and rec.get("b") is not None
    # Third begin recycles the oldest slot ("a"): its trace is gone.
    t3 = rec.begin("c", t0=1.0)
    assert rec.get("a") is None
    assert rec.get("b") is not None
    assert rec.get("c")["status"] == "in_flight"
    rec.finish(t3, "ok")
    assert rec.stats()["recycled_total"] == 1


def test_recycled_slot_state_resets():
    clock = Clock()
    rec = FlightRecorder(capacity=1, clock=clock)
    t1 = rec.begin("a", t0=0.0)
    t1.decode_steps = 7
    t1.mark("decode", 0.5)
    rec.finish(t1, "ok", t_end=1.0)
    t2 = rec.begin("b", t0=2.0)
    assert t2 is t1  # same preallocated slot, recycled
    assert t2.decode_steps == 0
    assert t2.status is None and t2.t_end is None
    assert len(t2.marks) == 1


def test_disabled_recorder_retains_nothing():
    rec = FlightRecorder(capacity=0, slow_ms=0.0)
    assert not rec.enabled
    assert rec.begin("a", t0=0.0) is None
    rec.finish(None, "ok")  # no-op, no crash
    rec.record_dropped("b")
    assert rec.get("a") is None and rec.get("b") is None
    assert rec.stats()["started_total"] == 0
    assert rec.phase_histograms() == {}


def test_finish_is_idempotent_first_terminal_wins():
    clock = Clock()
    rec = FlightRecorder(capacity=2, clock=clock)
    tr = rec.begin(1, t0=0.0)
    clock.t = 1.0
    rec.finish(tr, "cancelled")
    clock.t = 2.0
    rec.finish(tr, "ok")  # late racing path: ignored
    assert tr.status == "cancelled"
    assert tr.t_end == 1.0
    assert rec.stats()["finished_total"] == 1


def test_tail_threshold_is_inclusive_at_boundary():
    store = TailStore(slow_ms=100.0)
    assert store.should_capture(0.100, "ok") is True  # exactly at: slow
    assert store.should_capture(0.0999, "ok") is False
    assert store.should_capture(0.0, "error") is True
    assert store.should_capture(0.0, "shed") is True
    assert store.should_capture(0.0, "cancelled") is False
    # slow_ms=None disables capture entirely, even for errors.
    off = TailStore(slow_ms=None)
    assert not off.enabled
    assert off.should_capture(10.0, "error") is False


def test_tail_capture_outlives_ring_recycling():
    clock = Clock()
    rec = FlightRecorder(capacity=1, slow_ms=50.0, clock=clock)
    tr = rec.begin("slow-1", x_request_id="xrid-1", traceparent=TP, t0=0.0)
    clock.t = 0.2  # 200ms: above the 50ms threshold
    rec.finish(tr, "ok")
    rec.begin("next", t0=1.0)  # recycles slow-1's ring slot
    snap = rec.get("slow-1")
    assert snap is not None, "tail store should keep the slow trace"
    assert snap["total_seconds"] == 0.2
    assert rec.get("xrid-1") == snap  # x-request-id lookup hits too
    assert rec.stats()["tail_captured_total"] == 1


def test_tail_store_is_bounded_overwrite_oldest():
    clock = Clock()
    rec = FlightRecorder(capacity=8, slow_ms=0.0, tail_capacity=2,
                         clock=clock)
    for i in range(4):
        tr = rec.begin(f"r{i}", t0=float(i))
        clock.t = i + 1.0
        rec.finish(tr, "ok")
    snaps = rec.tail.snapshots()
    assert len(snaps) == 2
    assert {s["request_id"] for s in snaps} == {"r2", "r3"}


def test_record_dropped_is_terminal_and_captured():
    clock = Clock()
    rec = FlightRecorder(capacity=4, slow_ms=1000.0, clock=clock)
    rec.record_dropped("shed-1", traceparent=TP)
    d = rec.get("shed-1")
    assert d["status"] == "shed"
    assert [p["phase"] for p in d["phases"]] == ["qos_admission"]
    assert rec.stats()["tail_captured_total"] == 1  # shed => captured


def test_phase_histograms_feed_per_phase():
    clock = Clock()
    rec = FlightRecorder(capacity=4, clock=clock)
    tr = rec.begin(1, t0=0.0)
    clock.t = 0.01
    tr.mark("prefill")
    clock.t = 0.03
    rec.finish(tr, "ok")
    hists = rec.phase_histograms()
    assert set(hists) == {"queue_wait", "prefill"}
    assert hists["queue_wait"]["count"] == 1
    assert abs(hists["queue_wait"]["sum"] - 0.01) < 1e-12
    assert abs(hists["prefill"]["sum"] - 0.02) < 1e-12


def test_trace_id_parsed_from_traceparent():
    rec = FlightRecorder(capacity=2)
    tr = rec.begin(1, traceparent=TP, t0=0.0)
    assert tr.trace_id == "ab" * 16
    bad = rec.begin(2, traceparent="garbage", t0=0.0)
    assert bad.trace_id is None
    assert bad.traceparent == "garbage"  # kept verbatim for debugging


def test_in_flight_snapshot_uses_live_clock():
    clock = Clock()
    rec = FlightRecorder(capacity=2, clock=clock)
    rec.begin(1, t0=0.0)
    clock.t = 3.0
    d = rec.get(1)
    assert d["status"] == "in_flight"
    assert d["total_seconds"] == 3.0


def test_get_coerces_digit_strings():
    # HTTP path params arrive as strings; engine handoff ids are ints.
    rec = FlightRecorder(capacity=2)
    tr = rec.begin(42, t0=0.0)
    rec.finish(tr, "ok", t_end=1.0)
    assert rec.get("42")["request_id"] == 42


# -- PhaseClock: the engine loop's timeline ----------------------------------


class Spans:
    """An annotation factory that logs what was entered and left."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kw):
        log = self.log

        class Span:
            def __enter__(self):
                log.append(("enter", name, kw))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return Span()


def _one_cycle(clock, now, child=True):
    """admit 3 (match 1 inside) + grow 2 + sync 40 + fan_out 5 = 50 ns."""
    now.t = 100
    clock.begin("admit", live=2, tasks=1)
    if child:
        now.t = 101
        with clock.child("match", request_id="r0", tokens=7):
            now.t = 102
    now.t = 103
    clock.mark("grow")
    now.t = 105
    clock.mark("sync")
    now.t = 145
    clock.mark("fan_out")
    now.t = 150
    return clock.end()


@pytest.mark.parametrize("factory", [None, "spans"])
def test_phase_clock_telescopes(factory):
    """A cycle's phases sum to the cycle exactly; a child never exceeds
    its parent; the same holds with and without an annotation factory."""
    now = Clock(0)
    clock = PhaseClock(annotate=Spans() if factory else None, clock=now)
    assert _one_cycle(clock, now) == 50
    assert clock.cycle == {"admit": 3, "admit/match": 1, "grow": 2,
                           "sync": 40, "fan_out": 5}
    snap = clock.snapshot()
    assert snap["cycles"] == 1
    assert snap["cycle_seconds"] == 50 / 1e9
    in_cycle = sum(snap["seconds"][p] for p in LOOP_PHASES if p != "wait")
    assert in_cycle == pytest.approx(snap["cycle_seconds"], abs=1e-15)
    assert sum(v for k, v in clock.cycle.items() if "/" not in k) == 50
    assert snap["seconds"]["admit/match"] <= snap["seconds"]["admit"]
    assert set(snap["seconds"]) == set(LOOP_PHASES + LOOP_CHILDREN)
    assert snap["slow_cycles"] == 0 and snap["slow"] == []


def _pipelined_cycle(clock, now, delivers=True):
    """The loop's order since delivery moved behind the decode launch
    (admission moved there before it): admit 4 (match 1) + grow 2 +
    dispatch 3 + barrier 1 + fan_out 6 delivering the last chunk (all of
    it the child `shadow`) + admit 12 in the shadow (chunk_args 5,
    chunk_launch 2 inside it) + grow 2 ahead + sync 18 + fan_out 2 settling
    this chunk = 50 ns. Without `delivers` (the engine's first chunk: none
    before it to hand out) the sync takes the 7."""
    now.t = 100
    clock.begin("admit", live=2, tasks=1)
    now.t = 101
    with clock.child("match", request_id="r0", tokens=7):
        now.t = 102
    now.t = 104
    clock.mark("grow")
    now.t = 106
    clock.mark("dispatch")
    now.t = 109
    if delivers:
        clock.mark("barrier")
        now.t = 110
        clock.mark("fan_out")
        with clock.child("shadow"):
            now.t = 116
    clock.mark("admit")
    with clock.child("shadow"):
        now.t += 2
        with clock.child("chunk_args", request_id="r1", tokens=16):
            now.t += 5
        with clock.child("chunk_launch", request_id="r1", tokens=16):
            now.t += 2
        now.t += 3
    clock.mark("grow")
    now.t += 2
    clock.mark("sync")
    now.t = 148
    clock.mark("fan_out")
    now.t = 150
    return clock.end()


@pytest.mark.parametrize("factory", [None, "spans"])
def test_phase_marked_twice_accumulates_and_the_cycle_still_sums(factory):
    """`admit` and `grow` before the decode launch and again behind it,
    `fan_out` behind it (the last chunk's tokens out) and after the sync
    (this chunk settled): one counter each; the phases sum to the cycle
    in integer nanoseconds; a shadow is the part of its phase behind the
    launch, and `admit`'s holds the children that ran there."""
    now = Clock(0)
    clock = PhaseClock(annotate=Spans() if factory else None, clock=now)
    assert _pipelined_cycle(clock, now) == 50
    assert clock.cycle == {
        "admit": 4 + 12, "admit/match": 1, "grow": 2 + 2, "dispatch": 3,
        "barrier": 1, "fan_out": 6 + 2, "fan_out/shadow": 6,
        "admit/shadow": 12, "admit/chunk_args": 5, "admit/chunk_launch": 2,
        "sync": 18,
    }
    assert sum(v for k, v in clock.cycle.items() if "/" not in k) == 50
    assert clock.cycle_seconds("dispatch", "admit/shadow", "sync") == 33 / 1e9
    snap = clock.snapshot()
    sec = snap["seconds"]
    assert set(sec) == set(LOOP_PHASES + LOOP_CHILDREN)
    assert 0 < sec["admit/shadow"] == 12 / 1e9 <= sec["admit"] == 16 / 1e9
    assert 0 < sec["fan_out/shadow"] == 6 / 1e9 < sec["fan_out"] == 8 / 1e9
    in_cycle = sum(sec[p] for p in LOOP_PHASES if p != "wait")
    assert in_cycle == pytest.approx(snap["cycle_seconds"], abs=1e-15)
    # A second cycle without a shadow adds to admit and fan_out alone.
    _one_cycle(clock, now)
    sec = clock.snapshot()["seconds"]
    assert sec["admit/shadow"] == 12 / 1e9 and sec["admit"] == 19 / 1e9
    assert sec["fan_out/shadow"] == 6 / 1e9 and sec["fan_out"] == 13 / 1e9


def test_a_cycle_with_nothing_to_deliver_has_no_delivery_shadow():
    """The first chunk of an engine has no chunk before it to hand out:
    no barrier, one `fan_out` (its settlement), no `fan_out/shadow`."""
    now = Clock(0)
    clock = PhaseClock(clock=now)
    assert _pipelined_cycle(clock, now, delivers=False) == 50
    assert clock.cycle["fan_out"] == 2 and clock.cycle["sync"] == 18 + 7
    assert "fan_out/shadow" not in clock.cycle and "barrier" not in clock.cycle
    sec = clock.snapshot()["seconds"]
    assert sec["fan_out/shadow"] == 0.0 and sec["fan_out"] == 2 / 1e9


def test_shadow_spans_nest_under_their_phase_behind_the_launch():
    now = Clock(0)
    spans = Spans()
    clock = PhaseClock(annotate=spans, clock=now)
    _pipelined_cycle(clock, now)
    names = [(e[0], e[1]) for e in spans.log]
    lo = names.index(("enter", "engine/dispatch"))
    assert names[lo:lo + 21] == [
        ("enter", "engine/dispatch"), ("exit", "engine/dispatch"),
        ("enter", "engine/barrier"), ("exit", "engine/barrier"),
        ("enter", "engine/fan_out"), ("enter", "engine/fan_out/shadow"),
        ("exit", "engine/fan_out/shadow"), ("exit", "engine/fan_out"),
        ("enter", "engine/admit"), ("enter", "engine/admit/shadow"),
        ("enter", "engine/admit/chunk_args"),
        ("exit", "engine/admit/chunk_args"),
        ("enter", "engine/admit/chunk_launch"),
        ("exit", "engine/admit/chunk_launch"),
        ("exit", "engine/admit/shadow"), ("exit", "engine/admit"),
        ("enter", "engine/grow"), ("exit", "engine/grow"),
        ("enter", "engine/sync"), ("exit", "engine/sync"),
        ("enter", "engine/fan_out"),
    ]
    for phase in ("admit", "grow", "fan_out"):
        assert sum(n == ("enter", f"engine/{phase}") for n in names) == 2
    assert sum(n == ("enter", "engine/fan_out/shadow") for n in names) == 1
    assert sum(n == ("enter", "engine/cycle") for n in names) == 1


@pytest.mark.parametrize("child", ["admit/shadow", "fan_out/shadow"])
def test_shadow_counters_on_a_live_engine_and_in_prometheus(child):
    """loop_admit_shadow_seconds_total and loop_fan_out_shadow_seconds_total
    sit beside their siblings in a live engine's stats() and under the one
    labelled Prometheus series, and never exceed their phase's counter."""
    import time

    import jax

    from dstack_tpu.server.metrics_registry import METRICS
    from dstack_tpu.workloads.config import PRESETS
    from dstack_tpu.workloads.serving import ServingEngine, prometheus_metrics
    from dstack_tpu.workloads.transformer import init_params

    cfg = PRESETS["tiny"].with_(remat=False)
    engine = ServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           slots=2, max_len=32)
    try:
        outs = [engine.submit([5, 7, 11 + i], max_new_tokens=6)
                for i in range(3)]  # the third waits for a slot
        for q in outs:
            while q.get(timeout=60) is not None:
                pass
        deadline = time.monotonic() + 30
        while engine.stats()["active"] and time.monotonic() < deadline:
            time.sleep(0.02)
        time.sleep(0.1)  # the last cycle publishes when it ends
        s = engine.stats()
    finally:
        engine.close()
    phase = child.split("/")[0]
    own = s[f"loop_{child.replace('/', '_')}_seconds_total"]
    assert 0 < own <= s[f"loop_{phase}_seconds_total"]
    in_cycles = sum(s[f"loop_{p}_seconds_total"]
                    for p in LOOP_PHASES if p != "wait")
    assert in_cycles == pytest.approx(s["loop_cycle_seconds_total"], abs=1e-9)
    text = prometheus_metrics(s)
    series = "dstack_tpu_serving_loop_phase_seconds_total"
    assert METRICS[series] == ("counter", ("phase",))
    assert f'{series}{{phase="{child}"}} {own}' in text
    assert (f'{series}{{phase="{phase}"}} '
            f'{s[f"loop_{phase}_seconds_total"]}') in text


def test_a_rehearsal_reads_the_delivery_shadow_share():
    """The benchmark's flow at a tiny size on the CPU: the counter reaches
    `/metrics`, the reader finds it and the line carries
    `scheduler.deliver_shadow_share` as a number (not UNREAD, not absent)."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral-7b.docqa",
         "--seed", "3000000411", "--seconds", "4", "--trace", "1",
         "--rehearsal"],
        cwd=repo, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "UNREAD scheduler.deliver_shadow_share" not in proc.stdout
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    share = line["metrics"]["scheduler.deliver_shadow_share"]
    assert share["unit"] == "%" and 0.0 < share["value"] <= 100.0
    assert line["correct"] is True and line["failed"] == 0


def test_phase_clock_spans_enter_and_leave_in_order():
    now = Clock(0)
    spans = Spans()
    clock = PhaseClock(annotate=spans, clock=now)
    _one_cycle(clock, now)
    assert [(e[0], e[1]) for e in spans.log] == [
        ("enter", "engine/cycle"), ("enter", "engine/admit"),
        ("enter", "engine/admit/match"), ("exit", "engine/admit/match"),
        ("exit", "engine/admit"),
        ("enter", "engine/grow"), ("exit", "engine/grow"),
        ("enter", "engine/sync"), ("exit", "engine/sync"),
        ("enter", "engine/fan_out"), ("exit", "engine/fan_out"),
        ("exit", "engine/cycle"),
    ]
    assert spans.log[0][2] == {"live": 2, "tasks": 1, "n": 0}
    assert spans.log[2][2] == {"request_id": "r0", "tokens": 7}


def test_phase_clock_wait_is_outside_cycles():
    """`mark()` outside a cycle is a free-standing phase: counted, on no
    cycle, and closed by whatever comes next."""
    now = Clock(0)
    spans = Spans()
    clock = PhaseClock(annotate=spans, clock=now)
    clock.mark("wait")
    now.t = 200
    clock.mark("wait")
    now.t = 300
    clock.begin("admit")
    now.t = 310
    assert clock.end() == 10
    snap = clock.snapshot()
    assert snap["seconds"]["wait"] == 300 / 1e9
    assert snap["cycles"] == 1 and snap["cycle_seconds"] == 10 / 1e9
    assert [e[1] for e in spans.log if e[0] == "enter"] == [
        "engine/wait", "engine/wait", "engine/cycle", "engine/admit"]
    assert clock.end() == 0  # nothing open: a no-op


def test_phase_clock_snapshot_holds_whole_cycles_only():
    now = Clock(0)
    clock = PhaseClock(clock=now)
    _one_cycle(clock, now)
    before = clock.snapshot()
    now.t = 200
    clock.begin("admit")
    now.t = 260
    clock.mark("sync")
    assert clock.snapshot() is before  # mid-cycle: nothing published yet
    assert clock.cycle_seconds("admit") == 60 / 1e9
    now.t = 300
    clock.end()
    assert clock.snapshot()["cycles"] == 2
    assert before["cycles"] == 1  # a taken snapshot is never mutated


def test_phase_clock_keeps_the_last_slow_cycles():
    now = Clock(0)
    clock = PhaseClock(clock=now, slow_seconds=1e-6, keep=2)
    for i in range(4):
        now.t = i * 10_000
        clock.begin("admit", live=i, tasks=0, pending=3)
        now.t += 400
        clock.mark("sync")
        now.t += 600 + i  # 1000 + i ns >= 1000 ns: slow, inclusive at i=0
        clock.end()
    now.t = 90_000
    clock.begin("admit")
    now.t += 999  # under the threshold
    clock.end()
    snap = clock.snapshot()
    assert snap["cycles"] == 5 and snap["slow_cycles"] == 4
    assert snap["slow_cycle_seconds"] == pytest.approx(4006 / 1e9)
    assert [c["live"] for c in snap["slow"]] == [2, 3]  # the last two
    last = snap["slow"][-1]
    assert last["t"] == 30_000 / 1e9 and last["pending"] == 3
    assert last["phases"] == {"admit": 400 / 1e9, "sync": 603 / 1e9}
    assert sum(last["phases"].values()) == pytest.approx(last["seconds"])


def test_phase_clock_close_leaves_open_spans_uncounted():
    now = Clock(0)
    spans = Spans()
    clock = PhaseClock(annotate=spans, clock=now)
    clock.begin("admit")
    child = clock.child("chunk_launch")
    child.__enter__()
    now.t = 50
    clock.close()  # the loop died mid-cycle
    assert [e[1] for e in spans.log if e[0] == "exit"] == [
        "engine/admit/chunk_launch", "engine/admit", "engine/cycle"]
    assert clock.snapshot()["cycles"] == 0
    assert clock.end() == 0


def test_phase_clock_readers_never_see_a_torn_cycle():
    """One writer, many readers, no lock: every snapshot a reader takes
    holds whole cycles (phases in cycles sum to the cycle total)."""
    import sys
    import threading
    import time

    clock = PhaseClock()
    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            snap = clock.snapshot()
            in_cycles = sum(v for k, v in snap["seconds"].items()
                            if "/" not in k and k != "wait")
            if abs(in_cycles - snap["cycle_seconds"]) > 1e-9:
                torn.append(snap)

    readers = [threading.Thread(target=reader) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in readers:
            t.start()
        deadline = time.monotonic() + 0.5
        cycles = 0
        while time.monotonic() < deadline:
            clock.begin("admit")
            with clock.child("match"):
                pass
            for phase in ("grow", "dispatch", "sync", "barrier", "fan_out"):
                clock.mark(phase)
            clock.end()
            clock.mark("wait")
            cycles += 1
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=10)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in readers)
    assert not torn
    assert clock.snapshot()["cycles"] == cycles > 0
