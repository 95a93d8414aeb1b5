"""Per-request flight recorder: a bounded ring of phase timelines.

PR 8 gave each *run* one trace; this gives each serving *request* one —
Dapper-style request-scoped tracing over the dataplane hot path. The
design constraints come from where it sits:

- **Fixed memory.** `capacity` trace slots are preallocated up front and
  recycled overwrite-oldest; a recorder never grows with traffic. The
  id index is evicted with the slot, so a recycled request's trace is
  simply gone (size the ring above max concurrent requests + the recent
  history you want to keep).
- **Zero allocation on the decode hot path.** A `RequestTrace` is a
  `__slots__` object whose per-chunk bookkeeping is plain attribute
  increments (`decode_steps += 1`); marks — the only appends — happen at
  phase *transitions*, of which a request has a handful over its whole
  life, never per token.
- **Telescoping phases.** A trace is an ordered list of transition marks;
  phase i spans mark[i] → mark[i+1] (the last phase ends at `t_end`), so
  per-phase durations sum *exactly* to the request's total latency, the
  same construction as the stage timeline's lane spans
  (docs/guides/observability.md).
- **Tail-based capture.** Full trace snapshots persist only for requests
  that were slow (`slow_ms`, inclusive) or ended in error/shed — the
  Dapper insight that the interesting traces live in the tail. The tail
  store is itself a bounded overwrite-oldest ring.

`PhaseClock` applies the same telescoping construction to the engine
*loop* that serves those requests: one `mark(phase)` feeds an always-on
counter and, when the engine hands it an annotation factory, a span of
the same name on the profiler's timeline.

The module is stdlib-only (plus the server's histogram primitive) so the
dataplane worker can import it without pulling in JAX.
"""

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from dstack_tpu.server.tracing import HistogramData

# Canonical phase vocabulary (docs + dashboards key on these literals).
# Not every request visits every phase: a unified request never ships KV,
# a decode-role request starts at adoption, qos_admission only exists
# when the server gated the request before submit.
PHASES = (
    "qos_admission",    # native-server arrival -> engine submit
    "adapter_acquire",  # LoRA acquire inside submit (adapter requests)
    "queue_wait",       # submit -> admission pop (decode role: receipt)
    "prefill",          # admission -> first token finalized
    "kv_ship",          # prefill role: gather + wire + decode-side ack
    "kv_adopt",         # decode role: pop -> payload scattered into pool
    "kv_swap_out",      # preemption: chain gathered + parked host-side
    "kv_swap_in",       # readmission: chain scattered back into the pool
    "decode",           # first token delivered -> last token
    "proxy",            # dataplane worker: ingress -> upstream headers
)

_TERMINAL = ("ok", "error", "shed", "cancelled")

# Phases of one engine-loop iteration (the loop's counters, Prometheus
# labels and `engine/<phase>` span names are generated from these). `wait`
# is the only phase outside a cycle. A cycle with something live launches
# and settles chunk N and hands out N-1's tokens:
# admit -> grow -> dispatch N -> barrier -> fan_out (N-1's tokens) -> admit
# -> grow -> sync N -> fan_out (N settled): `admit`, `grow` and `fan_out`
# occur twice and accumulate, and the phases still sum to the cycle.
LOOP_PHASES = (
    "wait",      # nothing to do (or a starved pool's 1 ms sleep): device idle, rightly
    "admit",     # before grow (readmit / preempt / adopt / leftover chunks): device drained;
                 # again behind dispatch (the shadow: admission, prefill chunks): hidden
    "grow",      # decode-block growth: what is left of it, and a starved slot's fate, before
                 # dispatch; again behind it, ahead for the next chunk (tables, its key): hidden
    "dispatch",  # decode launch (spec: draft launch -> verify launch)
    "sync",      # device_get: the host is blocked on a busy device
    "barrier",   # first-token order barrier, ahead of a delivery: behind the launch, hidden
    "fan_out",   # after the sync, the settlement (lengths, ended slots freed): device drained;
                 # behind the next launch, the last chunk's tokens to consumers: hidden
)
# Nested work inside a phase, as "<phase>/<child>". `admit/shadow` is the
# part of `admit` spent while a decode chunk was in flight (it holds the
# other three where they ran there): host time the device does not wait for.
# `fan_out/shadow` is the delivery of a chunk's tokens behind the next
# chunk's launch; what is left of `fan_out` is the settlement, and the
# delivery of a chunk after which nothing was live to launch.
LOOP_CHILDREN = ("admit/match", "admit/chunk_args", "admit/chunk_launch",
                 "admit/shadow", "fan_out/shadow")
# A cycle this long is a stall (the longest healthy cycle on the benchmark
# ledger is ~0.27 s): counted, and the last few kept whole.
SLOW_CYCLE_SECONDS = 1.0
SLOW_CYCLES_KEPT = 8


class RequestTrace:
    """One request's phase timeline + hot-path counters. Mutated by the
    engine threads without a lock: each field has a single writer at any
    point in the request's life, and readers (`to_dict`) tolerate a torn
    in-progress view — this is a flight recorder, not a ledger."""

    __slots__ = (
        "request_id", "x_request_id", "trace_id", "traceparent", "role",
        "status", "t_end", "marks",
        # hot-path counters (attribute increments only)
        "prefill_chunks", "prefill_tokens", "decode_steps", "decode_tokens",
        "spec_rounds", "spec_drafted", "spec_accepted", "spec_rejected",
        "kv_payload_bytes",
        "_clock",
    )

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self.reset(None)

    def reset(self, request_id: Any, *, x_request_id: Optional[str] = None,
              trace_id: Optional[str] = None,
              traceparent: Optional[str] = None,
              role: str = "unified") -> None:
        self.request_id = request_id
        self.x_request_id = x_request_id
        self.trace_id = trace_id
        self.traceparent = traceparent
        self.role = role
        self.status: Optional[str] = None
        self.t_end: Optional[float] = None
        self.marks: List[Tuple[str, float]] = []
        self.prefill_chunks = 0
        self.prefill_tokens = 0
        self.decode_steps = 0
        self.decode_tokens = 0
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_rejected = 0
        self.kv_payload_bytes = 0

    def mark(self, phase: str, t: Optional[float] = None) -> None:
        """Open `phase` (closing the previous one) at `t`."""
        self.marks.append((phase, self._clock() if t is None else t))

    @property
    def t_start(self) -> Optional[float]:
        return self.marks[0][1] if self.marks else None

    def total_seconds(self) -> float:
        if not self.marks:
            return 0.0
        end = self.t_end if self.t_end is not None else self._clock()
        return end - self.marks[0][1]

    def phase_durations(self) -> List[Tuple[str, float, float]]:
        """[(phase, start_offset_s, duration_s)] — telescoping: the sum
        of durations equals `total_seconds()` by construction."""
        if not self.marks:
            return []
        t0 = self.marks[0][1]
        end = self.t_end if self.t_end is not None else self._clock()
        out = []
        for i, (phase, t) in enumerate(self.marks):
            nxt = self.marks[i + 1][1] if i + 1 < len(self.marks) else end
            out.append((phase, t - t0, max(0.0, nxt - t)))
        return out

    def to_dict(self) -> Dict[str, Any]:
        counters = {
            k: getattr(self, k)
            for k in ("prefill_chunks", "prefill_tokens", "decode_steps",
                      "decode_tokens", "spec_rounds", "spec_drafted",
                      "spec_accepted", "spec_rejected", "kv_payload_bytes")
            if getattr(self, k)
        }
        return {
            "request_id": self.request_id,
            "x_request_id": self.x_request_id,
            "trace_id": self.trace_id,
            "traceparent": self.traceparent,
            "role": self.role,
            "status": self.status if self.status is not None else "in_flight",
            "total_seconds": self.total_seconds(),
            "phases": [
                {"phase": p, "start_s": s, "duration_s": d}
                for p, s, d in self.phase_durations()
            ],
            "counters": counters,
        }


class TailStore:
    """Bounded store of full trace snapshots for tail-latency debugging.
    Captures when the total crossed `slow_ms` (inclusive — a request *at*
    the threshold is a slow request) or the request ended badly; disabled
    entirely when `slow_ms` is None."""

    def __init__(self, slow_ms: Optional[float], capacity: int = 64):
        self.slow_ms = slow_ms
        self.capacity = max(1, capacity)
        self._snaps: List[Dict[str, Any]] = []
        self._next = 0
        self.captured_total = 0

    @property
    def enabled(self) -> bool:
        return self.slow_ms is not None

    def should_capture(self, total_seconds: float, status: str) -> bool:
        if self.slow_ms is None:
            return False
        if status in ("error", "shed"):
            return True
        return total_seconds * 1000.0 >= self.slow_ms

    def capture(self, snapshot: Dict[str, Any]) -> None:
        self.captured_total += 1
        if len(self._snaps) < self.capacity:
            self._snaps.append(snapshot)
        else:
            self._snaps[self._next] = snapshot
            self._next = (self._next + 1) % self.capacity

    def snapshots(self) -> List[Dict[str, Any]]:
        return list(self._snaps)


class FlightRecorder:
    """Preallocated ring of `RequestTrace` slots with an id index.

    `capacity == 0` disables recording entirely: `begin()` returns None
    and every engine-side mark site is a no-op `if rec is not None`
    guard — recorder off means zero retained traces, not empty ones.
    """

    def __init__(self, capacity: int = 256, *,
                 slow_ms: Optional[float] = None,
                 tail_capacity: int = 64,
                 role: str = "unified",
                 clock: Callable[[], float] = time.monotonic):
        self.capacity = max(0, int(capacity))
        self.role = role
        self._clock = clock
        self._ring = [RequestTrace(clock) for _ in range(self.capacity)]
        self._next = 0
        self._index: Dict[Any, RequestTrace] = {}
        self._lock = threading.Lock()
        self.tail = TailStore(slow_ms, tail_capacity)
        self.phase_hist: Dict[str, HistogramData] = {}
        self.started_total = 0
        self.finished_total = 0
        self.recycled_total = 0

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    def begin(self, request_id: Any, *, x_request_id: Optional[str] = None,
              traceparent: Optional[str] = None,
              first_phase: str = "queue_wait",
              t0: Optional[float] = None) -> Optional[RequestTrace]:
        """Claim a slot (overwrite-oldest) and open `first_phase`.
        Returns None when the recorder is disabled."""
        if not self.capacity:
            return None
        trace_id = None
        if traceparent:
            from dstack_tpu.utils.tracecontext import parse_traceparent

            ctx = parse_traceparent(traceparent)
            trace_id = ctx.trace_id if ctx is not None else None
        with self._lock:
            rec = self._ring[self._next]
            self._next = (self._next + 1) % self.capacity
            if rec.marks:  # slot held a previous request: evict its keys
                self.recycled_total += 1
                for key in (rec.request_id, rec.x_request_id):
                    if key is not None and self._index.get(key) is rec:
                        del self._index[key]
            self.started_total += 1
            if request_id is None:
                request_id = f"req-{self.started_total}"
            rec.reset(request_id, x_request_id=x_request_id,
                      trace_id=trace_id, traceparent=traceparent,
                      role=self.role)
            self._index[request_id] = rec
            if x_request_id is not None:
                self._index[x_request_id] = rec
        rec.mark(first_phase, self._clock() if t0 is None else t0)
        return rec

    def finish(self, rec: Optional[RequestTrace], status: str = "ok",
               t_end: Optional[float] = None) -> None:
        """Close the trace: stamp the terminal status, feed the per-phase
        histograms, and tail-capture when it qualifies. Idempotent — the
        first terminal status wins (handoff/cancel races call this from
        more than one path)."""
        if rec is None or rec.t_end is not None:
            return
        rec.t_end = self._clock() if t_end is None else t_end
        rec.status = status if status in _TERMINAL else "error"
        with self._lock:
            self.finished_total += 1
            for phase, _start, duration in rec.phase_durations():
                hist = self.phase_hist.get(phase)
                if hist is None:
                    hist = self.phase_hist[phase] = HistogramData()
                hist.observe(duration)
            if self.tail.should_capture(rec.total_seconds(), rec.status):
                self.tail.capture(rec.to_dict())

    def record_dropped(self, request_id: Any, *, status: str = "shed",
                       x_request_id: Optional[str] = None,
                       traceparent: Optional[str] = None,
                       t0: Optional[float] = None) -> None:
        """One-shot trace for a request rejected before it got a
        timeline (QoS shed, engine overload): a single zero-or-tiny
        phase, terminal immediately — so the tail store still sees it."""
        rec = self.begin(request_id, x_request_id=x_request_id,
                         traceparent=traceparent, first_phase="qos_admission",
                         t0=t0)
        self.finish(rec, status)

    def get(self, key: Any) -> Optional[Dict[str, Any]]:
        """Trace snapshot by engine request id or client X-Request-ID:
        the live ring first, then the tail store (a slow trace outlives
        its recycled ring slot there)."""
        with self._lock:
            rec = self._index.get(key)
            if rec is None and isinstance(key, str) and key.isdigit():
                rec = self._index.get(int(key))
            if rec is not None:
                return rec.to_dict()
            for snap in reversed(self.tail.snapshots()):
                if key in (snap.get("request_id"), snap.get("x_request_id")):
                    return snap
        return None

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": self.enabled,
                "capacity": self.capacity,
                "started_total": self.started_total,
                "finished_total": self.finished_total,
                "recycled_total": self.recycled_total,
                "tail_enabled": self.tail.enabled,
                "tail_slow_ms": self.tail.slow_ms,
                "tail_captured_total": self.tail.captured_total,
            }

    def phase_histograms(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {p: h.to_dict() for p, h in self.phase_hist.items()}


class PhaseClock:
    """The engine loop's own timeline: telescoping phases per cycle.

    `begin(phase)` opens a cycle and its first phase, `mark(phase)` closes
    the open phase and opens the next, `end()` closes both — so a cycle's
    phases sum to the cycle exactly (integer nanoseconds); a phase marked
    again in the same cycle (`admit`, before and behind the decode launch)
    accumulates. `mark()` outside a cycle opens a free-standing phase
    (`wait`) that the next `mark()`/`begin()` closes. `child(name)` times
    nested work inside the open phase of a cycle; children may nest.

    Each interval is two things at once: nanoseconds added to a counter
    that is always on, and — when `annotate` is given (the engine passes
    `jax.profiler.TraceAnnotation`; this module stays stdlib-only) — an
    annotation `engine/<phase>` entered and left around the same
    interval, which costs a flag test while no profiler runs and lands
    on the profiler's timeline while one does.

    Single writer (the loop thread). Readers on other threads take
    `snapshot()`, which is replaced whole at the end of each cycle (and of
    each free-standing phase), so it always holds whole cycles: the
    phases that ran inside cycles sum to `cycle_seconds`. Cycles of
    `slow_seconds` or more are counted and the last `keep` of them kept
    with their phases — tail capture, as `TailStore` does for requests.
    """

    def __init__(self, *, annotate: Optional[Callable[..., Any]] = None,
                 slow_seconds: float = SLOW_CYCLE_SECONDS,
                 keep: int = SLOW_CYCLES_KEPT,
                 clock: Callable[[], int] = time.monotonic_ns):
        self._annotate = annotate
        self._slow_ns = int(slow_seconds * 1e9)
        self._keep = max(1, keep)
        self._now = clock
        # Totals in ns, keyed by phase and by "<phase>/<child>".
        self._totals: Dict[str, int] = dict.fromkeys(
            LOOP_PHASES + LOOP_CHILDREN, 0)
        self._cycles = 0
        self._cycle_ns = 0
        self._slow_cycles = 0
        self._slow_cycle_ns = 0
        self._slow: List[Dict[str, Any]] = []
        # The open cycle: its closed phases and children so far (ns).
        self.cycle: Dict[str, int] = {}
        self._cycle_t0: Optional[int] = None
        self._cycle_kw: Dict[str, Any] = {}
        self._phase: Optional[str] = None
        self._phase_t0 = 0
        self._spans: List[Any] = []  # entered annotations, outermost first
        self._publish()

    def _publish(self) -> None:
        self._snapshot = {
            "seconds": {k: ns / 1e9 for k, ns in self._totals.items()},
            "cycles": self._cycles,
            "cycle_seconds": self._cycle_ns / 1e9,
            "slow_cycles": self._slow_cycles,
            "slow_cycle_seconds": self._slow_cycle_ns / 1e9,
            "slow": self._slow,
        }

    def snapshot(self) -> Dict[str, Any]:
        """Totals as of the last whole cycle (never mutated afterwards)."""
        return self._snapshot

    # -- spans ---------------------------------------------------------------

    def _enter(self, name: str, kw: Dict[str, Any]) -> None:
        if self._annotate is not None:
            span = self._annotate(f"engine/{name}", **kw)
            span.__enter__()
            self._spans.append(span)

    def _leave(self) -> None:
        if self._annotate is not None:
            self._spans.pop().__exit__(None, None, None)

    # -- phases --------------------------------------------------------------

    def _open_phase(self, phase: str, t: int) -> None:
        self._phase = phase
        self._phase_t0 = t
        self._enter(phase, {})

    def _close_phase(self, t: int) -> None:
        phase = self._phase
        if phase is None:
            return
        self._phase = None
        self._leave()
        dt = t - self._phase_t0
        if self._cycle_t0 is not None:
            self.cycle[phase] = self.cycle.get(phase, 0) + dt
        else:
            self._totals[phase] = self._totals.get(phase, 0) + dt
            self._publish()

    def begin(self, phase: str, **kw: Any) -> None:
        """Open a cycle and its first phase at one instant (closing a
        free-standing phase). `kw` goes on the cycle's span and, should
        the cycle turn out slow, in its record."""
        t = self._now()
        self._close_phase(t)
        self.cycle = {}
        self._cycle_t0 = t
        self._cycle_kw = kw
        self._enter("cycle", dict(kw, n=self._cycles))
        self._open_phase(phase, t)

    def mark(self, phase: str) -> int:
        """Open `phase` (closing the previous one) now; returns now (ns)."""
        t = self._now()
        self._close_phase(t)
        self._open_phase(phase, t)
        return t

    def child(self, name: str, **kw: Any) -> "_ChildSpan":
        """`with clock.child("match", request_id=..., tokens=...)`: nested
        work inside the open phase, counted and shown as `<phase>/<name>`."""
        return _ChildSpan(self, f"{self._phase}/{name}", kw)

    def end(self) -> int:
        """Close the open phase and the cycle at one instant and publish;
        returns the cycle's nanoseconds (0 when no cycle was open)."""
        t = self._now()
        self._close_phase(t)
        if self._cycle_t0 is None:
            return 0
        self._leave()
        ns = t - self._cycle_t0
        for key, dt in self.cycle.items():
            self._totals[key] = self._totals.get(key, 0) + dt
        if ns >= self._slow_ns:
            self._slow_cycles += 1
            self._slow_cycle_ns += ns
            self._slow = (self._slow + [{
                "t": self._cycle_t0 / 1e9, "seconds": ns / 1e9,
                "phases": {k: v / 1e9 for k, v in self.cycle.items()},
                **self._cycle_kw,
            }])[-self._keep:]
        self._cycles += 1
        self._cycle_ns += ns
        self._cycle_t0 = None
        self._publish()
        return ns

    def close(self) -> None:
        """Leave whatever is still entered (the loop is exiting, possibly
        mid-cycle after a failure); the open cycle is not counted."""
        while self._spans:
            self._spans.pop().__exit__(None, None, None)
        self._phase = None
        self._cycle_t0 = None

    def cycle_seconds(self, *phases: str) -> float:
        """Seconds the open cycle has spent in `phases` (closed ones)."""
        return sum(self.cycle.get(p, 0) for p in phases) / 1e9


class _ChildSpan:
    __slots__ = ("_clock", "_key", "_kw", "_t0")

    def __init__(self, clock: PhaseClock, key: str, kw: Dict[str, Any]):
        self._clock = clock
        self._key = key
        self._kw = kw

    def __enter__(self) -> "_ChildSpan":
        self._clock._enter(self._key, self._kw)
        self._t0 = self._clock._now()
        return self

    def __exit__(self, *exc: Any) -> None:
        clock = self._clock
        dt = clock._now() - self._t0
        clock._leave()
        clock.cycle[self._key] = clock.cycle.get(self._key, 0) + dt
