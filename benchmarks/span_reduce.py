"""Device idle time, charged to the engine loop's own spans.

`trace_reduce.py` finds the gaps in which no program ran on the first device
and names each by the best-fitting Python frame of any host thread: a guess.
Where the program marks its loop itself (`ServingEngine._loop` under
`utils/flight_recorder.PhaseClock`: `engine/cycle` holding `engine/admit`,
`engine/grow`, `engine/dispatch`, `engine/sync`, `engine/barrier`,
`engine/fan_out`, with `engine/admit/<child>` nested, and `engine/wait`
between cycles) the gaps can be charged exactly. This reads two things only:

- the device lines, for the same stretch and the same gaps as `trace_reduce`
  (first to last device event; `gaps_between` over the first device's
  `XLA Modules`, gaps under `MIN_GAP_NS` dropped), so `gap_s` and `window_s`
  here are the numbers there;
- the host events whose name starts `engine/`, and nothing else of the host
  lines (under the Python tracer they hold frames by the hundred thousand;
  no stats are read). The profiler names a line after its OS thread
  (`python3`), so the loop's line is the one that holds these events.

Every nanosecond of every gap goes to the DEEPEST span that covers it
(`admit/chunk_args` before `admit` before `cycle`), keyed by the span's path
without `engine/`; what no span covers goes to `(unattributed)` (the cycle
that was open when the profiler started has no span: an annotation is
recorded only if it began under the profiler). The charges sum to `gap_s`.
A trace without one `engine/` span raises `TraceError`: a program that marks
nothing must not read as a device that never idles.

    python -m benchmarks.span_reduce <dir or .xplane.pb> [--out file]
"""

import argparse
import json
import os
import sys
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Tuple

from benchmarks.trace_reduce import (
    DEVICE_PLANE,
    HOST_PLANE,
    MIN_GAP_NS,
    MODULES_LINE,
    OPS_LINE,
    TraceError,
    find_xplane,
    gaps_between,
)

SPAN_PREFIX = "engine/"
UNATTRIBUTED = "(unattributed)"

Interval = Tuple[float, float]
Segment = Tuple[float, float, str]


def _intervals(line) -> List[Interval]:
    return [(e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events if e.duration_ns > 0]


def device_gaps(planes) -> Tuple[List[Interval], float, float]:
    """(gaps on the first device, start, end of the traced stretch), as
    `trace_reduce.reduce` takes them."""
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    if not devices:
        raise TraceError("no device plane in the trace")
    lo = hi = None
    first: List[Interval] = []
    for d, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        modules = _intervals(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        ops = _intervals(lines[OPS_LINE]) if OPS_LINE in lines else []
        for start, end in modules + ops:
            lo = start if lo is None else min(lo, start)
            hi = end if hi is None else max(hi, end)
        if d == 0:
            first = modules or ops
    if lo is None:
        raise TraceError("no operation ran on a device inside the trace")
    return gaps_between(first, lo, hi), lo, hi


def engine_spans(planes) -> List[Segment]:
    """(start, end, path) of the `engine/` events on the loop's line: the
    host line that holds the most of them."""
    best: List[Segment] = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            found = [(e.start_ns, e.start_ns + e.duration_ns,
                      e.name[len(SPAN_PREFIX):])
                     for e in line.events
                     if e.name.startswith(SPAN_PREFIX) and e.duration_ns > 0]
            if len(found) > len(best):
                best = found
    if not best:
        raise TraceError(
            f"no {SPAN_PREFIX}* span in the trace: the program does not mark"
            " its loop (or the profiler saw no whole cycle)")
    return best


def deepest_segments(spans: List[Segment]) -> List[Segment]:
    """Properly nested spans -> disjoint (start, end, path) segments in time
    order, each named by the innermost span that covers it."""
    out: List[Segment] = []
    stack: List[Segment] = []   # open spans, outermost first
    cursor = 0.0                # segments are emitted up to here

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            _, end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(start)
        if stack:
            if start > cursor:
                out.append((cursor, start, stack[-1][2]))
            # A child does not outlive its parent (a nanosecond of skew).
            end = min(end, stack[-1][1])
        cursor = start
        stack.append((start, end, name))
    close_until(float("inf"))
    return out


def charge(gaps: List[Interval], segments: List[Segment]) -> Dict[str, float]:
    """Nanoseconds of gap per span path; both lists sorted and disjoint."""
    charged: Dict[str, float] = defaultdict(float)
    i = 0
    for a, b in gaps:
        covered = 0.0
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            overlap = min(b, segments[j][1]) - max(a, segments[j][0])
            if overlap > 0:
                charged[segments[j][2]] += overlap
                covered += overlap
            j += 1
        if b - a > covered:
            charged[UNATTRIBUTED] += b - a - covered
    return dict(charged)


def reduce(path: Path) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    xplane = find_xplane(Path(path))
    planes = list(ProfileData.from_file(str(xplane)).planes)
    gaps, lo, hi = device_gaps(planes)
    spans = engine_spans(planes)
    charged = charge(gaps, deepest_segments(spans))
    counts: Dict[str, int] = defaultdict(int)
    for _, _, name in spans:
        counts[name] += 1
    return {
        "xplane": str(xplane),
        "window_s": (hi - lo) / 1e9,
        "gap_s": sum(b - a for a, b in gaps) / 1e9,
        "min_gap_ns": MIN_GAP_NS,
        "gaps": len(gaps),
        "spans": dict(counts),
        "idle_in_s": {name: ns / 1e9 for name, ns in sorted(charged.items())},
    }


def idle_in(reduced: Dict[str, Any], phase: str) -> float:
    """Seconds of device gap charged to `phase` and to what is nested in it
    (`admit` takes `admit/chunk_args` too; `admit/chunk_args` only itself)."""
    return sum(seconds for name, seconds in reduced["idle_in_s"].items()
               if name == phase or name.startswith(phase + "/"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", type=Path)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        reduced = reduce(args.path)
    except TraceError as e:
        print(f"span_reduce: {e}", file=sys.stderr)
        return 1
    text = json.dumps(reduced)
    if args.out:
        args.out.write_text(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
