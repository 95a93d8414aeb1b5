"""From a profiler trace (`.xplane.pb`) to the numbers the trace readers use.

One reduction for every cell and every PR, kept with the benchmark and
checked on recorded traces (tests/test_trace_reduce.py):

- busy_s: per device, the length of the UNION of the intervals in which an
  operation ran (the device's op line); reported as the mean over devices,
  with window_s, the traced stretch: from the first device event to the
  last, so that the profiler's own start and stop (a second of host time
  around a few training steps) do not count as idle. Idle share is theirs.
- modules: per XLA module (one jitted program), the number of executions
  and their device durations.
- ops: per module and operation, count and SELF time (an operation's
  duration minus the operations nested inside it on the same line: a
  `while` holds its body's operations), so sums do not count twice.
- idle_gaps: the stretches in which no module ran on the first device, each
  charged to the host event that fits it best (overlap over the longer of
  the two), summed by that event's name. With the Python tracer on, the
  name is a source line and function of the engine loop or the client.

Read with nothing but JAX (`jax.profiler.ProfileData`), in a process of its
own that is pinned to the CPU: the parent never imports JAX, and the child
that held the chip has ended by then.

    python -m benchmarks.trace_reduce <dir or .xplane.pb> [--describe]
"""

import argparse
import bisect
import json
import os
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 20_000          # shorter gaps are launch latency, not a finding
MAX_GAPS_NAMED = 400         # the longest gaps get a name; all are counted
TOP = 10
DETAIL_STATS = ("tf_op", "long_name", "hlo_op", "hlo_module", "kernel_details",
                "name", "equation")


class TraceError(Exception):
    """The trace holds nothing to read, or a pattern matched nothing."""


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.glob("**/*.xplane.pb"))
    if not found:
        raise TraceError(f"no .xplane.pb under {path}")
    return found[-1]


def _events(line, with_detail: bool = True) -> List[Tuple[float, float, str, str]]:
    """(start_ns, end_ns, name, detail) of every event with a duration; the
    detail is the event's string stats that can carry a kernel's or a
    program's name (host lines are read without: they have millions)."""
    out = []
    for e in line.events:
        if e.duration_ns <= 0:
            continue
        detail = " ".join(
            [f"{k}={v}" for k, v in e.stats
             if k in DETAIL_STATS and isinstance(v, str)] + hlo_words(e.name)
        ) if with_detail else ""
        out.append((e.start_ns, e.start_ns + e.duration_ns, e.name, detail))
    out.sort(key=lambda ev: (ev[0], -ev[1]))
    return out


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def self_times(events: List[Tuple[float, float, str, str]]) -> List[float]:
    """Duration of each event minus what its nested events cover. `events`
    is sorted by (start, -end), so a parent comes before its children."""
    own = [end - start for start, end, _, _ in events]
    stack: List[int] = []
    for i, (start, end, _, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= end - start
        stack.append(i)
    return own


def gaps_between(intervals: List[Tuple[float, float]], lo: float,
                 hi: float) -> List[Tuple[float, float]]:
    gaps, cursor = [], lo
    for start, end in sorted(intervals):
        if start > cursor:
            gaps.append((cursor, min(start, hi)))
        cursor = max(cursor, end)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    return [(a, b) for a, b in gaps if b - a >= MIN_GAP_NS]


def name_gaps(gaps: List[Tuple[float, float]],
              host: List[Tuple[float, float, str, str]]) -> Dict[str, float]:
    """Seconds of gap charged to each host event name (best fit per gap)."""
    if not host:
        return {"(no host events in the trace)": sum(b - a for a, b in gaps) / 1e9}
    import numpy as np

    starts = np.array([h[0] for h in host])
    ends = np.array([h[1] for h in host])
    lengths = ends - starts
    charged: Dict[str, float] = defaultdict(float)
    by_length = sorted(gaps, key=lambda g: g[0] - g[1])
    for a, b in by_length[:MAX_GAPS_NAMED]:
        overlap = np.minimum(ends, b) - np.maximum(starts, a)
        fit = overlap / np.maximum(lengths, b - a)
        best = int(np.argmax(fit))
        name = host[best][2] if fit[best] > 0 else "(no host event overlaps)"
        charged[name] += (b - a) / 1e9
    rest = sum(b - a for a, b in by_length[MAX_GAPS_NAMED:]) / 1e9
    if rest:
        charged["(shorter gaps, not named)"] += rest
    return dict(charged)


def reduce(path: Path) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(Path(path))))
    planes = list(data.planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise TraceError(
            "no device plane in the trace (planes: "
            + ", ".join(p.name for p in planes) + ")"
        )
    host: List[Tuple[float, float, str, str]] = []
    for p in planes:
        if p.name == HOST_PLANE:
            for line in p.lines:
                host.extend(_events(line, with_detail=False))
    span_lo = span_hi = None
    per_device = []
    modules: Dict[str, List[float]] = defaultdict(list)
    ops: Dict[str, Dict[str, List[Any]]] = defaultdict(dict)
    first_device_modules: List[Tuple[float, float]] = []
    for d, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines and MODULES_LINE not in lines:
            raise TraceError(
                f"{plane.name} has neither {OPS_LINE!r} nor {MODULES_LINE!r}"
                f" (lines: {sorted(lines)})"
            )
        mod_events = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        op_events = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        busy_from = op_events or mod_events
        per_device.append({
            "plane": plane.name,
            "busy_s": union_length((s, e) for s, e, _, _ in busy_from) / 1e9,
            "op_events": len(op_events), "module_events": len(mod_events),
        })
        for s, e, _, _ in busy_from + mod_events:
            span_lo = s if span_lo is None else min(span_lo, s)
            span_hi = e if span_hi is None else max(span_hi, e)
        if d == 0:
            first_device_modules = [(s, e) for s, e, _, _ in (mod_events or op_events)]
        for s, e, name, _ in mod_events:
            modules[_module_name(name)].append((e - s) / 1e9)
        mod_starts = [m[0] for m in mod_events]
        own = self_times(op_events)
        for (s, e, name, detail), t in zip(op_events, own):
            i = bisect.bisect_right(mod_starts, s) - 1
            inside = (_module_name(mod_events[i][2])
                      if i >= 0 and mod_events[i][1] >= e else "(outside a module)")
            row = ops[inside].setdefault(name, [0, 0.0, detail])
            row[0] += 1
            row[1] += t / 1e9
    if span_lo is None or not any(d["busy_s"] > 0 for d in per_device):
        raise TraceError("no operation ran on a device inside the trace")
    n = len(per_device)
    gaps = gaps_between(first_device_modules, span_lo, span_hi)
    flat_ops = defaultdict(float)
    for module, module_ops in ops.items():
        for name, (_, seconds, _) in module_ops.items():
            flat_ops[f"{module}: {short_op(name)}"] += seconds / n
    return {
        "xplane": str(find_xplane(Path(path))),
        "devices": n,
        "window_s": (span_hi - span_lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "per_device": per_device,
        "modules": {
            name: {"count": len(v), "total_s": sum(v), "durations_s": sorted(v)}
            for name, v in modules.items()
        },
        "ops": {m: {name: {"count": c, "self_s": t, "detail": d}
                    for name, (c, t, d) in rows.items()}
                for m, rows in ops.items()},
        "gap_s": sum(b - a for a, b in gaps) / 1e9,
        "breakdown": {
            "device_ops": _top(flat_ops),
            "idle_gaps": _top(name_gaps(gaps, host)),
        },
    }


def hlo_words(event_name: str) -> List[str]:
    """What an HLO line says about the operation itself, apart from its name:
    the opcode and, for a custom call, its target (`tpu_custom_call` is a
    Pallas kernel). Operands are left out on purpose: a fusion that merely
    reads `%all-gather-done.5` is not a collective."""
    _, eq, rest = event_name.partition(" = ")
    if not eq:
        return []
    opcode = re.search(r" ([a-z][a-z\-]*)\(", " " + rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return ([f"opcode={opcode.group(1)}"] if opcode else []) + \
        ([f"target={target.group(1)}"] if target else [])


def op_head(event_name: str) -> str:
    return event_name.partition(" = ")[0]


def short_op(event_name: str) -> str:
    """On a TPU an operation's event is named by its whole HLO line;
    `%copy.93 = bf16[12,4608,16,8,128]{...} copy(...)` -> `%copy.93
    bf16[12,4608,16,8,128]`: the name and what it produces."""
    m = re.match(r"(%?[\w.\-]+) = \(?(\w+\[[\d,]*\])?", event_name)
    return " ".join(x for x in m.groups() if x) if m else event_name[:120]


def _module_name(event_name: str) -> str:
    """`jit_decode_steps(1234567890)` -> `jit_decode_steps`: the number is a
    fingerprint of one compilation and changes with the program."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _top(seconds_by_name: Dict[str, float]) -> List[List[Any]]:
    ranked = sorted(seconds_by_name.items(), key=lambda kv: kv[1], reverse=True)
    return [[name, seconds] for name, seconds in ranked[:TOP]]


def matching_modules(reduced: Dict[str, Any], pattern: str) -> Dict[str, Any]:
    found = {k: v for k, v in reduced["modules"].items() if re.search(pattern, k)}
    if not found:
        raise TraceError(
            f"module pattern {pattern!r} matches nothing; the trace has: "
            + ", ".join(sorted(reduced["modules"]))
        )
    return found


def matching_ops(reduced: Dict[str, Any], pattern: str,
                 module_pattern: Optional[str] = None) -> Dict[str, float]:
    """{"count", "self_s"} summed over the operations whose name (on a TPU:
    the HLO line up to its `=`) or detail (name-bearing stats, the opcode, a
    custom call's target) matches `pattern`, inside the modules that match
    `module_pattern` (all modules without one). Per device (mean over them)."""
    count, seconds = 0, 0.0
    for module, rows in reduced["ops"].items():
        if module_pattern is not None and not re.search(module_pattern, module):
            continue
        for name, row in rows.items():
            if re.search(pattern, op_head(name)) or re.search(pattern, row["detail"]):
                count += row["count"]
                seconds += row["self_s"]
    if not count:
        raise TraceError(
            f"operation pattern {pattern!r} matches nothing"
            + (f" inside modules matching {module_pattern!r}" if module_pattern else "")
        )
    n = reduced["devices"]
    return {"count": count / n, "self_s": seconds / n}


def describe(path: Path, limit: int = 12) -> str:
    """What a trace holds, for a first look by hand: planes, lines, and the
    longest events of each line with their stats."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(Path(path))))
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}  stats={list(plane.stats)[:6]}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            by_name: Dict[str, List[float]] = defaultdict(list)
            sample = {}
            for e in events:
                by_name[e.name].append(e.duration_ns)
                sample.setdefault(e.name, e)
            ranked = sorted(by_name.items(), key=lambda kv: -sum(kv[1]))[:limit]
            for name, durs in ranked:
                stats = [(k, v) for k, v in sample[name].stats][:8]
                out.append(f"      {sum(durs) / 1e6:10.3f} ms  x{len(durs):<6} {name[:90]}"
                           f"  {stats}")
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", type=Path)
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--out", type=Path)
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.describe:
        print(describe(args.path))
        return 0
    try:
        reduced = reduce(args.path)
    except TraceError as e:
        print(f"trace_reduce: {e}", file=sys.stderr)
        return 1
    text = json.dumps(reduced)
    if args.out:
        args.out.write_text(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
