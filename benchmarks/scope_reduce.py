"""Device time by model component: each operation's self time, charged to
the `jax.named_scope` its program put it under.

A TPU trace names an operation's event by its HLO line (`%fusion.486 =
bf16[16,2048]{...} fusion(...)`) and no stat of the event carries a scope.
The scope is in the trace one level down: the plane `/host:metadata` has no
lines and one `event_metadata` entry per compiled program, named like the
program's `XLA Modules` events (`jit_decode_steps(<fingerprint>)`), whose ONE
stat `Hlo Proto` holds the serialized `HloProto` of the OPTIMISED module. Its
instructions are the ones the op events are named after, and each carries
`metadata.op_name`, the whole scope path:
`jit(decode_steps)/while/body/closed_call/while/body/closed_call/mlp/dot_general`.
`jax.profiler.ProfileData` shows neither a plane's `event_metadata` nor bytes
stats, so that plane is read here by hand, off the wire (`fields`: varints and
length-delimited fields; what is not asked for, the planes' `lines` among it,
is skipped by its length and never decoded; no import beyond the standard
library: the machine with the chip has JAX, not TensorFlow).

The join is device operation -> instruction -> scope -> component:

- the device walk is `trace_reduce.reduce`'s (the `XLA Ops` line's events,
  their SELF times, each inside the `XLA Modules` event that holds it), keyed
  by the module EVENT, fingerprint and all, and the instruction name: the
  `jit_chunk_prefill` buckets are programs of their own, each with its own
  `fusion.1`;
- an operation goes to ONE component (`component_of`): a Pallas kernel by its
  name (`KERNELS`: a kernel call may carry no scope); a `while`,
  `conditional` or `call` to `other` (its body's operations are events of
  their own; what it keeps is loop overhead); an operation that is or holds
  a `dot` or `convolution` by that product's scope; else by its own
  `op_name`; a fusion the compiler gave no `op_name` by the scope most of
  the instructions fused into it carry. The scope is the LAST entry of
  `SCOPES` in the path. ONE label a fusion: a norm fused into the next matmul
  counts with the matmul, a residual add with the product it follows;
- what names no scope is `other`: the layer scan's own slicing of the stacked
  weights, re-layouts (`copy`) the compiler put in, the step loop's
  bookkeeping. Work no component owns, and worth reading when it is large.

Per stripped module name (`jit_decode_steps`), summed over devices:
`total_self_s`, `by_component_s` (all of `COMPONENTS`; they sum to the total),
`by_scope_s`, `unresolved_s` (operations whose program or instruction the trace
does not hold: counted under `other`), and the ten longest operations under
`other`. A trace without `/host:metadata` raises `TraceError`.

    python -m benchmarks.scope_reduce <dir or .xplane.pb> [--out file] [--describe]

reads any profile `jax.profiler` wrote, of the benchmark or of a server.
"""

import argparse
import bisect
import json
import os
import re
import sys
from collections import Counter, defaultdict
from pathlib import Path
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

from benchmarks.trace_reduce import (
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    TOP,
    TraceError,
    _events,
    _module_name,
    find_xplane,
    op_head,
    self_times,
    short_op,
)

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
OTHER = "other"
COMPONENTS = ("attn_proj", "attn", "mlp", "experts", "router", "mixer", "head", OTHER)
# The programs' vocabulary (dstack_tpu/workloads; PERF.md section 3).
SCOPES = {
    "embed": "head", "head": "head", "sample": "head",
    "attn/qkv": "attn_proj", "attn/out": "attn_proj", "mla/project": "attn_proj",
    "attn/write": "attn", "attn/full": "attn", "attn/window": "attn",
    "mla/attend": "attn",
    "mlp": "mlp",
    "moe/route": "router", "moe/experts": "experts", "moe/shared": "experts",
    "mamba/proj": "mixer", "mamba/conv": "mixer", "mamba/scan": "mixer",
    "mamba/state": "mixer",
}
KERNELS = (
    (re.compile(r"^(ragged|latent)_paged_attention"), "attn"),
    (re.compile(r"^t?gmm\b"), "experts"),
    (re.compile(r"^selective_scan_"), "mixer"),
)
HOLDS_A_BODY = ("while", "conditional", "call")
PRODUCTS = ("dot", "convolution")
# A transformation wraps the scopes it passes: `transpose(jvp(mlp))`.
_WRAPPERS = re.compile(
    r"^(?:(?:jvp|transpose|vmap|remat|checkpoint|custom_jvp|custom_vjp|shard_map)\()+"
    r"|\)+$")


# ------------------------------------------------------------- off the wire

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one protobuf message: an int for a
    varint, a memoryview INTO `buf` (no copy, not decoded) for the rest."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise TraceError(f"wire type {wire} at byte {i}: not a protobuf message")
        yield key >> 3, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _ints(wire: int, value) -> List[int]:
    """A repeated int64 field's values: packed (one blob) or one by one."""
    if wire == 0:
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


class Instruction(NamedTuple):
    opcode: str
    op_name: str
    calls: Tuple[int, ...]      # ids of the computations it holds


def hlo_instructions(hlo_proto) -> Tuple[Dict[str, Instruction], Dict[int, List[str]]]:
    """One serialized `HloProto` -> ({instruction name: Instruction},
    {computation id: its instructions' names}). HloProto.hlo_module=1;
    HloModuleProto.computations=3; HloComputationProto.instructions=2, id=5;
    HloInstructionProto.name=1, opcode=2, metadata=7, called_computation_ids=38;
    OpMetadata.op_name=2."""
    by_name: Dict[str, Instruction] = {}
    bodies: Dict[int, List[str]] = {}
    for f, _, module in fields(hlo_proto):
        if f != 1:
            continue
        for mf, _, computation in fields(module):
            if mf != 3:
                continue
            names, comp_id = [], None
            for cf, cw, cv in fields(computation):
                if cf == 5:
                    comp_id = cv
                if cf != 2:
                    continue
                name = opcode = op_name = ""
                calls: List[int] = []
                for xf, xw, xv in fields(cv):
                    if xf == 1:
                        name = _text(xv)
                    elif xf == 2:
                        opcode = _text(xv)
                    elif xf == 38:
                        calls += _ints(xw, xv)
                    elif xf == 7:
                        for of, _, ov in fields(xv):
                            if of == 2:
                                op_name = _text(ov)
                by_name[name] = Instruction(opcode, op_name, tuple(calls))
                names.append(name)
            bodies[comp_id] = names
    return by_name, bodies


def metadata_programs(xspace) -> Dict[str, Any]:
    """{program's event name: its `Hlo Proto` bytes (a memoryview)} from the
    plane `/host:metadata` of a serialized XSpace. XSpace.planes=1;
    XPlane.name=2, event_metadata=4 (map: value=2), stat_metadata=5;
    XEventMetadata.name=2, stats=5; XStat.metadata_id=1, bytes_value=6;
    XStatMetadata.id=1, name=2."""
    for f, _, plane in fields(xspace):
        if f != 1:
            continue
        name, entries, stat_names = "", [], {}
        for pf, _, pv in fields(plane):
            if pf == 2:
                name = _text(pv)
                if name != METADATA_PLANE:
                    break
            elif pf == 4:
                entries += [v for mf, _, v in fields(pv) if mf == 2]
            elif pf == 5:
                for mf, _, v in fields(pv):
                    if mf == 2:
                        row = {sf: sv for sf, _, sv in fields(v)}
                        stat_names[row.get(1)] = _text(row.get(2, b""))
        if name != METADATA_PLANE:
            continue
        programs = {}
        for entry in entries:
            event_name, blob = "", None
            for ef, _, ev in fields(entry):
                if ef == 2:
                    event_name = _text(ev)
                elif ef == 5:
                    stat = {sf: sv for sf, _, sv in fields(ev)}
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT and 6 in stat:
                        blob = stat[6]
            if blob is not None:
                programs[event_name] = blob
        return programs
    raise TraceError(
        f"no {METADATA_PLANE} plane in the trace: the profiler kept no program's"
        " HLO, so no operation can be resolved to a scope")


# ----------------------------------------------- instruction -> component

def scope_of(op_name: str) -> Optional[str]:
    """The LAST entry of `SCOPES` in an `op_name` path, or None."""
    parts = [_WRAPPERS.sub("", part) for part in op_name.split("/")]
    for i in range(len(parts) - 1, -1, -1):
        if i and f"{parts[i - 1]}/{parts[i]}" in SCOPES:
            return f"{parts[i - 1]}/{parts[i]}"
        if parts[i] in SCOPES:
            return parts[i]
    return None


class Program:
    """One optimised module: its instructions, and each one's label (kept:
    an instruction runs thousands of times in a trace)."""

    def __init__(self, hlo_proto):
        self.by_name, self.bodies = hlo_instructions(hlo_proto)
        self._labels: Dict[str, Tuple[str, str]] = {}

    def fused_into(self, name: str) -> List[Instruction]:
        """The instructions of the computations `name` holds, nested fusions'
        too (never a loop's or a branch's body: those run as events of their
        own)."""
        found, todo = [], [name]
        while todo:
            instruction = self.by_name.get(todo.pop())
            if instruction is None or instruction.opcode in HOLDS_A_BODY:
                continue
            for comp_id in instruction.calls:
                for inner in self.bodies.get(comp_id, ()):
                    found.append(self.by_name[inner])
                    todo.append(inner)
        return found

    def component_of(self, name: str) -> Tuple[str, str]:
        """(component, scope) of the instruction `name`; the scope is a
        kernel's pattern for a kernel and "" for `other`."""
        if name not in self._labels:
            self._labels[name] = self._label(name)
        return self._labels[name]

    def _label(self, name: str) -> Tuple[str, str]:
        for pattern, component in KERNELS:
            if pattern.search(name):
                return component, f"kernel:{pattern.pattern}"
        own = self.by_name[name]
        if own.opcode in HOLDS_A_BODY:
            return OTHER, ""
        fused = self.fused_into(name)
        for instruction in [own] + fused:
            if instruction.opcode in PRODUCTS:
                scope = scope_of(instruction.op_name)
                if scope is not None:
                    return SCOPES[scope], scope
        scope = scope_of(own.op_name)
        if scope is None and not own.op_name and fused:
            votes = Counter(s for s in (scope_of(i.op_name) for i in fused) if s)
            scope = votes.most_common(1)[0][0] if votes else None
        return (SCOPES[scope], scope) if scope else (OTHER, "")


# --------------------------------------------------------------- the walk

def reduce(path: Path) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    xplane = find_xplane(Path(path))
    raw = memoryview(xplane.read_bytes())
    blobs = metadata_programs(raw)
    programs: Dict[str, Program] = {}
    planes = [p for p in ProfileData.from_file(str(xplane)).planes
              if DEVICE_PLANE.match(p.name)]
    if not planes:
        raise TraceError("no device plane in the trace")
    modules: Dict[str, Dict[str, Any]] = {}
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines or MODULES_LINE not in lines:
            continue
        mod_events = _events(lines[MODULES_LINE], with_detail=False)
        op_events = _events(lines[OPS_LINE], with_detail=False)
        mod_starts = [m[0] for m in mod_events]
        for (start, end, name, _), own in zip(op_events, self_times(op_events)):
            i = bisect.bisect_right(mod_starts, start) - 1
            if i < 0 or mod_events[i][1] < end:
                continue                    # outside a module: no program's
            event = mod_events[i][2]
            row = modules.setdefault(_module_name(event), {
                "programs": set(), "total": 0.0, "unresolved": 0.0,
                "component": defaultdict(float), "scope": defaultdict(float),
                "other": defaultdict(float),
            })
            row["programs"].add(event)
            row["total"] += own
            if event not in programs and event in blobs:
                programs[event] = Program(blobs[event])
            program = programs.get(event)
            instruction = op_head(name).lstrip("%")
            if program is None or instruction not in program.by_name:
                component, scope = OTHER, ""
                row["unresolved"] += own
            else:
                component, scope = program.component_of(instruction)
            row["component"][component] += own
            if scope:
                row["scope"][scope] += own
            else:
                row["other"][short_op(name)] += own
    if not modules:
        raise TraceError("no operation ran inside a module on a device")
    return {
        "xplane": str(xplane),
        "devices": len(planes),
        "programs_in_metadata": len(blobs),
        "modules": {
            module: {
                "programs": sorted(row["programs"]),
                "total_self_s": row["total"] / 1e9,
                "unresolved_s": row["unresolved"] / 1e9,
                "by_component_s": {c: row["component"].get(c, 0.0) / 1e9
                                   for c in COMPONENTS},
                "by_scope_s": {s: ns / 1e9 for s, ns in sorted(row["scope"].items())},
                "other_top": [
                    [op, ns / 1e9] for op, ns in
                    sorted(row["other"].items(), key=lambda kv: -kv[1])[:TOP]],
            }
            for module, row in sorted(modules.items())
        },
    }


def component_seconds(reduced: Dict[str, Any], module_pattern: str,
                      components: List[str]) -> Tuple[float, float]:
    """(seconds under `components`, the programs' own device seconds) over the
    modules matching `module_pattern`. A module none of whose operations
    resolved to an instruction raises: no scopes read must not read as a
    program that is all `other`."""
    found = {m: row for m, row in reduced["modules"].items()
             if re.search(module_pattern, m)}
    if not found:
        raise TraceError(
            f"module pattern {module_pattern!r} matches nothing; the trace has: "
            + ", ".join(sorted(reduced["modules"])))
    total = sum(row["total_self_s"] for row in found.values())
    if total <= 0 or sum(row["unresolved_s"] for row in found.values()) >= total:
        raise TraceError(
            f"no operation of {sorted(found)} resolves to an instruction: the"
            f" trace's {METADATA_PLANE} holds no HLO of these programs")
    seconds = sum(row["by_component_s"][c] for row in found.values()
                  for c in components)
    return seconds, total


def describe(path: Path) -> str:
    """What the trace holds below `ProfileData`: every plane's name and its
    counts, and for each program of `/host:metadata` how many instructions
    carry an `op_name`, and how many of those a scope of the vocabulary."""
    raw = memoryview(find_xplane(Path(path)).read_bytes())
    out = []
    for f, _, plane in fields(raw):
        if f != 1:
            continue
        counts: Dict[int, int] = defaultdict(int)
        name = ""
        for pf, _, pv in fields(plane):
            counts[pf] += 1
            if pf == 2:
                name = _text(pv)
        out.append(f"PLANE {name!r}: lines={counts[3]} event_metadata={counts[4]}"
                   f" stat_metadata={counts[5]} stats={counts[6]}")
    for event, blob in sorted(metadata_programs(raw).items()):
        by_name, _ = hlo_instructions(blob)
        named = [i for i in by_name.values() if i.op_name]
        scoped = Counter(scope_of(i.op_name) for i in named)
        scoped.pop(None, None)
        out.append(f"  PROGRAM {event}: {HLO_PROTO_STAT} {len(blob)} bytes,"
                   f" {len(by_name)} instructions, {len(named)} with an op_name,"
                   f" {sum(scoped.values())} under a scope {dict(scoped.most_common())}")
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", type=Path)
    ap.add_argument("--out", type=Path)
    ap.add_argument("--describe", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        if args.describe:
            print(describe(args.path))
            return 0
        reduced = reduce(args.path)
    except TraceError as e:
        print(f"scope_reduce: {e}", file=sys.stderr)
        return 1
    text = json.dumps(reduced)
    if args.out:
        args.out.write_text(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
