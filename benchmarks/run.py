"""One command, one cell, one line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell's files by the names in BENCHMARK.json, starts ONE child that
holds the chip(s) (children/serve.py runs the native server's `main()`,
children/train.py the trainer's step), drives it with the traffic mix's
generator, reduces, prints, exits. The last line of stdout is the result
object; everything else (the run's summary, what was left out and why) is
on earlier lines or under benchmarks/out/.

This process never imports JAX: a parent that has touched JAX holds the chip
and the child that needs it then fails or hangs (checked before the result
is printed). There is no CPU fallback: without a TPU that has the chips the
cell asks for, the child says so and this exits non-zero with no result.
`--rehearsal` runs the same flow at a tiny size on the CPU for the sandbox,
and says so on its line; it is not a cell.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks import cellfiles  # noqa: E402
from benchmarks.childproc import Child, ChildFailed  # noqa: E402

TRACE_REDUCE_TIMEOUT_S = 200


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(rehearsal: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO), env.get("PYTHONPATH")) if p)
    env["PYTHONUNBUFFERED"] = "1"
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def reduce_trace(trace_dir: Path, out: Path) -> dict:
    """In a process of its own, pinned to the CPU: reading a trace needs JAX's
    reader, and this process stays without JAX."""
    env = child_env(rehearsal=True)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.trace_reduce", str(trace_dir),
         "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=TRACE_REDUCE_TIMEOUT_S,
    )
    if proc.returncode != 0:
        raise ChildFailed(f"trace reduction failed:\n{proc.stderr[-3000:]}")
    return json.loads(out.read_text())


def read_metrics(cell, group: str, obs: dict) -> dict:
    from benchmarks.trace_reduce import TraceError

    out = {}
    for entry in cell.metrics(group):
        spec = cellfiles.metric_file(group, entry["name"])
        reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        try:
            value = reader.read(obs, spec["args"])
        except TraceError as e:
            # Not a zero and not a guess: the metric is left out and named.
            print(f"UNREAD {entry['name']}: {e}", flush=True)
            continue
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def open_child(args, cell, log_name: str, trace: bool) -> SimpleNamespace:
    """The cell's child (not started yet) and what a generator needs to drive
    it. Everything a run leaves behind goes under benchmarks/out/<cell>/."""
    out_dir = cellfiles.OUT_DIR / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    port = free_port()
    child_args = ["--cell", cell.name, "--seed", str(args.seed), "--port", str(port)]
    if args.rehearsal:
        child_args.append("--rehearsal")
    child = Child(f"benchmarks.children.{cell.kind}", child_args,
                  child_env(args.rehearsal), REPO, out_dir / log_name)
    return SimpleNamespace(
        cell=cell, seed=args.seed, seconds=args.seconds, trace=trace, port=port,
        child=child, out_dir=out_dir, trace_dir=out_dir / "trace",
        t_process_start=T_PROCESS_START,
    )


async def measure(args, cell) -> dict:
    ctx = open_child(args, cell, "child.log", bool(args.trace))
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    generator = importlib.import_module(f"benchmarks.generators.{cell.generator}")
    await ctx.child.start()
    try:
        obs = await generator.run(ctx)
    finally:
        code = await ctx.child.close()
    if code != 0:
        raise ChildFailed(f"the child exited {code}:\n{ctx.child.tail()}")
    obs["device"] = obs["ready"]["device"]
    obs["model_fields"] = cell.model_fields
    if not args.rehearsal:
        obs["peaks"] = cell.peaks(obs["device"]["kind"])
    if args.trace and not args.rehearsal:
        obs["trace"] = reduce_trace(ctx.trace_dir, ctx.out_dir / "trace_reduced.json")
    return obs


def result_line(args, cell, obs: dict) -> dict:
    group = "per_layer" if args.trace else "end_to_end"
    device = dict(obs["device"])
    device["memory_peak_bytes"] = obs["done"].get("memory_peak_bytes") or 0
    line = {
        "correct": bool(obs["correct"]), "attempted": obs["attempted"],
        "failed": obs["failed"], "metrics": read_metrics(cell, group, obs),
        "device": device,
    }
    trace = obs.get("trace")
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    if args.rehearsal:
        line["rehearsal"] = "tiny size on the CPU: control flow only, no device number"
    return line


def summary(cell, obs: dict) -> dict:
    keep = {k: obs.get(k) for k in (
        "kind", "setup_s", "attempted", "failed", "correct", "reasons",
        "compiles_in_window", "offered", "trace_span", "job")}
    keep["ready"] = obs["ready"]
    keep["memory"] = obs["done"].get("memory_peak_bytes")
    if obs["kind"] == "serve":
        statuses = {}
        for r in obs["requests"]:
            statuses[str(r["status"])] = statuses.get(str(r["status"]), 0) + 1
        keep["requests"] = {"all": len(obs["requests"]), "by_status": statuses}
    else:
        keep["steps"] = len(obs["steps"])
        keep["losses"] = [s["loss"] for s in obs["steps"]][:3] + ["..."] + \
            [s["loss"] for s in obs["steps"]][-1:]
    return keep


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the same flow at a tiny size on the CPU; not a cell")
    args = ap.parse_args()
    try:
        cell = cellfiles.Cell(args.workload, rehearsal=args.rehearsal)
        obs = asyncio.run(measure(args, cell))
        line = result_line(args, cell, obs)
    except (cellfiles.CellError, ChildFailed) as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr, flush=True)
        return 1
    detail = {k: v for k, v in obs.items() if k != "trace"}
    (cellfiles.OUT_DIR / cell.name / f"run_trace{args.trace}.json").write_text(
        json.dumps(detail))
    if "jax" in sys.modules:
        print("benchmarks/run.py: the parent imported jax", file=sys.stderr)
        return 1
    print("SUMMARY " + json.dumps(summary(cell, obs)), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
