"""A program's device time by model component (`scope_reduce.py`).

One op:
  component_share   100 x the self time of the operations under `components`
                    (names of `scope_reduce.COMPONENTS`) over the self time of
                    ALL operations of the modules matching `module`. The
                    denominator is the PROGRAM's device time, not the traced
                    stretch: a share does not move with load or idle, and a
                    program's eight shares sum to 100.

Contract: without a trace (a `--trace 0` run, a rehearsal) `read` returns
None. With one, the reduction runs ONCE per run, on the path in
`obs["trace"]["xplane"]`, in a process of its own pinned to the CPU (the device
walk needs JAX's reader and the parent never imports JAX); its result is kept
in `obs["scopes"]` and beside the run's other files as `scopes_reduced.json`.
A trace that holds no program's HLO, a module none of whose operations
resolves, or a reduction that fails raises `TraceError`, which `run.py` prints
as `UNREAD <metric>` and leaves out of the line: never a zero. A program from
before the scopes reads mostly `other`: a number, and a true one.
"""

import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, Optional

from benchmarks import cellfiles, scope_reduce
from benchmarks.trace_reduce import TraceError

REDUCE_TIMEOUT_S = 200


def reduced_scopes(obs: Dict[str, Any]) -> Dict[str, Any]:
    if "scopes" not in obs:
        obs["scopes"] = _reduce(Path(obs["trace"]["xplane"]))
    if "error" in obs["scopes"]:
        raise TraceError(obs["scopes"]["error"])
    return obs["scopes"]


def _reduce(xplane: Path) -> Dict[str, Any]:
    # <out>/<cell>/trace/plugins/profile/<stamp>/<host>.xplane.pb
    run_dir = next((p.parent for p in xplane.parents if p.name == "trace"),
                   xplane.parent)
    out = run_dir / "scopes_reduced.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        p for p in (str(cellfiles.REPO), os.environ.get("PYTHONPATH")) if p))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.scope_reduce", str(xplane),
             "--out", str(out)],
            cwd=cellfiles.REPO, env=env, capture_output=True, text=True,
            timeout=REDUCE_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"scope reduction took over {REDUCE_TIMEOUT_S} s"}
    if proc.returncode != 0:
        return {"error": proc.stderr.strip()[-500:] or "scope reduction failed"}
    return json.loads(out.read_text())


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    if obs.get("trace") is None:
        return None
    if args["op"] == "component_share":
        seconds, total = scope_reduce.component_seconds(
            reduced_scopes(obs), args["module"], args["components"])
        return 100.0 * seconds / total
    raise ValueError(f"unknown op {args['op']!r}")
