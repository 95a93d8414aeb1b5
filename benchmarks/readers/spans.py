"""Device idle time by the engine loop's own spans (`span_reduce.py`).

One op:
  idle_in   100 x the seconds of device gap charged to the span path `phase`
            (and to what is nested in it: `admit` takes `admit/chunk_args`
            too) over the traced stretch `window_s`. `(unattributed)` is the
            gap time no `engine/` span covers. Over all paths the values sum
            to gap_s / window_s of `trace_reduce`'s reduction.

Contract: without a trace (a `--trace 0` run, a rehearsal) `read` returns
None. With one, the reduction runs ONCE per run, on the path in
`obs["trace"]["xplane"]`, in a process of its own pinned to the CPU (reading
a trace needs JAX's reader and the parent never imports JAX); its result is
kept in `obs["spans"]` and beside the run's other files as
`spans_reduced.json`. A trace that holds no `engine/` span (a program from
before the phase clock) or a reduction that fails raises `TraceError`, which
`run.py` prints as `UNREAD <metric>` and leaves out of the line: never a zero.
"""

import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, Optional

from benchmarks import cellfiles, span_reduce
from benchmarks.trace_reduce import TraceError

REDUCE_TIMEOUT_S = 200


def reduced_spans(obs: Dict[str, Any]) -> Dict[str, Any]:
    if "spans" not in obs:
        obs["spans"] = _reduce(Path(obs["trace"]["xplane"]))
    if "error" in obs["spans"]:
        raise TraceError(obs["spans"]["error"])
    return obs["spans"]


def _reduce(xplane: Path) -> Dict[str, Any]:
    # <out>/<cell>/trace/plugins/profile/<stamp>/<host>.xplane.pb
    run_dir = next((p.parent for p in xplane.parents if p.name == "trace"),
                   xplane.parent)
    out = run_dir / "spans_reduced.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        p for p in (str(cellfiles.REPO), os.environ.get("PYTHONPATH")) if p))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.span_reduce", str(xplane),
             "--out", str(out)],
            cwd=cellfiles.REPO, env=env, capture_output=True, text=True,
            timeout=REDUCE_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"span reduction took over {REDUCE_TIMEOUT_S} s"}
    if proc.returncode != 0:
        return {"error": proc.stderr.strip()[-500:] or "span reduction failed"}
    return json.loads(out.read_text())


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    if obs.get("trace") is None:
        return None
    if args["op"] == "idle_in":
        reduced = reduced_spans(obs)
        return 100.0 * span_reduce.idle_in(reduced, args["phase"]) / reduced["window_s"]
    raise ValueError(f"unknown op {args['op']!r}")
