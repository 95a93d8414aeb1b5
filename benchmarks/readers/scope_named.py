"""A program's device time under ONE `jax.named_scope` that
`scope_reduce.SCOPES` does not list (readers/scopes.py reads those).

`scope_reduce.py` charges an operation to the LAST entry of its vocabulary in
the operation's scope path, and its vocabulary is a table in a file this
reader may not edit. A scope a later PR adds inside one of the vocabulary's
(`attn/qkv/attn_gate`) therefore counts there with its parent. To read it
apart, this module runs the same reduction in a process of its own with the
scope added to the vocabulary as a component of its own name; nothing else of
the reduction changes, and the component shares the other metrics read come
from their own, unchanged run.

    share = 100 x self time under `scope` / self time of ALL operations of
            the modules matching `module`

Contract (readers/scopes.py's): without a trace `read` returns None; the
reduction runs once a run and scope and is kept in `obs`; a reduction that
fails raises `TraceError` (`UNREAD`, never a zero). A program that has no
operation under the scope (the parent of the PR that added it) reads None:
the metric is left out of the line.

    python -m benchmarks.readers.scope_named <xplane> --scope attn_gate --out f.json
"""

import argparse
import json
import os
import re
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, Optional

from benchmarks import cellfiles, scope_reduce
from benchmarks.trace_reduce import TraceError

REDUCE_TIMEOUT_S = 200


def _reduce(xplane: Path, scope: str) -> Dict[str, Any]:
    run_dir = next((p.parent for p in xplane.parents if p.name == "trace"),
                   xplane.parent)
    out = run_dir / f"scope_{scope.replace('/', '_')}_reduced.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        p for p in (str(cellfiles.REPO), os.environ.get("PYTHONPATH")) if p))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.readers.scope_named", str(xplane),
             "--scope", scope, "--out", str(out)],
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
    scope = args["scope"]
    kept = obs.setdefault("scopes_named", {})
    if scope not in kept:
        kept[scope] = _reduce(Path(obs["trace"]["xplane"]), scope)
    reduced = kept[scope]
    if "error" in reduced:
        raise TraceError(reduced["error"])
    found = [row for m, row in reduced["modules"].items()
             if re.search(args["module"], m)]
    total = sum(row["total_self_s"] for row in found)
    seconds = sum(row["by_scope_s"].get(scope, 0.0) for row in found)
    if total <= 0 or seconds <= 0:
        return None
    return 100.0 * seconds / total


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--scope", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    scope_reduce.SCOPES = {**scope_reduce.SCOPES, args.scope: args.scope}
    scope_reduce.COMPONENTS = scope_reduce.COMPONENTS + (args.scope,)
    Path(args.out).write_text(json.dumps(scope_reduce.reduce(Path(args.xplane))))


if __name__ == "__main__":
    main()
