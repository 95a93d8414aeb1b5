"""`stats_diff` for counters a program may not have yet: None unless every
counter the metric names is in both `/metrics` snapshots.

`stats_diff` raises on a missing key, which is right for a counter every
program has and wrong for one a PR adds: the driver runs the benchmark as
that PR leaves it on the parent commit too, whose `stats()` lacks the
counter, and a metric new in a PR is to be left out there, not to fail the
run. Same arguments, same arithmetic."""

from typing import Any, Dict, Optional

from benchmarks.readers import stats_diff


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    if obs["kind"] != "serve":
        return None
    den = args.get("den")
    keys = list(args["num"]) + (list(den) if isinstance(den, list) else [])
    stats = obs["stats"]
    if any(k not in stats[end] for end in ("before", "after") for k in keys):
        return None
    return stats_diff.read(obs, args)
