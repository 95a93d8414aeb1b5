"""Counters of `ServingEngine.stats()` (`GET /metrics`) as the difference of
two snapshots, one at each end of the window: sum of the `num` counters'
growth over the sum of the `den` counters' growth (over the window's length
for `den: "window_s"`, over 1 without `den`), times `scale`."""

from typing import Any, Dict, Optional


def growth(obs: Dict[str, Any], keys) -> float:
    before, after = obs["stats"]["before"], obs["stats"]["after"]
    return sum(after[k] - before[k] for k in keys)


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    if obs["kind"] != "serve":
        return None
    num = growth(obs, args["num"])
    den = args.get("den")
    if den is None:
        bottom = 1.0
    elif den == "window_s":
        bottom = obs["window"]["stats_span_s"]
    else:
        bottom = growth(obs, den)
    if not bottom:
        return None
    return num / bottom * args.get("scale", 1.0)
