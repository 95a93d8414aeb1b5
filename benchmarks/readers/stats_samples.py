"""Gauges of `GET /metrics` sampled once a second inside the window (traced
runs only): the `stat` (max or mean) of `num` over `den`, times `scale`."""

from typing import Any, Dict, Optional


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    if obs["kind"] != "serve":
        return None
    seconds = obs["window"]["seconds"]
    ratios = [
        s[args["num"]] / s[args["den"]] for s in obs["stats"]["samples"]
        if 0.0 <= s["t"] < seconds and s.get(args["den"])
    ]
    if not ratios:
        return None
    value = max(ratios) if args["stat"] == "max" else sum(ratios) / len(ratios)
    return value * args.get("scale", 1.0)
