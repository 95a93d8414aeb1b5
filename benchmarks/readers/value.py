"""A number the run already holds: `key` is a dotted path into the
observation (setup_s, done.memory_peak_bytes, ready.warmup.warmup_seconds);
with `over`, the ratio of two such numbers. `scale` multiplies the result."""

from typing import Any, Dict, Optional


def lookup(obs: Dict[str, Any], path: str) -> Optional[float]:
    node: Any = obs
    for part in path.split("."):
        if not isinstance(node, dict) or node.get(part) is None:
            return None
        node = node[part]
    return float(node)


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    value = lookup(obs, args["key"])
    if value is None:
        return None
    if "over" in args:
        den = lookup(obs, args["over"])
        if not den:
            return None
        value /= den
    return value * args.get("scale", 1.0)
