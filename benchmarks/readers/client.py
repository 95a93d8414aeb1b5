"""Numbers from the client's own clock: the request records the load
generator kept (generators/serving.py says what a record holds).

args: `field` (ttft_ms: due -> first chunk; ttft_sent_ms: sent -> first
chunk; tpot_ms: (last - first) / (tokens - 1); late_ms: due -> sent),
`stat` (p50, p90, p99, mean, count), `population` (due_in_window,
sent_in_window, finished_in_window). Or `op: token_rate`: output tokens
received inside the window per second, a request's tokens spread evenly
between its first and last chunk.

Percentiles are Harrell-Davis estimates (Biometrika 69, 1982): the weighted
mean of ALL order statistics, with the weights a Beta((n+1)q, (n+1)(1-q))
distribution puts on each. A window holds some tens of requests, and the
engine delivers first tokens once per scheduling cycle (a quarter of a
second in the first cells), so the single order statistic at the 90th
percentile of 41 requests moved by 4.4% between two runs of one trace on the
chip where this estimate moved by 0.9% (PERF.md, PR 22). It estimates the
same quantile; it does not average the tail away.
"""

import math
from typing import Any, Dict, List, Optional


def _betacf(a: float, b: float, x: float) -> float:
    """Continued fraction of the incomplete beta function (Lentz)."""
    tiny = 1e-300
    c, d = 1.0, 1.0 - (a + b) * x / (a + 1.0)
    d = 1.0 / (d if abs(d) > tiny else tiny)
    h = d
    for m in range(1, 400):
        for num in (m * (b - m) * x / ((a + 2 * m - 1) * (a + 2 * m)),
                    -(a + m) * (a + b + m) * x / ((a + 2 * m) * (a + 2 * m + 1))):
            d = 1.0 + num * d
            d = 1.0 / (d if abs(d) > tiny else tiny)
            c = 1.0 + num / c
            c = c if abs(c) > tiny else tiny
            h *= d * c
        if abs(d * c - 1.0) < 1e-12:
            break
    return h


def beta_cdf(x: float, a: float, b: float) -> float:
    """Regularised incomplete beta function I_x(a, b)."""
    if x <= 0.0:
        return 0.0
    if x >= 1.0:
        return 1.0
    front = math.exp(math.lgamma(a + b) - math.lgamma(a) - math.lgamma(b)
                     + a * math.log(x) + b * math.log(1.0 - x))
    if x < (a + 1.0) / (a + b + 2.0):
        return front * _betacf(a, b, x) / a
    return 1.0 - front * _betacf(b, a, 1.0 - x) / b


def percentile(values: List[float], q: float) -> float:
    """Harrell-Davis estimate of the q-th percentile (see the module's note)."""
    v = sorted(values)
    n = len(v)
    if n == 1:
        return v[0]
    a, b = (n + 1) * q / 100.0, (n + 1) * (1.0 - q / 100.0)
    edges = [beta_cdf(i / n, a, b) for i in range(n + 1)]
    return sum(x * (edges[i + 1] - edges[i]) for i, x in enumerate(v))


def _field(r: Dict[str, Any], field: str) -> Optional[float]:
    if field == "late_ms":
        return (r["sent"] - r["due"]) * 1e3
    if r["first"] is None:
        return None
    if field == "ttft_ms":
        return (r["first"] - r["due"]) * 1e3
    if field == "ttft_sent_ms":
        return (r["first"] - r["sent"]) * 1e3
    if field == "tpot_ms":
        if not r["ok"] or r["tokens"] < 2:
            return None
        return (r["last"] - r["first"]) / (r["tokens"] - 1) * 1e3
    raise ValueError(f"unknown field {field!r}")


def population(obs: Dict[str, Any], which: str) -> List[Dict[str, Any]]:
    seconds = obs["window"]["seconds"]
    inside = lambda t: t is not None and 0.0 <= t < seconds
    key = {"due_in_window": "due", "sent_in_window": "sent",
           "finished_in_window": "done"}[which]
    return [r for r in obs["requests"] if inside(r[key])]


def token_rate(obs: Dict[str, Any]) -> float:
    seconds = obs["window"]["seconds"]
    total = 0.0
    for r in obs["requests"]:
        if r["first"] is None or not r["tokens"]:
            continue
        total += 0.0 <= r["first"] < seconds
        span = r["last"] - r["first"]
        if span > 0:
            overlap = min(r["last"], seconds) - max(r["first"], 0.0)
            total += (r["tokens"] - 1) * max(0.0, overlap) / span
    return total / seconds


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    if obs["kind"] != "serve":
        return None
    if args.get("op") == "token_rate":
        return token_rate(obs)
    values = [
        v for v in (_field(r, args["field"])
                    for r in population(obs, args["population"]))
        if v is not None
    ]
    stat = args["stat"]
    if stat == "count":
        return float(len(values))
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    return percentile(values, float(stat[1:]))
