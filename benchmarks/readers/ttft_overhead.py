"""What the server adds to a first token around the engine: the client's
mean time from SENDING a request to its first chunk, minus the engine's own
mean time from submit to first token over the same window
(`ttft_seconds_sum` / `admitted_total`, as window differences). HTTP accept
and parse, the thread per connection, the tokenizer, the SSE write, and the
first token the server holds back when it is the lead byte of a multi-byte
character are all in it."""

from typing import Any, Dict, Optional

from benchmarks.readers import stats_diff


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    if obs["kind"] != "serve":
        return None
    seconds = obs["window"]["seconds"]
    mine = [r for r in obs["requests"]
            if r["first"] is not None and 0.0 <= r["first"] < seconds]
    admitted = stats_diff.growth(obs, ["admitted_total"])
    if not mine or not admitted:
        return None
    client_ms = sum(r["first"] - r["sent"] for r in mine) / len(mine) * 1e3
    engine_ms = stats_diff.growth(obs, ["ttft_seconds_sum"]) / admitted * 1e3
    return client_ms - engine_ms
