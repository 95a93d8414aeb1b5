"""Operations and bytes the paged-attention DECODE calls of a model that
mixes window and full layers need, from its shapes. One call is one layer of
one decode step over every live row; a decode step makes one a layer.

Per row with `ctx` cached positions, H query heads in KV groups, head size
hd (the published `head_dim`, not hidden / heads): a FULL layer scores and
sums over all `ctx` positions, a WINDOW layer over its `min(ctx, window)`
newest. Either way that is 4 x seen x H x hd FLOPs and 2 x seen x KV x hd
elements of keys and values, read once for all the heads of a group, plus
the row's query in and its output out, 2 x H x hd elements. Nothing else is
needed: the blocks of a window layer that lie behind the window, the part of
a block before it, table columns past the context, padded rows and the
cross-group pairs the kernel masks are the implementation's, not the
algorithm's.

The number of calls is counted from the trace's decode modules (executions x
steps a program), each step making one call a layer of each kind, so the
count is the same whichever implementation (one Pallas call a layer, or the
lax path's many operations) did the work.

Hand count (tests/test_opsbytes_window.py): 16 rows at ctx 16,500, H 32,
KV 4, hd 128, window 1,024, bf16. A full layer: (2 x 16 x 16,500 x 4 x 128 +
2 x 16 x 32 x 128) x 2 = 540,934,144 bytes; a window layer: (2 x 16 x 1,024 x
4 x 128 + 131,072) x 2 = 33,816,576 bytes; a step of 2 full + 6 window
layers 1,284,767,744 bytes, where 8 full layers would read 4,327,473,152.
"""

from typing import Any, Dict, Optional

from benchmarks import trace_reduce
# One layer's call over rows that SEE `sum_ctx` positions in all: the same
# count whether they are a full layer's contexts or a window layer's windows.
from benchmarks.opsbytes.paged_attention import decode_call as layer_call

SLIDING = "sliding_attention"


def live_contexts(obs: Dict[str, Any], window: int,
                  samples: int = 50) -> Optional[Dict[str, float]]:
    """Mean over the traced stretch of (rows decoding, their summed context,
    their summed min(context, window)), from the client's records: a request
    decodes from its first chunk to its last, and its context grows evenly
    from the prompt to prompt + tokens."""
    span = obs.get("trace_span")
    if not span:
        return None
    t0, t1 = span
    rows = full = seen = 0.0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        for r in obs["requests"]:
            if r["first"] is None or r["last"] is None or not r["tokens"]:
                continue
            if r["first"] <= t <= r["last"]:
                grown = (r["tokens"] - 1) * (t - r["first"]) / max(r["last"] - r["first"], 1e-9)
                ctx = r["prompt_tokens"] + grown
                rows += 1
                full += ctx
                seen += min(ctx, window)
    return {"rows": rows / samples, "sum_ctx": full / samples,
            "sum_window_ctx": seen / samples}


def step_calls(live: Dict[str, float], fields: Dict[str, Any]) -> Dict[str, float]:
    """The calls of ONE decode step, a layer each, summed by kind."""
    kinds = list(fields["layer_types"])[: fields["n_layers"]]
    head_dim = fields.get("head_size") or fields["d_model"] // fields["n_heads"]
    total = {"flops": 0.0, "bytes": 0.0}
    for kind in kinds:
        seen = live["sum_window_ctx"] if kind == SLIDING else live["sum_ctx"]
        one = layer_call(seen, live["rows"], fields["n_heads"],
                         fields["n_kv_heads"], head_dim)
        total = {k: total[k] + one[k] for k in total}
    return total


def needed(obs: Dict[str, Any], reduced: Dict[str, Any], found: Dict[str, float],
           args: Dict[str, Any]) -> Optional[Dict[str, float]]:
    f = obs["model_fields"]
    if not f.get("layer_types"):
        return None
    live = live_contexts(obs, int(f.get("sliding_window") or 0))
    if live is None or not live["rows"]:
        return None
    modules = trace_reduce.matching_modules(reduced, args["module"])
    launches = sum(m["count"] for m in modules.values()) / reduced["devices"]
    steps = launches * obs["stats"]["after"]["steps_per_sync"]
    return {k: v * steps for k, v in step_calls(live, f).items()}
