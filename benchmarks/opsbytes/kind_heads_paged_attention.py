"""Operations and bytes the paged-attention DECODE calls of a model need
whose QUERY-HEAD COUNT goes by layer (`heads_per_layer`: 48 on a full layer,
72 on a sliding layer, the KV heads the same in both), from its shapes. One
call is one layer of one decode step over every live row; a decode step
makes one a layer.

`opsbytes/window_paged_attention.py` counts every layer at the ONE count
`n_heads`; here each layer is counted at its own. Per row with `ctx` cached
positions, H_l query heads on KV heads of size hd: a FULL layer scores and
sums over all `ctx` positions, a WINDOW layer over its `min(ctx, window)`
newest: 4 x seen x H_l x hd FLOPs and 2 x seen x KV x hd elements of keys and
values (read once for the H_l / KV heads of a group), plus the row's query in
and its output out, 2 x H_l x hd elements. The gate on the heads' outputs is
no part of the call. Calls are counted from the trace's decode modules
(executions x steps a program, one call a layer), so the count is the same
whichever implementation did the work.

Hand count (tests/test_opsbytes_laguna.py): 16 rows at ctx 8,400, KV 8, hd
128, window 512, bf16. A full layer, 48 heads: (2 x 16 x 8,400 x 8 x 128 + 2
x 16 x 48 x 128) x 2 = 550,895,616 bytes; a sliding layer, 72 heads: (2 x 16
x 512 x 8 x 128 + 2 x 16 x 72 x 128) x 2 = 34,144,256 bytes; a step of 2 full
+ 3 sliding layers 1,204,224,000 bytes and 4 x 16 x 128 x (2 x 8,400 x 48 + 3
x 512 x 72) = 7,511,998,464 FLOPs.
"""

from typing import Any, Dict, Optional

from benchmarks import trace_reduce
from benchmarks.opsbytes.paged_attention import decode_call as layer_call
from benchmarks.opsbytes.window_paged_attention import SLIDING, live_contexts


def step_calls(live: Dict[str, float], fields: Dict[str, Any]) -> Dict[str, float]:
    """The calls of ONE decode step, a layer each at its own head count."""
    n = fields["n_layers"]
    kinds = list(fields["layer_types"])[:n]
    heads = list(fields["heads_per_layer"])[:n]
    head_dim = fields.get("head_size") or fields["d_model"] // fields["n_heads"]
    total = {"flops": 0.0, "bytes": 0.0}
    for kind, h in zip(kinds, heads):
        seen = live["sum_window_ctx"] if kind == SLIDING else live["sum_ctx"]
        one = layer_call(seen, live["rows"], h, fields["n_kv_heads"], head_dim)
        total = {k: total[k] + one[k] for k in total}
    return total


def needed(obs: Dict[str, Any], reduced: Dict[str, Any], found: Dict[str, float],
           args: Dict[str, Any]) -> Optional[Dict[str, float]]:
    f = obs["model_fields"]
    if not f.get("layer_types") or not f.get("heads_per_layer"):
        return None
    live = live_contexts(obs, int(f.get("sliding_window") or 0))
    if live is None or not live["rows"]:
        return None
    modules = trace_reduce.matching_modules(reduced, args["module"])
    launches = sum(m["count"] for m in modules.values()) / reduced["devices"]
    steps = launches * obs["stats"]["after"]["steps_per_sync"]
    return {k: v * steps for k, v in step_calls(live, f).items()}
