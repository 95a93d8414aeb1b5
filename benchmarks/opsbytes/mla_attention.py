"""Operations and bytes the latent-attention DECODE call needs, from its
shapes. One call is one layer of one decode step over every live row,
attending in the absorbed form over the latent cache.

A cached token is one row for all H heads: `kv_lora_rank` latent values and
`qk_rope_head_dim` rotary key values (512 + 64 = 576 for GLM-4.7-Flash). Per
row with `ctx` cached positions a head scores over the whole row and sums
the latent part, so 2 x ctx x (row + latent) FLOPs a head; the row's ctx
cache rows are read ONCE for all heads, ctx x row elements, plus its absorbed
query in (H x row) and its latent output out (H x latent). Nothing else is
needed: the padding of a pool row to whole lanes, table columns past the
context and dead rows are the implementation's, not the algorithm's.

The number of calls is counted from the trace's decode modules (executions x
steps a program x layers), not from the matched operations, so the count is
the same whichever implementation (one Pallas call, or the lax path's many
operations) did the work.

Hand count (tests/test_opsbytes_mla.py): 16 rows at ctx 8192, H 20, row 576,
latent 512, bf16: 16 x 8192 x 20 x 2 x (576 + 512) = 5,704,253,440 FLOPs;
(16 x 8192 x 576 + 16 x 20 x (576 + 512)) x 2 = 151,691,264 bytes.
"""

from typing import Any, Dict, Optional

from benchmarks import trace_reduce
from benchmarks.opsbytes.paged_attention import live_context


def decode_call(sum_ctx: float, rows: float, heads: int, latent: int, rope: int,
                dtype_bytes: int = 2) -> Dict[str, float]:
    row = latent + rope
    return {
        "flops": 2.0 * sum_ctx * heads * (row + latent),
        "bytes": (sum_ctx * row + rows * heads * (row + latent)) * dtype_bytes,
    }


def needed(obs: Dict[str, Any], reduced: Dict[str, Any], found: Dict[str, float],
           args: Dict[str, Any]) -> Optional[Dict[str, float]]:
    live = live_context(obs)
    f = obs["model_fields"]
    if live is None or not live["rows"] or not f.get("kv_lora_rank"):
        return None
    modules = trace_reduce.matching_modules(reduced, args["module"])
    launches = sum(m["count"] for m in modules.values()) / reduced["devices"]
    calls = launches * obs["stats"]["after"]["steps_per_sync"] * f["n_layers"]
    one = decode_call(live["sum_ctx"], live["rows"], f["n_heads"],
                      f["kv_lora_rank"], f["qk_rope_head_dim"])
    return {k: v * calls for k, v in one.items()}
