"""Operations and bytes the ragged paged-attention DECODE call needs, from
its shapes. One call is one layer of one decode step over every live row.

Per row with `ctx` cached positions, H query heads in KV groups, head size
hd: scores and weighted sum are 2 x ctx x hd multiply-adds a head, so
4 x ctx x H x hd FLOPs; the row's keys and values are read once for all the
heads of a group, 2 x ctx x KV x hd elements, plus its query in and its
output out, 2 x H x hd elements. Nothing else is needed: table columns past
the context, padded rows and the cross-group pairs the kernel masks are the
implementation's, not the algorithm's.

Hand count (tests/test_opsbytes.py): 20 rows at ctx 512, H 32, KV 8, hd 128,
bf16: 20 x 4 x 512 x 32 x 128 = 167,772,160 FLOPs;
20 x (2 x 512 x 8 x 128 + 2 x 32 x 128) x 2 = 42,270,720 bytes.
"""

from typing import Any, Dict, Optional


def decode_call(sum_ctx: float, rows: float, heads: int, kv_heads: int,
                head_dim: int, dtype_bytes: int = 2) -> Dict[str, float]:
    return {
        "flops": 4.0 * sum_ctx * heads * head_dim,
        "bytes": (2.0 * sum_ctx * kv_heads * head_dim
                  + 2.0 * rows * heads * head_dim) * dtype_bytes,
    }


def live_context(obs: Dict[str, Any], samples: int = 50) -> Optional[Dict[str, float]]:
    """Mean over the traced stretch of (rows decoding, their summed context),
    from the client's records: a request decodes from its first chunk to its
    last, and its context grows evenly from the prompt to prompt + tokens."""
    span = obs.get("trace_span")
    if not span:
        return None
    t0, t1 = span
    rows = ctx = 0.0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        for r in obs["requests"]:
            if r["first"] is None or r["last"] is None or not r["tokens"]:
                continue
            if r["first"] <= t <= r["last"]:
                grown = (r["tokens"] - 1) * (t - r["first"]) / max(r["last"] - r["first"], 1e-9)
                rows += 1
                ctx += r["prompt_tokens"] + grown
    return {"rows": rows / samples, "sum_ctx": ctx / samples}


def needed(obs: Dict[str, Any], reduced: Dict[str, Any], found: Dict[str, float],
           args: Dict[str, Any]) -> Optional[Dict[str, float]]:
    live = live_context(obs)
    if live is None or not live["rows"]:
        return None
    f = obs["model_fields"]
    one = decode_call(live["sum_ctx"], live["rows"], f["n_heads"], f["n_kv_heads"],
                      f["d_model"] // f["n_heads"])
    return {k: v * found["count"] for k, v in one.items()}
