"""Operations and bytes the state-space layers' state update needs, from
shapes and live slots: the same whatever implements it (XLA's fusions under
the `mamba/scan` and `mamba/conv` scopes today, a `selective_scan_*` kernel
later: both read on this yardstick).

One UPDATE is one state-space layer of one decode step (over the live slots)
or of one prefill chunk (over its valid tokens). With Di = mamba_expand x
d_model, N = mamba_d_state, K = mamba_d_conv, activations of 2 bytes:

- a slot's state is h, N x Di float32, and the convolution's tail, (K - 1) x
  Di activations: read once and written once an update;
- a token brings the convolution's input x and the gate z (Di activations
  each), and delta (Di float32), B and C (N float32 each) from the
  projections, and takes y out (Di activations): 8 Di + 8 N bytes in, 2 Di out;
- element-wise work a token and state value: exp, two products and a sum for
  h, a product and a sum for y, two products for the inputs: 9 a value, and
  2 K a channel for the convolution. (The chip's matrix peak says nothing
  about them; they are counted so that the roofline's bound is said, and it
  is bytes by a factor of hundreds.)

Nothing else is needed: the rows of slots that are not live, which the
decode program moves all the same (kv.live_state_share), a state read twice
by two fusions, and a chunk's padded tail are the implementation's.

Updates are counted from the MODULES the trace holds (decode: executions x
steps a program x state layers; chunk: executions x state layers), as
opsbytes/mla_attention.py counts its calls, and not from the matched
operations: an XLA formulation is several operations an update and a kernel
is one. Live slots come from the client's records over the traced stretch
(opsbytes/paged_attention.live_context); a chunk's valid tokens from the
server's counters over the window (prefill tokens computed / chunks).

Hand count (tests/test_opsbytes_ssm.py), Di 5120, N 16, K 4, bf16: one
decode update over 64 live slots: 64 x (2 x 327,680 + 2 x 30,720 + 8 x 5120
+ 8 x 16 + 2 x 5120) = 64 x 768,128 = 49,160,192 bytes, 64 x (9 x 81,920 +
8 x 5120) = 49,807,360 operations; one chunk update of 300 valid tokens:
716,800 + 300 x 51,328 = 16,115,200 bytes.
"""

from typing import Any, Dict, Optional

from benchmarks import trace_reduce
from benchmarks.opsbytes.paged_attention import live_context


def state_layers(f: Dict[str, Any]) -> int:
    period, offset = f.get("attn_layer_period", 0), f.get("attn_layer_offset", 0)
    if not period:
        return 0
    return sum(i % period != offset for i in range(f["n_layers"]))


def update(tokens: float, rows: float, d_inner: int, d_state: int, d_conv: int,
           dtype_bytes: int = 2) -> Dict[str, float]:
    """One state-space layer's update: `rows` sequences' state in and out,
    `tokens` tokens through the recurrence (a decode step: one a live slot)."""
    state = d_state * d_inner * 4 + (d_conv - 1) * d_inner * dtype_bytes
    token = (3 * dtype_bytes + 4) * d_inner + 8 * d_state
    return {
        "flops": tokens * (9.0 * d_state * d_inner + 2.0 * d_conv * d_inner),
        "bytes": 2.0 * rows * state + tokens * token,
    }


def needed(obs: Dict[str, Any], reduced: Dict[str, Any], found: Dict[str, float],
           args: Dict[str, Any]) -> Optional[Dict[str, float]]:
    f = obs["model_fields"]
    layers = state_layers(f)
    if not layers:
        return None
    modules = trace_reduce.matching_modules(reduced, args["module"])
    launches = sum(m["count"] for m in modules.values()) / reduced["devices"]
    stats = obs["stats"]
    sizes = (f["mamba_expand"] * f["d_model"], f["mamba_d_state"], f["mamba_d_conv"])
    if "decode" in args["module"]:
        live = live_context(obs)
        if live is None or not live["rows"]:
            return None
        updates = launches * stats["after"]["steps_per_sync"] * layers
        one = update(live["rows"], live["rows"], *sizes)
    else:
        diff = lambda key: stats["after"][key] - stats["before"][key]
        if not diff("prefill_chunks_total"):
            return None
        tokens = diff("prefill_tokens_computed_total") / diff("prefill_chunks_total")
        updates = launches * layers
        one = update(tokens, 1.0, *sizes)
    return {k: v * updates for k, v in one.items()}
