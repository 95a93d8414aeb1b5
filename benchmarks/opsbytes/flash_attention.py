"""Operations and bytes causal flash attention needs in a training step,
forward and backward, from its shapes. Per head and sequence of S positions
of head size hd, with the causal half: forward is two matmuls (scores,
weighted sum) = 2 x S^2 x hd FLOPs; backward needs five (scores again, dV,
dP, dQ, dK) = 5 x S^2 x hd. A forward pass repeated for rematerialisation,
or scores recomputed once more because dQ and dK/dV are separate kernels,
are the implementation's and do not count.

Bytes, each tensor once: forward reads q (H heads) and k, v (KV heads) and
writes o; backward reads q, k, v, o, do and writes dq, dk, dv.

Hand count (tests/test_opsbytes.py): 2 rows, S 4096, H 32, KV 8, hd 128,
bf16, one layer: 2 x 32 x 7 x 4096^2 x 128 = 962,072,674,304 FLOPs;
2 x 4096 x 128 x 2 x (2 x 32 + 2 x 8  +  4 x 32 + 2 x 8 + 2 x 8)
= 503,316,480 bytes.
"""

from typing import Any, Dict, Optional

from benchmarks import trace_reduce


def layer(rows: float, seq_len: int, heads: int, kv_heads: int, head_dim: int,
          dtype_bytes: int = 2) -> Dict[str, float]:
    per_tensor = rows * seq_len * head_dim * dtype_bytes
    forward = 2 * heads + 2 * kv_heads
    backward = 4 * heads + 2 * kv_heads + 2 * kv_heads
    return {
        "flops": rows * heads * 7.0 * seq_len * seq_len * head_dim,
        "bytes": per_tensor * (forward + backward),
    }


def needed(obs: Dict[str, Any], reduced: Dict[str, Any], found: Dict[str, float],
           args: Dict[str, Any]) -> Optional[Dict[str, float]]:
    f = obs["model_fields"]
    steps = sum(m["count"] for m in trace_reduce.matching_modules(
        reduced, args["step_module"]).values()) / reduced["devices"]
    rows_per_device = obs["job"]["rows"] / reduced["devices"]
    one = layer(rows_per_device, obs["job"]["seq_len"], f["n_heads"], f["n_kv_heads"],
                f["d_model"] // f["n_heads"])
    return {k: v * f["n_layers"] * steps for k, v in one.items()}
