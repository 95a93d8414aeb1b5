"""Model FLOPs of one trained token, forward and backward (3 x forward): the
standard accounting behind model-FLOPs utilisation (PaLM, appendix B).
Copied from `ModelConfig.flops_per_token` so that no PR can move it; PERF.md
lists the original under Open questions.

Counts: the parameter matmuls a token passes through (attention
projections; the MLP, or for an expert layer the k experts a token is routed
to plus the router), causal attention scores and weighted sum at `seq_len`
(2 x S x H x hd a layer, the causal half of 4), and the output head. Does
not count: recomputation, slots of expert capacity that hold no token, the
dispatch and combine matmuls of a dense MoE formulation, the embedding
lookup."""

from typing import Any, Dict


def flops_per_token(f: Dict[str, Any], seq_len: int) -> float:
    d, ff, v = f["d_model"], f["d_ff"], f["vocab_size"]
    heads, kv = f["n_heads"], f["n_kv_heads"]
    hd = d // heads
    attn_proj = 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
    experts = f.get("n_experts", 0)
    if experts:
        mlp = 3 * 2 * d * ff * f["experts_per_token"] + 2 * d * experts
    else:
        mlp = 3 * 2 * d * ff
    per_layer = attn_proj + mlp + 2 * seq_len * heads * hd
    return 3.0 * (f["n_layers"] * per_layer + 2 * d * v)
