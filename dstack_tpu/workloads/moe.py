"""Sparse mixture-of-experts MLP with expert parallelism, TPU-native.

Mixtral-class MoE done the GShard/Switch way by default: routing builds
dense dispatch/combine tensors and the layer is einsums — every op is
static-shaped, tiles onto the MXU, and XLA inserts the token all-to-all
from the sharding constraints (expert weights and expert inputs live on
the "expert" mesh axis; tokens live on the batch axes). Capacity
overflow drops tokens by construction: `one_hot` of an out-of-range slot
index is the zero row, so overflowing tokens simply fall out of dispatch
and keep their residual value. A gather/scatter formulation of the SAME
permutation exists as `config.moe_impl="gather"` (`_moe_mlp_gather`) —
measured 6% slower on v5e (docs/design/perf.md: the combine's backward
scatter-add runs far below MXU throughput), kept as the counterfactual.

Parity note: the reference orchestrator ships no model math (SURVEY §2.7
"absent by design" — users bring torch MoE in containers); this is part of
the framework-native workload library the orchestrator launches.
"""

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dstack_tpu.workloads.config import ModelConfig

Params = Dict[str, Any]


def expert_capacity(c: ModelConfig, seq_len: int) -> int:
    """Per-expert slot count for one batch row's sequence (static)."""
    return max(
        1,
        int(
            math.ceil(
                c.experts_per_token * seq_len * c.capacity_factor / c.n_experts
            )
        ),
    )


def route_assignments(
    c: ModelConfig, h: jnp.ndarray, router: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing -> (gate_vals (B,S,k) f32, gate_idx (B,S,k) i32,
    slot (B,S,k) i32, sel (B,S,k,E) f32 one-hot, aux scalar).
    slot >= C marks a dropped token.

    Scores are a softmax over the experts, or (router_score "sigmoid") a
    sigmoid per expert; then the k best are chosen by score + `bias`
    ((E,) f32, the aux-loss-free router's selection bias: it chooses and
    does not weigh), their scores renormalised to sum to 1
    and multiplied by routed_scaling. A sigmoid router has no
    load-balance term: its aux is 0.

    Slot assignment is priority-ordered: every token's first choice is
    placed before any token's second choice (GShard ordering), via one
    cumsum over the (choice-major) flattened token axis.
    """
    B, S, _ = h.shape
    E, k = c.n_experts, c.experts_per_token

    logits = jnp.einsum(
        "bsd,de->bse", h, router, preferred_element_type=jnp.float32
    )
    if c.router_score == "sigmoid":
        probs = jax.nn.sigmoid(logits)  # (B,S,E) f32
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        gate_vals, gate_idx = lax.top_k(probs, k)  # (B,S,k)
    else:
        _, gate_idx = lax.top_k(probs + bias, k)
        gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )
    if c.routed_scaling != 1.0:
        gate_vals = gate_vals * c.routed_scaling

    sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (B,S,k,E)
    # Choice-major flatten so cumsum hands out slots first-choices-first.
    sel_flat = sel.transpose(0, 2, 1, 3).reshape(B, k * S, E)
    pos_flat = jnp.cumsum(sel_flat, axis=1) * sel_flat - 1.0
    pos = pos_flat.reshape(B, k, S, E).transpose(0, 2, 1, 3)  # (B,S,k,E)
    slot = jnp.sum(pos * sel, axis=-1).astype(jnp.int32)  # (B,S,k)

    if c.router_score == "sigmoid":
        return gate_vals, gate_idx, slot, sel, jnp.float32(0.0)
    # Switch-style load-balance loss: E * sum_e mean_prob_e * top1_share_e.
    mean_prob = jnp.mean(probs, axis=(0, 1))  # (E,)
    top1_share = jnp.mean(sel[:, :, 0, :], axis=(0, 1))  # (E,)
    aux = jnp.float32(E) * jnp.sum(mean_prob * top1_share)
    return gate_vals, gate_idx, slot, sel, aux


def route(
    c: ModelConfig, h: jnp.ndarray, router: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing -> (dispatch (B,S,E,C), combine (B,S,E,C), aux scalar)."""
    C = expert_capacity(c, h.shape[1])
    gate_vals, _, slot, sel, aux = route_assignments(c, h, router, bias)
    slot_oh = jax.nn.one_hot(slot, C, dtype=jnp.float32)  # 0-row when >= C
    dispatch = jnp.einsum("bske,bskc->bsec", sel, slot_oh)
    combine = jnp.einsum("bsk,bske,bskc->bsec", gate_vals, sel, slot_oh)
    return dispatch, combine, aux


def _expert_ffn(h_dtype, expert_in: jnp.ndarray, p: Params) -> jnp.ndarray:
    """SwiGLU over the expert bank: (E,B,C,D) -> (E,B,C,D)."""

    def bank(w):
        # Serving may hand us int8 expert banks; the convert+scale fuses
        # into the einsum read (workloads/quant.py).
        from dstack_tpu.workloads.quant import QTensor, dequantize_tensor

        return dequantize_tensor(w, h_dtype) if isinstance(w, QTensor) else w

    gate = jnp.einsum(
        "ebcd,edf->ebcf", expert_in, bank(p["we_gate"]),
        preferred_element_type=jnp.float32,
    )
    up = jnp.einsum("ebcd,edf->ebcf", expert_in, bank(p["we_up"]))
    act = (jax.nn.silu(gate).astype(h_dtype)) * up
    return jnp.einsum("ebcf,efd->ebcd", act, bank(p["we_down"]))


def moe_mlp(
    c: ModelConfig,
    h: jnp.ndarray,
    p: Params,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The routed SwiGLU experts on a normed input h -> (out, aux_loss).

    p carries: router (D,E) f32, we_gate/we_up (E,D,F), we_down (E,F,D).
    Two interchangeable dispatch formulations (config.moe_impl):
      - "einsum": dense GShard dispatch/combine tensors; every op a
        static matmul. Costs 2*E*C*D FLOPs/token each way (~30% of the
        active-expert FLOPs at the bench shape).
      - "gather": the same slot permutation applied with take/scatter —
        O(k*D)/token of data movement, zero dispatch FLOPs. Backward of
        the gathers is a unique-index scatter-add. Same math: identical
        terms, f32-accumulated (tests pin equality).
    """
    if c.moe_impl == "gather":
        return _moe_mlp_gather(c, h, p, mesh)
    if c.moe_impl != "einsum":
        raise ValueError(
            f'moe_impl={c.moe_impl!r}: expected "einsum" or "gather"'
        )
    with jax.named_scope("moe/route"):
        dispatch, combine, aux = route(
            c, h, p["router"], p.get("router_bias")
        )

    def constrain(x, spec):
        if mesh is not None and "expert" in mesh.axis_names:
            return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        return x

    # Token all-to-all: tokens (batch-sharded) -> expert slots
    # (expert-sharded). XLA materializes the collective from the two
    # constraints on either side of this einsum.
    expert_in = jnp.einsum(
        "bsec,bsd->ebcd", dispatch.astype(h.dtype), h
    )
    expert_in = constrain(expert_in, P("expert", ("data", "fsdp"), None, None))
    with jax.named_scope("moe/experts"):
        expert_out = _expert_ffn(h.dtype, expert_in, p)
    expert_out = constrain(
        expert_out, P("expert", ("data", "fsdp"), None, None)
    )

    out = jnp.einsum(
        "bsec,ebcd->bsd", combine.astype(h.dtype), expert_out
    )
    return out, aux


def _moe_mlp_gather(
    c: ModelConfig,
    h: jnp.ndarray,
    p: Params,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gather/scatter dispatch: the einsum path's math without its FLOPs.

    Builds the inverse slot permutation (src token per expert slot) with
    one small int scatter, then moves rows with gathers. Dropped tokens
    (slot >= C) route to a zero pad row both ways, matching the einsum
    path's zero contribution. The gate multiply stays f32.
    """
    B, S, D = h.shape
    E, k = c.n_experts, c.experts_per_token
    C = expert_capacity(c, S)
    gate_vals, gate_idx, slot, _, aux = route_assignments(
        c, h, p["router"], p.get("router_bias")
    )

    def constrain(x, spec):
        if mesh is not None and "expert" in mesh.axis_names:
            return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        return x

    valid = slot < C
    # Flat slot id; overflow writes the trailing dummy column (sliced off).
    sid = jnp.where(valid, gate_idx * C + slot, E * C)  # (B,S,k)
    b_ix = jnp.arange(B)[:, None, None]
    s_ix = jnp.broadcast_to(jnp.arange(S)[None, :, None], (B, S, k))
    # Inverse permutation: src[b, e*C+c] = s. Slot ids are unique per b by
    # construction (the cumsum hands each slot to at most one token), so
    # the scatter has no collisions; empty slots keep the S sentinel and
    # gather the zero pad row.
    src = jnp.full((B, E * C + 1), S, jnp.int32)
    src = src.at[b_ix, sid].set(s_ix, mode="drop")[:, : E * C]

    h_pad = jnp.concatenate([h, jnp.zeros((B, 1, D), h.dtype)], axis=1)
    expert_in = jnp.take_along_axis(h_pad, src[:, :, None], axis=1)
    expert_in = expert_in.reshape(B, E, C, D).transpose(1, 0, 2, 3)
    expert_in = constrain(expert_in, P("expert", ("data", "fsdp"), None, None))

    expert_out = _expert_ffn(h.dtype, expert_in, p)
    expert_out = constrain(
        expert_out, P("expert", ("data", "fsdp"), None, None)
    )

    flat = expert_out.transpose(1, 0, 2, 3).reshape(B, E * C, D)
    flat = jnp.concatenate([flat, jnp.zeros((B, 1, D), flat.dtype)], axis=1)
    gathered = flat[b_ix, sid]  # (B,S,k,D); overflow ids hit the zero row
    out = jnp.sum(
        gate_vals[..., None] * gathered.astype(jnp.float32), axis=2
    ).astype(h.dtype)
    return out, aux


def moe_block(
    c: ModelConfig,
    x: jnp.ndarray,
    p: Params,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pre-norm MoE block with residual: x -> (x + moe(norm(x)), aux).
    With shared experts (`ws_*` weights) every token also passes through
    their SwiGLU, added beside the routed sum."""
    from dstack_tpu.workloads.transformer import _silu, linear, rms_norm

    h = rms_norm(x, p["mlp_norm"], c.norm_eps)
    out, aux = moe_mlp(c, h, p, mesh)
    if "ws_gate" in p:
        with jax.named_scope("moe/shared"):
            gate = _silu(linear(h, p["ws_gate"]))
            out = out + linear(gate * linear(h, p["ws_up"]), p["ws_down"])
    return x + out, aux
