"""Sharding rules for the flagship workload (scaling-book recipe).

Pick a mesh, annotate params + activations with NamedSharding, let XLA
insert the collectives; the axes follow the standard layout:

  data  — pure data parallelism across slices/hosts (gradient psum on ICI/DCN)
  fsdp  — data parallelism with weights sharded (all-gather on use,
          reduce-scatter on grad) — the default way to span hosts
  seq   — sequence/context parallelism; activations sharded over sequence,
          attention runs as a ppermute ring (attention.py)
  model — tensor parallelism within a host's ICI-contiguous chips
  expert — expert parallelism: MoE expert banks sharded over experts, the
          token dispatch einsum becomes the all-to-all (workloads/moe.py)

Weight matrices are sharded ("fsdp" on the input dim, "model" on the output
dim) or transposed for the second matmul of each pair, so forward needs only
all-gathers on "fsdp" and one psum on "model" per block — the layout the
scaling-book derives for dense transformers.
"""

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "fsdp", "seq", "model", "expert")


def make_mesh(
    devices=None,
    *,
    data: int = 1,
    fsdp: Optional[int] = None,
    seq: int = 1,
    model: int = 1,
    expert: int = 1,
) -> Mesh:
    """Build a Mesh over the given (default: all) devices.

    `fsdp=None` absorbs whatever factor remains after data*seq*model*expert.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if fsdp is None:
        denom = data * seq * model * expert
        if n % denom:
            raise ValueError(f"{denom=} does not divide {n} devices")
        fsdp = n // denom
    shape = (data, fsdp, seq, model, expert)
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh {dict(zip(AXES, shape))} != {n} devices")
    return Mesh(np.array(devices).reshape(shape), AXES)


# Param-tree partition specs; layer stacks carry a leading None (layer dim).
PARAM_SPECS: Dict[str, Any] = {
    "embed": P(None, "fsdp"),
    "layers": {
        "wq": P(None, "fsdp", "model"),
        "wk": P(None, "fsdp", "model"),
        "wv": P(None, "fsdp", "model"),
        "wo": P(None, "model", "fsdp"),
        "w_gate": P(None, "fsdp", "model"),
        "w_up": P(None, "fsdp", "model"),
        "w_down": P(None, "model", "fsdp"),
        # MoE variants (present instead of w_gate/w_up/w_down when
        # n_experts > 0): expert bank over "expert", each expert's matrices
        # sharded like the dense MLP.
        "router": P(None, None, None),
        "we_gate": P(None, "expert", "fsdp", "model"),
        "we_up": P(None, "expert", "fsdp", "model"),
        "we_down": P(None, "expert", "model", "fsdp"),
        "router_bias": P(None, None),
        # Shared experts: a dense SwiGLU beside the bank.
        "ws_gate": P(None, "fsdp", "model"),
        "ws_up": P(None, "fsdp", "model"),
        "ws_down": P(None, "model", "fsdp"),
        # Latent attention (present instead of wq/wk/wv): the rank
        # bottlenecks stay whole, the per-head up-projections shard
        # like wq.
        "wq_a": P(None, "fsdp", None),
        "q_norm": P(None, None),
        "wq_b": P(None, None, "model"),
        "wkv_a": P(None, "fsdp", None),
        "kv_norm": P(None, None),
        "wkv_b": P(None, None, "model"),
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
    },
    "final_norm": P(None),
    "lm_head": P("fsdp", "model"),
}
# A model's leading dense layers are a second stack of the same blocks.
PARAM_SPECS["dense_layers"] = PARAM_SPECS["layers"]

# LoRA adapter matrices ride under "layers" as f"{base}_a" (L, in, r) /
# f"{base}_b" (L, r, out): A is sharded on its input dim like the base
# weight's input, B on its output dim; the tiny rank dim stays replicated.
LORA_SPECS: Dict[str, Any] = {
    "_a": P(None, "fsdp", None),
    "_b": P(None, None, "model"),
}

# Column-parallel serving layout: "model" appears ONLY on output dims, so
# every contraction runs over a replicated axis. Standard TP (contraction
# sharded on wo/w_down) inserts psums whose summation order differs from
# the unsharded program — near-tied temp-0 argmaxes flip and token streams
# diverge within a few decode steps. With outputs-only sharding each shard
# computes its columns of every matmul bit-identically to the unsharded
# program (all-gathers move bits, they never re-reduce), so a sharded
# engine stays token- and KV-pool-bit-exact vs single-device. That trades
# a psum for an all-gather per block — fine at the latency-bound decode
# shapes serving cares about, and it is the property the disaggregation
# drill pins.
SERVING_PARAM_SPECS: Dict[str, Any] = {
    "embed": P(None, None),
    "layers": {
        "wq": P(None, None, "model"),
        "wk": P(None, None, "model"),
        "wv": P(None, None, "model"),
        "wo": P(None, None, "model"),
        "w_gate": P(None, None, "model"),
        "w_up": P(None, None, "model"),
        "w_down": P(None, None, "model"),
        "router": P(None, None, None),
        "we_gate": P(None, "expert", None, "model"),
        "we_up": P(None, "expert", None, "model"),
        "we_down": P(None, "expert", None, "model"),
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
    },
    "final_norm": P(None),
    "lm_head": P(None, "model"),
}

# Serving LoRA: the x@A contraction (over d_model) must stay replicated
# like every other serving contraction, so A is fully replicated and only
# B's output dim rides "model" (matching the base weight's output shard).
SERVING_LORA_SPECS: Dict[str, Any] = {
    "_a": P(None, None, None),
    "_b": P(None, None, "model"),
}

# Paged KV pools are (L, num_blocks, block_size) + ModelConfig.
# kv_row_shapes(): (KV_heads, head_dim) for the GQA models a mesh serves;
# shard the KV-head dim over "model" to match the column-parallel wk/wv
# output shard. (A latent pool has one row for all heads and nothing to
# shard there: the engine refuses a mesh for it, and SERVING_PARAM_SPECS
# has no entry for its weights.) Block tables / lengths / sampling params
# stay replicated (they are host-driven control state).
SERVING_KV_POOL_SPEC = P(None, None, None, "model", None)

# Activations: batch over (data, fsdp), sequence over seq.
BATCH_SPEC = P(("data", "fsdp"), "seq")


def param_shardings(mesh: Mesh, params_like: Any) -> Any:
    """NamedSharding tree matching a params (or opt-state) pytree.

    Optimizer states mirror their param's spec; scalars are replicated.
    """
    specs = _broadcast_specs(params_like)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def serving_param_shardings(mesh: Mesh, params_like: Any) -> Any:
    """NamedSharding tree for the column-parallel serving layout.

    Works for the float target, an int8 `QTensor` drafter (q mirrors its
    float parent, per-channel scales replicate), and LoRA-extended trees.
    """
    specs = _broadcast_specs(
        params_like, specs=SERVING_PARAM_SPECS, lora=SERVING_LORA_SPECS,
        table="SERVING_PARAM_SPECS",
    )
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def serving_state_shardings(mesh: Mesh, state_like: Any) -> Any:
    """Shardings for a `PagedDecodeState`-shaped pytree: the k/v block
    pools shard over "model" on the KV-head dim, everything else (block
    tables, lengths, sampling params — host-driven control state) is
    replicated."""

    def spec_for(path: Tuple, leaf: Any) -> NamedSharding:
        key = None
        if path:
            p = path[-1]
            key = getattr(p, "name", getattr(p, "key", None))
        if key in ("k", "v") and getattr(leaf, "ndim", 0) == 5:
            return NamedSharding(mesh, SERVING_KV_POOL_SPEC)
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, state_like)


def _broadcast_specs(
    tree: Any,
    specs: Optional[Dict[str, Any]] = None,
    lora: Optional[Dict[str, Any]] = None,
    table: str = "PARAM_SPECS",
) -> Any:
    """Map a spec table onto an arbitrary pytree shaped like params (e.g.
    the adam mu/nu trees), replicating anything that isn't a weight array.

    Two families of leaves don't appear in the tables by name and get
    structural rules instead: LoRA adapters (dict keys `f"{base}_a"` /
    `f"{base}_b"` next to a base weight that does have a rule) and
    `QTensor` int8 weights (NamedTuple leaves `.q` / `.scale` hanging off
    a keyed weight — q inherits the parent's spec unchanged, scale is
    per-output-channel f32 and replicates)."""
    spec_table = PARAM_SPECS if specs is None else specs
    lora_table = LORA_SPECS if lora is None else lora

    def spec_for(path: Tuple, leaf: Any) -> P:
        node: Any = spec_table
        for p in path:
            key = getattr(p, "key", getattr(p, "name", None))
            if isinstance(node, dict):
                if key in node:
                    node = node[key]
                elif (
                    isinstance(key, str)
                    and key[-2:] in lora_table
                    and key[:-2] in node
                ):
                    node = lora_table[key[-2:]]
            elif key == "scale":
                # QTensor per-channel scale: (..., 1, out) f32, replicated.
                return P()
            # key == "q" falls through: the int8 payload has the same
            # shape/layout as its float parent, so the parent's spec holds.
        ndim = getattr(leaf, "ndim", 0)
        if isinstance(node, P):
            if ndim == len(node):
                return node
            if ndim == 0:
                return P()  # optimizer scalars (step counts etc.)
            raise ValueError(
                f"param at {jax.tree_util.keystr(path)} has ndim={ndim} but "
                f"its {table} entry is {node} — update sharding rules"
            )
        if ndim >= 2:
            # A weight-sized array with no matching rule would silently
            # replicate (and so would its f32 optimizer moments) — fail loud.
            raise ValueError(
                f"no {table} entry for weight at {jax.tree_util.keystr(path)} "
                f"(shape {getattr(leaf, 'shape', '?')}) — add a sharding rule"
            )
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, tree)


class ServingShardings(NamedTuple):
    """The four sharding handles a serving-engine jitted program needs:
    the params tree, the PagedDecodeState tree, a bare KV pool array, and
    the replicated sharding for host-driven scalars/tables. Passed into
    the `kv_blocks` factories so every program is jitted with explicit
    in/out shardings — same traced logic, partitioned state."""

    params: Any
    state: Any
    pool: NamedSharding
    replicated: NamedSharding


def make_serving_shardings(
    mesh: Mesh, params_like: Any, state_like: Any
) -> ServingShardings:
    return ServingShardings(
        params=serving_param_shardings(mesh, params_like),
        state=serving_state_shardings(mesh, state_like),
        pool=NamedSharding(mesh, SERVING_KV_POOL_SPEC),
        replicated=NamedSharding(mesh, P()),
    )


def shard_tree(mesh: Mesh, tree: Any) -> Any:
    """Device_put a pytree with its canonical shardings."""
    return jax.device_put(tree, param_shardings(mesh, tree))
