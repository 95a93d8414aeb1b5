"""Sparse mixture-of-experts MLP with expert parallelism, TPU-native.

Mixtral-class MoE, one result in two formulations that `plan` chooses
between from a call's shapes, at trace time:

- capacity (GShard/Switch): routing builds dense dispatch/combine tensors
  and the layer is einsums over experts x capacity slots a batch row —
  every op is static-shaped, tiles onto the MXU, and XLA inserts the token
  all-to-all from the sharding constraints (expert weights and expert
  inputs live on the "expert" mesh axis; tokens live on the batch axes).
  Capacity overflow drops tokens by construction: `one_hot` of an
  out-of-range slot index is the zero row, so overflowing tokens simply
  fall out of dispatch and keep their residual value. It multiplies every
  slot, filled or not, and reads every expert's weights: right where the
  rows touch most of the bank, or where the bank is sharded.
- routed: the rows routing chose, sorted by expert, go through three
  grouped matmuls whose group sizes are the rows each expert received
  (`_moe_mlp_routed`): no (B,S,E,C) tensor, no dispatch FLOPs, no empty
  slot, no read of an expert that received no row. A dropped row weighs
  zero, so the result is the capacity path's at any capacity factor. A
  train step, a long prefill chunk (docs/design/perf.md) and a decode
  launch whose few rows touch few of the experts take it.

A device may hold a SHARE of the bank (`ModelConfig.held`: experts
`first .. first + held - 1` of the `n_experts` the router scores, as one of
the devices that divide a layer under expert parallelism holds). Routing, the
top k, the renormalisation and the scaling are over all `n_experts`; the
weights, the dispatch and the grouped matmul are over the experts held, and a
token's pairs that fall on experts held elsewhere add nothing here. What comes
out is this device's part of the layer's sum; nothing stands in for the other
devices or for the exchange with them. With the whole bank held both paths
are what they were, operation for operation.

Parity note: the reference orchestrator ships no model math (SURVEY §2.7
"absent by design" — users bring torch MoE in containers); this is part of
the framework-native workload library the orchestrator launches.
"""

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dstack_tpu.workloads.config import ModelConfig

Params = Dict[str, Any]

# The mesh axes a batch's rows are sharded over (sharding.BATCH_SPEC).
_BATCH_AXES = ("data", "fsdp")


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
    """Top-k routing -> (dispatch (B,S,E,C), combine (B,S,E,C), aux scalar),
    E the experts held here."""
    gate_vals, gate_idx, slot, sel, aux = route_assignments(c, h, router, bias)
    return _dispatch_combine(c, h.shape[1], gate_vals, slot, sel) + (aux,)


def _dispatch_combine(c: ModelConfig, seq_len: int, gate_vals, slot, sel):
    """The capacity path's dispatch and combine tensors (B,S,E,C) from
    `route_assignments`' answer, over the columns of the experts held."""
    C = expert_capacity(c, seq_len)
    if c.expert_share:
        first, held = c.held
        sel = sel[..., first:first + held]
    slot_oh = jax.nn.one_hot(slot, C, dtype=jnp.float32)  # 0-row when >= C
    dispatch = jnp.einsum("bske,bskc->bsec", sel, slot_oh)
    combine = jnp.einsum("bsk,bske,bskc->bsec", gate_vals, sel, slot_oh)
    return dispatch, combine


def local_pairs(c: ModelConfig, gate_idx: jnp.ndarray, counted: jnp.ndarray):
    """-> (2,) int32: the (token, expert) pairs routing chose for the tokens
    `counted` (B,S) bool marks (padding and dead rows route too, and count
    for nothing), and those of them that fell on an expert held here."""
    first, held = c.held
    here = (gate_idx >= first) & (gate_idx < first + held)
    return jnp.stack([
        jnp.sum(counted) * gate_idx.shape[-1],
        jnp.sum(here & counted[..., None]),
    ]).astype(jnp.int32)


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


def bank_slots(
    rows: int, row_len: int, k: int, n_experts: int, capacity: int, tile: int,
    held: Optional[int] = None, touched: float = 1.0,
) -> Tuple[int, int]:
    """Expert slots one layer multiplies for `rows` batch rows of
    `row_len` tokens under each formulation -> (capacity, routed), in a
    bank that holds `held` of the `n_experts` routed over (None: all).

    The capacity dispatch fills `capacity` slots an expert and row whether
    or not a token was routed there; the routed path multiplies the routed
    rows that fall on the bank (all rows * row_len * k of them on a whole
    bank, held / n_experts of them in the mean on a share: what the counts
    of a run read is `local_pairs`), and its grouped matmul at most one
    row tile more for each of the `touched` share of the experts held
    (a group that ends inside a tile pays for the whole tile)."""
    held = n_experts if held is None else held
    return (
        held * rows * capacity,
        rows * row_len * k * held // n_experts + round(held * touched) * tile,
    )


def row_tile(routed_rows: int, n_experts: int) -> int:
    """The grouped matmul's row tile for `routed_rows` rows in
    `n_experts` groups: the largest of 512, 256, 128 that divides the
    rows and does not exceed an expert's mean share of them (a group
    pays for whole tiles), at least 128 (the MXU's width); 0 where 128
    does not divide the rows, which the routed path then cannot take."""
    if routed_rows % 128:
        return 0
    tile = 128
    while (
        tile < 512
        and routed_rows % (2 * tile) == 0
        and 2 * tile * n_experts <= routed_rows
    ):
        tile *= 2
    return tile


def touched_share(tokens: int, k: int, n_experts: int) -> float:
    """The share of the experts that `tokens` tokens are expected to route
    to, each choosing k of the `n_experts` at random: 1 - (1 - k / E) **
    tokens. Routing is over all the experts, so it is also the share of
    the experts of any part of the bank that a device holds."""
    return 1.0 - (1.0 - k / n_experts) ** tokens


# A call of one token a row (a decode launch) is bound by the weights' BYTES:
# the capacity path reads every expert held, the routed path only those some
# row chose, in row tiles of `_FEW_ROWS_TILE` (the routed rows are padded to a
# whole tile). `plan` routes it where its rows are expected to touch at most
# `_TOUCHED_MAX` of the experts (the measurements that set both: `plan`).
_FEW_ROWS_TILE = 32
_TOUCHED_MAX = 0.80


def plan(
    c: ModelConfig, rows: int, row_len: int, whole_bank: bool = True
) -> Tuple[bool, int, int]:
    """Which formulation a call of `rows` x `row_len` tokens on a device
    takes -> (routed, slots it multiplies, the routed path's row tile).

    A pure function of the call's shapes: capacity wherever the bank is
    not whole on the device (`whole_bank` false: `moe_mlp` tells from the
    mesh and the weights' types). Else a call of one token a row is routed
    where its rows are expected to touch at most `_TOUCHED_MAX` of the
    experts (`touched_share`): the grouped matmul then reads only theirs;
    a longer call where the routed path multiplies at most two thirds of
    the capacity path's slots. `moe_mlp` dispatches on it, `_moe_mlp_routed`
    takes its tile and the engine's slot counters read it, so the three
    cannot disagree.

    What set the two thirds: one expert layer forward on the v5e, both
    ways, ms (my chip run, PR 32; capacity slots : routed slots with
    their tiles -> capacity, routed):
      train row, 4,096 tokens top-2 of 8      32,768 : 12,288 -> 80.4, 24.9
                                     (forward and backward 245.5, ~78)
      512-token chunk, top-4 of 64 (latent)   32,768 : 10,240 -> 6.00, 2.88
      512-token chunk, top-8 of 64 (mellum)   32,768 : 12,288 -> 4.10, 2.29
      256-token chunk, top-4 of 64            16,384 :  9,216 -> 3.07, 2.62
      256-token chunk, top-8 of 64            16,384 : 10,240 -> 2.23, 2.07
      128-token chunk, top-8 of 64             8,192 :  9,216 -> 1.84, 1.97
      128-token chunk, top-2 of 8 (rollout)    1,024 :  1,280 -> 4.59, 5.02
    What set `_TOUCHED_MAX` and `_FEW_ROWS_TILE`: one expert layer of 16
    rows of one token, routed at random, both ways on a TPU v5e, ms (the
    experts the rows touch -> capacity, routed at row tiles of 16 and 32
    beside 2 MiB weight tiles, and of 32 beside 4 MiB ones):
      top-10 of 256, 128 held, 3,072 x 1,024    47.1% -> 3.25, 1.54 1.53 1.53
      top-4 of 64, 2,048 x 1,536                64.4% -> 1.65, 1.16 1.14 1.14
      top-8 of 64, 2,304 x 896                  88.2% -> 1.14, 1.09 1.06 1.02
      top-2 of 8, 4,096 x 14,336                99.0% -> 3.79, 4.14 3.79 3.77
    The routed path takes 1.0 to 1.1 times the touched share of the
    capacity path's time, so the two meet near 0.9 of the experts;
    `_TOUCHED_MAX` keeps a tenth of room below that."""
    k, E, held = c.experts_per_token, c.n_experts, c.held[1]
    capacity = expert_capacity(c, row_len)
    if row_len == 1:
        touched, tile = touched_share(rows, k, E), _FEW_ROWS_TILE
        at_capacity, routed = bank_slots(
            rows, 1, k, E, capacity, tile, held, touched)
        takes = touched <= _TOUCHED_MAX
    else:
        tile = row_tile(rows * row_len * k, E)
        at_capacity, routed = bank_slots(rows, row_len, k, E, capacity, tile, held)
        takes = tile > 0 and 3 * routed <= 2 * at_capacity
    if whole_bank and takes:
        return True, routed, tile
    return False, at_capacity, tile


def _whole_bank(p: Params, mesh: Optional[Mesh], partitioned: bool) -> bool:
    """True where every device holds, or is handed, the whole expert bank
    as plain arrays: no int8 bank (the grouped matmul takes no scale), no
    program that GSPMD partitions without telling this module how
    (`partitioned`: a serving engine on a mesh) and no mesh axis but the
    batch axes larger than 1 (an `expert` axis makes the dispatch einsum
    the token all-to-all; `model` and `seq` shard the bank's columns and
    a row's tokens)."""
    from dstack_tpu.workloads.quant import QTensor

    if partitioned or any(
        isinstance(p[w], QTensor) for w in ("we_gate", "we_up", "we_down")
    ):
        return False
    return mesh is None or all(
        size == 1 for axis, size in mesh.shape.items()
        if axis not in _BATCH_AXES
    )


def takes_routed_path(
    c: ModelConfig,
    rows: int,
    row_len: int,
    p: Params,
    mesh: Optional[Mesh] = None,
    partitioned: bool = False,
) -> bool:
    """Whether `moe_mlp` on `rows` x `row_len` tokens (the whole batch's,
    under this mesh) with the weights `p` takes the routed path: `plan`
    on what one device holds and on whether the bank is whole there."""
    shards = _batch_shards(mesh)
    return rows % shards == 0 and plan(
        c, rows // shards, row_len, _whole_bank(p, mesh, partitioned)
    )[0]


def moe_mlp(
    c: ModelConfig,
    h: jnp.ndarray,
    p: Params,
    mesh: Optional[Mesh] = None,
    partitioned: bool = False,
    layer: Optional[jnp.ndarray] = None,
    counted: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """The routed SwiGLU experts on a normed input h -> (out, aux_loss), and
    with `counted` (B,S) bool -> (out, aux_loss, `local_pairs` of the tokens
    it marks).

    p carries: router (D,E) f32, we_gate/we_up (E,D,F), we_down (E,F,D).
    `plan` chooses the formulation from what this call can see: the rows
    and tokens a device holds, the mesh, the weights' types (and
    `partitioned`, which a caller inside a GSPMD-partitioned program
    states because a traced weight does not).

    A caller whose layers are a stack may hand the routed path the WHOLE
    stack of each bank, (L,E,D,F), and `layer`, this layer's index in it
    (`kv_blocks._layer_loop` does, having asked `takes_routed_path`): the
    grouped matmul's kernel then reads the layer's experts in place,
    where a layer cut out of the stack is a copy of the bank a layer
    (1.9 of a 512-token chunk's 4.2 ms a layer at 64 experts of
    2,304 x 896: my chip run and AOT, PR 32)."""
    if layer is not None or takes_routed_path(
        c, h.shape[0], h.shape[1], p, mesh, partitioned
    ):
        return _moe_mlp_routed(c, h, p, mesh, layer, counted)
    return _moe_mlp_capacity(c, h, p, mesh, counted)


def _batch_shards(mesh: Optional[Mesh]) -> int:
    """The devices a batch's rows are spread over."""
    if mesh is None:
        return 1
    return math.prod(mesh.shape[a] for a in _BATCH_AXES if a in mesh.shape)


def _moe_mlp_capacity(
    c: ModelConfig,
    h: jnp.ndarray,
    p: Params,
    mesh: Optional[Mesh] = None,
    counted: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """The capacity path: dense GShard dispatch/combine tensors, every op
    a static matmul over E x C slots a row (2*E*C*D FLOPs a token each
    way for the dispatch and the combine alone), E the experts held."""
    with jax.named_scope("moe/route"):
        gate_vals, gate_idx, slot, sel, aux = route_assignments(
            c, h, p["router"], p.get("router_bias")
        )
        dispatch, combine = _dispatch_combine(c, h.shape[1], gate_vals, slot, sel)
        tally = () if counted is None else (local_pairs(c, gate_idx, counted),)

    def constrain(x, spec):
        if mesh is not None and "expert" in mesh.axis_names:
            return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        return x

    with jax.named_scope("moe/experts"):
        # Token all-to-all: tokens (batch-sharded) -> expert slots
        # (expert-sharded). XLA materializes the collective from the two
        # constraints on either side of this einsum.
        expert_in = jnp.einsum(
            "bsec,bsd->ebcd", dispatch.astype(h.dtype), h
        )
        expert_in = constrain(expert_in, P("expert", ("data", "fsdp"), None, None))
        expert_out = _expert_ffn(h.dtype, expert_in, p)
        expert_out = constrain(
            expert_out, P("expert", ("data", "fsdp"), None, None)
        )

        out = jnp.einsum(
            "bsec,ebcd->bsd", combine.astype(h.dtype), expert_out
        )
        return (out, aux) + tally


def _weight_tile(tile: int, k: int, n: int) -> Tuple[int, int]:
    """(tk, tn) of the grouped matmul's (k, n) weight tile beside a row
    tile of `tile`: the contraction whole where it is short (consecutive
    row tiles of one expert then reuse the tile without a new fetch; on
    the v5e 2.11 ms a 512-token chunk's bank at 64 experts against 2.55
    at tk 1,024 and 3.16 at 512 x 512), and the widest multiple of 128
    that divides n inside 4 MiB of bf16, 2 MiB beside a row tile above
    128: what the kernel's double buffers and f32 accumulator leave of
    the 16 MiB of VMEM a call may use."""
    tk = k if k <= 2304 else 1024
    room = (4 if tile <= 128 else 2) * 2**20 // (2 * tk)
    tn = max(
        (t for t in range(128, min(n, room) + 1, 128) if n % t == 0),
        default=min(n, 128),
    )
    return tk, tn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(
    x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray, tile: int
) -> jnp.ndarray:
    """x (m, k) rows sorted by group, w (G, k, n), group_sizes (G,)
    summing to m -> (m, n) in x's dtype: the rows of group g times w[g],
    f32-accumulated. megablox's Pallas kernels (`gmm`, and `tgmm` for
    the weights' gradient); interpreted off the TPU, which is for tests.

    Timed alone on the v5e beside `jax.lax.ragged_dot` (XLA's own
    kernel, whose row tile is not the caller's to choose), the SwiGLU
    bank's three products forward, ms (my chip run, PR 32): 4,096 rows
    of 64 experts 2,304 x 896: 2.11 against 7.62; 2,048 rows of 64
    experts 2,048 x 1,536: 2.62 against 4.81; 8,192 rows of 8 experts
    4,096 x 14,336: 24.0 against 29.1, forward and backward 67.7
    against 99.5."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(
        x, w, group_sizes, x.dtype,
        (tile, *_weight_tile(tile, w.shape[1], w.shape[2])),
        interpret=jax.default_backend() != "tpu",
    )


def _grouped_matmul_fwd(x, w, group_sizes, tile):
    return _grouped_matmul(x, w, group_sizes, tile), (x, w, group_sizes)


def _grouped_matmul_bwd(tile, residuals, g):
    """dx = g times each group's w transposed, dw[g] = that group's rows
    of x transposed times g. Both under one plain tiling: `tgmm` keeps a
    (tk, tn) f32 accumulator, which the forward's whole-contraction tile
    would not leave room for."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    x, w, group_sizes = residuals
    tiling = (tile, 1024, 1024)
    interpret = jax.default_backend() != "tpu"
    dx = gmm(
        g, w, group_sizes, x.dtype, tiling, transpose_rhs=True,
        interpret=interpret,
    )
    dw = tgmm(
        x.swapaxes(0, 1), g, group_sizes, w.dtype, tiling,
        num_actual_groups=w.shape[0], interpret=interpret,
    )
    return dx, dw, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@jax.custom_vjp
def _take_rows(x: jnp.ndarray, idx: jnp.ndarray, readers: jnp.ndarray):
    """x[idx], for an `idx` whose inverse the caller knows: `readers`
    (len(x), m) lists the m output rows that read each row of x. The
    backward is then a gather too (the cotangent's rows at `readers`,
    summed over m in f32) where autodiff would scatter-add."""
    return x[idx]


def _take_rows_fwd(x, idx, readers):
    return x[idx], readers


def _take_rows_bwd(readers, g):
    dx = jnp.sum(g[readers].astype(jnp.float32), axis=1).astype(g.dtype)
    return dx, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _padded(x: jnp.ndarray, pad: int) -> jnp.ndarray:
    """x (m, D) with `pad` rows of zeros below it."""
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _routed_bank(
    tile: int,
    h: jnp.ndarray,
    gates: jnp.ndarray,
    gate_idx: jnp.ndarray,
    slot: jnp.ndarray,
    we_gate: jnp.ndarray,
    we_up: jnp.ndarray,
    we_down: jnp.ndarray,
    layer: Optional[jnp.ndarray] = None,
    share: Optional[int] = None,
) -> jnp.ndarray:
    """The bank over ONE device's rows: h (B,S,D); gates (f32, 0 for a
    dropped row), gate_idx, slot (B,S,k); the bank whole (E,D,F), or with
    `layer` the stack (L,E,D,F) it is layer `layer` of -> (B,S,D).

    `share` is None for a whole bank, else the first expert held: experts
    `share .. share + E - 1` of those `gate_idx` names. The static T * k
    rows stay: the pairs that fall on the experts held are sorted to the
    front by expert, the others behind them as one more group, the last,
    for which the bank has no weights: the grouped matmul visits no tile
    of a group beyond its weights and gives zero rows for it, forward and
    backward (megablox's own form of a bank sharded by experts). Where T * k
    is no whole number of row tiles, `pad` zero rows make it one, in a last
    group of that kind too."""
    B, S, D = h.shape
    E, k = we_gate.shape[-3], gate_idx.shape[-1]
    T = B * S
    pad = -T * k % tile
    with jax.named_scope("moe/experts"):
        if share is not None:
            here = (gate_idx >= share) & (gate_idx < share + E)
            gate_idx = jnp.where(here, gate_idx - share, E)  # E: no column
        # Expert-major place of every routed row: the experts before its own,
        # then the earlier batch rows' share of its expert, then its slot
        # (route_assignments' cumsum already counted its place in its row).
        sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (B,S,k,E)
        counts = jnp.sum(sel, axis=(1, 2))  # (B,E)
        group_sizes = jnp.sum(counts, axis=0)  # (E,)
        first = jnp.cumsum(group_sizes) - group_sizes  # (E,)
        before = first[None, :] + jnp.cumsum(counts, axis=0) - counts  # (B,E)
        dest = (slot + jnp.einsum("bske,be->bsk", sel, before)).reshape(T * k)
        if share is not None:
            here = here.reshape(T * k)
            n_here = jnp.sum(group_sizes)
            dest = jnp.where(here, dest, n_here + jnp.cumsum(~here) - 1)
        src = jnp.argsort(dest)  # sorted row -> t * k + j
        if layer is not None:
            # The stack as L * E groups, every one empty but this layer's: an
            # empty group costs the kernel no tile and no read.
            L = we_gate.shape[0]
            group_sizes = lax.dynamic_update_slice(
                jnp.zeros((L * E,), group_sizes.dtype), group_sizes, (layer * E,)
            )
            we_gate, we_up, we_down = (
                w.reshape((L * E,) + w.shape[2:]) for w in (we_gate, we_up, we_down)
            )
        if share is not None:
            group_sizes = jnp.append(group_sizes, T * k - n_here)
        if pad:
            group_sizes = jnp.append(group_sizes, pad)
        x = _padded(_take_rows(h.reshape(T, D), src // k, dest.reshape(T, k)), pad)
        gate = _grouped_matmul(x, we_gate, group_sizes, tile)
        up = _grouped_matmul(x, we_up, group_sizes, tile)
        act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        y = _grouped_matmul(act.astype(h.dtype), we_down, group_sizes, tile)
        y = _take_rows(y[: T * k] if pad else y, dest, src[:, None])  # (T*k, D)
        out = jnp.sum(
            gates.reshape(T, k, 1) * y.reshape(T, k, D).astype(jnp.float32),
            axis=1,
        )
        return out.astype(h.dtype).reshape(B, S, D)


def _moe_mlp_routed(
    c: ModelConfig,
    h: jnp.ndarray,
    p: Params,
    mesh: Optional[Mesh] = None,
    layer: Optional[jnp.ndarray] = None,
    counted: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """The routed path: only the rows routing chose are multiplied.

    Same scores, same top-k, same slots as the capacity path
    (`route_assignments`); a row whose slot is beyond the capacity keeps
    its place in the sort and weighs zero, as `one_hot` of an
    out-of-range slot does there, so the two agree at any capacity
    factor. On a mesh each device sorts and multiplies its own batch rows
    under `shard_map`, handed the whole bank: the all-gather of the
    weights and the reduction of their gradient over the devices are the
    ones GSPMD lays around the capacity path's einsums."""
    B, S, _ = h.shape
    with jax.named_scope("moe/route"):
        gate_vals, gate_idx, slot, _, aux = route_assignments(
            c, h, p["router"], p.get("router_bias")
        )
        gates = jnp.where(slot < expert_capacity(c, S), gate_vals, 0.0)
        tally = () if counted is None else (local_pairs(c, gate_idx, counted),)
    tile = plan(c, B // _batch_shards(mesh), S)[2]
    bank = functools.partial(
        _routed_bank, tile, **({"share": c.held[0]} if c.expert_share else {})
    )
    weights = [p["we_gate"], p["we_up"], p["we_down"]]
    if layer is not None:
        weights.append(layer)
    if mesh is not None and mesh.size > 1:
        batch = tuple(a for a in _BATCH_AXES if a in mesh.shape)
        bank = jax.shard_map(
            bank, mesh=mesh,
            in_specs=(P(batch),) * 4 + (P(),) * len(weights),
            out_specs=P(batch), check_vma=False,
        )
    return (bank(h, gates, gate_idx, slot, *weights), aux) + tally


def moe_block(
    c: ModelConfig,
    x: jnp.ndarray,
    p: Params,
    mesh: Optional[Mesh] = None,
    partitioned: bool = False,
    layer: Optional[jnp.ndarray] = None,
    counted: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """Pre-norm MoE block with residual: x -> (x + moe(norm(x)), aux), and
    with `counted` -> (.., .., `local_pairs`) as `moe_mlp` gives them.
    With shared experts (`ws_*` weights) every token also passes through
    their SwiGLU, added beside the routed sum."""
    from dstack_tpu.workloads.transformer import _silu, linear, rms_norm
    with jax.named_scope("moe/route"):
        h = rms_norm(x, p["mlp_norm"], c.norm_eps)
    out, aux, *tally = moe_mlp(c, h, p, mesh, partitioned, layer, counted)
    if "ws_gate" in p:
        with jax.named_scope("moe/shared"):
            gate = _silu(linear(h, p["ws_gate"]))
            out = out + linear(gate * linear(h, p["ws_up"]), p["ws_down"])
    with jax.named_scope("moe/experts"):
        return (x + out, aux, *tally)
