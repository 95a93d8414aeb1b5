"""Flagship model configuration (llama-family decoder).

Frozen dataclass so configs are hashable and can ride through `jax.jit`
static args. Dimensions are kept multiples of 128 so every matmul tiles
cleanly onto the 128x128 MXU (pallas_guide: Tiling Constraints).
"""

import math
import os
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple, Union

import jax.numpy as jnp

_DTYPE = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# The kinds a `layer_types` entry may name, as published configs spell them
# (MAMBA is the `jamba` model type's word for its state-space layers, HYBRID
# the `zaya` type's for a layer of compressed convolutional attention and then
# an expert sublayer: full attention as far as a window or a cache goes).
FULL, SLIDING, MAMBA = "full_attention", "sliding_attention", "mamba"
HYBRID = "hybrid"


def period_of(kinds: Tuple[str, ...]) -> Tuple[str, ...]:
    """The shortest run of kinds whose repetition, cut where `kinds` ends,
    is `kinds`: (a, b, b, b, a, b, b) has the period (a, b, b, b) once whole
    and then its first three."""
    for p in range(1, len(kinds) + 1):
        if all(k == kinds[i % p] for i, k in enumerate(kinds)):
            return kinds[:p]
    return kinds


@dataclass(frozen=True)
class RopeParams:
    """One kind of layer's rotary embedding, as a published
    `rope_parameters` group gives it. `rope_type` "default" reads `theta`
    alone; "yarn" (arXiv:2309.00071, as `transformers` computes it) blends
    each frequency between itself and itself / `factor` by where it lies
    between the `beta_fast` and `beta_slow` rotations over
    `original_max_position_embeddings`, and multiplies cos and sin by
    `attention_factor` (0.1 ln(factor) + 1 when the group gives none).
    `partial_rotary_factor` < 1 rotates the first `rotary_dim(head)` values
    of a head and passes the rest as they are; the frequencies, YaRN's
    blend among them, are then those of the rotated width."""

    theta: float
    rope_type: str = "default"
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0
    truncate: bool = True
    partial_rotary_factor: float = 1.0

    @classmethod
    def of(cls, group: Dict[str, Any], theta: float) -> "RopeParams":
        fields = {"theta": theta, **{
            "theta" if k == "rope_theta" else k: v for k, v in group.items()
        }}
        if set(fields) - set(cls.__dataclass_fields__) or fields.get(
            "rope_type", "default"
        ) not in ("default", "yarn"):
            raise ValueError(f"rope_parameters group {group!r}: not understood")
        rope = cls(**fields)
        if not 0 < rope.partial_rotary_factor <= 1:
            raise ValueError("partial_rotary_factor must lie in (0, 1]")
        if rope.rope_type == "yarn" and not (
            rope.factor >= 1 and rope.original_max_position_embeddings > 0
        ):
            raise ValueError(
                "yarn needs factor >= 1 and original_max_position_embeddings"
            )
        return rope

    def rotary_dim(self, head_dim: int) -> int:
        """The leading values of a head that are rotated (an even count)."""
        return int(head_dim * self.partial_rotary_factor) // 2 * 2

    def inv_freq(self, dim: int) -> Tuple[Tuple[float, ...], float]:
        """(the dim / 2 inverse frequencies, the factor on cos and sin) for
        a rotated width of `dim`."""
        extra = [self.theta ** (-2.0 * i / dim) for i in range(dim // 2)]
        if self.rope_type == "default":
            return tuple(extra), 1.0

        def correction(rotations: float) -> float:
            return (
                dim * math.log(self.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(self.theta))
            )

        low, high = correction(self.beta_fast), correction(self.beta_slow)
        if self.truncate:
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, dim - 1)
        if low == high:
            high += 0.001
        ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
                for i in range(dim // 2)]
        factor = self.attention_factor or 0.1 * math.log(self.factor) + 1.0
        return tuple(
            f / self.factor * r + f * (1.0 - r) for f, r in zip(extra, ramp)
        ), factor


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4  # grouped-query attention
    d_ff: int = 1536
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    # Rematerialization ladder: "none" (save all activations — fastest when
    # they fit), "dots" (save only batch-free dots), "full" (save nothing),
    # or "auto" (estimate activation HBM vs what the train state leaves
    # free and pick — resolve_remat). True/False mean full/none.
    remat: Union[bool, str] = "auto"
    # Sparse MoE (0 = dense MLP). With n_experts > 0 every block's MLP is
    # a routed top-k SwiGLU expert bank (workloads/moe.py) and d_ff is the
    # per-expert hidden dim.
    n_experts: int = 0
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # Chunked cross-entropy: compute the lm-head + softmax-xent over
    # sequence chunks of this many tokens inside a rematerialized
    # lax.scan, so the full (B, S, V) f32 logits tensor is never
    # materialized (train.loss_fn). 0 = off (dense logits). The math is
    # identical (per-token logsumexp; f32 accumulation) — only the
    # association order of the token-sum changes. Costs one extra
    # lm-head matmul in backward; frees vocab_size*(4+dtype_bytes)
    # bytes/token of saved residuals, which is what lets the flagship
    # bench shape run the remat-free rung (docs/design/perf.md).
    ce_chunk: int = 0
    # Latent attention (MLA), absent at 0: queries go through a rank-
    # `q_lora_rank` bottleneck, keys and values come from ONE latent row a
    # token of `kv_lora_rank` values plus `qk_rope_head_dim` rotary key
    # values shared by every head (transformer.project_latent). A head
    # scores over `qk_nope_head_dim + qk_rope_head_dim` values and emits
    # `v_head_dim`; none of them is d_model // n_heads. n_kv_heads is not
    # read: the cache keeps no per-head rows (kv_row_shapes).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Expert models whose first `n_dense_layers` blocks are plain SwiGLU at
    # width `dense_d_ff` (params["dense_layers"]); the n_layers -
    # n_dense_layers that follow carry the expert bank at width d_ff.
    n_dense_layers: int = 0
    dense_d_ff: int = 0
    # Shared experts: a SwiGLU of width n_shared_experts * d_ff that every
    # token passes through beside its routed experts.
    n_shared_experts: int = 0
    # Router (moe.route_assignments). "softmax": scores are a softmax over
    # the experts, top-k by score. "sigmoid": scores are sigmoids, top-k by
    # score + a per-expert bias that chooses and does not weigh (the
    # `router_bias` leaf). The chosen weights are renormalised to sum to 1
    # and multiplied by routed_scaling.
    router_score: str = "softmax"
    routed_scaling: float = 1.0
    # A query head's size where the model publishes one that is not
    # d_model // n_heads (0 = that quotient): wq is d_model x n_heads *
    # head_size, wo its transpose's shape. Read through `head_dim`.
    head_size: int = 0
    # Layers of more than one kind of attention, as published: one entry a
    # layer, FULL or SLIDING (a list is taken and kept as a tuple). A
    # SLIDING layer's query at position i sees keys j with i - j <
    # `sliding_window`. Longer than n_layers it is cut to its first
    # entries (a cut in depth keeps the head of the pattern); shorter is an
    # error. Empty = every layer full.
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 0
    # Rotary embedding by kind of layer: a mapping kind -> published group
    # (`RopeParams.of`), kept as a tuple of (kind, RopeParams). A kind it
    # does not name rotates by `rope_theta`.
    rope_parameters: Any = ()
    # State-space (Mamba-1) layers beside attention layers, as the `jamba`
    # model type publishes them: with `attn_layer_period` > 0 layer i is an
    # attention layer iff i % attn_layer_period == attn_layer_offset and a
    # MAMBA layer otherwise (`layer_types` is derived here and not given).
    # A MAMBA layer keeps no cache rows: what a request carries through it
    # is a state of `mamba_d_state` x d_inner values in float32 and the last
    # `mamba_d_conv` - 1 rows of its convolution's input, whatever the
    # context (`state_shapes`). d_inner = mamba_expand x d_model.
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # False: queries and keys are not rotated (position reaches such a model
    # through its state-space layers only).
    use_rope: bool = True
    # True: the head is the embedding's transpose, ONE leaf
    # (`transformer.head_weights`; `init_params` makes no `lm_head`).
    tie_embeddings: bool = False
    # Query heads by layer, as published: one entry a layer, cut and checked
    # like `layer_types`. The count must be a function of the layer's KIND
    # (`heads(kind)`); where the kinds differ in it, `wq`, `wo` and the gate
    # are a stack a kind (`heads_by_kind`, transformer.init_params). Empty =
    # n_heads in every layer. The KV heads are the same in every layer.
    heads_per_layer: Tuple[int, ...] = ()
    # A gate on each query head's output: g = act(h Wg), one value a token
    # and head from the block's normed input (Wg is d_model x heads, the
    # `wg` leaf), float32; the head's attention output is multiplied by it
    # before the out projection. "" = no gate, else the activation:
    # "softplus" or "sigmoid".
    attn_gate: str = ""
    # The device's share of the expert bank: of the `n_experts` the router
    # scores (its published width), the bank here holds `experts_held`,
    # starting at `experts_first` (0 held = all). Routing, top-k, the
    # renormalisation and the scaling are over all `n_experts`; a token's
    # pairs that fall on experts held elsewhere add nothing here (moe.py).
    experts_held: int = 0
    experts_first: int = 0
    # Compressed convolutional attention (CCA, arXiv:2510.04476; the `zaya`
    # model type), on at cca_time0 > 0: queries and keys are projected to
    # their heads' widths, then mixed over time by two causal convolutions of
    # `cca_time0` and `cca_time1` taps (transformer.cca_project); half of a
    # KV head's value comes from the token before. A slot carries the
    # convolutions' inputs of its last tokens from program to program
    # (`cca_tail_width`). It comes with the router of the same model type:
    # `router_hidden_size` > 0 scores the experts AND a skip choice
    # through an MLP of that width over a projection carried from layer to
    # layer (transformer.depth_router; `expert_skip`), and both sublayers
    # merge into the residual stream by learned scales (`residual_merge`).
    cca_time0: int = 0
    cca_time1: int = 0
    router_hidden_size: int = 0

    def __post_init__(self):
        set_ = lambda k, v: object.__setattr__(self, k, v)
        set_("sliding_window", int(self.sliding_window or 0))
        kinds = tuple(self.layer_types or ())
        if self.attn_layer_period:
            if not 0 <= self.attn_layer_offset < self.attn_layer_period:
                raise ValueError(
                    f"attn_layer_offset {self.attn_layer_offset} must lie in"
                    f" [0, attn_layer_period={self.attn_layer_period})"
                )
            derived = tuple(
                FULL if i % self.attn_layer_period == self.attn_layer_offset
                else MAMBA for i in range(self.n_layers)
            )
            # (`with_` hands the derived pattern back, of the old depth: a
            # run of the same pattern is no conflict.)
            if kinds[: self.n_layers] != derived[: len(kinds)]:
                raise ValueError(
                    "attn_layer_period derives layer_types: give one of them"
                )
            kinds = derived
        if MAMBA in kinds:
            if self.mamba_dt_rank < 1 or self.mamba_d_conv < 2:
                raise ValueError(
                    "a state-space layer needs mamba_dt_rank >= 1 and"
                    " mamba_d_conv >= 2"
                )
            if self.n_experts:
                raise ValueError(
                    "state-space layers beside an expert bank: no program"
                    " runs that pattern"
                )
        if kinds:
            if len(kinds) < self.n_layers:
                raise ValueError(
                    f"layer_types names {len(kinds)} layers, n_layers is"
                    f" {self.n_layers}"
                )
            kinds = kinds[: self.n_layers]
            if set(kinds) - {FULL, SLIDING, MAMBA, HYBRID}:
                raise ValueError(
                    f"layer_types {sorted(set(kinds))}: expected {FULL!r},"
                    f" {SLIDING!r}, {MAMBA!r} or {HYBRID!r}"
                )
            if (HYBRID in kinds) != self.cca or (self.cca and set(kinds) != {HYBRID}):
                raise ValueError(
                    f"a {HYBRID!r} layer is a CCA layer (cca_time0,"
                    " cca_time1): a model has only those, or none"
                )
            if SLIDING in kinds and self.sliding_window < 1:
                raise ValueError("a sliding_attention layer needs sliding_window")
            if self.kv_lora_rank:
                raise ValueError(
                    "layer_types beside latent attention: no program runs"
                    " that pattern"
                )
        set_("layer_types", kinds)
        heads = tuple(self.heads_per_layer or ())
        if heads:
            if len(heads) < self.n_layers:
                raise ValueError(
                    f"heads_per_layer names {len(heads)} layers, n_layers is"
                    f" {self.n_layers}"
                )
            heads = heads[: self.n_layers]
            by_kind = set(zip(kinds or (FULL,) * self.n_layers, heads))
            if len(by_kind) != len({kind for kind, _ in by_kind}):
                raise ValueError(
                    "heads_per_layer must give every layer of one kind the"
                    f" same count, not {sorted(by_kind)}"
                )
            if self.kv_lora_rank or MAMBA in kinds or any(
                h < 1 or h % self.n_kv_heads for h in heads
            ):
                raise ValueError(
                    "heads_per_layer needs attention layers of per-head K/V"
                    " rows and counts that n_kv_heads divides"
                )
        set_("heads_per_layer", heads)
        if self.attn_gate not in ("", "softplus", "sigmoid"):
            raise ValueError(
                f"attn_gate={self.attn_gate!r}: expected '', 'softplus' or"
                " 'sigmoid'"
            )
        if self.attn_gate and (self.kv_lora_rank or MAMBA in kinds):
            raise ValueError(
                "attn_gate beside latent attention or state-space layers: no"
                " program runs that pattern"
            )
        if self.experts_held or self.experts_first:
            if not (
                0 <= self.experts_first
                and 0 < self.experts_held
                and self.experts_first + self.experts_held <= self.n_experts
            ):
                raise ValueError(
                    f"experts {self.experts_first} .."
                    f" {self.experts_first + self.experts_held - 1} held of"
                    f" n_experts={self.n_experts}: not a share of the bank"
                )
        ropes = self.rope_parameters or ()
        if isinstance(ropes, dict):
            # (a published mapping may carry a top-level `rope_type` beside
            # its groups by kind: it names no kind)
            ropes = tuple(
                (kind, RopeParams.of(group, self.rope_theta))
                for kind, group in sorted(ropes.items())
                if isinstance(group, dict)
            )
        set_("rope_parameters", tuple(ropes))
        if self.kv_lora_rank > 0 and not (
            self.q_lora_rank > 0 and self.qk_rope_head_dim > 0
            and self.qk_nope_head_dim > 0 and self.v_head_dim > 0
        ):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank,"
                " qk_nope_head_dim, qk_rope_head_dim and v_head_dim"
            )
        if self.n_dense_layers and not (
            self.n_experts > 0 and self.dense_d_ff > 0
            and self.n_dense_layers < self.n_layers
        ):
            raise ValueError(
                "n_dense_layers leads an expert model: it needs n_experts,"
                " dense_d_ff and at least one expert layer after it"
            )
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"router_score={self.router_score!r}: expected 'softmax' or"
                " 'sigmoid'"
            )
        if self.cca or self.router_hidden_size:
            if not (
                self.cca_time0 > 0 and self.cca_time1 > 0
                and self.router_hidden_size > 0 and self.n_experts > 0
                and self.experts_per_token == 1 and self.head_dim % 2 == 0
                and self.n_heads % self.n_kv_heads == 0
                and self.router_score == "softmax"
            ) or (
                self.latent or self.attn_gate or self.heads_per_layer
                or self.n_dense_layers or self.n_shared_experts
                or self.experts_held or MAMBA in kinds
            ):
                raise ValueError(
                    "CCA (cca_time0, cca_time1) comes with its router"
                    " (router_hidden_size, top-1 of softmax-scored experts and"
                    " a skip) on a bank held whole, and with no other"
                    " attention or MLP variant: no program runs another"
                    " pattern"
                )

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def head_dim(self) -> int:
        """Values a query head scores over."""
        if self.latent:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_size or self.d_model // self.n_heads

    @property
    def layer_period(self) -> Tuple[str, ...]:
        """The kinds of one period of the layer pattern: the shortest run
        of layers whose repetition is the whole stack ((FULL,) for a model
        of one kind). The layer loops scan over periods, so each kind is a
        call site of its own with its window and its rotary embedding
        static (`transformer.scan_layers`)."""
        kinds = self.layer_types or (FULL,)
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and all(
                k == kinds[i % p] for i, k in enumerate(kinds)
            ):
                return kinds[:p]

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The kind of every layer, in order (FULL where no pattern is given)."""
        return self.layer_types or (FULL,) * self.n_layers

    @property
    def stack_kinds(self) -> Tuple[Tuple[str, ...], ...]:
        """The kinds of each stack of blocks, in the order the stacks run
        (`transformer.layer_stacks`): the leading dense layers take the
        head of the pattern, the expert layers what follows. Each stack is
        scanned over its OWN period (`period_of`), which need not divide it:
        a published depth may end inside a period."""
        kinds, nd = self.layer_kinds, self.n_dense_layers
        return (kinds[:nd], kinds[nd:]) if nd else (kinds,)

    def heads(self, kind: str = FULL) -> int:
        """Query heads of a `kind` layer."""
        return dict(zip(self.layer_kinds, self.heads_per_layer)).get(
            kind, self.n_heads
        )

    @property
    def heads_by_kind(self) -> bool:
        """The kinds of attention layer differ in their query heads: `wq`,
        `wo` and the gate are then no one stack (transformer.init_params)."""
        return len(set(self.heads_per_layer)) > 1

    @property
    def held(self) -> Tuple[int, int]:
        """(first expert held here, how many): the whole bank unless
        `experts_held` says otherwise."""
        return self.experts_first, self.experts_held or self.n_experts

    @property
    def expert_share(self) -> bool:
        """The bank here is a share of the experts the router scores."""
        return 0 < self.experts_held < self.n_experts

    def window(self, kind: str) -> int:
        """Keys a query of a `kind` layer sees, itself included; 0 = all."""
        return self.sliding_window if kind == SLIDING else 0

    @property
    def cca(self) -> bool:
        """Attention is compressed convolutional attention (`cca_time0`)."""
        return self.cca_time0 > 0

    @property
    def expert_skip(self) -> bool:
        """The router scores a choice of NO expert beside the experts (a
        token that takes it adds nothing to its layer), through its MLP of
        `router_hidden_size` (moe.route_assignments)."""
        return self.router_hidden_size > 0

    @property
    def n_choices(self) -> int:
        """What the router chooses among: the experts, and the skip."""
        return self.n_experts + self.expert_skip

    @property
    def pairs_on_device(self) -> bool:
        """Where a routed pair lands is not a function of shapes (a share of
        the bank, or a skip): the programs count the pairs on the device
        (kv_blocks: `moe_pairs`)."""
        return self.expert_share or self.expert_skip

    def cca_tail_width(self) -> int:
        """Values one slot carries a CCA layer from program to program, side
        by side in ONE row (as `state_shapes`' tail): the first convolution's
        inputs at the last `cca_time0` - 1 tokens, the second's at the last
        `cca_time1` - 1 (a query's and a key head's width each, all heads),
        and the shifted half of every KV head's value at the last token —
        the ONE place the tail's geometry is derived."""
        qk = (self.n_heads + self.n_kv_heads) * self.head_dim
        return ((self.cca_time0 - 1 + self.cca_time1 - 1) * qk
                + self.n_kv_heads * self.head_dim // 2)

    def cca_params(self) -> int:
        """One CCA layer's weights beyond the attention projections: the
        two convolutions and each query head's temperature."""
        hd, groups = self.head_dim, self.n_heads + self.n_kv_heads
        return (self.cca_time0 * groups * hd + self.cca_time1 * groups * hd * hd
                + self.n_heads)

    def router_params(self) -> int:
        """One expert layer's router: a d_model x n_experts projection, or
        with `router_hidden_size` its down-projection, depth weight, norm
        and three layers to the `n_choices` scores."""
        d, r = self.d_model, self.router_hidden_size
        if not r:
            return d * self.n_experts
        return d * r + 1 + r + 2 * r * r + r * self.n_choices

    @property
    def n_state_layers(self) -> int:
        return sum(kind == MAMBA for kind in self.layer_types)

    @property
    def has_state_layers(self) -> bool:
        """Some layer carries a recurrent state from token to token. A
        cached block chain is then worth nothing without the state at its
        end, a rejected draft would have to roll the state back, and a
        preempted slot's chain drops it: the engine reads this once
        (serving.ServingEngine) for all it refuses or turns off."""
        return self.n_state_layers > 0

    @property
    def n_attn_layers(self) -> int:
        """Layers that keep cache rows: the KV pool's layer axis."""
        return self.n_layers - self.n_state_layers

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    def state_shapes(self) -> Tuple[Tuple[int, int], Tuple[int, int]]:
        """One slot's recurrent state in one state-space layer: the scan's
        `h` (d_state, d_inner), float32, and the convolution's tail
        (d_conv - 1, d_inner) in the activation dtype — the ONE place the
        state's geometry is derived, as `kv_row_shapes` is for a row's.
        d_inner is the minor axis of both: d_state (16) there would be
        padded to the TPU's 128 lanes, eight times the bytes."""
        return (
            (self.mamba_d_state, self.d_inner),
            (self.mamba_d_conv - 1, self.d_inner),
        )

    def state_row_bytes(self) -> int:
        """Bytes one slot's state takes over all state-space layers, at any
        context."""
        (n, di), (k, _) = self.state_shapes()
        return self.n_state_layers * di * (n * 4 + k * self.dtype_bytes)

    def rope(self, kind: str) -> RopeParams:
        return dict(self.rope_parameters).get(kind) or RopeParams(self.rope_theta)

    @property
    def latent_row(self) -> int:
        """Values a token keeps per layer under latent attention: the
        normed latent, then the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def kv_row_shapes(self) -> Tuple[Tuple[int, int], Tuple[int, int]]:
        """The trailing (heads, width) of one cached token's row in the
        k pool and in the v pool — the ONE place the cache geometry is
        derived; pools, transfers, the host tier and budgets read it.
        GQA keeps a key and a value per KV head. Latent attention keeps
        one row for all heads and no value row (its values are the first
        kv_lora_rank columns of the key row, so the v pool is zero wide);
        the row is padded to whole 128-value lanes, which is what the TPU
        tiles, and `latent_row` is what the algorithm needs."""
        if self.latent:
            return (1, -(-self.latent_row // 128) * 128), (1, 0)
        return (self.n_kv_heads, self.head_dim), (self.n_kv_heads, self.head_dim)

    def kv_row_bytes(self) -> int:
        """Bytes one token allocates per layer across both pools."""
        (kh, kw), (vh, vw) = self.kv_row_shapes()
        return (kh * kw + vh * vw) * self.dtype_bytes

    @property
    def activation_dtype(self):
        return _DTYPE[self.dtype]

    @property
    def dtype_bytes(self) -> int:
        return jnp.dtype(_DTYPE[self.dtype]).itemsize

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    def attn_params(self, kind: str = FULL) -> int:
        """Weights of a `kind` block's attention projections, its gate among
        them (no norms)."""
        d, h = self.d_model, self.heads(kind)
        if self.latent:
            return (
                d * self.q_lora_rank + self.q_lora_rank * h * self.head_dim
                + d * self.latent_row
                + self.kv_lora_rank
                * h * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d
            )
        hd = self.head_dim
        gate = d * h if self.attn_gate else 0
        return d * (h + 2 * self.n_kv_heads) * hd + h * hd * d + gate

    def mlp_params(self, dense: bool = False) -> int:
        """Weights of one block's MLP: the plain SwiGLU of a leading
        dense layer (`dense`) or of a model without experts, else the
        experts held here, the shared experts and the router (whole)."""
        d = self.d_model
        if self.n_experts == 0:
            return 3 * d * self.d_ff
        if dense:
            return 3 * d * self.dense_d_ff
        return (
            3 * d * self.d_ff * (self.held[1] + self.n_shared_experts)
            + self.router_params()
        )

    def mamba_matmul_params(self) -> int:
        """Weights of one state-space mixer's four projections."""
        d, di = self.d_model, self.d_inner
        r, n = self.mamba_dt_rank, self.mamba_d_state
        return d * 2 * di + di * (r + 2 * n) + r * di + di * d

    def mamba_params(self) -> int:
        """Every leaf of one state-space mixer: the projections, the
        convolution and its bias, dt's bias, A_log, D and the three inner
        norms."""
        di, n = self.d_inner, self.mamba_d_state
        return (
            self.mamba_matmul_params() + di * (self.mamba_d_conv + 1) + di
            + di * n + di + self.mamba_dt_rank + 2 * n
        )

    def param_count(self) -> int:
        """Approximate parameter count (embedding + head untied; norms
        and the router's selection bias are not counted). A model with
        state-space layers is counted leaf by leaf, norms included and the
        head once where it is tied: its published count is checked against
        this one (benchmarks/tests/test_cell_jamba.py). So is a model whose
        query heads go by layer, or whose heads are gated: every leaf but
        the router's selection bias, a buffer the gradient does not move."""
        nd = self.n_dense_layers
        heads = (1 if self.tie_embeddings else 2) * self.d_model * self.vocab_size
        if self.cca:
            # every leaf: attention, convolutions and temperatures, router,
            # experts, the two norms and the two pairs of residual scales
            return (
                self.n_layers * (self.attn_params() + self.cca_params()
                                 + self.mlp_params() + 6 * self.d_model)
                + heads + self.d_model
            )
        if self.heads_per_layer or self.attn_gate:
            return (
                sum(map(self.attn_params, self.layer_kinds))
                + nd * self.mlp_params(dense=True)
                + (self.n_layers - nd) * self.mlp_params()
                + self.n_layers * 2 * self.d_model + heads + self.d_model
            )
        if self.has_state_layers:
            return (
                self.n_state_layers * self.mamba_params()
                + self.n_attn_layers * self.attn_params()
                + self.n_layers * (self.mlp_params() + 2 * self.d_model)
                + heads + self.d_model
            )
        return (
            self.n_layers * self.attn_params()
            + nd * self.mlp_params(dense=True)
            + (self.n_layers - nd) * self.mlp_params()
            + heads
        )

    def resolve_remat(
        self,
        batch_tokens: int,
        shards: Optional[Dict[str, int]] = None,
        *,
        seq_len: Optional[int] = None,
        attn_scores: bool = False,
    ) -> str:
        """Pick the remat policy for a training step of `batch_tokens`
        (global) on a mesh of `shards` (axis -> size).

        "auto" compares the per-device saved-activation estimate of the
        no-remat forward against the HBM a device has left after the train
        state (bf16 params+grads, f32 Adam moments = 12 B/param, divided
        over the weight-sharding axes). Budget knob: DSTACK_TPU_HBM_GB
        (default 16, a v5e/v6e chip).
        """
        r = self.remat
        if r is True or r == "full":
            return "full"
        if r is False or r == "none":
            return "none"
        if r == "dots":
            return "dots"
        if r != "auto":
            raise ValueError(
                f"remat={r!r}: expected 'auto', 'none', 'dots', 'full' or a bool"
            )
        shards = shards or {}
        hbm = float(os.environ.get("DSTACK_TPU_HBM_GB", "16")) * 2**30
        weight_shard = (
            shards.get("fsdp", 1) * shards.get("model", 1)
            * shards.get("pipe", 1) * shards.get("expert", 1)
        )
        act_shard = (
            shards.get("data", 1) * shards.get("fsdp", 1) * shards.get("seq", 1)
        )
        state_bytes = 12 * self.param_count() / weight_shard
        budget = max(hbm - state_bytes, 0.15 * hbm)
        d, f = self.d_model, self.d_ff
        db = self.dtype_bytes
        kv = self.n_kv_heads * self.head_dim
        # MoE: each token funds k routed experts' activations plus the
        # capacity-factor slack in the dispatch buffers.
        mlp_width = f * (
            self.experts_per_token * self.capacity_factor
            if self.n_experts > 0 else 1
        )
        # Per-layer residuals the no-remat backward keeps. The SwiGLU gate
        # rides through transformer._silu (custom VJP) precisely so the
        # saved intermediates stay in activation dtype — without it,
        # autodiff keeps two f32 (L, B, S, d_ff) buffers per layer
        # (measured on v5e: the dominant no-remat allocation). Four
        # d_ff-wide residuals survive: gate preact, silu out, up, product.
        per_token = int(
            (6 * d + 2 * kv) * db          # norms, q/kv post-rope, attn out
            + mlp_width * 4 * db
        )
        if attn_scores and seq_len:
            # Plain (non-flash) attention keeps the f32 score and prob
            # matrices for backward: O(S) per token per head. The Pallas
            # flash kernels recompute these in their own backward, which is
            # exactly what lets long-context no-remat fit.
            per_token += 2 * seq_len * self.n_heads * 4
        # The lm-head/loss residuals sit outside the scanned layers but
        # compete for the same budget: the lse-form CE (train.ce_from_logits)
        # saves the f32 logits for backward and nothing else vocab-wide.
        # Chunked CE
        # recomputes the chunk logits in backward, keeping only the
        # final-norm hidden states plus one transient (chunk, V) buffer —
        # but loss_fn falls back to dense logits when the sequence does
        # not divide into ce_chunk slices (and when seq_len is unknown
        # here, assume dense: over-counting picks a safer rung).
        if self.ce_chunk > 0 and seq_len and seq_len % self.ce_chunk == 0:
            head_per_token = d * db
        else:
            # lse-form CE saves the f32 logits only (no log-prob tensor).
            head_per_token = self.vocab_size * 4
        act_bytes = (
            batch_tokens / max(act_shard, 1)
            * (per_token * self.n_layers + head_per_token)
        )
        return "none" if act_bytes < 0.6 * budget else "dots"

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Approximate forward+backward FLOPs per token (3x forward).

        With `seq_len`, includes the causal attention-score FLOPs
        (QK^T + AV: 2 * 2 * S * d per token per layer, halved by the
        causal mask) — the standard model-FLOPs accounting MFU uses
        (PaLM appendix B). Without it, only parameter matmuls count
        (a conservative lower bound). MoE counts the k active experts
        per token plus the router matmul, not the full expert bank; of a
        share of the bank, the part of the k that falls here in the mean."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        kinds = self.layer_types or (FULL,)
        # (the projections of each kind's own head count, the gate's too;
        # a CCA layer's convolutions beside them)
        per_layer = 2 * sum(map(self.attn_params, kinds)) / len(kinds)
        if self.cca:
            per_layer += 2 * self.cca_params()
        if self.has_state_layers:
            # Mixers by kind, averaged over the layers: a state-space one
            # is its projections and, a token, the convolution's taps and
            # about 9 element-wise operations a state value (exp, two
            # products and a sum for h, a product and a sum for y).
            di, n = self.d_inner, self.mamba_d_state
            mamba = (2 * self.mamba_matmul_params()
                     + 2 * self.mamba_d_conv * di + 9 * di * n)
            per_layer = (
                self.n_attn_layers * 2 * self.attn_params()
                + self.n_state_layers * mamba
            ) / self.n_layers
        if seq_len:
            # causal QK^T + AV: 2 * (scored + emitted) a head and key, over
            # the S / 2 keys a query sees in the mean; a window layer's
            # queries see min(position + 1, window), w - w^2 / 2S of them.
            v_dim = self.v_head_dim if self.latent else self.head_dim
            head_keys = sum(
                0 if kind == MAMBA
                else self.heads(kind) * (w - w * w / (2.0 * seq_len))
                if 0 < w < seq_len
                else self.heads(kind) * seq_len / 2
                for kind, w in zip(kinds, map(self.window, kinds))
            ) / len(kinds)
            per_layer += 2 * head_keys * (self.head_dim + v_dim)
        if self.n_experts > 0:
            active = (
                self.experts_per_token * self.held[1] / self.n_choices
                + self.n_shared_experts
            )
            mlp = 3 * 2 * d * f * active + 2 * self.router_params()
        else:
            mlp = 3 * 2 * d * f
        nd = self.n_dense_layers
        embed = 2 * d * v
        fwd = (
            self.n_layers * per_layer + (self.n_layers - nd) * mlp
            + nd * 3 * 2 * d * self.dense_d_ff + embed
        )
        return 3.0 * fwd


# Named presets: tiny for tests/dryrun, the rest sized for real slices.
PRESETS: Dict[str, ModelConfig] = {
    "tiny": ModelConfig(
        vocab_size=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=256, remat=False,
    ),
    # Single v5e/v6e chip fine-tune scale; what chip_smoke.py trains.
    "smol-1b": ModelConfig(
        vocab_size=32768, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        d_ff=5632, max_seq_len=2048,
    ),
    # smol-1b at 8k context (long-rope), the longctx-v5e.yml example:
    # 14.6k tok/s measured on one v5e at full 16-layer depth (auto remat
    # picks "dots"; the half-depth bench shape runs remat-free at 29.5k).
    # Unlocked by the O(S) flash backward + the 512 tile cap —
    # docs/design/perf.md "Long context on one chip".
    "smol-1b-8k": ModelConfig(
        vocab_size=32768, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        d_ff=5632, max_seq_len=8192, rope_theta=1e6,
    ),
    # llama-8b-shaped, for v5p-8 and up.
    "llama-8b": ModelConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=8192,
    ),
    # llama-70b-shaped: the fsdp x tp x sp regime on v5p-512 and up.
    "llama-70b": ModelConfig(
        vocab_size=128256, d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        d_ff=28672, max_seq_len=8192,
    ),
    # Sparse MoE for tests/dryrun (expert-parallel over the "expert" axis).
    # Window and full attention layers mixed (three window, one full, the
    # period twice), a head size that is not d_model // n_heads, YaRN on
    # the full layers only, softmax-routed experts with no token dropped:
    # every mechanism of the `mellum` block at a size the CPU runs, with
    # contexts several windows long.
    "tiny-window": ModelConfig(
        vocab_size=512, d_model=96, n_layers=8, n_heads=4, n_kv_heads=2,
        head_size=32, d_ff=48, max_seq_len=256, remat=False, n_experts=8,
        experts_per_token=3, capacity_factor=8 / 3, norm_eps=1e-6,
        layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 2, sliding_window=8,
        rope_parameters={
            FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                   "original_max_position_embeddings": 32},
            SLIDING: {"rope_type": "default", "rope_theta": 10000.0},
        },
    ),
    "tiny-moe": ModelConfig(
        vocab_size=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=256, remat=False, n_experts=4,
        experts_per_token=2,
    ),
    # State-space (Mamba-1) layers and attention layers in one layer loop
    # (mmam twice), four query heads on ONE KV head, no rotary embedding, a
    # tied head: every mechanism of the `jamba` block at a size the CPU
    # runs.
    "tiny-mamba": ModelConfig(
        vocab_size=512, d_model=64, n_layers=8, n_heads=4, n_kv_heads=1,
        d_ff=128, max_seq_len=512, remat=False, norm_eps=1e-6,
        attn_layer_period=4, attn_layer_offset=2, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8, use_rope=False,
        tie_embeddings=True,
    ),
    # Latent attention + a leading dense layer + sigmoid-routed experts
    # beside a shared one, for tests/dryrun: every mechanism of the
    # glm4_moe_lite block at a size the CPU runs (1 dense + 2 expert
    # layers). capacity_factor = experts / experts per token: no drop.
    "tiny-latent": ModelConfig(
        vocab_size=512, d_model=128, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=64, max_seq_len=256, remat=False, n_experts=8,
        experts_per_token=2, capacity_factor=4.0, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=16,
        v_head_dim=32, n_dense_layers=1, dense_d_ff=256,
        n_shared_experts=1, router_score="sigmoid", routed_scaling=1.8,
    ),
    # Query heads and a per-head output gate by layer kind (4 on a full
    # layer, 6 on a window layer, 2 KV heads), a leading dense layer BESIDE
    # the layer pattern, half of a head rotated with YaRN on the full kind,
    # sigmoid-routed experts with scaling beside a shared one: every
    # mechanism of the `laguna` block at a size the CPU runs. The bank is
    # whole here; `with_(experts_held=4)` is one of two devices' share.
    "tiny-laguna": ModelConfig(
        vocab_size=512, d_model=96, n_layers=5, n_heads=4, n_kv_heads=2,
        head_size=32, d_ff=48, max_seq_len=256, remat=False, n_experts=8,
        experts_per_token=3, capacity_factor=8 / 3, norm_eps=1e-6,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
        heads_per_layer=(4, 6, 6, 6, 4), sliding_window=8,
        attn_gate="softplus", n_dense_layers=1, dense_d_ff=192,
        n_shared_experts=1, router_score="sigmoid", routed_scaling=2.5,
        rope_parameters={
            FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                   "original_max_position_embeddings": 32,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 100.0,
                      "partial_rotary_factor": 1},
        },
    ),
    # Compressed convolutional attention (two causal convolutions of two taps
    # over queries and keys, the value half-shifted, queries and keys on the
    # sphere with a temperature a head, half of a head rotated) in heads of
    # 16 on a hidden size of 64, then 8 experts top-1 beside a skip, scored
    # by a router MLP over a projection carried from layer to layer, each
    # sublayer merged by learned scales, a tied head: every mechanism of the
    # `zaya` block at a size the CPU runs. capacity_factor = experts: no drop.
    "tiny-zaya": ModelConfig(
        vocab_size=512, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_size=16, d_ff=32, max_seq_len=256, remat=False, n_experts=8,
        experts_per_token=1, capacity_factor=8.0, tie_embeddings=True,
        layer_types=(HYBRID,) * 3, cca_time0=2, cca_time1=2,
        router_hidden_size=32,
        rope_parameters={
            HYBRID: {"rope_type": "default", "rope_theta": 10000.0,
                     "partial_rotary_factor": 0.5},
        },
    ),
    # Mixtral-shaped 8x top-2 at the 1B-active scale.
    "smol-moe": ModelConfig(
        vocab_size=32768, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        d_ff=5632, max_seq_len=2048, n_experts=8, experts_per_token=2,
    ),
}
