"""Llama-family decoder in pure JAX, written for XLA/TPU.

Design (deliberately not a torch translation):
- one stacked parameter pytree per weight kind with a leading layer dim,
  consumed by `lax.scan` — a single traced block regardless of depth, so
  compile time and HLO size are O(1) in n_layers;
- `jax.checkpoint` around the scanned block body (policy: keep nothing)
  trades FLOPs for HBM, the standard TPU remat recipe;
- bf16 storage, f32 accumulation on the MXU via preferred_element_type;
- RMSNorm computed in f32;
- attention is injected (`attention_fn`) so the same forward serves the
  single-chip fused path and the ring/sequence-parallel path.

Parity target: the reference's fine-tuning examples run llama-style models
via TRL/torch inside containers (reference: examples/fine-tuning/trl/,
examples/accelerators/tpu/README.md); this module is the TPU-native
equivalent workload the orchestrator launches.
"""

import math
from dataclasses import replace
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dstack_tpu.workloads.attention import plain_attention
from dstack_tpu.workloads.config import (
    FULL,
    HYBRID,
    MAMBA,
    ModelConfig,
    RopeParams,
    period_of,
)
from dstack_tpu.workloads.selective_scan import (
    selective_scan,
    selective_scan_chunk,
)

Params = Dict[str, Any]
AttentionFn = Callable[..., jnp.ndarray]


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Initialise bf16 params. Layer weights are stacked on axis 0 for scan.

    Blocks of one kind share a stack. A model whose first
    `n_dense_layers` blocks are plain SwiGLU and the rest carry experts
    has two: `dense_layers` (run first) and `layers`; every other model
    has `layers` alone, as before. A model with state-space layers keeps
    in `layers` what every layer has (the two norms, the MLP) and under
    `mixers` one stack a KIND of mixer, each as long as the model has
    layers of that kind (`mixer_stacks`, `scan_layers`). So does a model
    whose kinds of attention layer differ in their query heads
    (`heads_by_kind`): `wq`, `wo` and the gate `wg` are a stack a kind, for
    each stack of layers (`dense_mixers` beside `dense_layers`); `wk` and
    `wv`, alike in every layer, stay with the layers. An expert layer's
    bank holds the experts this device holds (`ModelConfig.held`); its
    router scores all of them. A tied head is no leaf: `head_weights` reads
    the embedding."""
    c = config
    dt = c.activation_dtype
    keys = jax.random.split(key, 8)

    def norm_init(shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5).astype(dt)

    D, V = c.d_model, c.vocab_size

    def attention(keys, L, kind=FULL):
        hd, H = c.head_dim, c.heads(kind)
        weights = {
            "wq": dense(keys[1], (L, D, H * hd), D),
            "wk": dense(keys[2], (L, D, c.n_kv_heads * hd), D),
            "wv": dense(keys[3], (L, D, c.n_kv_heads * hd), D),
            "wo": dense(keys[4], (L, H * hd, D), H * hd),
        }
        if c.attn_gate:
            weights["wg"] = dense(jax.random.fold_in(keys[1], 1), (L, D, H), D)
        return weights

    def own_attention(key, kinds):
        """What only the layers of one kind have, a stack a kind of `kinds`."""
        return {
            kind: {
                w: a for w, a in attention(
                    jax.random.split(jax.random.fold_in(key, i), 8),
                    kinds.count(kind), kind,
                ).items() if w in HEADS_LEAVES
            }
            for i, kind in enumerate(sorted(set(kinds)))
        }

    def stack(keys, L, d_ff, experts):
        """L blocks of one kind: attention + dense MLP or expert bank."""
        layers = {
            "attn_norm": norm_init((L, D)),
            "mlp_norm": norm_init((L, D)),
        }
        if c.latent:
            H, qr, kvr = c.n_heads, c.q_lora_rank, c.kv_lora_rank
            qk, kq = jax.random.split(keys[1]), jax.random.split(keys[2])
            layers["wq_a"] = dense(qk[0], (L, D, qr), D)
            layers["q_norm"] = norm_init((L, qr))
            layers["wq_b"] = dense(qk[1], (L, qr, H * c.head_dim), qr)
            layers["wkv_a"] = dense(kq[0], (L, D, c.latent_row), D)
            layers["kv_norm"] = norm_init((L, kvr))
            layers["wkv_b"] = dense(
                kq[1], (L, kvr, H * (c.qk_nope_head_dim + c.v_head_dim)), kvr
            )
            layers["wo"] = dense(
                keys[4], (L, H * c.v_head_dim, D), H * c.v_head_dim
            )
        elif not c.has_state_layers:   # (theirs are stacks of their own: `mixers`)
            layers.update({
                w: a for w, a in attention(keys, L).items()
                if not (c.heads_by_kind and w in HEADS_LEAVES)
            })
        F = d_ff
        if experts:
            E, held = c.n_experts, c.held[1]
            # Router stays f32: tiny, and routing decisions are precision-
            # sensitive (a bf16 tie flips top-k membership).
            layers["router"] = (
                jax.random.normal(keys[5], (L, D, E), dtype=jnp.float32) * D**-0.5
            )
            ek = jax.random.split(keys[6], 3)
            layers["we_gate"] = dense(ek[0], (L, held, D, F), D)
            layers["we_up"] = dense(ek[1], (L, held, D, F), D)
            layers["we_down"] = dense(ek[2], (L, held, F, D), F)
            if c.router_score == "sigmoid":
                # The selection bias of an aux-loss-free router: a buffer
                # the published models start at zero and move by a rule
                # outside the gradient.
                layers["router_bias"] = jnp.zeros((L, E), jnp.float32)
            if c.n_shared_experts:
                Fs = F * c.n_shared_experts
                sk = jax.random.split(keys[7], 3)
                layers["ws_gate"] = dense(sk[0], (L, D, Fs), D)
                layers["ws_up"] = dense(sk[1], (L, D, Fs), D)
                layers["ws_down"] = dense(sk[2], (L, Fs, D), Fs)
        else:
            layers["w_gate"] = dense(keys[5], (L, D, F), D)
            layers["w_up"] = dense(keys[6], (L, D, F), D)
            layers["w_down"] = dense(keys[7], (L, F, D), F)
        if c.cca:
            layers.update(cca_leaves(c, jax.random.fold_in(keys[3], 7), L, dense))
        return layers

    def mamba(key, L):
        """L state-space mixers. The recurrence starts in the regime the
        published initialisation gives: A_log = log(1..d_state) a channel,
        D = 1, dt's bias the inverse softplus of a step log-uniform in
        [1e-3, 1e-1] (a random A_log makes the state vanish or explode).
        A_log and the convolution keep d_inner as their minor axis, as the
        state does (config.state_shapes)."""
        Di, N, R, K = c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
        ks = jax.random.split(key, 7)
        step = jnp.exp(jax.random.uniform(
            ks[5], (L, Di), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "in_proj": dense(ks[0], (L, D, 2 * Di), D),
            "conv_w": dense(ks[1], (L, K, Di), K),
            "conv_b": dense(ks[6], (L, Di), K),
            "x_proj": dense(ks[2], (L, Di, R + 2 * N), Di),
            "dt_norm": norm_init((L, R)),
            "b_norm": norm_init((L, N)),
            "c_norm": norm_init((L, N)),
            "dt_proj": dense(ks[3], (L, R, Di), R),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.broadcast_to(jnp.log(
                jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], (L, N, Di)),
            "D": jnp.ones((L, Di), jnp.float32),
            "out_proj": dense(ks[4], (L, Di, D), Di),
        }

    nd = c.n_dense_layers
    params = {
        "embed": dense(keys[0], (V, D), D),
        "layers": stack(keys, c.n_layers - nd, c.d_ff, c.n_experts > 0),
        "final_norm": norm_init((D,)),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(key, 99), (D, V), D)
    if c.has_state_layers:
        params["mixers"] = {
            MAMBA: mamba(jax.random.fold_in(key, 97), c.n_state_layers),
            FULL: attention(keys, c.n_attn_layers),
        }
    if nd:
        params["dense_layers"] = stack(
            jax.random.split(jax.random.fold_in(key, 98), 8), nd,
            c.dense_d_ff, False,
        )
    if c.heads_by_kind:
        for name, kinds, fold in zip(
            ("dense_mixers", "mixers") if nd else ("mixers",), c.stack_kinds,
            (96, 95) if nd else (95,),
        ):
            params[name] = own_attention(jax.random.fold_in(key, fold), kinds)
    return params


# The attention leaves whose shape goes by a layer's query heads.
HEADS_LEAVES = ("wq", "wo", "wg")


def layer_stacks(params: Params):
    """The model's stacks of blocks in the order they run: the leading
    dense layers (if the model has them), then `layers`."""
    if "dense_layers" in params:
        return (params["dense_layers"], params["layers"])
    return (params["layers"],)


def mixer_stacks(params: Params):
    """For each of `layer_stacks`, the stacks that only its layers of one
    kind have, by kind (None for a model whose every layer has the same
    leaves): `scan_layers`' `own`."""
    if "dense_layers" in params:
        return (params.get("dense_mixers"), params.get("mixers"))
    return (params.get("mixers"),)


def head_weights(params: Params):
    """The head's (d_model, vocab) weights: `lm_head`, or the embedding's
    transpose where the two are tied (one leaf; the transpose is the
    product's dimension numbers, no copy)."""
    if "lm_head" in params:
        return params["lm_head"]
    return params["embed"].T


def linear(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for a raw array or an int8 QTensor (workloads/quant.py).

    The QTensor path reads int8 from HBM (the point: decode is
    weight-bandwidth-bound), upcasts into the matmul, applies the
    per-channel scale, and returns x.dtype. The raw path is exactly the
    plain matmul the training step always ran."""
    from dstack_tpu.workloads.quant import QTensor

    if isinstance(w, QTensor):
        y = jnp.matmul(
            x, w.q.astype(x.dtype), preferred_element_type=jnp.float32
        )
        return (y * w.scale).astype(x.dtype)
    return x @ w


def logits_linear(x: jnp.ndarray, w) -> jnp.ndarray:
    """The lm-head matmul: f32 logits from bf16/quantized weights."""
    from dstack_tpu.workloads.quant import QTensor

    with jax.named_scope("head"):
        if isinstance(w, QTensor):
            y = jnp.matmul(
                x, w.q.astype(x.dtype), preferred_element_type=jnp.float32
            )
            return y * w.scale
        y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
        return y.astype(jnp.float32)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * weight).astype(x.dtype)


def _rope(x: jnp.ndarray, positions: jnp.ndarray, rope: RopeParams) -> jnp.ndarray:
    """Rotary embedding. x: (B, S, H, hd); positions: (S,) or (B, S);
    `rope` a layer kind's `RopeParams` (config.rope): a scaled kind's
    frequencies and its factor on cos and sin are constants of the trace.
    A partial rotation turns the first `rotary_dim` values of a head, as a
    head of that width, and passes the rest unrotated and unscaled."""
    rot = rope.rotary_dim(x.shape[-1])
    if rot < x.shape[-1]:
        whole = replace(rope, partial_rotary_factor=1.0)
        return jnp.concatenate(
            [_rope(x[..., :rot], positions, whole), x[..., rot:]], axis=-1
        )
    hd = x.shape[-1]
    factor = 1.0
    if rope.rope_type == "default":
        inv_freq = 1.0 / (
            rope.theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        )
    else:
        inv_freq, factor = rope.inv_freq(hd)
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def project_qkv(c: ModelConfig, x: jnp.ndarray, p: Params,
                positions: jnp.ndarray, kind: str = FULL):
    """Pre-norm QKV projection with the rope of a `kind` layer: one function
    for the training block and the KV-cache decode path (generate.py)."""
    b, s, _ = x.shape
    hd = c.head_dim
    rope = c.rope(kind)
    with jax.named_scope("attn/qkv"):
        h = rms_norm(x, p["attn_norm"], c.norm_eps)
        q = linear(h, p["wq"]).reshape(b, s, c.heads(kind), hd)
        k = linear(h, p["wk"]).reshape(b, s, c.n_kv_heads, hd)
        v = linear(h, p["wv"]).reshape(b, s, c.n_kv_heads, hd)
        if not c.use_rope:
            return q, k, v
        return _rope(q, positions, rope), _rope(k, positions, rope), v


def scan_layers(block, carry, xs, kinds, own=None):
    """`lax.scan` of `block(carry, x, kind) -> (carry, y)` over the leading
    (layer) axis of `xs`, one PERIOD of the layers' pattern a scan step: the
    `p` blocks of a period run in order inside the body, each traced with
    its own kind, block j of step t reading layer t * p + j of `xs`. A stack
    of one kind is the plain scan over its layers. -> (carry, ys) with `ys`
    on the layer axis.

    `kinds` names the kind of each layer of `xs` (one of
    `ModelConfig.stack_kinds`); the period is `config.period_of` it. Where
    the period does not divide the stack, the layers past the last whole
    period run after the scan, one block each, in order.

    `own` maps a kind to what only the layers of that kind have (a stack
    of mixer weights, a cache, an index), its leading axis as long as
    `xs` has layers of that kind. The block of layer i then gets
    `(x, own_x)`, `own_x` the entry of `own[kind]` at i's rank among the
    layers of its kind, and `ys` comes back as a mapping kind -> the `y`s
    of that kind's layers (a kind without layers has no entry).

    A block indexes the stack itself, one layer at a time, as a plain
    scan's body does: handed a whole period as the step's `xs`, XLA cuts
    the period's weights out of the stack into a buffer of their own every
    step (three copies of 1 GB a step at 4 layers of 64 experts: AOT for
    the v5e, PERF.md section 6, PR 31)."""
    tree_map = jax.tree_util.tree_map
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    period = period_of(tuple(kinds))
    p = len(period)
    if p == 1:
        if own is not None:
            carry, ys = lax.scan(
                lambda carry, x: block(carry, x, period[0]), carry,
                (xs, own[period[0]]),
            )
            return carry, {period[0]: ys}
        return lax.scan(lambda carry, x: block(carry, x, period[0]), carry, xs)

    def at(tree, index):
        # (the index is computed where it is read, leaf by leaf, as the
        # programs of PR 31 were traced: tests/test_tpu_lowering.py)
        return tree_map(
            lambda a: lax.dynamic_index_in_dim(a, index(), keepdims=False), tree
        )

    def run(carry, t, kinds_run):
        """The blocks of `kinds_run` from period t's first layer on."""
        ys, by_kind = [], {kind: [] for kind in kinds_run}
        for j, kind in enumerate(kinds_run):
            x = at(xs, lambda: t * p + j)
            if own is not None:
                rank = len(by_kind[kind])
                x = (x, at(own[kind], lambda: t * period.count(kind) + rank))
            carry, y = block(carry, x, kind)
            ys.append(y)
            by_kind[kind].append(y)
        stacked = lambda of: tree_map(lambda *a: jnp.stack(a), *of)
        if own is None:
            return carry, stacked(ys)
        return carry, {kind: stacked(of) for kind, of in by_kind.items()}

    carry, ys = lax.scan(
        lambda carry, t: run(carry, t, period), carry,
        jnp.arange(n // p, dtype=jnp.int32),
    )
    ys = tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), ys
    )
    if n % p:
        carry, tail = run(carry, n // p, period[: n % p])
        if own is None:
            ys = tree_map(lambda a, b: jnp.concatenate([a, b]), ys, tail)
        else:
            ys = {
                kind: tree_map(
                    lambda a, b: jnp.concatenate([a, b]), y, tail[kind]
                ) if kind in tail else y
                for kind, y in ys.items()
            }
    return carry, ys


def project_latent(c: ModelConfig, x: jnp.ndarray, p: Params,
                   positions: jnp.ndarray, width: Optional[int] = None):
    """Latent attention's projections -> (q (B, S, H, nope + rope) with its
    rope columns rotated, row (B, S, kv_lora_rank + rope), zero-padded to
    `width` values where a cache's row is wider): the query per
    head, and the ONE row a token keeps for all heads — the normed latent
    `c_kv`, then the rotated key `k_rope` every head shares. Nothing else
    of a token is cached; keys and values are up-projections of the
    latent (`expand_latent`) or folded into the query and the output
    (`absorb_query`, `latent_output`)."""
    b, s, _ = x.shape
    nope, kvr = c.qk_nope_head_dim, c.kv_lora_rank
    rope = c.rope(FULL)
    with jax.named_scope("mla/project"):
        h = rms_norm(x, p["attn_norm"], c.norm_eps)
        cq = rms_norm(linear(h, p["wq_a"]), p["q_norm"], c.norm_eps)
        q = linear(cq, p["wq_b"]).reshape(b, s, c.n_heads, c.head_dim)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], positions, rope)],
            axis=-1,
        )
        kv = linear(h, p["wkv_a"])
        k_rope = _rope(kv[:, :, None, kvr:], positions, rope)[:, :, 0]
        parts = [rms_norm(kv[..., :kvr], p["kv_norm"], c.norm_eps), k_rope]
        if width is not None and width > c.latent_row:
            parts.append(jnp.zeros((b, s, width - c.latent_row), k_rope.dtype))
        row = jnp.concatenate(parts, axis=-1)
    return q, row


def _wkv_b(c: ModelConfig, p: Params):
    """The latent's up-projection per head: W_uk (kvr, H, nope) for keys,
    W_uv (kvr, H, v) for values."""
    w = p["wkv_b"].reshape(
        c.kv_lora_rank, c.n_heads, c.qk_nope_head_dim + c.v_head_dim
    )
    return w[..., : c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def expand_latent(c: ModelConfig, row: jnp.ndarray, p: Params):
    """Expanded form: the full key and value of every head from the
    cached rows, (B, S, H, nope + rope) and (B, S, H, v). What `forward`
    runs (any attention_fn then applies); never run per decode step."""
    b, s, _ = row.shape
    kvr = c.kv_lora_rank
    w_uk, w_uv = _wkv_b(c, p)
    k_nope = jnp.einsum("bsc,chn->bshn", row[..., :kvr], w_uk)
    v = jnp.einsum("bsc,chv->bshv", row[..., :kvr], w_uv)
    k_rope = jnp.broadcast_to(
        row[:, :, None, kvr:], (b, s, c.n_heads, c.qk_rope_head_dim)
    )
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def absorb_query(c: ModelConfig, q: jnp.ndarray, p: Params, width: int):
    """Absorbed form, query side: fold W_uk into the query so it scores
    straight against cached rows — q_lat = q_nope W_uk^T, then
    [q_lat | q_rope | 0...] of `width` values (the pool row's, padding
    included). (B, S, H, nope + rope) -> (B, S, H, width)."""
    nope = c.qk_nope_head_dim
    with jax.named_scope("mla/project"):
        w_uk, _ = _wkv_b(c, p)
        q_lat = jnp.einsum("bshn,chn->bshc", q[..., :nope], w_uk)
        pad = width - c.latent_row
        parts = [q_lat, q[..., nope:]]
        if pad:
            parts.append(jnp.zeros(q.shape[:3] + (pad,), q.dtype))
        return jnp.concatenate(parts, axis=-1)


def latent_output(c: ModelConfig, o_lat: jnp.ndarray, p: Params):
    """Absorbed form, output side: o_lat (B, S, H * kvr), each head's
    weighted sum of latents -> its value through W_uv, then `wo`."""
    b, s, _ = o_lat.shape
    with jax.named_scope("mla/project"):
        _, w_uv = _wkv_b(c, p)
        o = jnp.einsum(
            "bshc,chv->bshv",
            o_lat.reshape(b, s, c.n_heads, c.kv_lora_rank), w_uv,
        )
        return linear(o.reshape(b, s, c.n_heads * c.v_head_dim), p["wo"])


@jax.custom_vjp
def _silu(x: jnp.ndarray) -> jnp.ndarray:
    """silu computed in f32, residual saved in x.dtype.

    Without this, autodiff keeps BOTH f32 (B, S, d_ff) intermediates of
    `silu(x.astype(f32)).astype(bf16)` for backward — on v5e they are the
    single largest no-remat allocation (see config.resolve_remat). The
    custom VJP saves only the bf16 pre-activation and recomputes the f32
    sigmoid in backward: same forward numerics, ~2x less MLP activation
    HBM, which is what lets the flagship fine-tune run remat-free at
    batch sizes that previously forced a remat rung."""
    return jax.nn.silu(x.astype(jnp.float32)).astype(x.dtype)


def _silu_fwd(x):
    return _silu(x), x


def _silu_bwd(x, g):
    xf = x.astype(jnp.float32)
    s = jax.nn.sigmoid(xf)
    grad = s * (1.0 + xf * (1.0 - s))
    return ((g.astype(jnp.float32) * grad).astype(x.dtype),)


_silu.defvjp(_silu_fwd, _silu_bwd)


def mlp_block(c: ModelConfig, x: jnp.ndarray, p: Params) -> jnp.ndarray:
    """Pre-norm SwiGLU MLP with residual — shared with generate.py."""
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp_norm"], c.norm_eps)
        gate, up = _silu(linear(h, p["w_gate"])), linear(h, p["w_up"])
        return x + linear(gate * up, p["w_down"])


def mamba_inputs(c: ModelConfig, x: jnp.ndarray, p: Params, tail0, n_valid):
    """What a state-space mixer computes before its recurrence, from the
    normed input `x` (B, S, d), the convolution's tail the rows come with
    (B, K - 1, Di) and the rows' counts of valid tokens (B,) -> (u, z (B, S,
    Di) in the activation dtype; delta (B, S, Di), B, C (B, S, N) float32;
    the tail after the rows' last VALID token). delta is 0 past a row's
    valid tokens: they move no state."""
    s = x.shape[1]
    di, n, r, k = c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
    f32 = jnp.float32
    with jax.named_scope("mamba/proj"):
        xz = linear(x, p["in_proj"])
        xs, z = xz[..., :di], xz[..., di:]
    with jax.named_scope("mamba/conv"):
        padded = jnp.concatenate([tail0.astype(x.dtype), xs], axis=1)
        conv = sum(
            padded[:, j:j + s].astype(f32) * p["conv_w"][j].astype(f32)
            for j in range(k)
        ) + p["conv_b"].astype(f32)
        u = jax.nn.silu(conv).astype(x.dtype)
        # Input i sits at row i + K - 1 of `padded`: the last K - 1 valid
        # ones are its rows n_valid .. n_valid + K - 2, reaching into the
        # tail the row came with where it has fewer.
        tail = jax.vmap(
            lambda rows, at: lax.dynamic_slice_in_dim(rows, at, k - 1, axis=0)
        )(padded, n_valid)
    with jax.named_scope("mamba/proj"):
        dbc = linear(u, p["x_proj"])
        eps = c.norm_eps
        dt = rms_norm(dbc[..., :r], p["dt_norm"], eps)
        b_in = rms_norm(dbc[..., r:r + n], p["b_norm"], eps).astype(f32)
        c_out = rms_norm(dbc[..., r + n:], p["c_norm"], eps).astype(f32)
        delta = jax.nn.softplus(linear(dt, p["dt_proj"]).astype(f32) + p["dt_bias"])
        valid = jnp.arange(s, dtype=jnp.int32)[None, :] < n_valid[:, None]
        delta = jnp.where(valid[..., None], delta, 0.0)
    return u, z, delta, b_in, c_out, tail


def mamba_output(x_dtype, y, u, z, p: Params):
    """The recurrence's y (B, S, Di) float32 -> the mixer's output: the
    skip D u, the gate silu(z), the out projection."""
    with jax.named_scope("mamba/proj"):
        y = (y + p["D"] * u.astype(jnp.float32)) * jax.nn.silu(z.astype(jnp.float32))
        return linear(y.astype(x_dtype), p["out_proj"])


def mamba_mixer(c: ModelConfig, x: jnp.ndarray, p: Params, h0=None, tail0=None,
                n_valid=None, scan_impl: str = "lax"):
    """A state-space (Mamba-1, with Jamba's three inner norms) mixer on the
    normed input `x` (B, S, d) -> (its output (B, S, d) before the
    residual, h (B, N, Di) float32, tail (B, K - 1, Di)).

    `h0` and `tail0` are the state the rows come with (zero: a sequence's
    start). Only the first `n_valid` (B,) tokens of a row are the
    sequence's (None: all): the rest move nothing — their delta is 0, and
    the tail that comes back is the last K - 1 VALID inputs of the
    convolution. A row with no valid token keeps its state bit for bit.

    A = -exp(A_log), softplus and the recurrence are float32, as the
    published implementation computes them; `h` stays float32 from token
    to token (tests/test_state_space_model.py shows what bfloat16 costs).
    `scan_impl` "pallas" runs a chunk of one row through the chunk kernel
    (workloads/selective_scan.py; the decode step's kernel works on the
    state POOL and is called from kv_blocks._layer_loop)."""
    b, s, _ = x.shape
    f32 = jnp.float32
    if h0 is None:
        h0 = jnp.zeros((b,) + c.state_shapes()[0], f32)
    if tail0 is None:
        tail0 = jnp.zeros((b,) + c.state_shapes()[1], x.dtype)
    if n_valid is None:
        n_valid = jnp.full((b,), s, jnp.int32)
    u, z, delta, b_in, c_out, tail = mamba_inputs(c, x, p, tail0, n_valid)
    with jax.named_scope("mamba/scan"):
        a = -jnp.exp(p["A_log"].astype(f32))
        if scan_impl == "pallas" and b == 1 and s % 8 == 0:
            y, h = selective_scan_chunk(
                delta[0], u[0].astype(f32), b_in[0], c_out[0], a, h0[0].astype(f32)
            )
            y, h = y[None], h[None]
        else:
            y, h = selective_scan(delta, u.astype(f32), b_in, c_out, a, h0.astype(f32))
        h = jnp.where((n_valid > 0)[:, None, None], h, h0.astype(f32))
    return mamba_output(x.dtype, y, u, z, p), h.astype(h0.dtype), tail


def apply_remat(
    body, c: ModelConfig, n_tokens: int, mesh=None,
    seq_len: Optional[int] = None, attn_scores: bool = False,
):
    """Wrap a scanned block body per the resolved remat policy.

    Shapes inside jit are global, so the per-device estimate divides by
    the mesh's activation/weight sharding factors (config.resolve_remat).
    attn_scores marks the plain O(S^2)-memory attention path; the flash
    kernels recompute scores in backward and don't pay it."""
    shards = dict(mesh.shape) if mesh is not None else None
    policy = c.resolve_remat(
        n_tokens, shards, seq_len=seq_len, attn_scores=attn_scores
    )
    if policy == "none":
        return body
    policies = {
        "full": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    return jax.checkpoint(body, policy=policies[policy])


def _block(
    c: ModelConfig,
    x: jnp.ndarray,
    p: Params,
    positions: jnp.ndarray,
    attention_fn: AttentionFn,
    mesh=None,
    kind: str = FULL,
    depth=None,
):
    """One decoder block of a `kind` layer -> (x, router_aux). aux is 0.0
    for dense models. A block carries experts iff its weights do
    (`router`): the leading dense layers of an expert model run the plain
    MLP. A CCA block (`kind` HYBRID) takes the router's `depth` carry too
    and hands it on: -> (x, router_aux, depth)."""
    b, s, _ = x.shape
    if kind == HYBRID:
        return cca_block(c, x, p, positions, attention_fn, mesh, depth)
    if kind == MAMBA:
        out, _, _ = mamba_mixer(c, rms_norm(x, p["attn_norm"], c.norm_eps), p)
        return mlp_block(c, x + out, p), jnp.float32(0.0)
    if c.latent:
        q, row = project_latent(c, x, p, positions)
        k, v = expand_latent(c, row, p)
        attn = attention_fn(q, k, v).reshape(b, s, c.n_heads * c.v_head_dim)
    else:
        q, k, v = project_qkv(c, x, p, positions, kind)
        # Only a window layer names its window: an attention_fn written
        # for full layers alone keeps working on models that have no other.
        kw = {"window": c.window(kind)} if c.window(kind) else {}
        attn = attention_fn(q, k, v, **kw).reshape(b, s, c.heads(kind) * c.head_dim)
    x = x + attn_output(attn, p, head_gate(c, x, p))
    if "router" in p:
        from dstack_tpu.workloads.moe import moe_block

        return moe_block(c, x, p, mesh)
    return mlp_block(c, x, p), jnp.float32(0.0)


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,
    *,
    attention_fn: Optional[AttentionFn] = None,
    positions: Optional[jnp.ndarray] = None,
    mesh=None,
    return_aux: bool = False,
    return_hidden: bool = False,
):
    """tokens (B, S) int32 -> logits (B, S, V) in f32.

    With return_aux=True returns (logits, aux) where aux is the summed
    router load-balance loss over layers (0.0 for dense models).
    With return_hidden=True the lm-head matmul is skipped and the
    final-norm hidden states (B, S, D) come back in place of logits —
    the chunked-CE loss (train.loss_fn) applies the head itself per
    sequence chunk so the full logits tensor is never materialized."""
    c = config
    attn = attention_fn or plain_attention
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    x = embed_tokens(params, tokens)

    quadratic = getattr(attn, "memory_is_quadratic", None)
    if quadratic is not None:
        attn_scores = quadratic(tokens.shape[1], c.head_dim, c.dtype_bytes)
    else:
        attn_scores = attn is plain_attention

    def block(kind):
        def body(carry, layer_p):
            x, aux, *depth = carry
            if isinstance(layer_p, tuple):   # (every layer's, its kind's own)
                layer_p = {**layer_p[0], **layer_p[1]}
            x, layer_aux, *depth = _block(
                c, x, layer_p, positions, attn, mesh, kind, *depth)
            return (x, aux + layer_aux, *depth), None

        return apply_remat(
            body, c, tokens.shape[0] * tokens.shape[1], mesh,
            seq_len=tokens.shape[1], attn_scores=attn_scores,
        )

    blocks = {kind: block(kind) for kind in set(c.layer_types or (FULL,))}
    carry = (x, jnp.float32(0.0)) + (
        (depth_carry(c, x),) if c.expert_skip else ())
    for stack, own, kinds in zip(
        layer_stacks(params), mixer_stacks(params), c.stack_kinds
    ):
        carry, _ = scan_layers(
            lambda carry, p, kind: blocks[kind](carry, p), carry, stack,
            kinds, own=own,
        )
    x, aux = carry[:2]

    x = final_norm(c, params, x)
    if return_hidden:
        return (x, aux) if return_aux else x
    logits = logits_linear(x, head_weights(params))
    if return_aux:
        return logits, aux
    return logits


# The two ends of every program and the attention block's way out, each
# under its scope (a device profile is read by them: benchmarks/scope_reduce.py).


def embed_tokens(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    """tokens (...) int32 -> their embedding rows (..., d)."""
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], tokens, axis=0)


def final_norm(c: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The norm before the head (`logits_linear` carries the same scope)."""
    with jax.named_scope("head"):
        return rms_norm(x, params["final_norm"], c.norm_eps)


def attn_output(attn: jnp.ndarray, p: Params, gate=None) -> jnp.ndarray:
    """The attention block's out projection (before the residual), of the
    heads' outputs (B, S, H * hd), each multiplied by its `gate` (B, S, H)
    where the model gates its heads (`head_gate`)."""
    with jax.named_scope("attn/out"):
        if gate is not None:
            with jax.named_scope("attn_gate"):
                b, s, h = gate.shape
                attn = (
                    attn.reshape(b, s, h, -1).astype(jnp.float32) * gate[..., None]
                ).astype(attn.dtype).reshape(attn.shape)
        return linear(attn, p["wo"])


def head_gate(c: ModelConfig, x: jnp.ndarray, p: Params):
    """The per-head output gate of the block whose input is `x`: act(h Wg)
    (B, S, H) float32, from the same normed input as the queries; None for
    a model without (`ModelConfig.attn_gate`). Under `attn/qkv` it counts
    with the attention's projections, and by its own name apart."""
    if not c.attn_gate:
        return None
    act = {"softplus": jax.nn.softplus, "sigmoid": jax.nn.sigmoid}[c.attn_gate]
    with jax.named_scope("attn/qkv/attn_gate"):
        h = rms_norm(x, p["attn_norm"], c.norm_eps)
        return act(linear(h, p["wg"]).astype(jnp.float32))


# Compressed convolutional attention and its router (the `zaya` block;
# ModelConfig.cca). Each form the published configuration leaves open lives
# in one function below, as benchmarks/reference/zaya.py writes it out.


def cca_leaves(c: ModelConfig, key: jax.Array, L: int, dense) -> Params:
    """L CCA layers' leaves beyond the attention projections and the bank:
    the two convolutions (`cca_conv0` a tap and channel of every query and
    key head, `cca_conv1` a tap and head, channels into channels), each query
    head's temperature, the router (its down-projection, depth weight, norm
    and three layers, the last `router`, to the `n_choices` scores, float32)
    and the residual scales of each sublayer (row 0 on the stream, row 1 on
    the sublayer's output; 1 at the start). The temperatures start near
    sqrt(head_dim), where a score of two unit vectors has the spread a plain
    head's score has; the depth weight at 0.5; the router's last layer four
    times wider than its fan-in gives, so that seeded scores spread as a
    trained router's do (17 nearly equal logits weigh every expert by about
    1/17 and make a third of the positions near-ties: 0.36 of them within
    0.002 at one, 0.06 at four, on the CPU at `tiny-zaya`'s widening)."""
    D, R, hd = c.d_model, c.router_hidden_size, c.head_dim
    G = c.n_heads + c.n_kv_heads
    ks = jax.random.split(key, 7)
    normal = lambda k, shape, fan_in: (
        jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5)
    return {
        "cca_conv0": dense(ks[0], (L, c.cca_time0, G * hd), c.cca_time0),
        "cca_conv1": dense(ks[1], (L, c.cca_time1, G, hd, hd), c.cca_time1 * hd),
        "cca_temp": jax.random.uniform(
            ks[2], (L, c.n_heads), jnp.float32, 0.75, 1.25) * hd**0.5,
        "router_down": normal(ks[3], (L, D, R), D),
        "router_gamma": jnp.full((L,), 0.5, jnp.float32),
        "router_norm": jnp.ones((L, R), jnp.float32),
        "router_w1": normal(ks[4], (L, R, R), R),
        "router_w2": normal(ks[5], (L, R, R), R),
        "router": normal(ks[6], (L, R, c.n_choices), R) * 4,
        "res_attn": jnp.ones((L, 2, D), jnp.float32),
        "res_mlp": jnp.ones((L, 2, D), jnp.float32),
    }


def cca_project(c: ModelConfig, x: jnp.ndarray, p: Params,
                positions: jnp.ndarray, tail=None, n_valid=None):
    """A CCA layer's queries, keys and values from its block input x (B, S,
    d) -> (q (B, S, H, hd), k, v (B, S, KV, hd), the tail after each row's
    last VALID token (B, `cca_tail_width`)).

    `tail` is what a row comes with from its earlier tokens (zero: the
    sequence's start, as the convolutions see zeros before position 0);
    only the first `n_valid` (B,) tokens of a row are the sequence's (None:
    all). With n = RMSNorm(x), q~ = n Wq and k~ = n Wk (u = [q~; k~], every
    query head and then every key head):
      c1_t = sum_j a_j * u_{t-j}                (depthwise, cca_time0 taps)
      c2_t = sum_j B_j^(g) c1_{t-j}             (inside a head g, cca_time1 taps)
      q^h = c2^(q,h) + (q~^h + k~^(g(h))) / 2,  k^g = c2^(k,g) + (mean_(h in g)
      q~^h + k~^g) / 2
      q <- tau_h sqrt(hd) q / |q|, k <- k / |k|, then the rotary embedding
      v_t = [n_t Wv's first half of each KV head; n_(t-1) Wv's second half]
    c1 is rounded to the activation dtype where it is computed, so a chunk
    boundary (the tail keeps it in that dtype) changes nothing."""
    b, s, _ = x.shape
    H, KV, hd = c.n_heads, c.n_kv_heads, c.head_dim
    t0, t1, half = c.cca_time0, c.cca_time1, hd // 2
    width, G = (H + KV) * hd, H + KV
    f32, dt = jnp.float32, x.dtype
    if tail is None:
        tail = jnp.zeros((b, c.cca_tail_width()), dt)
    if n_valid is None:
        n_valid = jnp.full((b,), s, jnp.int32)
    with jax.named_scope("attn/qkv"):
        n = rms_norm(x, p["attn_norm"], c.norm_eps)
        u = jnp.concatenate([linear(n, p["wq"]), linear(n, p["wk"])], axis=-1)
        v = linear(n, p["wv"]).reshape(b, s, KV, hd)
    with jax.named_scope("attn/full/attn_conv"):
        cut = (t0 - 1) * width, (t0 + t1 - 2) * width
        u_all = jnp.concatenate(
            [tail[:, :cut[0]].reshape(b, t0 - 1, width).astype(dt), u], axis=1)
        c1 = sum(
            u_all[:, t0 - 1 - j:t0 - 1 - j + s].astype(f32)
            * p["cca_conv0"][j].astype(f32) for j in range(t0)
        ).astype(dt)
        c1_all = jnp.concatenate(
            [tail[:, cut[0]:cut[1]].reshape(b, t1 - 1, width).astype(dt), c1],
            axis=1)
        # (float32 operands: a product with a batch axis of bf16 into float32
        # is no kernel XLA:CPU has; on the TPU the default precision takes
        # the same one bf16 pass)
        c2 = sum(
            jnp.einsum(
                "bsgi,gij->bsgj",
                c1_all[:, t1 - 1 - j:t1 - 1 - j + s].reshape(b, s, G, hd).astype(f32),
                p["cca_conv1"][j].astype(f32),
            ) for j in range(t1)
        )
        q0 = u[..., :H * hd].reshape(b, s, KV, H // KV, hd).astype(f32)
        k0 = u[..., H * hd:].reshape(b, s, KV, 1, hd).astype(f32)
        q = c2[:, :, :H] + 0.5 * (q0 + k0).reshape(b, s, H, hd)
        k = c2[:, :, H:] + 0.5 * (jnp.mean(q0, axis=3) + k0[:, :, :, 0])
        q = q * lax.rsqrt(jnp.maximum(jnp.sum(q * q, -1, keepdims=True), 1e-12)) * (
            p["cca_temp"][:, None] * hd**0.5)
        k = k * lax.rsqrt(jnp.maximum(jnp.sum(k * k, -1, keepdims=True), 1e-12))
        shifted = tail[:, cut[1]:].reshape(b, 1, KV, half).astype(dt)
        v_all = jnp.concatenate([shifted, v[..., half:]], axis=1)
        v = jnp.concatenate([v[..., :half], v_all[:, :s]], axis=-1)
        # Input i of a convolution sits at row i + taps - 1 of its `_all`:
        # the last taps - 1 valid ones are its rows n_valid .. n_valid +
        # taps - 2, reaching into the tail the row came with where it has
        # fewer (a row with no valid token keeps its tail bit for bit).
        last = lambda rows, n: jax.vmap(
            lambda r, at: lax.dynamic_slice_in_dim(r, at, n, axis=0)
        )(rows, n_valid).reshape(b, -1)
        tail = jnp.concatenate(
            [last(u_all, t0 - 1), last(c1_all, t1 - 1),
             last(v_all.reshape(b, s + 1, KV * half), 1)], axis=-1)
    with jax.named_scope("attn/qkv"):
        rope = c.rope(HYBRID)
        q = _rope(q, positions, rope).astype(dt)
        k = _rope(k, positions, rope).astype(dt)
    return q, k, v, tail


def residual_merge(x: jnp.ndarray, y: jnp.ndarray, scales) -> jnp.ndarray:
    """A sublayer's output y into the stream x by learned scales (2, d):
    scales[0] * x + scales[1] * y, float32."""
    return (scales[0] * x.astype(jnp.float32)
            + scales[1] * y.astype(jnp.float32)).astype(x.dtype)


def depth_carry(c: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """The router's carry before the first layer: zero (B, S, R) float32."""
    return jnp.zeros(x.shape[:2] + (c.router_hidden_size,), jnp.float32)


def depth_router(c: ModelConfig, h: jnp.ndarray, p: Params, depth):
    """The router of a skip-choice expert layer on its normed input h (B, S,
    d) and the carry `depth` from the layer before -> (the last hidden layer
    of its MLP (B, S, R) float32, whose product with `router` gives the
    `n_choices` scores' logits (moe.route_assignments), the carry for the
    next layer). r = h W_down; r^ = r + gamma r^_prev (exponential depth
    averaging: the first layer's carry is zero); the logits z = W_3 gelu(W_2
    gelu(W_1 RMSNorm(r^))), gelu the exact (erf) form."""
    with jax.named_scope("moe/route"):
        f32 = jnp.float32
        depth = jnp.einsum("bsd,dr->bsr", h, p["router_down"],
                           preferred_element_type=f32) + p["router_gamma"] * depth
        z = rms_norm(depth, p["router_norm"], c.norm_eps)
        for w in ("router_w1", "router_w2"):
            z = jax.nn.gelu(z @ p[w], approximate=False)
        return z, depth


def cca_block(c: ModelConfig, x: jnp.ndarray, p: Params, positions,
              attention_fn: AttentionFn, mesh, depth):
    """A whole CCA block on a full sequence -> (x, router_aux, depth)."""
    from dstack_tpu.workloads.moe import moe_block

    b, s, _ = x.shape
    q, k, v, _ = cca_project(c, x, p, positions)
    attn = attention_fn(q, k, v).reshape(b, s, c.n_heads * c.head_dim)
    with jax.named_scope("attn/out"):
        x = residual_merge(x, attn_output(attn, p), p["res_attn"])
    return moe_block(c, x, p, mesh, depth=depth)
