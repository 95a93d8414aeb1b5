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

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dstack_tpu.workloads.attention import plain_attention
from dstack_tpu.workloads.config import FULL, ModelConfig, RopeParams

Params = Dict[str, Any]
AttentionFn = Callable[..., jnp.ndarray]


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Initialise bf16 params. Layer weights are stacked on axis 0 for scan.

    Blocks of one kind share a stack. A model whose first
    `n_dense_layers` blocks are plain SwiGLU and the rest carry experts
    has two: `dense_layers` (run first) and `layers`; every other model
    has `layers` alone, as before."""
    c = config
    dt = c.activation_dtype
    keys = jax.random.split(key, 8)

    def norm_init(shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5).astype(dt)

    D, V = c.d_model, c.vocab_size

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
        else:
            hd = c.head_dim
            layers["wq"] = dense(keys[1], (L, D, c.n_heads * hd), D)
            layers["wk"] = dense(keys[2], (L, D, c.n_kv_heads * hd), D)
            layers["wv"] = dense(keys[3], (L, D, c.n_kv_heads * hd), D)
            layers["wo"] = dense(keys[4], (L, c.n_heads * hd, D), c.n_heads * hd)
        F = d_ff
        if experts:
            E = c.n_experts
            # Router stays f32: tiny, and routing decisions are precision-
            # sensitive (a bf16 tie flips top-k membership).
            layers["router"] = (
                jax.random.normal(keys[5], (L, D, E), dtype=jnp.float32) * D**-0.5
            )
            ek = jax.random.split(keys[6], 3)
            layers["we_gate"] = dense(ek[0], (L, E, D, F), D)
            layers["we_up"] = dense(ek[1], (L, E, D, F), D)
            layers["we_down"] = dense(ek[2], (L, E, F, D), F)
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
        return layers

    nd = c.n_dense_layers
    params = {
        "embed": dense(keys[0], (V, D), D),
        "layers": stack(keys, c.n_layers - nd, c.d_ff, c.n_experts > 0),
        "final_norm": norm_init((D,)),
        "lm_head": dense(jax.random.fold_in(key, 99), (D, V), D),
    }
    if nd:
        params["dense_layers"] = stack(
            jax.random.split(jax.random.fold_in(key, 98), 8), nd,
            c.dense_d_ff, False,
        )
    return params


def layer_stacks(params: Params):
    """The model's stacks of blocks in the order they run: the leading
    dense layers (if the model has them), then `layers`."""
    if "dense_layers" in params:
        return (params["dense_layers"], params["layers"])
    return (params["layers"],)


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

    if isinstance(w, QTensor):
        y = jnp.matmul(
            x, w.q.astype(x.dtype), preferred_element_type=jnp.float32
        )
        return y * w.scale
    return jnp.matmul(
        x, w, preferred_element_type=jnp.float32
    ).astype(jnp.float32)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * weight).astype(x.dtype)


def _rope(x: jnp.ndarray, positions: jnp.ndarray, rope: RopeParams) -> jnp.ndarray:
    """Rotary embedding. x: (B, S, H, hd); positions: (S,) or (B, S);
    `rope` a layer kind's `RopeParams` (config.rope): a scaled kind's
    frequencies and its factor on cos and sin are constants of the trace."""
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
    """Pre-norm QKV projection with the rope of a `kind` layer — shared by
    the training block and the KV-cache decode path (generate.py) so they
    cannot drift."""
    b, s, _ = x.shape
    hd = c.head_dim
    rope = c.rope(kind)
    h = rms_norm(x, p["attn_norm"], c.norm_eps)
    q = linear(h, p["wq"]).reshape(b, s, c.n_heads, hd)
    k = linear(h, p["wk"]).reshape(b, s, c.n_kv_heads, hd)
    v = linear(h, p["wv"]).reshape(b, s, c.n_kv_heads, hd)
    return _rope(q, positions, rope), _rope(k, positions, rope), v


def scan_layers(c: ModelConfig, block, carry, xs):
    """`lax.scan` of `block(carry, x, kind) -> (carry, y)` over the leading
    (layer) axis of `xs`, one PERIOD of the model's layer pattern a scan
    step (config.layer_period): the `p` blocks of a period run in order
    inside the body, each traced with its own kind, block j of step t
    reading layer t * p + j of `xs`. A model of one kind is the plain scan
    over its layers. -> (carry, ys) with `ys` on the layer axis.

    A block indexes the stack itself, one layer at a time, as a plain
    scan's body does: handed a whole period as the step's `xs`, XLA cuts
    the period's weights out of the stack into a buffer of their own every
    step (three copies of 1 GB a step at 4 layers of 64 experts: AOT for
    the v5e, PERF.md section 6, PR 31)."""
    period = c.layer_period
    p = len(period)
    if p == 1:
        return lax.scan(lambda carry, x: block(carry, x, period[0]), carry, xs)
    tree_map = jax.tree_util.tree_map
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]

    def body(carry, t):
        ys = []
        for j, kind in enumerate(period):
            x = tree_map(
                lambda a: lax.dynamic_index_in_dim(a, t * p + j, keepdims=False),
                xs,
            )
            carry, y = block(carry, x, kind)
            ys.append(y)
        return carry, tree_map(lambda *a: jnp.stack(a), *ys)

    carry, ys = lax.scan(body, carry, jnp.arange(n // p, dtype=jnp.int32))
    return carry, tree_map(
        lambda a: a.reshape((a.shape[0] * p,) + a.shape[2:]), ys
    )


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
    h = rms_norm(x, p["mlp_norm"], c.norm_eps)
    gate = _silu(linear(h, p["w_gate"]))
    up = linear(h, p["w_up"])
    return x + linear(gate * up, p["w_down"])


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
):
    """One decoder block of a `kind` layer -> (x, router_aux). aux is 0.0
    for dense models. A block carries experts iff its weights do
    (`router`): the leading dense layers of an expert model run the plain
    MLP."""
    b, s, _ = x.shape
    if c.latent:
        q, row = project_latent(c, x, p, positions)
        k, v = expand_latent(c, row, p)
        attn = attention_fn(q, k, v).reshape(b, s, c.n_heads * c.v_head_dim)
    else:
        q, k, v = project_qkv(c, x, p, positions, kind)
        # Only a window layer names its window: an attention_fn written
        # for full layers alone keeps working on models that have no other.
        kw = {"window": c.window(kind)} if c.window(kind) else {}
        attn = attention_fn(q, k, v, **kw).reshape(b, s, c.n_heads * c.head_dim)
    x = x + linear(attn, p["wo"])
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

    x = jnp.take(params["embed"], tokens, axis=0)

    quadratic = getattr(attn, "memory_is_quadratic", None)
    if quadratic is not None:
        attn_scores = quadratic(tokens.shape[1], c.head_dim, c.dtype_bytes)
    else:
        attn_scores = attn is plain_attention

    def block(kind):
        def body(carry, layer_p):
            x, aux = carry
            x, layer_aux = _block(c, x, layer_p, positions, attn, mesh, kind)
            return (x, aux + layer_aux), None

        return apply_remat(
            body, c, tokens.shape[0] * tokens.shape[1], mesh,
            seq_len=tokens.shape[1], attn_scores=attn_scores,
        )

    blocks = {kind: block(kind) for kind in set(c.layer_period)}
    carry = (x, jnp.float32(0.0))
    for stack in layer_stacks(params):
        carry, _ = scan_layers(
            c, lambda carry, p, kind: blocks[kind](carry, p), carry, stack
        )
    x, aux = carry

    x = rms_norm(x, params["final_norm"], c.norm_eps)
    if return_hidden:
        return (x, aux) if return_aux else x
    logits = logits_linear(x, params["lm_head"])
    if return_aux:
        return logits, aux
    return logits
