"""Autoregressive generation with a KV cache (the serving-side workload).

The training side runs `train.make_train_step`; services (JetStream/vLLM in
the examples) bring their own engines — this module is the framework-native
decode path for the same llama-family checkpoints: jitted prefill + a
`lax.scan` decode loop over a static-shape KV cache, so the whole
generation compiles to one XLA program (no per-token Python dispatch, no
dynamic shapes — pallas_guide/XLA semantics).

Consistency contract: prefill+decode must reproduce `transformer.forward`
logits exactly for the same tokens — pinned by tests/test_generate.py.
MoE caveat: capacity-based token dropping (workloads/moe.py) is a
*training-throughput* approximation, not model semantics; decode evaluates
the un-dropped top-k routing (each step has no cross-token competition), so
MoE decode matches `forward` exactly only when forward's capacity admits
every token (tests pin this with a high capacity_factor). When training
drops tokens, decode is the more faithful computation, not a divergence.
"""

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dstack_tpu.workloads.attention import NEG_INF, _repeat_kv
from dstack_tpu.workloads.config import FULL, MAMBA, ModelConfig
from dstack_tpu.workloads.transformer import (
    absorb_query,
    attn_output,
    embed_tokens,
    final_norm,
    head_gate,
    head_weights,
    latent_output,
    layer_stacks,
    logits_linear,
    mamba_mixer,
    mixer_stacks,
    mlp_block,
    project_latent,
    project_qkv,
    rms_norm,
    scan_layers,
)

Params = Dict[str, Any]


class KVCache(NamedTuple):
    """Static-shape per-layer cache: k/v (L, B, max_len, KV, hd); a
    latent-attention model keeps one (1, row) latent row a token in k
    and a zero-wide v (ModelConfig.kv_row_shapes). A model with
    state-space layers keeps rows for its attention layers only (L counts
    those) and, a state-space layer, the scan's state and the
    convolution's tail (ModelConfig.state_shapes)."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray  # () int32 — filled positions
    ssm: Optional[jnp.ndarray] = None   # (Ls, B, d_state, d_inner) float32
    conv: Optional[jnp.ndarray] = None  # (Ls, B, d_conv - 1, d_inner)


def init_cache(
    config: ModelConfig, batch: int, max_len: int, dtype=None
) -> KVCache:
    c = config
    k_row, v_row = c.kv_row_shapes()
    shape = (c.n_attn_layers, batch, max_len)
    dtype = dtype or c.activation_dtype
    recurrent = {}
    if c.has_state_layers:
        h_row, tail_row = c.state_shapes()
        rows = (c.n_state_layers, batch)
        recurrent = {"ssm": jnp.zeros(rows + h_row, jnp.float32),
                     "conv": jnp.zeros(rows + tail_row, dtype)}
    return KVCache(
        **recurrent,
        k=jnp.zeros(shape + k_row, dtype),
        v=jnp.zeros(shape + v_row, dtype),
        length=jnp.zeros((), jnp.int32),
    )


def _cached_attention(q, ck, cv, valid_len, scale=None, window=0):
    """q (B, S, H, hd) against cache k/v (B, max_len, KV, hd); positions at
    or beyond valid_len (zero padding) are masked out. Causality inside the
    new tokens is handled by the caller's masking of valid_len per row.
    `cv` may be narrower than `ck` (latent attention's values are the
    leading columns of its key rows); `scale` defaults to hd ** -0.5. A
    sliding-attention layer's rows see their `window` newest positions."""
    b, s, h, hd = q.shape
    n_rep = h // ck.shape[2]
    k = _repeat_kv(ck, n_rep)
    v = _repeat_kv(cv, n_rep)
    scale = hd ** -0.5 if scale is None else scale
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    kpos = jnp.arange(ck.shape[1], dtype=jnp.int32)
    # Row i of this chunk may attend cache positions <= valid_len[i]-1.
    mask = kpos[None, :] < valid_len[:, None]  # (S, max_len)
    if window:
        mask &= kpos[None, :] >= valid_len[:, None] - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype).reshape(b, s, h * v.shape[-1])


def _forward_cached(
    config: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,
    cache: KVCache,
) -> Tuple[jnp.ndarray, KVCache]:
    """Run `tokens` (B, S) starting at cache.length; returns logits of the
    LAST position (B, V) and the extended cache. Used for both prefill
    (S = prompt len, cache empty) and decode (S = 1)."""
    c = config
    b, s = tokens.shape
    start = cache.length
    positions = start + jnp.arange(s, dtype=jnp.int32)  # (S,)
    # Row i sees cache slots [0, start+i] — causal over old + new tokens.
    valid_len = start + 1 + jnp.arange(s, dtype=jnp.int32)

    x = embed_tokens(params, tokens)

    mixers = mixer_stacks(params)

    def block(x, layer, kind):
        if c.has_state_layers:
            # (what every layer has, what its kind has: weights and cache)
            p, (own, ck, cv) = layer
            p = {**p, **own}
        elif isinstance(layer[0], tuple):
            # (every layer's weights and cache, its kind's own weights)
            (p, ck, cv), own = layer
            p = {**p, **own}
        else:
            p, ck, cv = layer
        if kind == MAMBA:
            out, ck, cv = mamba_mixer(
                c, rms_norm(x, p["attn_norm"], c.norm_eps), p, ck, cv
            )
            return mlp_block(c, x + out, p), (ck, cv)
        if c.latent:
            # The cache row is [c_kv | k_rope | pad]; decode attends in
            # the absorbed form (transformer.absorb_query), as the paged
            # engine does.
            q, row = project_latent(c, x, p, positions, ck.shape[-1])
            ck = lax.dynamic_update_slice(
                ck, row[:, :, None].astype(ck.dtype), (0, start, 0, 0)
            )
            o_lat = _cached_attention(
                absorb_query(c, q, p, ck.shape[-1]), ck,
                ck[..., : c.kv_lora_rank], valid_len, c.head_dim ** -0.5,
            )
            x = x + latent_output(c, o_lat, p)
        else:
            q, k, v = project_qkv(c, x, p, positions, kind)
            ck = lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, start, 0, 0)
            )
            cv = lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, start, 0, 0)
            )
            attn = _cached_attention(
                q, ck, cv, valid_len, window=c.window(kind)
            )
            x = x + attn_output(attn, p, head_gate(c, x, p))
        if "router" in p:
            from dstack_tpu.workloads.moe import moe_block

            x, _ = moe_block(c, x, p)
        else:
            x = mlp_block(c, x, p)
        return x, (ck, cv)

    if c.has_state_layers:
        x, caches = scan_layers(block, x, params["layers"], c.layer_kinds, own={
            kind: (stack,) + ((cache.ssm, cache.conv) if kind == MAMBA
                              else (cache.k, cache.v))
            for kind, stack in mixers[0].items()
        })
        (ssm, conv), (new_k, new_v) = (
            caches.get(MAMBA, (cache.ssm, cache.conv)),
            caches.get(FULL, (cache.k, cache.v)),
        )
        x = final_norm(c, params, x)
        logits = logits_linear(x[:, -1], head_weights(params))
        return logits, KVCache(new_k, new_v, start + s, ssm, conv)
    new_k, new_v, first = [], [], 0
    for stack, own, kinds in zip(layer_stacks(params), mixers, c.stack_kinds):
        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        x, (sk, sv) = in_layer_order(kinds, *scan_layers(
            block, x,
            (stack, cache.k[first:first + n], cache.v[first:first + n]),
            kinds, own=own,
        ))
        new_k.append(sk)
        new_v.append(sv)
        first += n
    new_k = new_k[0] if len(new_k) == 1 else jnp.concatenate(new_k)
    new_v = new_v[0] if len(new_v) == 1 else jnp.concatenate(new_v)
    x = final_norm(c, params, x)
    logits = logits_linear(x[:, -1], head_weights(params))
    return logits, KVCache(k=new_k, v=new_v, length=start + s)


def in_layer_order(kinds, x, ys):
    """`scan_layers`' answer with the `ys` on the layer axis: they come
    back by kind where the layers' kinds have stacks of their own."""
    if not isinstance(ys, dict):
        return x, ys
    rank = {kind: 0 for kind in ys}
    rows = []
    for kind in kinds:
        rows.append(jax.tree_util.tree_map(lambda a: a[rank[kind]], ys[kind]))
        rank[kind] += 1
    return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *rows)


def generate(
    config: ModelConfig,
    params: Params,
    prompt: jnp.ndarray,
    *,
    max_new_tokens: int,
    max_len: Optional[int] = None,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """Greedy (or temperature-sampled) generation: prompt (B, S) int32 ->
    (B, max_new_tokens) int32. Jit-compatible: static shapes throughout."""
    c = config
    b, s = prompt.shape
    # The last generated token is never fed back, so the cache only needs
    # room for s + max_new_tokens - 1 positions (one forward per token, no
    # wasted trailing forward).
    max_len = max_len or min(c.max_seq_len, s + max_new_tokens - 1)
    assert s + max_new_tokens - 1 <= max_len, (s, max_new_tokens, max_len)
    cache = init_cache(c, b, max_len)
    logits, cache = _forward_cached(c, params, prompt, cache)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def pick(logits, key):
        if temperature > 0.0:
            return jax.random.categorical(key, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    keys = jax.random.split(rng, max_new_tokens)
    first = pick(logits, keys[0]).astype(jnp.int32)  # (B,)

    def step(carry, key):
        token, cache = carry
        logits, cache = _forward_cached(c, params, token[:, None], cache)
        nxt = pick(logits, key).astype(jnp.int32)
        return (nxt, cache), nxt

    (_, _), rest = lax.scan(step, (first, cache), keys[1:])
    return jnp.concatenate([first[:, None], rest.T], axis=1)  # (B, N)
