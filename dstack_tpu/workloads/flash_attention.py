"""Flash attention: fused Pallas TPU kernels for the single-device hot path.

The streaming-softmax math is the same as `attention._block_attend`; here
the blocking happens *inside* one chip's VMEM instead of across devices:
the (S, S) probability matrix is never materialized in HBM, in forward or
backward — q/k/v tiles stream HBM→VMEM, logits/probabilities live only in
registers/VMEM (pallas_guide: Memory Spaces, Tiling Constraints, Patterns:
Custom VJP). The ring path composes with these kernels too: each ring
step's per-shard block runs `flash_block_attend` on TPU (see the ring
section at the bottom).

This is a capability the reference cannot have: dstack is an orchestrator
with no compute kernels at all (SURVEY §2.7) — the TPU-native framework
ships its own. Backward recomputes probabilities blockwise from the saved
logsumexp (standard flash backward), so residual memory is O(S) per head
row, not O(S^2).

Dispatch rules (`use_flash`): TPU backend, head_dim a multiple of 128
(bf16/f32 lane tiling), seq divisible by the block size and small enough
that one head's K/V fits VMEM comfortably. Everything else falls back to
`plain_attention`, including CPU tests — which also validate these kernels
via `interpret=True`.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import os as _os

# Max block sizes (env-tunable perf knobs): the actual block per call is the
# largest divisor of seq up to the max — 1024x1024 measured 25% faster than
# 256x256 on v5e at seq 2048 (fewer grid steps, better MXU occupancy), while
# shorter sequences still dispatch with smaller blocks.
MIN_BLK = 128


MAX_BLK = 1024  # 2048-wide blocks put a >16MB f32 logits tile on the
# kernel stack and exceed the scoped-VMEM limit (measured on v5e); 1024
# keeps the (blk_q, blk_k) f32 block at 4MB with room for accumulators
# and double-buffering.


def _env_block(name: str, default: int) -> int:
    """Env perf knob, normalized to a power of two in [MIN_BLK, MAX_BLK] —
    anything else would let _pick_block return a non-divisor of seq (and
    silently drop query tiles) or blow the kernel's scoped VMEM."""
    try:
        raw = int(_os.getenv(name, str(default)))
    except ValueError:
        return default
    blk = MIN_BLK
    while blk * 2 <= min(raw, MAX_BLK):
        blk *= 2
    return blk


BLK_Q = _env_block("DSTACK_TPU_FLASH_BLOCK_Q", 1024)
BLK_K = _env_block("DSTACK_TPU_FLASH_BLOCK_K", 1024)
NEG_INF = -1e30
# One head's full K+V ride in VMEM (~16MB/core): budget them to 8MB so q/o
# tiles, f32 accumulators and double-buffering fit alongside. The check
# scales with head_dim and element size — a seq-only cap would admit
# f32/hd-256 shapes that blow VMEM and crash at compile instead of falling
# back. Empirically verified on v5e: every admitted bf16/hd-128 shape up to
# the budget boundary (seq 16384, KV exactly 8MB) compiles and runs — as a
# STANDALONE kernel. Inside a multi-layer model, 1024-wide tiles at
# seq 8192+ crashed the AOT compile helper of the platform in use then,
# which is why _pick_block caps long-sequence tiles at 512 (see its
# docstring before raising the cap; the reason has not been re-verified
# against the current libtpu — ROADMAP S8).
KV_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def use_flash(
    seq_len: int,
    head_dim: int,
    *,
    dtype_bytes: int = 2,
    interpret: bool = False,
    kv_block_size: int = None,
    num_heads: int = None,
    num_kv_heads: int = None,
    model_shards: int = 1,
    window: int = 0,
) -> bool:
    """Whether the fused Pallas path handles this shape on this backend.

    A sliding-attention layer (`window` > 0) whose sequence is longer than
    its window is not handled: the flash kernels mask causally and know no
    window (the paged kernel does, paged_attention.py). A sequence inside
    the window is plain causal attention.

    With `kv_block_size` set, the caller attends over paged KV blocks
    (paged_attention.ragged_attention): the kernel streams one
    `kv_block_size`-row tile at a time, so the dense `seq % MIN_BLK`
    rule would wrongly reject block-granular windows — the paged rules
    are block-aligned seq and a single K+V tile within the VMEM budget.

    Under a "model"-sharded mesh each shard's program sees
    `num_heads / model_shards` query heads and `num_kv_heads /
    model_shards` KV heads — the rule must judge THAT geometry, not the
    global one, or the Pallas-vs-lax choice flips incorrectly (e.g. a
    global n_rep of 2 can be per-shard n_rep 1, or fractional). Pass the
    GLOBAL counts plus `model_shards`; the per-shard division happens
    here. `model_shards > 1` currently always answers False: pallas_call
    carries no SPMD partitioning rule, and inside a GSPMD-partitioned
    program its lowering is refused outright ("Mosaic kernels cannot be
    automatically partitioned") — the lax path is what partitions. (The
    trainer's flash call runs under shard_map instead, attention.py.)
    """
    import os

    if os.getenv("DSTACK_TPU_FLASH_ATTENTION", "1") == "0":
        return False
    if window and seq_len > window:
        return False
    if not interpret and jax.default_backend() != "tpu":
        return False
    if model_shards < 1:
        raise ValueError(f"model_shards must be >= 1, got {model_shards}")
    if num_heads is not None or num_kv_heads is not None:
        if num_heads is None or num_kv_heads is None:
            raise ValueError(
                "num_heads and num_kv_heads must be passed together"
            )
        if num_heads % model_shards or num_kv_heads % model_shards:
            raise ValueError(
                f"heads ({num_heads} q / {num_kv_heads} kv) must divide"
                f" model_shards={model_shards} — the engine validates"
                " this at construction"
            )
        per_q = num_heads // model_shards
        per_kv = num_kv_heads // model_shards
        # The kernels replicate KV across the GQA group via an integral
        # n_rep; a per-shard geometry that breaks it must fall back.
        if per_kv < 1 or per_q % per_kv:
            return False
    if model_shards > 1:
        return False  # no pallas SPMD partitioning rule (see docstring)
    if kv_block_size is not None:
        tile_bytes = 2 * kv_block_size * head_dim * dtype_bytes  # K + V tile
        return (
            head_dim % 128 == 0
            and seq_len % kv_block_size == 0
            and tile_bytes <= KV_VMEM_BUDGET_BYTES
        )
    kv_bytes = 2 * seq_len * head_dim * dtype_bytes  # K + V, one head
    return (
        head_dim % 128 == 0
        and seq_len % MIN_BLK == 0
        and kv_bytes <= KV_VMEM_BUDGET_BYTES
    )


def _pick_block(seq: int, max_blk: int) -> int:
    """Largest power-of-two block <= max_blk that divides seq.

    Long sequences cap at 512 (overriding even the env knob): measured
    on v5e, 1024x1024 tiles inside a multi-layer scanned model at
    S=8192 crash the TPU compiler (host-side AOT helper exits 1; the
    kernel ALONE compiles fine — the blowup needs several in-module
    instantiations), while 512 compiles everywhere and is within
    run-to-run noise at every measured shape (docs/design/perf.md).
    """
    if seq > 4096:
        max_blk = min(max_blk, 512)
    blk = max_blk
    while blk > MIN_BLK and seq % blk != 0:
        blk //= 2
    assert seq % blk == 0, (seq, blk)  # guaranteed by use_flash + _env_block
    return blk


# ---- forward ---------------------------------------------------------------


def _streaming_attend(q_ref, k_ref, v_ref, *, causal: bool, blk_k: int):
    """Shared streaming-softmax body: returns unnormalized (o, m, l) for
    this grid tile's queries against the whole K/V in VMEM. Epilogues
    differ per kernel (normalize+lse vs raw ring partials)."""
    blk_q, hd = q_ref.shape[1], q_ref.shape[2]
    seq = k_ref.shape[1]
    iq = pl.program_id(1)
    q_start = iq * blk_q
    q = q_ref[0].astype(jnp.float32)  # (blk_q, hd)
    scale = hd ** -0.5

    n_blocks = seq // blk_k
    if causal:
        # Blocks strictly above the diagonal contribute nothing; bound the
        # loop by the last block any of this tile's queries can see.
        n_blocks = jnp.minimum(n_blocks, (q_start + blk_q + blk_k - 1) // blk_k)

    def body(j, carry):
        o, m, l = carry
        k = k_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (blk_q, blk_k)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            cols = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            logits = jnp.where(rows >= cols, logits, NEG_INF)
        blk_m = jnp.max(logits, axis=-1, keepdims=True)  # (blk_q, 1)
        blk_m = jnp.maximum(blk_m, NEG_INF / 2)
        p = jnp.exp(logits - blk_m)
        blk_l = jnp.sum(p, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, blk_m)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(blk_m - m_new)
        l_new = l * alpha + blk_l * beta
        o_new = o * alpha + beta * jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((blk_q, hd), jnp.float32)
    m0 = jnp.full((blk_q, 1), NEG_INF / 2, jnp.float32)
    l0 = jnp.zeros((blk_q, 1), jnp.float32)
    return jax.lax.fori_loop(0, n_blocks, body, (o0, m0, l0))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool, blk_k: int):
    o, m, l = _streaming_attend(q_ref, k_ref, v_ref, causal=causal, blk_k=blk_k)
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _flash_fwd_call(q, k, v, causal: bool, interpret: bool):
    bh, seq, hd = q.shape
    blk_q = _pick_block(seq, BLK_Q)
    blk_k = _pick_block(seq, BLK_K)
    grid = (bh, seq // blk_q)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, blk_k=blk_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i: (b, i, 0)),
            # lse rides as (bh, 1, seq): TPU requires the last two block
            # dims to be (8k, 128k) or full-size — (1, BLK) satisfies it.
            pl.BlockSpec((1, 1, blk_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---- backward --------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, causal, blk_k):
    blk_q, hd = q_ref.shape[1], q_ref.shape[2]
    seq = k_ref.shape[1]
    iq = pl.program_id(1)
    q_start = iq * blk_q
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]  # (blk_q, 1)
    delta = delta_ref[0, 0][:, None]
    scale = hd ** -0.5

    n_blocks = seq // blk_k
    if causal:
        n_blocks = jnp.minimum(n_blocks, (q_start + blk_q + blk_k - 1) // blk_k)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            cols = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            logits = jnp.where(rows >= cols, logits, NEG_INF)
        p = jnp.exp(logits - lse)  # normalized probabilities
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(0, n_blocks, body, jnp.zeros((blk_q, hd), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, causal, blk_q
):
    blk_k, hd = k_ref.shape[1], k_ref.shape[2]
    seq = q_ref.shape[1]
    jk = pl.program_id(1)
    k_start = jk * blk_k
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    scale = hd ** -0.5

    n_blocks = seq // blk_q
    start = jnp.array(0, jnp.int32)
    if causal:
        # Query blocks strictly before this kv block see none of it.
        start = k_start // blk_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * blk_q, blk_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * blk_q, blk_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * blk_q, blk_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(i * blk_q, blk_q)][:, None]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            rows = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            logits = jnp.where(rows >= cols, logits, NEG_INF)
        p = jnp.exp(logits - lse)  # (blk_q, blk_k)
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    dk0 = jnp.zeros((blk_k, hd), jnp.float32)
    dv0 = jnp.zeros((blk_k, hd), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, n_blocks, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_call(q, k, v, do, lse, delta, causal: bool, interpret: bool):
    bh, seq, hd = q.shape
    blk_q = _pick_block(seq, BLK_Q)
    blk_k = _pick_block(seq, BLK_K)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, blk_k=blk_k),
        grid=(bh, seq // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, blk_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, blk_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, blk_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, blk_q=blk_q),
        grid=(bh, seq // blk_k),
        in_specs=[
            pl.BlockSpec((1, seq, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, seq, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, seq), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, seq), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---- custom-vjp wrapper ----------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal: bool, interpret: bool):
    o, _ = _flash_fwd_call(q, k, v, causal, interpret)
    return o


def _flash_fwd(q, k, v, causal, interpret):
    o, lse = _flash_fwd_call(q, k, v, causal, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, interpret, residuals, do):
    q, k, v, o, lse = residuals
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, None, :]
    dq, dk, dv = _flash_bwd_call(q, k, v, do, lse, delta, causal, interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in for `plain_attention`: q (B, S, H, hd), k/v (B, S, KV, hd).

    GQA expansion happens OUTSIDE the custom-vjp boundary, so autodiff of
    the broadcast sums dk/dv over the query-head groups automatically.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    n_rep = h // kv
    if n_rep > 1:
        from dstack_tpu.workloads.attention import _repeat_kv

        k = _repeat_kv(k, n_rep)
        v = _repeat_kv(v, n_rep)

    def to_bh(x):  # (B, S, H, hd) -> (B*H, S, hd)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), causal, interpret)
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


# ---- ring-step block attend ------------------------------------------------
# The ring path (attention._ring_attention_local) consumes per-step partial
# results (unnormalized o, running max m, sum l) and merges them across ring
# hops. This kernel computes one step's partials WITHOUT materializing the
# (Sq_shard, Sk_shard) logits in HBM — at 32k context over 4 devices that
# matrix is 256MB f32 per step per head batch, the long-context memory wall.
# Backward recomputes through the jnp reference (same math, XLA-fused), so
# gradients stay exact while the forward gets the fused kernel. Known
# limitation: that recompute re-materializes the per-step logits in the
# BACKWARD pass, so training at extreme context keeps the old memory
# profile there (inference/serving gets the full win). A blockwise ring
# backward needs cotangents w.r.t. the (o, m, l) partials — a different
# derivation than _bwd_dq/_bwd_dkv's normalized-output form.


def _block_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *, causal, blk_k):
    o, m, l = _streaming_attend(q_ref, k_ref, v_ref, causal=causal, blk_k=blk_k)
    o_ref[0] = o  # unnormalized, relative to m — the ring merge normalizes
    m_ref[0, 0] = m[:, 0]
    l_ref[0, 0] = l[:, 0]


def _block_ref_bh(q, k, v, causal: bool):
    """jnp reference of the kernel in (BH, S, hd) layout — the backward
    path AND the numerics oracle (same math as attention._block_attend)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask[None], logits, NEG_INF)
    m = jnp.maximum(jnp.max(logits, axis=-1), NEG_INF / 2)  # (BH, Sq)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return o, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_block(q, k, v, causal: bool, interpret: bool):
    bh, sq, hd = q.shape
    seq_k = k.shape[1]
    blk_q = _pick_block(sq, BLK_Q)
    blk_k = _pick_block(seq_k, BLK_K)
    o, m, l = pl.pallas_call(
        functools.partial(_block_fwd_kernel, causal=causal, blk_k=blk_k),
        grid=(bh, sq // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_k, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_k, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, blk_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, blk_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, m[:, 0, :], l[:, 0, :]


def _ring_block_fwd(q, k, v, causal, interpret):
    out = _ring_block(q, k, v, causal, interpret)
    return out, (q, k, v)


def _ring_block_bwd(causal, interpret, residuals, cotangents):
    q, k, v = residuals
    # Exact gradients by recompute through the fused-by-XLA reference; the
    # (m, l) cotangents from the ring merge flow through automatically.
    _, vjp = jax.vjp(lambda q, k, v: _block_ref_bh(q, k, v, causal), q, k, v)
    return vjp(cotangents)


_ring_block.defvjp(_ring_block_fwd, _ring_block_bwd)


def flash_block_attend(q, k, v, *, causal: bool, interpret: bool = False):
    """One ring step's partials — drop-in for attention._block_attend with a
    static tril/full mask. q/k/v: (B, S, H, hd) with kv already
    GQA-expanded; returns (o (B,S,H,hd) f32 unnormalized, m (B,H,S),
    l (B,H,S))."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    # The kernel's causal mask is the absolute row>=col diagonal, which
    # equals the ring's shifted-tril only for equal shards.
    assert not causal or sq == sk, (sq, sk)

    def to_bh(x, s):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    o, m, l = _ring_block(to_bh(q, sq), to_bh(k, sk), to_bh(v, sk), causal, interpret)
    o = o.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
    return o, m.reshape(b, h, sq), l.reshape(b, h, sq)
