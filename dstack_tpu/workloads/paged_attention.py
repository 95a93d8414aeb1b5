"""Ragged paged attention: attend straight over the block-table pool.

The paged-KV engine (workloads/kv_blocks.py) stores every slot's KV cache
as scattered `(block_size, KV, hd)` blocks inside one shared
`(L, num_blocks, block_size, KV, hd)` pool, indexed by per-slot block
tables. Until r12 every attention consumer first *gathered* a slot's
blocks into a dense `(max_len, KV, hd)` scratch view and ran dense
attention over it — a whole-pool data movement per dispatch that
BENCH_serving_r10 measured at −63.6% single-stream throughput vs the
dense engine, despite a cross-chunk view cache built solely to amortize
it. This module deletes that trade entirely: attention runs directly
against the pool, vLLM-PagedAttention-style, one block at a time with a
streaming softmax, and the dense view is never materialized.

`ragged_attention` takes the STACKED pool and a layer index, never one
layer's `(num_blocks, block_size, KV, hd)` slab: the paged programs
carry the whole pool through their layer loop (kv_blocks._layer_loop),
and a slab cut out of it to feed this module would be a copy of every
block of the layer per layer-step — the traced runs of PR 22 / PR 24
read 0.44 ms per slab at a 151 MB layer, four per layer-step, 64% of the
chat cell's device time. Both paths below address layer l's block j
inside the stack instead.

Two implementations behind one dispatch seam (`ragged_attention`):

- `_ragged_attention_pallas`: a Pallas TPU kernel. Block tables ride in
  as scalar-prefetch operands (pallas_guide: PrefetchScalarGridSpec) so
  each grid step's BlockSpec index_map resolves `tables[b, j]` into the
  pool's block axis and the DMA engine streams exactly that block's
  `(block_size * KV, hd)` K/V slab HBM→VMEM — the gather IS the
  index_map. The pool goes in as `(L * num_blocks, block_size * KV, hd)`
  (a bitcast of the stack) and the layer index as a third
  scalar-prefetch operand, so the index_map lands on row
  `layer * num_blocks + tables[b, j]`. Softmax state (running max m,
  denominator l, unnormalized output o) accumulates in VMEM scratch
  across the innermost grid axis, the standard flash accumulation (same math as
  `attention._block_attend`). Pad-sentinel table entries (== num_blocks)
  clamp to a real block in the index_map and are masked out of the
  logits, as are rows at or beyond each query's `valid_len`. The block
  shapes are chosen for the TPU (8, 128) tiling rule (see the note above
  the kernel); tests/test_tpu_lowering.py lowers it for platform "tpu"
  at the engine's shapes and chip_smoke.py compiles and checks it on the
  chip.

- `_ragged_attention_lax`: pure-lax path for CPU, sharded engines and
  geometries the kernel does not take. Two `lax.fori_loop` passes walk
  the table columns — softmax stats first (running max + rescaled denominator), then the PV
  accumulation with probabilities normalized at the final stats and
  quantized to q.dtype, reproducing the flat softmax's rounding profile
  (see the function docstring: temperature-0 bit-exactness against the
  dense engine depends on it). Each step gathers only the current
  `(B, block_size)` block column — O(B·block_size) transient memory,
  never a dense `(max_len)` view. Both loops are capped at the number
  of columns any live row actually needs, so short contexts don't pay
  for the table tail. The block column is gathered from the flattened
  `(L * num_blocks, ...)` stack at `layer * num_blocks + block`.

Both paths mask, scale, and accumulate identically, so the
interpret-mode parity test (tests/test_paged_attention.py) pins them
together to f32 rounding (the kernel folds its softmax into one pass;
on the test's f32 inputs the quantization casts are no-ops).

Latent pools (`latent_values` > 0, kv_blocks' pool of a latent-attention
model): a token keeps ONE row for all heads, `(bs, 1, width)` a block, and
its value is the first `latent_values` columns of that same row. Both
paths then read the k pool alone — the value tile is a lane-aligned slice
of the key tile already in VMEM, never a second fetch — and emit
`(B, S, H * latent_values)`; every head shares the row, so the GQA
kernel's cross-group mask has no counterpart. The caller passes `scale`
(the model's head size, not the row's width, sets it).

Semantics: query row (b, i) attends cache positions `p < valid_len[b, i]`
in slot b's context; position p lives at block `tables[b, p // bs]`, row
`p % bs` of layer `layer` of the pool. Garbage in masked rows (unwritten
blocks, pad sentinels, stale reuse) never reaches the softmax.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dstack_tpu.workloads.attention import NEG_INF, _repeat_kv

__all__ = ["ragged_attention", "dispatch_path"]


def dispatch_path(
    max_len: int,
    head_dim: int,
    kv_block_size: int,
    *,
    dtype_bytes: int = 2,
    interpret: bool = False,
    num_heads: Optional[int] = None,
    num_kv_heads: Optional[int] = None,
    model_shards: int = 1,
) -> str:
    """Which implementation `ragged_attention` will run for this geometry.

    Static (shape + backend) decision, resolved at trace time — the
    serving engine calls it once at construction to label the
    `dstack_tpu_serving_attn_dispatch_total{path=...}` counter without a
    device sync. Delegates to `flash_attention.use_flash` with the paged
    block geometry so the dense-prefill seq-divisibility rule doesn't
    apply (the kernel streams block_size-granular tiles; max_len only
    needs to be block-aligned, which the pool guarantees). Sharded
    engines pass their GLOBAL head counts plus the mesh's "model" extent:
    the rule judges the per-shard geometry each partitioned program
    actually sees (and answers "lax_ragged" whenever model_shards > 1 —
    pallas_call has no SPMD partitioning rule; the lax fallback is the
    path GSPMD partitions). The engine passes the answer to every
    program factory as `attn_impl`, so this is also the path it traces.
    """
    from dstack_tpu.workloads.flash_attention import use_flash

    ok = use_flash(
        max_len,
        head_dim,
        dtype_bytes=dtype_bytes,
        interpret=interpret,
        kv_block_size=kv_block_size,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        model_shards=model_shards,
    )
    return "pallas" if ok else "lax_ragged"


def ragged_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,
    tables: jnp.ndarray,
    valid_len: jnp.ndarray,
    *,
    impl: Optional[str] = None,
    interpret: bool = False,
    latent_values: int = 0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Ragged paged attention over one layer of the stacked block pool.

    q:        (B, S, H, hd)       queries (S=1 decode, S=k+1 verify, S=C chunk)
    k_pool:   (L, NB, bs, KV, hd) the whole shared block pool, all layers
    v_pool:   (L, NB, bs, KV, hd)
    layer:    () int32            the layer whose blocks are attended, < L
    tables:   (B, MB) int32       per-slot block tables, pad sentinel == NB
    valid_len:(B, S) int32        row (b, i) attends positions < valid_len[b, i]

    Returns (B, S, H*hd) in q.dtype, matching the dense consumers' shape.
    With `latent_values` the k pool is (L, NB, bs, 1, width), q is
    (B, S, H, width), v_pool is not read and the result is
    (B, S, H*latent_values). `scale` defaults to hd ** -0.5.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if impl is None:
        impl = dispatch_path(
            tables.shape[1] * k_pool.shape[2],
            q.shape[-1],
            k_pool.shape[2],
            dtype_bytes=k_pool.dtype.itemsize,
            interpret=interpret,
        )
    if impl == "pallas":
        if latent_values:
            return _latent_attention_pallas(
                q, k_pool, layer, tables, valid_len, interpret=interpret,
                latent_values=latent_values, scale=scale,
            )
        return _ragged_attention_pallas(
            q, k_pool, v_pool, layer, tables, valid_len, interpret=interpret
        )
    return _ragged_attention_lax(
        q, k_pool, v_pool, layer, tables, valid_len,
        latent_values=latent_values, scale=scale,
    )


# ------------------------------------------------------------- lax fallback


def _ragged_attention_lax(q, k_pool, v_pool, layer, tables, valid_len, *,
                          latent_values=0, scale=None):
    """Gather-free fallback: two fori_loop passes over table columns.

    Per step the only gather is `jnp.take(pool, layer * NB + tables[:, j])`
    over the stack flattened to (L * NB, bs, KV, hd) — one (B, bs, KV, hd)
    block column, clip-guarded against the pad sentinel (BEFORE the layer
    offset, so a sentinel never reaches into layer l+1) and masked before
    the softmax; the layer's slab is never cut out of the stack. Pass 1
    streams the softmax stats (running max, rescaled denominator); pass
    2 accumulates the PV
    product with the probabilities normalized at the FINAL (m, l) and
    quantized to q.dtype first. That quantization is deliberate: the
    dense consumers this path replaced (generate._cached_attention,
    attention.decode_attention) all run
    `softmax(logits).astype(q.dtype)` before PV, and the serving tests
    pin the engine bit-exact against them at temperature 0 — near-tied
    logits (observed gaps under 1e-2) flip the argmax if the paged path
    keeps f32 probabilities the flat path rounded away. Recomputing the
    QK logits in pass 2 costs one extra (B, S, bs) einsum per column and
    buys exactness without any (max_len)-sized scratch.
    """
    b, s, h, hd = q.shape
    n_layers, nb, bs, kv, _ = k_pool.shape
    mb = tables.shape[1]
    n_rep = h // kv
    scale = hd ** -0.5 if scale is None else scale
    k_pool = k_pool.reshape(n_layers * nb, bs, kv, hd)
    if latent_values:
        # One pool: a value is the leading columns of its key row, cut
        # out of the gathered block column, never out of the pool.
        v_pool, vd = k_pool, latent_values
    else:
        v_pool, vd = v_pool.reshape(n_layers * nb, bs, kv, hd), hd
    base = jnp.asarray(layer, jnp.int32) * nb

    # Columns any live row needs: garbage-masked steps past this are pure
    # no-ops, so skip them (short contexts in a MB-wide table).
    n_cols = jnp.minimum((jnp.max(valid_len) + bs - 1) // bs, mb)

    def _block(j):
        """Masked logits for table column j plus the clamped rows of
        the flattened stack its blocks live in.

        Same dtype/scale placement as the flat reference: the einsum
        takes q/k in storage dtype with an f32 accumulator, scale lands
        on the f32 logits.
        """
        col = lax.dynamic_index_in_dim(tables, j, axis=1, keepdims=False)
        safe = base + jnp.clip(col, 0, nb - 1)
        kb = _repeat_kv(jnp.take(k_pool, safe, axis=0), n_rep)
        logits = jnp.einsum(
            "bshd,bthd->bhst", q, kb, preferred_element_type=jnp.float32
        ) * scale  # (B, H, S, bs)
        pos = j * bs + jnp.arange(bs, dtype=jnp.int32)
        ok = (pos[None, None, :] < valid_len[:, :, None]) & (
            col < nb
        )[:, None, None]  # (B, S, bs)
        return jnp.where(ok[:, None], logits, NEG_INF), safe

    def stats(j, carry):
        m, l = carry  # (B, H, S, 1) f32
        logits, _ = _block(j)
        blk_m = jnp.maximum(
            jnp.max(logits, axis=-1, keepdims=True), NEG_INF / 2
        )
        m_new = jnp.maximum(m, blk_m)
        blk_l = jnp.sum(jnp.exp(logits - m_new), axis=-1, keepdims=True)
        return m_new, l * jnp.exp(m - m_new) + blk_l

    m0 = jnp.full((b, h, s, 1), NEG_INF / 2, jnp.float32)
    l0 = jnp.zeros((b, h, s, 1), jnp.float32)
    m, l = lax.fori_loop(0, n_cols, stats, (m0, l0))
    l = jnp.maximum(l, 1e-30)

    def accum(j, o):
        logits, safe = _block(j)
        vb = _repeat_kv(jnp.take(v_pool, safe, axis=0)[..., :vd], n_rep)
        p = (jnp.exp(logits - m) / l).astype(q.dtype)
        return o + jnp.einsum(
            "bhst,bthd->bhsd", p, vb, preferred_element_type=jnp.float32
        )

    o = lax.fori_loop(0, n_cols, accum, jnp.zeros((b, h, s, vd), jnp.float32))
    return o.astype(q.dtype).transpose(0, 2, 1, 3).reshape(b, s, h * vd)


# ------------------------------------------------------------ pallas kernel
#
# TPU block shapes must tile the LAST TWO array dims by (8, 128) — (16, 128)
# for bf16 — or span them whole. The pool's last two dims are (KV, hd), so a
# one-head K tile `(bs, 1, hd)` is not a legal block. The kernel instead
# takes every operand as a 2-D slab whose trailing dims are whole:
#
#   pool  (L, NB, bs, KV, hd) -> (L*NB, bs*KV, hd)   rows ordered (t, g)
#   q     (B, S, H, hd)       -> (B, S*H, hd)        rows ordered (s, h)
#
# (both reshapes keep the row-major order; with KV a multiple of the
# sublane tile they are layout bitcasts, not copies). One grid step then
# multiplies a tile of query rows against ALL of a block's (t, g) rows in a
# single matmul and masks the pairs whose query head does not belong to the
# column's KV head — KV times the needed MXU work, spent to keep every
# load a plain aligned tile: no strided sublane reads, no in-kernel
# transposes. ROADMAP S2 owns making it fast.


def _accumulate_block(logits, v, acc_ref, m_ref, l_ref):
    """One block's step of the streaming softmax, shared by both kernels:
    fold the masked (TQ, cols) logits and the block's (cols, vd) values
    into the running max, denominator and unnormalized output."""
    blk_m = jnp.maximum(
        jnp.max(logits, axis=-1, keepdims=True), NEG_INF / 2
    )
    p = jnp.exp(logits - blk_m)
    blk_l = jnp.sum(p, axis=-1, keepdims=True)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, blk_m)
    alpha = jnp.exp(m_prev - m_new)
    beta = jnp.exp(blk_m - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + blk_l * beta
    pv = lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (TQ, vd)
    acc_ref[...] = acc_ref[...] * alpha + beta * pv


def _paged_kernel(
    t_ref,  # scalar prefetch: (B, MB) block tables in SMEM
    nc_ref,  # scalar prefetch: (B,) table columns row b actually needs
    layer_ref,  # scalar prefetch: (1,) layer index — the index_maps' alone
    q_ref,  # (TQ, hd) query rows, ordered (s, h)
    vlen_ref,  # (TQ, 1) valid_len of each query row
    k_ref,  # (bs*KV, hd) — the block the index_map resolved for step j
    v_ref,  # (bs*KV, hd)
    o_ref,  # (TQ, hd), revisited across the innermost grid axis
    acc_ref,  # VMEM scratch (TQ, hd) f32
    m_ref,  # VMEM scratch (TQ, 1) f32
    l_ref,  # VMEM scratch (TQ, 1) f32
    *,
    block_size: int,
    num_pool_blocks: int,
    num_heads: int,
    num_kv_heads: int,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF / 2)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Columns past the row's live context hold nothing it may see: the
    # index_map parks their DMA on the last live block and the body is
    # skipped, so a short context in an MB-wide table costs grid steps,
    # not bandwidth or MXU time.
    @pl.when(j < nc_ref[b])
    def _attend():
        # Storage-dtype operands with f32 accumulation, scale applied to
        # the f32 logits — the same placement as attention._block_attend.
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        logits = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (TQ, bs*KV)
        # 2D iotas (TPU requires >= 2D). TQ is a multiple of H, so a
        # tile-local row index resolves the query head.
        row = lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        q_group = lax.div(lax.rem(row, num_heads), num_heads // num_kv_heads)
        pos = j * block_size + lax.div(col, num_kv_heads)
        ok = (q_group == lax.rem(col, num_kv_heads)) & (pos < vlen_ref[...])
        # Pad-sentinel columns clamp to block NB-1 in the index_map; mask
        # everything they contributed.
        ok &= t_ref[b, j] < num_pool_blocks
        logits = jnp.where(ok, logits, NEG_INF)

        _accumulate_block(logits, v, acc_ref, m_ref, l_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


# Query rows per grid step: bounds the kernel's VMEM (q/o tiles, the f32
# accumulators and one (TQ, bs*KV) logits tile) independently of the
# prefill chunk length.
_MAX_Q_ROWS = 512


def _q_tile_positions(s: int, h: int) -> int:
    """Query positions per tile: the largest divisor of S whose rows
    (positions x heads) fit _MAX_Q_ROWS and tile the sublanes; the whole
    of S when no divisor does (a full-extent block is always legal)."""
    if s * h <= _MAX_Q_ROWS:
        return s
    for ts in range(_MAX_Q_ROWS // h, 0, -1):
        if s % ts == 0 and (ts * h) % 16 == 0:
            return ts
    return s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ragged_attention_pallas(
    q, k_pool, v_pool, layer, tables, valid_len, *, interpret=False
):
    b, s, h, hd = q.shape
    n_layers, nb, bs, kv, _ = k_pool.shape
    mb = tables.shape[1]
    ts = _q_tile_positions(s, h)
    tq = ts * h
    grid = (b, s // ts, mb)

    valid_len = valid_len.astype(jnp.int32)
    n_cols = jnp.clip((jnp.max(valid_len, axis=1) + bs - 1) // bs, 1, mb)

    def _table_block(bi, qi, ji, t, nc, lyr):
        # The gather IS the index_map: scalar-prefetched tables steer the
        # DMA straight at the slot's j-th block of this layer (sentinel
        # clamps in-range of the layer's own NB blocks BEFORE the layer
        # offset; the kernel masks its rows). Past the row's last live
        # column the index stays put, and an unchanged block index is not
        # re-fetched.
        live = jnp.minimum(ji, nc[bi] - 1)
        return (lyr[0] * nb + jnp.minimum(t[bi, live], nb - 1), 0, 0)

    def _q_rows(bi, qi, ji, t, nc, lyr):
        return (bi, qi, 0)

    kernel = functools.partial(
        _paged_kernel,
        block_size=bs,
        num_pool_blocks=nb,
        num_heads=h,
        num_kv_heads=kv,
        scale=hd ** -0.5,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, tq, hd), _q_rows),
                pl.BlockSpec((None, tq, 1), _q_rows),
                pl.BlockSpec((None, bs * kv, hd), _table_block),
                pl.BlockSpec((None, bs * kv, hd), _table_block),
            ],
            out_specs=pl.BlockSpec((None, tq, hd), _q_rows),
            scratch_shapes=[
                pltpu.VMEM((tq, hd), jnp.float32),
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s * h, hd), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(
        tables,
        n_cols,
        jnp.asarray(layer, jnp.int32).reshape(1),
        q.reshape(b, s * h, hd),
        jnp.repeat(valid_len, h, axis=1)[:, :, None],
        k_pool.reshape(n_layers * nb, bs * kv, hd),
        v_pool.reshape(n_layers * nb, bs * kv, hd),
    )
    return out.reshape(b, s, h * hd)


# ----------------------------------------------------- latent pallas kernel
#
# A latent pool keeps one row a token for all heads, so the slabs are
#
#   pool  (L, NB, bs, 1, W) -> (L*NB, bs, W)       one row per position
#   q     (B, S, H, W)      -> (B, S*H, W)         rows ordered (s, h)
#
# and one grid step multiplies a tile of query rows against a block's bs
# rows: every pair is a wanted pair (no group mask), and the value tile is
# `k[:, :latent_values]`, a lane-aligned cut of the tile the step already
# holds. W is whole 128-value lanes (ModelConfig.kv_row_shapes pads the
# row), the query's padding columns are zero.


def _latent_kernel(
    t_ref,  # scalar prefetch: (B, MB) block tables in SMEM
    nc_ref,  # scalar prefetch: (B,) table columns row b actually needs
    layer_ref,  # scalar prefetch: (1,) layer index — the index_maps' alone
    q_ref,  # (TQ, W) absorbed query rows, ordered (s, h)
    vlen_ref,  # (TQ, 1) valid_len of each query row
    k_ref,  # (bs, W) — the block the index_map resolved for step j
    o_ref,  # (TQ, latent_values), revisited across the innermost grid axis
    acc_ref,  # VMEM scratch (TQ, latent_values) f32
    m_ref,  # VMEM scratch (TQ, 1) f32
    l_ref,  # VMEM scratch (TQ, 1) f32
    *,
    block_size: int,
    num_pool_blocks: int,
    latent_values: int,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF / 2)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < nc_ref[b])
    def _attend():
        q = q_ref[...]
        k = k_ref[...]
        logits = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (TQ, bs)
        pos = j * block_size + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        ok = (pos < vlen_ref[...]) & (t_ref[b, j] < num_pool_blocks)
        logits = jnp.where(ok, logits, NEG_INF)

        _accumulate_block(logits, k[:, :latent_values], acc_ref, m_ref, l_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("interpret", "latent_values", "scale")
)
def _latent_attention_pallas(
    q, k_pool, layer, tables, valid_len, *, interpret=False,
    latent_values, scale,
):
    b, s, h, w = q.shape
    n_layers, nb, bs, _, _ = k_pool.shape
    mb = tables.shape[1]
    ts = _q_tile_positions(s, h)
    tq = ts * h
    grid = (b, s // ts, mb)

    valid_len = valid_len.astype(jnp.int32)
    n_cols = jnp.clip((jnp.max(valid_len, axis=1) + bs - 1) // bs, 1, mb)

    def _table_block(bi, qi, ji, t, nc, lyr):
        # As _ragged_attention_pallas: the gather IS the index_map.
        live = jnp.minimum(ji, nc[bi] - 1)
        return (lyr[0] * nb + jnp.minimum(t[bi, live], nb - 1), 0, 0)

    def _q_rows(bi, qi, ji, t, nc, lyr):
        return (bi, qi, 0)

    kernel = functools.partial(
        _latent_kernel,
        block_size=bs,
        num_pool_blocks=nb,
        latent_values=latent_values,
        scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, tq, w), _q_rows),
                pl.BlockSpec((None, tq, 1), _q_rows),
                pl.BlockSpec((None, bs, w), _table_block),
            ],
            out_specs=pl.BlockSpec((None, tq, latent_values), _q_rows),
            scratch_shapes=[
                pltpu.VMEM((tq, latent_values), jnp.float32),
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s * h, latent_values), q.dtype),
        interpret=interpret,
        name="latent_paged_attention",
    )(
        tables,
        n_cols,
        jnp.asarray(layer, jnp.int32).reshape(1),
        q.reshape(b, s * h, w),
        jnp.repeat(valid_len, h, axis=1)[:, :, None],
        k_pool.reshape(n_layers * nb, bs, w),
    )
    return out.reshape(b, s, h * latent_values)
