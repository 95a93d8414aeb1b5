"""Ragged paged attention: attend straight over the block-table pool.

The paged-KV engine (workloads/kv_blocks.py) stores every slot's KV cache
as scattered `(block_size, KV, hd)` blocks inside one shared
`(L, num_blocks, block_size, KV, hd)` pool, indexed by per-slot block
tables. Gathering a slot's blocks into a dense `(max_len, KV, hd)`
scratch view to run dense attention over it is a whole-pool data
movement per dispatch. This module avoids it: attention runs directly
against the pool, vLLM-PagedAttention-style, one block at a time with a
streaming softmax, and the dense view is never materialized
(analysis/checkers/paged_gather.py keeps the gather out of kv_blocks.py).

`ragged_attention` takes the STACKED pool and a layer index, never one
layer's `(num_blocks, block_size, KV, hd)` slab: the paged programs
carry the whole pool through their layer loop (kv_blocks._layer_loop),
and a slab cut out of it to feed this module would be a copy of every
block of the layer per layer-step — the traced runs of PR 22 / PR 24
read 0.44 ms per slab at a 151 MB layer, four per layer-step, 64% of the
chat cell's device time. Both paths below address layer l's block j
inside the stack instead.

Two implementations behind one dispatch seam (`ragged_attention`):

- `_ragged_attention_pallas`: a Pallas TPU kernel whose grid is
  (slot, query tile) and whose body walks the tile's LIVE table columns.
  Block tables, the columns each tile may see and the layer index ride in
  as scalar-prefetch operands (pallas_guide: PrefetchScalarGridSpec); the
  pool goes in whole, left in HBM (`pl.ANY`), as `(L * num_blocks,
  block_size * KV, hd)` (a bitcast of the stack). A `fori_loop` with the
  tile's own trip count fetches row `layer * num_blocks + tables[b, j]`
  for a group of columns a step with `make_async_copy`, into one half of
  a two-slot VMEM buffer while the other half is attended: the gather is
  those DMAs, and a dead slot or the table's unused tail costs nothing.
  The unit of work is a (KV head, fetched group): a KV head's query rows
  (positions x its H / KV heads) are multiplied against THAT head's rows
  of the whole group, lifted out of the buffer in VMEM (`_head_rows`), so
  a query meets no key of another KV head and a group of eight 16-token
  blocks is one 128-column logits tile a KV head. Softmax state (running
  max m, denominator l, unnormalized output o), one set a KV head,
  accumulates in VMEM scratch group by group, the standard flash
  accumulation (same math as `attention._block_attend`). Pad-sentinel
  table entries (== num_blocks) clamp to a real block before the layer
  offset and are masked out of the logits, as are rows at or beyond each
  query's `valid_len` and the unfetched places of a row's cut last group
  (whose value rows are cleared: a masked column still meets them as
  0 x value). The slab shapes are chosen for the TPU (8, 128) tiling rule
  (see the note above the kernel); tests/test_tpu_lowering.py compiles it
  for a v5e at the engine's shapes, holds its grid to slots x query tiles
  and its products to one KV head's rows; chip_smoke.py checks it on the
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
together to f32 rounding (the kernel folds its softmax into one pass, a
group of blocks a step where the lax path takes a block; on the test's
f32 inputs the quantization casts are no-ops).

Latent pools (`latent_values` > 0, kv_blocks' pool of a latent-attention
model): a token keeps ONE row for all heads, `(bs, 1, width)` a block, and
its value is the first `latent_values` columns of that same row. Both
paths then read the k pool alone — the value tile is a lane-aligned slice
of the key rows already in VMEM, never a second fetch — and emit
`(B, S, H * latent_values)`. For the kernel such a pool is one KV head of
`width`-wide rows whose every query head shares it: the same walk, with
the value buffer the key buffer and a group sized to its products
(`_LATENT_GROUP_POSITIONS`), not to its copies. The caller passes `scale`
(the model's head size, not the row's width, sets it).

Window layers (`window` > 0, a sliding-attention layer of a model that
mixes kinds): row (b, i) attends only the `window` newest of its positions,
`valid_len - window <= p < valid_len`. Both paths then START their walk at
the first column any live row of the tile (the kernel) or of the call (the
lax path) still reaches, so a window layer's call costs its window's blocks
and not its context's: a decode row at 16k positions with a window of 1,024
and 128-token blocks walks 9 columns of 129. The blocks behind stay in the
pool (one pool, one geometry: ROADMAP R2's second half frees them).

Semantics: query row (b, i) attends cache positions `p < valid_len[b, i]`
in slot b's context; position p lives at block `tables[b, p // bs]`, row
`p % bs` of layer `layer` of the pool. Garbage in masked rows (unwritten
blocks, pad sentinels, stale reuse) never reaches the softmax. A row with
`valid_len` 0 (a retired slot) attends nothing and comes out zero.
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
    window: int = 0,
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
    (B, S, H*latent_values). `scale` defaults to hd ** -0.5. `window`
    (static) makes it a sliding-attention layer's call; a latent pool has
    none.
    """
    if window and latent_values:
        raise ValueError("latent attention has no window layers")
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
        return _ragged_attention_pallas(
            q, k_pool, v_pool, layer, tables, valid_len, interpret=interpret,
            window=window, latent_values=latent_values, scale=scale,
        )
    return _ragged_attention_lax(
        q, k_pool, v_pool, layer, tables, valid_len,
        latent_values=latent_values, scale=scale, window=window,
    )


def first_column(valid_len: jnp.ndarray, window: int, block_size: int,
                 axis=None) -> jnp.ndarray:
    """The first table column a walk over rows of `valid_len` has to visit
    in a layer of `window` > 0: the column of the oldest position the row
    with the SHORTEST live context still sees. Rows with nothing to see
    (valid_len 0) do not hold the walk back."""
    live = jnp.where(valid_len > 0, valid_len, jnp.iinfo(jnp.int32).max)
    oldest = jnp.maximum(jnp.min(live, axis=axis) - window, 0)
    # No live row: int32 max - window is past every column; the walk's end
    # (0 for such rows) bounds it.
    return oldest // block_size


# ------------------------------------------------------------- lax fallback


def _ragged_attention_lax(q, k_pool, v_pool, layer, tables, valid_len, *,
                          latent_values=0, scale=None, window=0):
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
    dense reference (generate._cached_attention) runs
    `softmax(logits).astype(q.dtype)` before PV, and the serving tests
    pin the engine bit-exact against it at temperature 0 — near-tied
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
    # A window layer's walk starts at the first column a live row reaches.
    c0 = jnp.minimum(first_column(valid_len, window, bs), n_cols) if window else 0

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
        if window:
            ok &= pos[None, None, :] >= valid_len[:, :, None] - window
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
    m, l = lax.fori_loop(c0, n_cols, stats, (m0, l0))
    l = jnp.maximum(l, 1e-30)

    def accum(j, o):
        logits, safe = _block(j)
        vb = _repeat_kv(jnp.take(v_pool, safe, axis=0)[..., :vd], n_rep)
        p = (jnp.exp(logits - m) / l).astype(q.dtype)
        return o + jnp.einsum(
            "bhst,bthd->bhsd", p, vb, preferred_element_type=jnp.float32
        )

    o = lax.fori_loop(c0, n_cols, accum, jnp.zeros((b, h, s, vd), jnp.float32))
    return o.astype(q.dtype).transpose(0, 2, 1, 3).reshape(b, s, h * vd)


# ------------------------------------------------------------ pallas kernel
#
# TPU block shapes must tile the LAST TWO array dims by (8, 128) — (16, 128)
# for bf16 — or span them whole. The pool's last two dims are (KV, hd), so a
# one-head K tile `(bs, 1, hd)` is not a legal block, and on the device two
# bf16 rows share a 32-bit sublane word: heads 2w and 2w+1 of one position
# lie 16 bits apart. The kernel takes its operands as
#
#   pool  (L, NB, bs, KV, hd) -> (L*NB, bs*KV, hd)   rows ordered (t, g)
#   q     (B, S, H, hd)       -> (B, KV, S*G, hd)    rows ordered (s, h % G)
#
# (the pool's is a layout bitcast of the stack, as it was; `(bs, KV*hd)` is
# the same bytes only in row-major terms, on the chip it is another tiling
# and XLA would copy the pool to make it. The query's and the output's are
# small transposes under the caller's `attn` scope, free at S = 1.) A block
# is fetched whole, one contiguous copy, and the unit of work is a (KV head,
# fetched group): `_head_rows` lifts head j's rows of the WHOLE group out of
# the buffer, `group * bs` positions x hd, with sublane-strided 32-bit loads
# and, for 16-bit pools, three integer operations a vector register that
# unzip the two heads of a word; a query row is multiplied against the keys
# of its own KV head alone, so every column of a logits tile is a wanted
# pair up to the length, window and sentinel masks, and the softmax's
# exponentials, its two reductions, the accumulator's rescale and `p.V` run
# once a (KV head, group), not once a block over KV times the pairs.
#
# The grid is (slot, query tile) and nothing else: a grid step costs time
# whether or not it has work, and a grid over the table's columns made a
# decode call cost its 16 x 288 steps whatever was live (PERF.md section 6,
# PR 28). A query tile is positions x the G heads of a KV head, every KV
# head's tile in one grid step with an accumulator each, so a chunk's tile
# fetches the row's keys and values once for all heads. The pool stays in
# HBM (`pl.ANY`) and the body walks the tile's live table columns itself, a
# group of blocks a loop step: while one group is attended, the DMAs of the
# next are in flight into the other half of a two-slot VMEM buffer. The trip
# count is the tile's own (`n_cols[b, tile]`, scalar-prefetched), so a dead
# slot makes no trip and a short context pays for its own blocks only.

# Bytes of K (and as many of V) in flight per buffer slot. The group of
# blocks one loop step fetches and attends is this over a block's bytes:
# eight 32 KiB blocks (128 positions) at 16-token blocks of 8 KV heads, one
# block from 256 tokens of 4 KV heads up.
_GROUP_BYTES = 256 * 1024

# A position no context reaches: what a pad sentinel's columns are given.
_NOWHERE = 1 << 30


# Positions one loop step of a LATENT pool's walk attends. Its steps are
# bound by the products, not the copies: every position's whole row (640
# values at GLM-4.7-Flash's widths) meets few query rows (20 heads of one
# position, a decode row), so a step's fixed cost is paid over more
# positions in a larger group. On the chip (PERF.md section 6), a decode
# call of 16 rows at 8,400 positions in 256-token blocks took 0.383 ms at
# one block a group, 0.292 at two and 0.278 at four, where its products
# alone took 0.275 at one.
_LATENT_GROUP_POSITIONS = 1024


def _group_blocks(block_bytes: int, table_cols: int) -> int:
    """Blocks one loop step of the paged kernel fetches and attends."""
    return max(1, min(_GROUP_BYTES // block_bytes, table_cols))


def _accumulate_block(logits, v, acc_ref, m_ref, l_ref):
    """One step of the streaming softmax: fold the masked (TQ, cols) logits
    and the step's (cols, vd) values into the running max, denominator and
    unnormalized output. With a leading axis on all five it is one such
    step a KV head, in one batched product."""
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
    heads = tuple(range(logits.ndim - 2))
    pv = lax.dot_general(
        p.astype(v.dtype), v,
        (((logits.ndim - 1,), (logits.ndim - 2,)), (heads, heads)),
        preferred_element_type=jnp.float32,
    )  # (TQ, vd)
    acc_ref[...] = acc_ref[...] * alpha + beta * pv


def _head_rows(buf, slot, num_kv_heads, positions):
    """Every KV head's rows of the group in buffer slot `slot`, whose rows
    are ordered (position, head): (KV, positions, hd). A sublane-strided
    load takes whole 32-bit words, and two heads of a 16-bit pool share
    one, so they come out together: a head pair's even and odd positions
    are loaded apart and their halves exchanged, exact bit moves that leave
    the packed rows a one-head buffer would hold (Mosaic loads no 16-bit
    type with a stride)."""
    if num_kv_heads == 1:
        return buf[slot][None]
    if buf.dtype.itemsize == 4:
        return jnp.stack([
            buf[slot, pl.ds(j, positions, stride=num_kv_heads), :]
            for j in range(num_kv_heads)
        ])
    words = buf.bitcast(jnp.uint32)  # row (t, w): heads 2w, 2w+1 of position t
    per_pos = num_kv_heads // 2
    even, odd = (
        jnp.stack([
            words[slot, pl.ds(w + per_pos * i, positions // 2, stride=2 * per_pos), :]
            for w in range(per_pos)
        ])
        for i in (0, 1)
    )  # (KV / 2, positions / 2, hd): a word = heads 2w | 2w+1 of one position
    pairs = jnp.stack([
        (even & 0xFFFF) | (odd << 16),  # head 2w at positions 2u | 2u+1
        (even >> 16) | (odd & jnp.uint32(0xFFFF0000)),  # head 2w+1
    ], axis=1)
    return pltpu.bitcast(
        pairs.reshape(num_kv_heads, positions // 2, pairs.shape[-1]), buf.dtype
    )


def _paged_kernel(
    t_ref,  # scalar prefetch: (B, MB) block tables in SMEM
    nc_ref,  # scalar prefetch: (B, q tiles) table columns a tile may see
    c0_ref,  # scalar prefetch: (B, q tiles) the first of them (window layers)
    layer_ref,  # scalar prefetch: (1,) layer index into the stacked pool
    q_ref,  # (KV, TQ, hd) query rows a KV head, ordered (s, h % G)
    vlen_ref,  # (TQ, 1) valid_len of each query row, the same for every head
    *refs,
    block_size: int,
    num_pool_blocks: int,
    group: int,
    scale: float,
    window: int,
    latent_values: int,
):
    """`refs`, in order: the whole K pool and the whole V pool, `(L*NB,
    bs*KV, hd)` each, left in HBM; the output `(KV, TQ, vd)`; VMEM scratch,
    two slots of one group `(2, group*bs*KV, hd)` a pool; the DMA
    semaphores `(2, group)`, one a buffered block; the f32 accumulators
    `(KV, TQ, vd)`, `(KV, TQ, 1)`, `(KV, TQ, 1)`. A latent pool (one KV
    head, `latent_values` > 0) comes without the V pool and its buffer: a
    value is the leading `latent_values` columns of its key row, cut out of
    the key buffer, so vd is `latent_values` there and hd elsewhere."""
    if latent_values:
        k_hbm, o_ref, k_buf, sems, acc_ref, m_ref, l_ref = refs
        pools, v_buf = ((k_hbm, k_buf),), k_buf
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    b = pl.program_id(0)
    n_cols = nc_ref[b, pl.program_id(1)]
    c0 = c0_ref[b, pl.program_id(1)]
    base = layer_ref[0] * num_pool_blocks
    num_kv_heads = q_ref.shape[0]
    block_rows = block_size * num_kv_heads
    positions = group * block_size

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF / 2)
    l_ref[...] = jnp.zeros_like(l_ref)

    # A group's columns are its positions, block after block (2D iota: TPU
    # requires >= 2D).
    lane = lax.broadcasted_iota(jnp.int32, (1, positions), 1)
    lane_block = lax.div(lane, block_size)

    def place(g):
        """The buffer rows of the group's g-th block."""
        return pl.ds(pl.multiple_of(g * block_rows, block_rows), block_rows)

    def each_live_block(step, slot, fn, carry):
        """Fold `fn(col, g, copies, carry)` over the blocks of group `step`
        that lie inside the tile's live columns `c0 .. n_cols`, in table
        order; `copies` are the block's DMAs (K and V; a latent block's
        one) into place g of buffer slot `slot`. The last group of a row
        is cut at `n_cols` here: nothing past it is fetched or waited for.
        A loop, not `group` copies of the body: tracing the copies cost
        every engine start seconds."""
        first = c0 + step * group

        def block(col, carry):
            g = col - first
            # The pad sentinel clamps inside the layer's own NB blocks
            # BEFORE the layer offset; `arrive` masks what it fetched.
            src = base + jnp.minimum(t_ref[b, col], num_pool_blocks - 1)
            rows = place(g)
            # One semaphore a buffered block, signalled by its K and its
            # V copy (a latent block's one copy): a semaphore counts bytes,
            # so one shared by a group's copies in flight could pass a wait
            # on parts of several.
            return fn(col, g, [
                pltpu.make_async_copy(
                    pool.at[src], buf.at[slot, rows], sems.at[slot, g]
                )
                for pool, buf in pools
            ], carry)

        return lax.fori_loop(
            first, jnp.minimum(first + group, n_cols), block, carry
        )

    def fetch(col, g, copies, carry):
        for copy in copies:
            copy.start()
        return carry

    def arrive(col, g, copies, pos):
        """Wait for a block; a pad sentinel's block was clamped to a real
        one, so its columns are sent where no row's length reaches."""
        for copy in copies:
            copy.wait()
        away = jnp.where(t_ref[b, col] < num_pool_blocks, 0, _NOWHERE)
        return pos + jnp.where(lane_block == g, away, 0)

    def attend_group(step, carry):
        slot = lax.rem(step, 2)
        each_live_block(step + 1, 1 - slot, fetch, 0)
        first = c0 + step * group
        pos = each_live_block(step, slot, arrive, first * block_size + lane)

        # A cut last group leaves buffer places unfetched. Their columns
        # lie at or past every row's length and are masked below, but a
        # masked column still meets its value row in `p.V` as 0 x value:
        # whatever the place holds (another row's block, or bits no copy
        # ever wrote, NaN among them) is cleared first. Keys need nothing:
        # their logits are replaced, not scaled. A latent pool's values are
        # cut out of its key rows, so there the key buffer is the one cleared.
        def clear(g, carry):
            v_buf[slot, place(g), :] = jnp.zeros(
                (block_rows, v_buf.shape[-1]), v_buf.dtype
            )
            return carry

        lax.fori_loop(jnp.minimum(n_cols - first, group), group, clear, 0)

        # The masks are the same for every KV head: made once a group.
        ok = pos < vlen_ref[...]  # (TQ, positions)
        if window:
            ok &= pos >= vlen_ref[...] - window
        # One batched step over the KV heads, not a loop over them: written
        # head after head the heads' chains (product, softmax, product, fold)
        # ran one behind the other, 0.17 us a live block of a decode call
        # against 0.12, and eight copies of the step cost every engine start
        # 3 s of tracing (PR 36, on the chip). Storage-dtype operands with
        # f32 accumulation, scale applied to the f32 logits: the same
        # placement as attention._block_attend.
        logits = lax.dot_general(
            q_ref[...], _head_rows(k_buf, slot, num_kv_heads, positions),
            (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32,
        ) * scale  # (KV, TQ, positions)
        _accumulate_block(
            jnp.where(ok[None], logits, NEG_INF),
            v_buf[slot, :, :latent_values][None] if latent_values
            else _head_rows(v_buf, slot, num_kv_heads, positions),
            acc_ref, m_ref, l_ref,
        )
        return carry

    each_live_block(0, 0, fetch, 0)
    lax.fori_loop(0, pl.cdiv(n_cols - c0, group), attend_group, 0)

    o_ref[...] = (
        acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    ).astype(o_ref.dtype)


# Query rows per tile: bounds a kernel's VMEM (q/o tiles, the f32
# accumulators and one logits tile) independently of the prefill chunk
# length. The GQA kernel holds a tile a KV head in one grid step, all of
# them within _MAX_STEP_ROWS.
_MAX_Q_ROWS = 512
_MAX_STEP_ROWS = 4096


def _q_tile_positions(s: int, rows: int, max_rows: int = _MAX_Q_ROWS) -> int:
    """Query positions per tile, at `rows` query rows a position (the heads
    of a KV head; every head of a latent pool): the largest divisor of S
    whose rows fit `max_rows` and tile the sublanes; the whole of S when no
    divisor does (a full-extent block is always legal)."""
    if s * rows <= max_rows:
        return s
    for ts in range(max_rows // rows, 0, -1):
        if s % ts == 0 and (ts * rows) % 16 == 0:
            return ts
    return s


def _head_tile_positions(s: int, h: int, kv: int) -> int:
    """Query positions per tile of the GQA kernel: a KV head's tile holds
    its h // kv heads a position, and a grid step a tile of every KV head."""
    return _q_tile_positions(s, h // kv, min(_MAX_Q_ROWS, _MAX_STEP_ROWS // kv))


@functools.partial(
    jax.jit, static_argnames=("interpret", "window", "latent_values", "scale")
)
def _ragged_attention_pallas(
    q, k_pool, v_pool, layer, tables, valid_len, *, interpret=False, window=0,
    latent_values=0, scale=None,
):
    b, s, h, hd = q.shape
    n_layers, nb, bs, kv, _ = k_pool.shape
    mb = tables.shape[1]
    n_rep = h // kv
    vd = latent_values or hd
    if kv > 1 and (kv * k_pool.dtype.itemsize) % 4:
        raise NotImplementedError(
            f"{kv} KV heads of {k_pool.dtype} do not fill 32-bit words"
        )
    ts = _head_tile_positions(s, h, kv)
    tq = ts * n_rep
    if latent_values:
        # Read alone: its values are cut out of its key rows.
        pools = [k_pool]
        group = max(1, min(_LATENT_GROUP_POSITIONS // bs, mb))
    else:
        pools = [k_pool, v_pool]
        group = _group_blocks(bs * kv * hd * k_pool.dtype.itemsize, mb)

    # No row sees past its table (the lax path's walk ends there too).
    valid_len = jnp.minimum(valid_len.astype(jnp.int32), mb * bs)
    # Table columns each query tile may see: up to its own longest row,
    # none for a tile of dead rows (valid_len 0).
    tiles = valid_len.reshape(b, s // ts, ts)
    n_cols = (jnp.max(tiles, axis=2) + bs - 1) // bs
    # ... from the first column its rows' windows reach (0: a full layer).
    c0 = (
        jnp.minimum(first_column(tiles, window, bs, axis=2), n_cols)
        if window else jnp.zeros_like(n_cols)
    )

    def _head_tiles(bi, qi, t, nc, c0, lyr):
        return (bi, 0, qi, 0)

    def _q_rows(bi, qi, t, nc, c0, lyr):
        return (bi, qi, 0)

    kernel = functools.partial(
        _paged_kernel,
        block_size=bs,
        num_pool_blocks=nb,
        group=group,
        scale=hd ** -0.5 if scale is None else scale,
        window=window,
        latent_values=latent_values,
    )
    if latent_values:
        name = "latent_paged_attention"
    else:  # the trace tells kinds apart by it
        name = "ragged_paged_attention" + "_window" * bool(window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, s // ts),
            in_specs=[
                pl.BlockSpec((None, kv, tq, hd), _head_tiles),
                pl.BlockSpec((None, tq, 1), _q_rows),
            ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
            out_specs=pl.BlockSpec((None, kv, tq, vd), _head_tiles),
            scratch_shapes=[
                pltpu.VMEM((2, group * bs * kv, hd), pool.dtype) for pool in pools
            ] + [
                pltpu.SemaphoreType.DMA((2, group)),
                pltpu.VMEM((kv, tq, vd), jnp.float32),
                pltpu.VMEM((kv, tq, 1), jnp.float32),
                pltpu.VMEM((kv, tq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, s * n_rep, vd), q.dtype),
        interpret=interpret,
        name=name,
    )(
        tables,
        n_cols,
        c0,
        jnp.asarray(layer, jnp.int32).reshape(1),
        # A KV head's query rows together: (b, j, s * G + h % G).
        q.reshape(b, s, kv, n_rep, hd).swapaxes(1, 2).reshape(b, kv, s * n_rep, hd),
        jnp.repeat(valid_len, n_rep, axis=1)[:, :, None],
        *(pool.reshape(n_layers * nb, bs * kv, hd) for pool in pools),
    )
    out = out.reshape(b, kv, s, n_rep, vd).swapaxes(1, 2)
    return out.reshape(b, s, h * vd)
