"""Attention: fused local path + ring attention for sequence parallelism.

Long-context is first-class here (the reference's only long-context knob is
the user's `MAX_MODEL_LEN` vLLM flag — SURVEY §5): when the device mesh has a
"seq" axis, q/k/v live sequence-sharded on the devices and attention runs as
a ring — each step computes one block of the streaming-softmax accumulation
while `jax.lax.ppermute` rotates the k/v shard one hop around the ICI ring,
overlapping compute with neighbor-to-neighbor transfer (the RDMA pattern in
pallas_guide "Patterns: Ring Collectives", expressed with XLA collectives so
the compiler schedules the overlap). On TPU each ring step's block runs the
fused Pallas kernel (flash_attention.flash_block_attend) so the
(shard, shard) logits never land in HBM; CPU/odd shapes keep the jnp path.

All matmuls accumulate in f32 (`preferred_element_type`) regardless of the
bf16 storage dtype.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, KV, hd) -> (B, S, KV*n_rep, hd) for grouped-query attention."""
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(
        b, s, kv * n_rep, hd
    )


def plain_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
) -> jnp.ndarray:
    """Reference-semantics causal attention; XLA fuses this well on one chip.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd). Returns (B, Sq, H, hd).
    With `window` a query sees only the `window` newest keys up to
    itself (a sliding-attention layer): key j iff 0 <= i - j < window.
    """
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window:
            mask &= ~jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq - window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    elif window:
        raise ValueError("a window is a causal layer's")
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


def _block_attend(q, k, v, mask):
    """One streaming-softmax block: returns (o_blk, logsumexp-pieces).

    q: (B, Sq, H, hd) local; k/v: (B, Sk, H, hd) (kv already GQA-expanded).
    mask: (Sq, Sk) bool or None. Returns unnormalised o, plus (m, l) stats.
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # (B, H, Sq)
    # Guard fully-masked rows (first ring steps of rank-0 queries).
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(logits - m_safe[..., None])
    l = jnp.sum(p, axis=-1)  # (B, H, Sq)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o, m_safe, l


def _ring_block_impl(sq: int, sk: int, hd: int, dtype) -> Optional[bool]:
    """Whether ring steps use the fused Pallas block kernel: None -> jnp
    path; otherwise the kernel's `interpret` flag. Forced modes via
    DSTACK_TPU_FLASH_RING: "0" disables, "interpret" runs the kernel in
    interpret mode (CPU tests)."""
    import os

    forced = os.getenv("DSTACK_TPU_FLASH_RING", "auto")
    if forced == "0":
        return None
    if sq != sk:
        return None
    from dstack_tpu.workloads.flash_attention import use_flash

    interpret = forced == "interpret"
    if not use_flash(sk, hd, dtype_bytes=dtype.itemsize, interpret=interpret):
        return None
    return interpret


def _ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool,
    traced_paths: set,
) -> jnp.ndarray:
    """Per-device body run under shard_map: q/k/v are local seq shards."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    n_rep = q.shape[2] // k.shape[2]
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    flash_impl = _ring_block_impl(sq, sk, hd, q.dtype)
    traced_paths.add("ring_jnp" if flash_impl is None else "ring_flash")

    # Block-level causal masks, selected per ring step by traced scalars:
    # kv block strictly after my queries -> fully masked; same block ->
    # lower-triangular; earlier block -> full attend. (Fully-masked rows
    # come out as l=0/o=0 via the NEG_INF guard in _block_attend.) Only the
    # jnp path consumes mask ARRAYS — the flash path selects a static mask
    # mode per lax.switch branch instead.
    if flash_impl is None and causal:
        tril = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        full = jnp.ones((sq, sk), dtype=bool)
        empty = jnp.zeros((sq, sk), dtype=bool)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _flash_step(q_, k_, v_, kv_idx):
        """Fused per-step partials: branch on the traced ring position so
        each branch gets a STATIC mask mode for the kernel (diagonal ->
        causal tril, earlier shard -> full attend, later -> nothing)."""
        from dstack_tpu.workloads.flash_attention import flash_block_attend

        def _empty(q_, k_, v_):
            return (
                jnp.zeros((b, sq, h, hd), jnp.float32),
                jnp.full((b, h, sq), NEG_INF / 2, jnp.float32),
                jnp.zeros((b, h, sq), jnp.float32),
            )

        def _tril(q_, k_, v_):
            return flash_block_attend(q_, k_, v_, causal=True, interpret=flash_impl)

        def _full(q_, k_, v_):
            return flash_block_attend(q_, k_, v_, causal=False, interpret=flash_impl)

        branch = jnp.where(kv_idx > my_idx, 0, jnp.where(kv_idx == my_idx, 1, 2))
        return lax.switch(branch, [_empty, _tril, _full], q_, k_, v_)

    def step(carry, t):
        o, m, l, k_t, v_t = carry
        # k/v travel the ring unexpanded; GQA-expand only for the local
        # compute so each ppermute hop moves 1/n_rep of the bytes.
        k_exp = _repeat_kv(k_t, n_rep)
        v_exp = _repeat_kv(v_t, n_rep)
        kv_idx = (my_idx - t) % n  # whose shard we hold at ring step t
        if flash_impl is not None and causal:
            blk_o, blk_m, blk_l = _flash_step(q, k_exp, v_exp, kv_idx)
        elif flash_impl is not None:
            from dstack_tpu.workloads.flash_attention import flash_block_attend

            blk_o, blk_m, blk_l = flash_block_attend(
                q, k_exp, v_exp, causal=False, interpret=flash_impl
            )
        else:
            if causal:
                mask = jnp.where(
                    kv_idx > my_idx, empty, jnp.where(kv_idx == my_idx, tril, full)
                )
            else:
                mask = None
            blk_o, blk_m, blk_l = _block_attend(q, k_exp, v_exp, mask)
        # Streaming-softmax merge of (o,m,l) with the new block.
        m_new = jnp.maximum(m, blk_m)
        alpha = jnp.exp(m - m_new)  # rescale old accumulation
        beta = jnp.exp(blk_m - m_new)
        l_new = l * alpha + blk_l * beta
        o_new = (
            o * alpha.transpose(0, 2, 1)[..., None].astype(o.dtype)
            + blk_o * beta.transpose(0, 2, 1)[..., None].astype(o.dtype)
        )
        # Rotate k/v one hop around the ICI ring (overlaps with next compute).
        k_nxt = lax.ppermute(k_t, axis_name, perm)
        v_nxt = lax.ppermute(v_t, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, sq, h, hd), dtype=jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF / 2, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, sq), dtype=jnp.float32)
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v), jnp.arange(n))
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype)


def make_attention_fn(
    mesh: Optional[Mesh] = None,
    *,
    seq_axis: str = "seq",
    batch_axes=("data", "fsdp"),
    heads_axis: str = "model",
    causal: bool = True,
):
    """Pick the attention implementation for a mesh.

    No mesh / no "seq" axis / seq axis of size 1 -> single-device path: the
    Pallas flash kernel when shapes qualify (TPU, 128-tiled head_dim,
    block-divisible seq — workloads/flash_attention.py), else plain fused
    attention (XLA shards heads/batch itself from the surrounding
    constraints). Otherwise -> ring attention under shard_map over seq.

    The returned function carries `traced_paths`: the set of
    implementations its traces actually took ("flash" / "plain" /
    "ring_flash" / "ring_jnp"), filled in as jit traces it — what ran,
    not what a dispatch rule would predict.
    """
    traced_paths = set()
    axis_names = mesh.axis_names if mesh is not None else ()
    batch = tuple(a for a in batch_axes if a in axis_names)
    heads = heads_axis if heads_axis in axis_names else None
    if seq_axis not in axis_names or mesh.shape[seq_axis] == 1:
        # A Pallas call has no SPMD partitioning rule: inside a jit that
        # GSPMD partitions over several devices its lowering is refused
        # ("Mosaic kernels cannot be automatically partitioned"). On a
        # multi-device mesh the kernel therefore runs under shard_map,
        # each device attending its own batch rows and heads — attention
        # never mixes either, so no collective is needed.
        def flash(q, k, v):
            from dstack_tpu.workloads.flash_attention import flash_attention

            return flash_attention(q, k, v, causal=causal)

        batch_shards = head_shards = 1
        if mesh is not None and mesh.size > 1:
            batch_shards = int(np.prod([mesh.shape[a] for a in batch]))
            head_shards = mesh.shape[heads] if heads else 1
            spec = P(batch if batch else None, None, heads, None)
            flash = jax.shard_map(
                flash, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )

        def single_device(q, k, v, window=0):
            from dstack_tpu.workloads.flash_attention import use_flash

            # Flash needs equal q/kv lengths, a shape the kernel takes,
            # a layer whose window (if it has one) covers the sequence
            # and — on a multi-device mesh — rows and KV heads that split
            # evenly over the axes they are sharded on.
            if (
                q.shape[1] == k.shape[1]
                and use_flash(
                    q.shape[1], q.shape[3], dtype_bytes=q.dtype.itemsize,
                    window=window,
                )
                and q.shape[0] % batch_shards == 0
                and k.shape[2] % head_shards == 0
            ):
                traced_paths.add("flash")
                return flash(q, k, v)
            traced_paths.add("plain")
            return plain_attention(q, k, v, causal=causal, window=window)

        def _quadratic(seq_len: int, head_dim: int, dtype_bytes: int = 2) -> bool:
            # The remat estimator asks whether this path saves O(S^2) score
            # tensors for backward: only when the flash kernel won't engage.
            from dstack_tpu.workloads.flash_attention import use_flash

            return not use_flash(seq_len, head_dim, dtype_bytes=dtype_bytes)

        single_device.memory_is_quadratic = _quadratic
        single_device.traced_paths = traced_paths
        return single_device

    spec = P(batch if batch else None, seq_axis, heads, None)
    body = functools.partial(
        _ring_attention_local, axis_name=seq_axis, causal=causal,
        traced_paths=traced_paths,
    )
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )

    def ring(q, k, v, window=0):
        if window and window < q.shape[1]:
            raise ValueError(
                "ring attention has no window: a sliding-attention layer"
                f" of window {window} cannot run sequence-parallel over"
                f" {q.shape[1]} positions"
            )
        return mapped(q, k, v)

    n_seq_shards = mesh.shape[seq_axis]

    def _ring_quadratic(seq_len: int, head_dim: int, dtype_bytes: int = 2) -> bool:
        # Mirror _ring_block_impl's gate on the LOCAL block: with the fused
        # kernel, memory is O(S_local); the jnp fallback saves f32
        # (B,H,Sq,Sk) residuals per ring step across the scan.
        import os

        s_local = max(seq_len // n_seq_shards, 1)
        if os.getenv("DSTACK_TPU_FLASH_RING", "auto") == "0":
            return True
        from dstack_tpu.workloads.flash_attention import use_flash

        interpret = os.getenv("DSTACK_TPU_FLASH_RING") == "interpret"
        return not use_flash(
            s_local, head_dim, dtype_bytes=dtype_bytes, interpret=interpret
        )

    ring.memory_is_quadratic = _ring_quadratic
    ring.traced_paths = traced_paths
    return ring
