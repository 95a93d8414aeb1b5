"""Ragged paged attention: kernel/fallback parity, dispatch, exactness.

The r12 contract (docs/guides/serving-tuning.md, "Ragged paged
attention"): attention over the block pool never materializes a dense
`(max_len)` view, the Pallas kernel (interpret=True on CPU) and the
pure-lax fallback implement the SAME streaming-softmax update, and the
engine's temp-0 output stays bit-exact vs the dense `generate()`
reference at lengths that are multiples of neither chunk nor block size
— through decode, chunked prefill, and full speculation rounds.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dstack_tpu.workloads.attention import _repeat_kv
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.generate import generate
from dstack_tpu.workloads.paged_attention import (
    _group_blocks,
    _ragged_attention_lax,
    _ragged_attention_pallas,
    dispatch_path,
    ragged_attention,
)
from dstack_tpu.workloads.serving import ServingEngine, prometheus_metrics
from dstack_tpu.workloads.transformer import init_params

CFG = PRESETS["tiny"].with_(remat=False)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _ragged_inputs(seed, B, S, H, KV, hd, NB, bs, MB):
    """Random pool + ragged tables with pad sentinels and per-row
    valid lengths that straddle block boundaries."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, S, H, hd)).astype(np.float32)
    kp = rng.standard_normal((NB, bs, KV, hd)).astype(np.float32)
    vp = rng.standard_normal((NB, bs, KV, hd)).astype(np.float32)
    tables = np.full((B, MB), NB, np.int32)
    nblk = rng.integers(1, MB + 1, B)
    blocks = rng.permutation(NB)[: int(nblk.sum())]
    c = 0
    for b in range(B):
        tables[b, : nblk[b]] = blocks[c : c + nblk[b]]
        c += nblk[b]
    vlen = np.stack(
        [rng.integers(1, nblk[b] * bs + 1, S) for b in range(B)]
    ).astype(np.int32)
    return (
        jnp.asarray(q),
        jnp.asarray(kp),
        jnp.asarray(vp),
        jnp.asarray(tables),
        jnp.asarray(vlen),
    )


def _flat_softmax_reference(q, k_pool, v_pool, tables, valid_len):
    """Dense flat-softmax oracle: densify the view (test-only!) and mask
    per row — the pre-r12 `_spec_attention` semantics."""
    B, S, H, hd = q.shape
    NB, bs, KV, _ = k_pool.shape
    MB = tables.shape[1]
    safe = jnp.clip(tables, 0, NB - 1)
    dk = jnp.take(k_pool, safe, axis=0).reshape(B, MB * bs, KV, hd)
    dv = jnp.take(v_pool, safe, axis=0).reshape(B, MB * bs, KV, hd)
    k = _repeat_kv(dk, H // KV).astype(jnp.float32)
    v = _repeat_kv(dv, H // KV).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) * (
        hd ** -0.5
    )
    kpos = jnp.arange(MB * bs)
    real = jnp.repeat(tables < NB, bs, axis=1)  # sentinel blocks masked
    mask = (kpos[None, None, :] < valid_len[:, :, None]) & real[:, None, :]
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out.astype(q.dtype).reshape(B, S, H * hd)


SHAPES = (
    # (B, S, H, KV, hd, NB, bs, MB): decode-, verify-, and chunk-shaped.
    (3, 1, 4, 2, 32, 16, 8, 6),
    (2, 5, 4, 4, 32, 12, 8, 5),
    (1, 16, 8, 2, 128, 20, 16, 4),
    # The cells' head geometries (the kernel's unit of work is a KV head):
    # 8 KV heads of 4 query heads, 4 of 8, twenty query heads on one.
    (2, 3, 32, 8, 128, 14, 16, 6),
    (2, 2, 32, 4, 128, 12, 16, 5),
    (2, 2, 20, 1, 128, 12, 16, 5),
)


# The kernel walks a query tile's live table columns a GROUP of blocks at
# a time (paged_attention._group_blocks) and multiplies a KV head's query
# rows against that head's rows of the whole group at once. These cases sit
# where that can go wrong; at 64 KiB blocks (f32, bs 16 x KV 8 x hd 128) a
# group is 4 blocks (8 at 4 KV heads, and at one KV head of 64-token
# blocks), so every table below is wider than one group.
#   name: ((B, S, H, KV, hd, NB, bs, MB), context per slot (0 = dead),
#          table entries (slot, column) punched out to the pad sentinel)
WALK_CASES = {
    # Most slots dead beside one long row: zero trips, zeros out.
    "dead_rows_beside_a_long_row": (
        (6, 1, 8, 8, 128, 40, 16, 11), (0, 0, 171, 0, 0, 0), ()),
    # 5 and 9 live blocks: the last group holds 1 block of 4.
    "live_blocks_not_a_multiple_of_the_group": (
        (2, 1, 8, 8, 128, 40, 16, 12), (70, 133), ()),
    # 10 columns, all live: the last group would reach past the table.
    "table_width_not_a_multiple_of_the_group": (
        (2, 1, 8, 8, 128, 40, 16, 10), (160, 150), ()),
    # Sentinels inside the live range, in a full group and in the cut
    # last group (7 live columns: groups 0-3, 4-6).
    "sentinel_inside_the_last_group": (
        (2, 3, 8, 8, 128, 40, 16, 9), (110, 100), ((0, 5), (1, 2), (1, 6))),
    # A chunk of two query tiles (2 x 32 positions x 16 heads): the first
    # ends at column 10, the second at 12.
    "chunk_tiles_end_at_different_columns": (
        (1, 64, 16, 8, 128, 40, 16, 14), (200,), ()),
    # The cells' head geometries, each with a cut last group, a dead row
    # and a sentinel inside the live range: 8 KV heads of 4 query heads
    # (9, 6 live blocks in groups of 4), 4 of 8 (13, 10 in groups of 8),
    # twenty query heads on one KV head (11, 9 in groups of 8).
    "kv8_g4_verify_rows": (
        (3, 3, 32, 8, 128, 40, 16, 10), (140, 0, 90), ((0, 2),)),
    "kv4_g8_decode_rows": (
        (3, 1, 32, 4, 128, 40, 16, 14), (200, 0, 150), ((2, 8),)),
    "kv1_h20_chunk_rows": (
        (2, 4, 20, 1, 128, 24, 64, 12), (700, 520), ((0, 3),)),
    # A 128-position chunk of 4 query heads a KV head: one 512-row tile a
    # KV head, every KV head's in one grid step.
    "kv8_g4_chunk_is_one_tile_a_kv_head": (
        (1, 128, 32, 8, 128, 20, 16, 12), (170,), ()),
    # The last group is cut and what its unfetched buffer places hold must
    # not reach the result. A row's only group, 3 blocks of 4: the places
    # no copy ever wrote (the interpreter starts scratch memory as NaN).
    "only_group_is_partial_over_unwritten_buffer": (
        (1, 1, 32, 8, 128, 12, 16, 6), (40,), ()),
    # ... and a short row after a long one: the places hold the long row's
    # keys and values (both slots of the buffer: 13 = 4 + 4 + 4 + 1).
    "partial_group_over_another_rows_blocks": (
        (2, 1, 32, 8, 128, 40, 16, 14), (200, 37), ()),
}


def _walk_inputs(seed, shape, ctx, holes):
    """Inputs of a WALK_CASES entry: slot b holds `ctx[b]` positions in
    distinct blocks; query row (b, i) is row i of the last S positions
    (a chunk's causal rows; S=1: the decode row), dead slots see nothing.
    Also returns the pool blocks some live row owns."""
    B, S, H, KV, hd, NB, bs, MB = shape
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, S, H, hd)).astype(np.float32)
    kp = rng.standard_normal((NB, bs, KV, hd)).astype(np.float32)
    vp = rng.standard_normal((NB, bs, KV, hd)).astype(np.float32)
    tables = np.full((B, MB), NB, np.int32)
    blocks = iter(rng.permutation(NB))
    for b, n in enumerate(ctx):
        for j in range(-(-n // bs)):
            tables[b, j] = next(blocks)
    for b, j in holes:
        tables[b, j] = NB
    vlen = np.stack([
        np.maximum(n - S + 1 + np.arange(S), 1) if n else np.zeros(S)
        for n in ctx
    ]).astype(np.int32)
    owned = sorted(set(tables[tables < NB].tolist()))
    return tuple(map(jnp.asarray, (q, kp, vp, tables, vlen))) + (owned,)


@pytest.mark.parametrize("case", WALK_CASES)
def test_pallas_walk_matches_lax_fallback(case):
    """The group walk against the lax path, in interpret mode, where it
    can go wrong: dead rows, a cut last group, a table narrower than
    whole groups, sentinels in a fetched group, tiles of one chunk that
    stop at different columns."""
    shape, ctx, holes = WALK_CASES[case]
    B, S, H, KV, hd, NB, bs, MB = shape
    group = _group_blocks(bs * KV * hd * 4, MB)
    live = [-(-n // bs) for n in ctx]
    assert group > 1 and (any(n % group for n in live if n) or MB % group)
    if "only_group" not in case:
        assert group < max(live)  # more than one loop step, several blocks each
    q, kp, vp, tables, vlen, _ = _walk_inputs(17, shape, ctx, holes)
    got_lax = _ragged_attention_lax(q, kp[None], vp[None], 0, tables, vlen)
    got_pal = _ragged_attention_pallas(
        q, kp[None], vp[None], 0, tables, vlen, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got_pal), np.asarray(got_lax), rtol=1e-6, atol=1e-6
    )
    dead = np.asarray(ctx) == 0
    assert not np.asarray(got_pal)[dead].any()  # a dead slot emits zeros


def test_group_size_follows_the_blocks_bytes():
    """The blocks a loop step fetches come from the block's bytes alone:
    several at the engine's 16-token blocks, one from 256 tokens up,
    never more than the table holds."""
    assert _group_blocks(16 * 8 * 128 * 2, 288) == 8
    assert _group_blocks(256 * 8 * 128 * 2, 18) == 1
    assert _group_blocks(8 * 2 * 32 * 4, 6) == 6


@pytest.mark.parametrize("shape", SHAPES)
def test_pallas_interpret_matches_lax_fallback(shape):
    """Both implementations share one streaming-softmax update rule —
    interpret-mode kernel output must match the fallback bit-tightly on
    identical inputs (sentinel-padded tables, ragged valid lengths)."""
    q, kp, vp, tables, vlen = _ragged_inputs(7, *shape)
    got_lax = _ragged_attention_lax(q, kp[None], vp[None], 0, tables, vlen)
    got_pal = _ragged_attention_pallas(
        q, kp[None], vp[None], 0, tables, vlen, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got_pal), np.asarray(got_lax), rtol=1e-6, atol=1e-6
    )


# (B, S, H, KV, hd, NB, bs, MB) of a bfloat16 pool: two KV heads share a
# 32-bit word there, and the kernel lifts a head's rows out of a fetched
# group by 32-bit strided loads and an exchange of halves.
PACKED_SHAPES = (
    (2, 3, 32, 8, 128, 20, 16, 9),   # 8 KV heads: four words a position
    (2, 1, 32, 4, 128, 20, 16, 9),   # 4 KV heads
    (1, 8, 4, 2, 128, 12, 16, 5),    # 2 KV heads: one word a position
    (2, 2, 20, 1, 128, 12, 16, 5),   # one KV head: nothing to lift
)


@pytest.mark.parametrize("shape", PACKED_SHAPES)
def test_pallas_lifts_a_kv_heads_rows_out_of_a_16_bit_pool(shape):
    """On a bfloat16 pool the kernel and the lax path agree to bfloat16's
    rounding (both hand the MXU storage-dtype operands and round the
    probabilities to it), and a head's rows are exactly its own: NaN in
    every OTHER head of the value pool's owned blocks reaches nothing when
    only one KV head's queries are kept."""
    B, S, H, KV, hd, NB, bs, MB = shape
    q, kp, vp, tables, vlen = (
        a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
        for a in _ragged_inputs(19, *shape)
    )
    got_lax = _ragged_attention_lax(q, kp[None], vp[None], 0, tables, vlen)
    got_pal = _ragged_attention_pallas(
        q, kp[None], vp[None], 0, tables, vlen, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got_pal, np.float32), np.asarray(got_lax, np.float32),
        rtol=2e-2, atol=2e-2,
    )
    for j in range(KV):
        others = jnp.arange(KV) != j
        poisoned = jnp.where(others[None, None, :, None], jnp.nan, vp)
        out = _ragged_attention_pallas(
            q, kp[None], poisoned[None], 0, tables, vlen, interpret=True
        ).reshape(B, S, KV, H // KV * hd)
        np.testing.assert_array_equal(
            np.asarray(out[:, :, j], np.float32),
            np.asarray(got_pal.reshape(out.shape)[:, :, j], np.float32),
        )


@pytest.mark.parametrize("shape", SHAPES)
def test_ragged_matches_flat_softmax_reference(shape):
    """The streaming accumulation equals a flat masked softmax over the
    densified view (the pre-r12 semantics) to f32 accuracy."""
    q, kp, vp, tables, vlen = _ragged_inputs(11, *shape)
    ref = _flat_softmax_reference(q, kp, vp, tables, vlen)
    got = ragged_attention(q, kp[None], vp[None], 0, tables, vlen)  # lax on CPU
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("impl", ["lax", "pallas"])
@pytest.mark.parametrize("shape", SHAPES)
def test_layer_index_addresses_one_layer_of_the_stack(shape, impl):
    """Over a stacked (L, NB, ...) pool with a layer index, both paths
    equal the per-slab call on `pool[l]` (a one-layer stack, layer 0) for
    a layer in the middle — with pad-sentinel table entries, and one lane
    whose whole table row is the sentinel (an inactive or padded slot).
    Every OTHER layer's V is NaN: a sentinel clamped after the layer
    offset instead of before it would read block 0 of layer l+1, and a
    masked NaN row survives the PV product as 0 * NaN."""
    B, S, H, KV, hd, NB, bs, MB = shape
    n_layers, layer = 4, 2
    q, _, _, tables, vlen = _ragged_inputs(5, *shape)
    tables = tables.at[B - 1].set(NB)  # the padded lane: nothing to attend
    vlen = vlen.at[B - 1].set(1)
    rng = np.random.default_rng(13)
    kp = rng.standard_normal((n_layers, NB, bs, KV, hd)).astype(np.float32)
    vp = rng.standard_normal((n_layers, NB, bs, KV, hd)).astype(np.float32)
    slab_k, slab_v = jnp.asarray(kp[layer])[None], jnp.asarray(vp[layer])[None]
    vp[np.arange(n_layers) != layer] = np.nan
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)

    if impl == "lax":
        fn = _ragged_attention_lax
    else:
        fn = lambda *a: _ragged_attention_pallas(*a, interpret=True)
    got = fn(q, kp, vp, jnp.int32(layer), tables, vlen)
    want = fn(q, slab_k, slab_v, jnp.int32(0), tables, vlen)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ref = _flat_softmax_reference(q, slab_k[0], slab_v[0], tables, vlen)
    # The flat softmax averages clamped garbage on the all-masked lane.
    live = np.arange(B) != B - 1
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(ref)[live], rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize(
    "case,impl", [(None, "lax"), *((c, "pallas") for c in (None, *WALK_CASES))]
)
def test_ragged_rows_never_see_masked_garbage(case, impl):
    """NaN planted in unwritten pool blocks and past valid_len must not
    leak: masking happens before the softmax, not after. The kernel walks
    a row's live columns only, so for it EVERY block that no live row
    owns is NaN in both pools: the output is finite and equal to the
    output over the clean pools. The lax path reads a clamped block for
    every row at every column some row needs (0 * NaN in PV), so it keeps
    the K pool's poison alone."""
    if case is None:
        q, kp, vp, tables, vlen = _ragged_inputs(3, 2, 2, 4, 2, 32, 10, 8, 4)
        vlen = jnp.minimum(vlen, 9)
        tables_np = np.asarray(tables)
        owned = set(tables_np[tables_np < 10].tolist())
    else:
        shape, ctx, holes = WALK_CASES[case]
        q, kp, vp, tables, vlen, owned = _walk_inputs(23, shape, ctx, holes)
        if holes:
            # A sentinel inside a live range clamps to the layer's last
            # block, which is fetched and masked: its values must be
            # finite, as the engine's written-or-zero pool's are.
            owned = {*owned, kp.shape[0] - 1}
    unused = sorted(set(range(kp.shape[0])) - set(owned))
    assert unused
    poison_k, poison_v = np.array(kp), np.array(vp)
    poison_k[unused] = np.nan
    if impl == "lax":
        fn = _ragged_attention_lax
    else:
        poison_v[unused] = np.nan
        fn = lambda *a: _ragged_attention_pallas(*a, interpret=True)
    out = fn(q, jnp.asarray(poison_k)[None], jnp.asarray(poison_v)[None],
             0, tables, vlen)
    assert np.isfinite(np.asarray(out)).all()
    clean = fn(q, kp[None], vp[None], 0, tables, vlen)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


def test_paged_dispatch_rules():
    """use_flash with paged-block geometry: the dense seq % 128 rule must
    not reject block-granular windows; the CPU backend without interpret
    still falls back; undersized head_dim still falls back."""
    from dstack_tpu.workloads.flash_attention import use_flash

    # 72 is not a multiple of the dense MIN_BLK=128: rejected dense,
    # admitted paged (block size 8 divides it).
    assert not use_flash(72, 128, interpret=True)
    assert use_flash(72, 128, interpret=True, kv_block_size=8)
    # Paged admission still needs block-aligned windows and lane-tiled
    # head_dim.
    assert not use_flash(70, 128, interpret=True, kv_block_size=8)
    assert not use_flash(72, 64, interpret=True, kv_block_size=8)
    # Off-TPU without interpret: always the lax fallback.
    assert not use_flash(72, 128, kv_block_size=8)
    assert dispatch_path(72, 128, 8) == "lax_ragged"
    assert dispatch_path(72, 128, 8, interpret=True) == "pallas"
    # The tiny test preset (head_dim 32) runs the fallback everywhere.
    assert dispatch_path(96, CFG.head_dim, 8, interpret=True) == "lax_ragged"


def test_env_kill_switch_forces_fallback(monkeypatch):
    monkeypatch.setenv("DSTACK_TPU_FLASH_ATTENTION", "0")
    assert dispatch_path(72, 128, 8, interpret=True) == "lax_ragged"


def test_dispatch_path_per_shard_heads():
    """Sharded engines pass GLOBAL head counts + the mesh's `model`
    extent; the path choice must reflect the per-shard geometry each
    partitioned program actually runs."""
    # Unsharded, integral per-shard GQA: the kernel path stands.
    assert dispatch_path(72, 128, 8, interpret=True,
                         num_heads=4, num_kv_heads=2,
                         model_shards=1) == "pallas"
    # model-sharded: always the lax fallback (GSPMD partitions it; the
    # pallas kernel would force a full gather of the sharded pools).
    assert dispatch_path(72, 128, 8, interpret=True,
                         num_heads=4, num_kv_heads=2,
                         model_shards=2) == "lax_ragged"
    # Per-shard n_rep must stay integral.
    assert dispatch_path(72, 128, 8, interpret=True,
                         num_heads=3, num_kv_heads=2,
                         model_shards=1) == "lax_ragged"
    # Indivisible head counts are a config error, not a silent fallback.
    with pytest.raises(ValueError):
        dispatch_path(72, 128, 8, interpret=True,
                      num_heads=6, num_kv_heads=2, model_shards=4)


# ------------------------------------------------- engine-level exactness


def _drain(q):
    out = []
    while True:
        tok = q.get(timeout=60)
        if isinstance(tok, BaseException):
            raise tok
        if tok is None:
            return out
        out.append(tok)


def _reference(params, prompt, n):
    toks = generate(
        CFG, params, jnp.asarray([prompt], dtype=jnp.int32),
        max_new_tokens=n, temperature=0.0,
    )
    return [int(t) for t in toks[0]]


def _prompt(seed, n):
    return [(i * 37 + seed * 13 + 5) % 100 + 1 for i in range(n)]


def test_engine_temp0_exact_decode_and_chunk_prefill_awkward(params):
    """Decode + chunked prefill through the ragged path at lengths that
    are multiples of neither chunk (16) nor block (8), crossing block
    boundaries mid-decode — bit-exact vs the dense reference."""
    engine = ServingEngine(CFG, params, slots=4, max_len=96,
                           prefill_chunk_tokens=16, kv_block_size=8)
    try:
        for seed, n, new in ((1, 5, 9), (2, 27, 8), (3, 33, 11)):
            p = _prompt(seed, n)
            assert _drain(engine.submit(p, max_new_tokens=new)) == \
                _reference(params, p, new), f"len={n}"
    finally:
        engine.close()


def test_engine_temp0_exact_spec_round_adversarial_drafter(params):
    """A full speculation round through the ragged draft + verify paths,
    against a random-init drafter (worst case: most drafts rejected, the
    rollback path exercised every round) at an awkward prompt length —
    still bit-exact vs the dense reference."""
    drafter = init_params(CFG, jax.random.PRNGKey(7))
    engine = ServingEngine(
        CFG, params, slots=2, max_len=96, prefill_chunk_tokens=16,
        kv_block_size=8, spec_enable=True, spec_max_draft=3,
        spec_draft_params=drafter, spec_min_accept=0.0,
    )
    try:
        p = _prompt(5, 27)
        assert _drain(engine.submit(p, max_new_tokens=10)) == \
            _reference(params, p, 10)
        assert engine.stats()["spec_rounds_total"] > 0
    finally:
        engine.close()


def test_attn_dispatch_counter_exposed(params):
    """The engine reports which attention path it dispatches and how
    often: stats() carries the per-path counters, the Prometheus
    exposition renders the labeled series, and on CPU every dispatch is
    the lax fallback."""
    from dstack_tpu.server.metrics_registry import METRICS

    engine = ServingEngine(CFG, params, slots=2, max_len=32)
    try:
        _drain(engine.submit([5, 7, 11], max_new_tokens=3))
        st = engine.stats()
        text = prometheus_metrics(st)
    finally:
        engine.close()
    assert st["attn_path"] == "lax_ragged"
    assert st["attn_dispatch_lax_ragged_total"] > 0
    assert st["attn_dispatch_pallas_total"] == 0
    assert METRICS["dstack_tpu_serving_attn_dispatch_total"] == (
        "counter", ("path",)
    )
    assert 'dstack_tpu_serving_attn_dispatch_total{path="lax_ragged"}' in text
    assert 'dstack_tpu_serving_attn_dispatch_total{path="pallas"} 0' in text
