"""Every Pallas kernel the TPU dispatch can select, lowered for the TPU
from the CPU suite — at the engine's and trainer's real bf16 shapes.

`jit(f).trace(*args).lower(lowering_platforms=("tpu",))` runs the
Pallas -> Mosaic lowering without a chip, so a block shape that breaks the
(8, 128) tiling rule, or a kernel that GSPMD is asked to partition, fails
HERE and not on chip time. Where libtpu can describe a v5e topology
without a device (it can in this container), the kernels are AOT-compiled
instead, which adds Mosaic's own verdict and the scoped-VMEM limit to the
lowering. Neither runs a kernel: numerics on the chip are chip_smoke.py's
phase b.
"""

import hashlib
import math
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from dstack_tpu.workloads import flash_attention as fa
from dstack_tpu.workloads import kv_blocks
from dstack_tpu.workloads import moe
from dstack_tpu.workloads import selective_scan as scans
from dstack_tpu.workloads.attention import make_attention_fn
from dstack_tpu.workloads.config import FULL, PRESETS, SLIDING
from dstack_tpu.workloads.paged_attention import (
    _group_blocks,
    _head_tile_positions,
    _latent_attention_pallas,
    _ragged_attention_pallas,
)
from dstack_tpu.workloads.serving import ServingEngine
from dstack_tpu.workloads.sharding import BATCH_SPEC, make_mesh, param_shardings
from dstack_tpu.workloads.train import loss_fn
from dstack_tpu.workloads.transformer import init_params

CFG = PRESETS["smol-1b"]
H, KV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
# The latent-attention block at GLM-4.7-Flash's head geometry (20 heads over
# one 512 + 64 row a token, padded to 640) with a leading dense layer; fewer
# experts and a smaller vocabulary than published, to compile quickly, and
# another hidden size (at 2048 `wo` is as large as a slab of the test's pool).
LATENT_CFG = CFG.with_(
    d_model=1536, n_heads=20, n_kv_heads=20, d_ff=1536, n_experts=8, experts_per_token=2,
    capacity_factor=4.0, q_lora_rank=768, kv_lora_rank=512,
    qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    n_dense_layers=1, dense_d_ff=10240, n_shared_experts=1,
    router_score="sigmoid", routed_scaling=1.8,
)
LATENT_W = LATENT_CFG.kv_row_shapes()[0][1]
# Window and full attention layers mixed (three window, one full: one period),
# a head size apart from d_model // n_heads, YaRN on the full layers: the
# `mellum` block's attention at smol-1b's widths.
WINDOW_CFG = CFG.with_(
    d_model=1536, n_heads=12, n_kv_heads=4, head_size=128, n_layers=4,
    layer_types=(SLIDING, SLIDING, SLIDING, FULL), sliding_window=1024,
    rope_parameters={FULL: {"rope_type": "yarn", "rope_theta": 500000.0,
                            "factor": 16.0,
                            "original_max_position_embeddings": 8192}},
)
# The new cell's kernel geometry (Mellum2-12B: 32 query heads over 4 KV heads
# of 128; 16 slots x 24,576 positions in 128-token blocks).
CELL_H, CELL_KV, CELL_BLOCK, CELL_MB, CELL_WINDOW = 32, 4, 128, 24576 // 128, 1024
# ServingEngine / native server defaults.
SLOTS, CHUNK, BLOCK, MAX_DRAFT = 8, 128, 16, 4
MAX_BLOCKS = CFG.max_seq_len // BLOCK
POOL_BLOCKS = SLOTS * MAX_BLOCKS
# The kernel addresses one layer inside the stacked pool; the programs'
# HLO guard below runs at this depth too (a middle layer exists).
POOL_LAYERS = 3
# The engine's own bucketing rule, so this list cannot drift from it.
PREFILL_BUCKETS = sorted({
    ServingEngine._pad_chunk(types.SimpleNamespace(prefill_chunk_tokens=CHUNK), n)
    for n in range(1, CHUNK + 1)
})
PAGED_SHAPES = (
    [("decode", SLOTS, 1)]
    + [("prefill", 1, s) for s in PREFILL_BUCKETS]
    + [("verify", SLOTS, k + 1) for k in range(1, MAX_DRAFT + 1)]
)


# The benchmark's dense serving cut (Mistral-7B's heads, 16 slots x a
# 4608-token context): the widest table the kernel walks, and a 128-token
# chunk of eight 512-row query tiles.
WIDE_H, WIDE_SLOTS, WIDE_BLOCKS = 32, 16, 4608 // BLOCK
WIDE_SHAPES = [
    ("decode", WIDE_SLOTS, 1), ("prefill", 1, CHUNK),
    ("verify", WIDE_SLOTS, MAX_DRAFT + 1),
]


def _paged_args(b, s, h, slots, max_blocks):
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = ((POOL_LAYERS, slots * max_blocks, BLOCK, KV, HD), bf16)
    return [((b, s, h, HD), bf16), pool, pool, ((), i32),
            ((b, max_blocks), i32), ((b, s), i32)]


def _flash_fwd(q, k, v):
    return fa.flash_attention(q, k, v, causal=True)


def _flash_fwd_bwd(q, k, v):
    return jax.grad(
        lambda q, k, v: _flash_fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)


def _kernels():
    """(id, fn, [(shape, dtype), ...]) for every TPU-dispatchable kernel."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    out = []
    for kind, b, s in PAGED_SHAPES:
        out.append((
            f"paged_{kind}_b{b}_s{s}", _ragged_attention_pallas,
            _paged_args(b, s, H, SLOTS, MAX_BLOCKS),
        ))
    for kind, b, s in WIDE_SHAPES:
        out.append((
            f"paged_{kind}_b{b}_s{s}_mb{WIDE_BLOCKS}", _ragged_attention_pallas,
            _paged_args(b, s, WIDE_H, WIDE_SLOTS, WIDE_BLOCKS),
        ))
    # The latent pool's kernel: decode, the cell's 512-token chunk (a
    # 320-row query tile), the smallest chunk bucket.
    for kind, b, s in [("decode", SLOTS, 1), ("prefill", 1, 512), ("prefill", 1, 8)]:
        out.append((
            f"latent_{kind}_b{b}_s{s}",
            lambda *a: _latent_attention_pallas(
                *a, latent_values=512, scale=256 ** -0.5),
            [((b, s, 20, LATENT_W), bf16),
             ((POOL_LAYERS, POOL_BLOCKS, BLOCK, 1, LATENT_W), bf16), ((), i32),
             ((b, MAX_BLOCKS), i32), ((b, s), i32)],
        ))
    # A window layer's call beside a full layer's at the window cell's
    # geometry: 16 decode rows, the 512-token chunk (8 query tiles of 64
    # positions x 8 heads a KV head), at 128-token blocks and at the cell's
    # own 256.
    for block in (CELL_BLOCK, 256):
        mb = 24576 // block
        cell_pool = ((POOL_LAYERS, 16 * mb, block, CELL_KV, HD), bf16)
        for kind, b, s in [("decode", 16, 1), ("prefill", 1, 512)]:
            for name, window in (("full", 0), ("window", CELL_WINDOW)):
                out.append((
                    f"paged_{name}_{kind}_b{b}_s{s}_mb{mb}",
                    lambda *a, w=window: _ragged_attention_pallas(*a, window=w),
                    [((b, s, CELL_H, HD), bf16), cell_pool, cell_pool, ((), i32),
                     ((b, mb), i32), ((b, s), i32)],
                ))
    # Query heads by layer kind on 8 KV heads of 128 (the `laguna` block: 48
    # on a full layer, 6 query rows a KV head; 72 on a sliding layer, 9) at
    # its cell's geometry, 16 slots x 12,288 positions in blocks of 256,
    # window 512: decode, the 512-token chunk, the smallest chunk bucket.
    by_kind = ((2, 16 * 48, 256, 8, HD), bf16)
    for heads, window in ((48, 0), (72, 512)):
        for kind, b, s in [("decode", 16, 1), ("prefill", 1, 512), ("prefill", 1, 8)]:
            out.append((
                f"paged_{heads // 8}on1of8_{kind}_b{b}_s{s}",
                lambda *a, w=window: _ragged_attention_pallas(*a, window=w),
                [((b, s, heads, HD), bf16), by_kind, by_kind, ((), i32),
                 ((b, 48), i32), ((b, s), i32)],
            ))
    # Twenty query heads on ONE KV head (the `jamba` block's two attention
    # layers) at its cell's geometry, 128 slots x 18 blocks of 256: a decode
    # row is a 20-row query tile, the 512-token chunk 32 tiles of 320 rows,
    # the smallest chunk bucket one of 160; a group is four blocks, 1,024
    # positions a product.
    one_kv = ((2, 128 * 18, 256, 1, HD), bf16)
    for kind, b, s in [("decode", 128, 1), ("prefill", 1, 512), ("prefill", 1, 8)]:
        out.append((
            f"paged_20on1_{kind}_b{b}_s{s}", _ragged_attention_pallas,
            [((b, s, 20, HD), bf16), one_kv, one_kv, ((), i32),
             ((b, 18), i32), ((b, s), i32)],
        ))
    # The state-space recurrence at the same cell's sizes (26 layers of 128
    # slots x 16 x 5,120 float32): the decode step's in-place update, and a
    # chunk of one sequence at the longest and the shortest bucket.
    f32, n, di = jnp.float32, 16, 5120
    out.append(("selective_scan_decode", scans.selective_scan_decode, [
        ((26, 128, n, di), f32), ((), i32), ((128,), jnp.bool_), ((128, di), f32),
        ((128, di), f32), ((128, n), f32), ((128, n), f32), ((n, di), f32)]))
    for s in (512, 8):
        out.append((f"selective_scan_chunk_s{s}", scans.selective_scan_chunk, [
            ((s, di), f32), ((s, di), f32), ((s, n), f32), ((s, n), f32),
            ((n, di), f32), ((n, di), f32)]))
    # The trainer's shape (S=2048) and one ring step's shard.
    q, kv = ((2, 2048, H, HD), bf16), ((2, 2048, KV, HD), bf16)
    out.append(("flash_fwd", _flash_fwd, [q, kv, kv]))
    out.append(("flash_fwd_bwd", _flash_fwd_bwd, [q, kv, kv]))
    ring = ((2, 1024, H, HD), bf16)
    for causal in (True, False):
        out.append((
            f"flash_block_causal{int(causal)}",
            lambda q, k, v, c=causal: fa.flash_block_attend(q, k, v, causal=c),
            [ring, ring, ring],
        ))
    return out


KERNELS = _kernels()


@pytest.fixture(scope="module")
def v5e():
    """One v5e device of a libtpu topology description — compile-only,
    no chip involved — or None where this libtpu cannot provide one."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception:  # no libtpu / no topology support: lower only
        return None
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name,fn,args", KERNELS, ids=[k[0] for k in KERNELS])
def test_kernel_builds_for_tpu(name, fn, args, v5e):
    """AOT-compile for a v5e (lowering, Mosaic, the 16 MiB scoped-VMEM
    limit); where no topology is to be had, at least lower for "tpu"."""
    if v5e is None:
        specs = [jax.ShapeDtypeStruct(shape, dt) for shape, dt in args]
        lowered = jax.jit(fn).trace(*specs).lower(lowering_platforms=("tpu",))
    else:
        specs = [
            jax.ShapeDtypeStruct(shape, dt, sharding=v5e) for shape, dt in args
        ]
        lowered = jax.jit(fn).lower(*specs)
        lowered.compile()
    assert "tpu_custom_call" in lowered.as_text()


def _pallas_grids(jaxpr):
    """The grid of every pallas_call in a jaxpr, nested calls included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


@pytest.mark.parametrize(
    "b,s,h,slots,max_blocks",
    [(b, s, H, SLOTS, MAX_BLOCKS) for _, b, s in PAGED_SHAPES]
    + [(b, s, WIDE_H, WIDE_SLOTS, WIDE_BLOCKS) for _, b, s in WIDE_SHAPES],
)
def test_paged_kernel_grid_has_no_table_column_axis(b, s, h, slots, max_blocks):
    """The paged kernel is ONE call whose grid is slots x query tiles: a
    grid step costs time with or without work, so a grid over the table's
    columns made a call cost its table whatever was live (PERF.md section
    6, PR 28). The live columns are a loop inside the body."""
    specs = [
        jax.ShapeDtypeStruct(shape, dt)
        for shape, dt in _paged_args(b, s, h, slots, max_blocks)
    ]
    grids = _pallas_grids(jax.make_jaxpr(_ragged_attention_pallas)(*specs).jaxpr)
    assert grids == [(b, s // _head_tile_positions(s, h, KV))]
    assert math.prod(grids[0]) < max_blocks  # nowhere near rows x columns


def _dot_generals(jaxpr):
    """Every dot_general of a jaxpr, loop and branch bodies included."""
    dots = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            dots.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            dots += _dot_generals(sub)
    return dots


@pytest.mark.parametrize(
    "b,s,h,kv,block,max_blocks",
    [(b, s, WIDE_H, KV, BLOCK, WIDE_BLOCKS) for _, b, s in WIDE_SHAPES]
    + [(16, 1, CELL_H, CELL_KV, 256, 96), (1, 512, CELL_H, CELL_KV, 256, 96),
       (128, 1, 20, 1, 256, 18)],
)
def test_paged_kernel_multiplies_a_query_against_its_own_kv_head(
    b, s, h, kv, block, max_blocks
):
    """The mechanism of PR 36, read from the kernel's body: the score
    product is batched over the KV heads, one KV head's query rows (tile
    positions x its h / kv heads) against THAT head's rows of a whole
    fetched group (group x block positions). So the pairs multiplied a
    fetched block are tile rows x block, where the all-pairs product (every
    head's rows against all of a block's block x kv rows, kv - 1 of kv pairs
    masked) made them tile rows x block x kv; and the probabilities meet as
    many value rows."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = ((POOL_LAYERS, b * max_blocks, block, kv, HD), bf16)
    specs = [
        jax.ShapeDtypeStruct(shape, dt)
        for shape, dt in [((b, s, h, HD), bf16), pool, pool, ((), i32),
                          ((b, max_blocks), i32), ((b, s), i32)]
    ]
    jaxpr = jax.make_jaxpr(_ragged_attention_pallas)(*specs).jaxpr
    ts = _head_tile_positions(s, h, kv)
    group = _group_blocks(block * kv * HD * 2, max_blocks)
    head_rows, positions = ts * (h // kv), group * block
    scores, values = _dot_generals(jaxpr)
    heads = ((0,), (0,))  # the batch axis of both operands: the KV head
    assert scores.params["dimension_numbers"] == (((2,), (2,)), heads)
    assert [v.aval.shape for v in scores.invars] == [
        (kv, head_rows, HD), (kv, positions, HD)]
    assert values.params["dimension_numbers"] == (((2,), (1,)), heads)
    assert [v.aval.shape for v in values.invars] == [
        (kv, head_rows, positions), (kv, positions, HD)]
    pairs_a_block = math.prod(scores.outvars[0].aval.shape) // group
    assert pairs_a_block == ts * h * block  # not x kv


# ------------------------------------------- the pool rides the layer loop
#
# The four paged programs carry the stacked KV pool through their layer
# loop (kv_blocks._layer_loop). Handed to the layer scan as `xs` and
# returned as `ys` instead, XLA slices each layer's K and V slab out and
# writes it back every layer-step and copies the whole pool once per
# decode step: 64% of a serving cell's device time on the v5e (PERF.md
# §6, PR 26). The compiled HLO shows either form, so it is read here.

PROGRAM_STEPS = 4  # the engine's steps_per_sync
# An op that moves the pool or one layer's slab of it. A scatter that
# updates the pool in place is the program's own write and is allowed.
_MOVES = re.compile(
    r"= \w+\[([\d,]+)\]\S* (copy|dynamic-slice|dynamic-update-slice)\("
)


_PRODUCES = re.compile(r"= \w+\[([\d,]+)\]\S* ([a-z][\w\-]*)\(")


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one: keep the
    whole-program compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _paged_program(name, cfg, attn_impl):
    """(jitted program, argument shapes) at the engine's geometry."""
    i32, f32 = jnp.int32, jnp.float32
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    st = jax.eval_shape(
        lambda: kv_blocks.init_paged_state(
            cfg, SLOTS, cfg.max_seq_len, BLOCK, POOL_BLOCKS
        )
    )
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt)
    if name == "decode_steps":
        fn = kv_blocks.make_paged_decode_step(
            cfg, PROGRAM_STEPS, attn_impl=attn_impl
        )
        return fn, (params, st, rng)
    if name == "chunk_prefill":
        fn = kv_blocks.make_chunk_prefill(cfg, CHUNK, attn_impl=attn_impl)
        return fn, (
            params, st, scalar(i32),
            jax.ShapeDtypeStruct((MAX_BLOCKS,), i32),
            jax.ShapeDtypeStruct((1, CHUNK), i32),
            scalar(i32), scalar(i32), scalar(i32), scalar(f32), scalar(f32),
            rng, scalar(jnp.bool_),
        )
    if name == "spec_draft":
        fn = kv_blocks.make_spec_draft(cfg, MAX_DRAFT, attn_impl=attn_impl)
        return fn, (
            params, st.k, st.v, st.block_tables, st.lengths, st.last_token,
            st.active, st.temperature, st.top_p, rng,
        )
    fn = kv_blocks.make_spec_verify(cfg, MAX_DRAFT, attn_impl=attn_impl)
    return fn, (
        params, st,
        jax.ShapeDtypeStruct((SLOTS, MAX_DRAFT), i32),
        jax.ShapeDtypeStruct((SLOTS, MAX_DRAFT, cfg.vocab_size), f32),
        rng,
    )


@pytest.mark.parametrize("name,kind", [
    ("decode_steps", "gqa"), ("chunk_prefill", "gqa"), ("spec_draft", "gqa"),
    ("spec_verify", "gqa"),
    # what the engine runs for a latent model (it refuses speculation)
    ("decode_steps", "latent"), ("chunk_prefill", "latent"),
    # ... and for a model of mixed layers: the scan steps over periods
    ("decode_steps", "window"), ("chunk_prefill", "window"),
])
def test_paged_program_moves_no_pool_or_slab(name, kind, v5e, no_compile_cache):
    """The optimized HLO of each paged program holds no copy,
    dynamic-slice or dynamic-update-slice that produces an array of the
    pool's or one layer slab's size. For the v5e with the Pallas kernel
    where libtpu describes one, and there its scratch is also smaller
    than one pool (K and V): updated in place, not held twice. Else for
    the backend at hand on the lax path. The latent model's programs run
    a dense layer and then scan the expert layers, the one latent pool a
    carry of both loops. A model of mixed layers scans over PERIODS of its
    pattern, each block reading its own layer of the weight stack: no op
    may cut a whole period's weights out of the stack either (handed to
    the scan as the step's `xs` they were: three copies of 1 GB a step at
    the window cell's sizes, PERF.md section 6, PR 31)."""
    base = {"gqa": CFG, "latent": LATENT_CFG, "window": WINDOW_CFG}[kind]
    period = len(WINDOW_CFG.layer_period)
    layers = 2 * period if kind == "window" else POOL_LAYERS
    kinds = {"layer_types": WINDOW_CFG.layer_types * 2} if kind == "window" else {}
    cfg = base.with_(n_layers=layers, remat=False, **kinds)
    fn, args = _paged_program(
        name, cfg, "pallas" if v5e is not None else "lax_ragged"
    )
    if v5e is not None:
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            args,
        )
    compiled = fn.lower(*args).compile()

    pool = (layers, POOL_BLOCKS, BLOCK) + cfg.kv_row_shapes()[0]
    sizes = {math.prod(pool): "pool", math.prod(pool[1:]): "slab"}
    # Sizes are compared, not shapes (a bitcast keeps the size): no
    # weight may share one, or its re-layout would read as pool traffic.
    weights = {math.prod(a.shape) for a in jax.tree.leaves(args[0])}
    weights |= {math.prod(a.shape[1:]) for a in jax.tree.leaves(args[0])}
    assert not weights & set(sizes)
    if kind == "window":
        assert len(cfg.layer_period) == period
        cuts = {
            period * math.prod(a.shape[1:]): f"period of {name} weights"
            for name, a in args[0]["layers"].items() if a.ndim > 2
        }
        assert not (weights | set(sizes)) & set(cuts)
        # Whatever the op (the cut that was there came fused with a bitcast).
        cut_out = [
            f"{m.group(2)} makes a {cuts[n]} [{m.group(1)}]"
            for m in _PRODUCES.finditer(compiled.as_text())
            if (n := math.prod(int(d) for d in m.group(1).split(","))) in cuts
        ]
        assert not cut_out, cut_out
    moved = [
        f"{m.group(2)} of the {sizes[n]} [{m.group(1)}]"
        for m in _MOVES.finditer(compiled.as_text())
        if (n := math.prod(int(d) for d in m.group(1).split(","))) in sizes
    ]
    assert not moved, moved
    # XLA:CPU's buffer assignment is not the chip's; a latent pool is a
    # sixteenth of the GQA one and smaller than a chunk's expert scratch.
    if v5e is not None and kind == "gqa":
        itemsize = jnp.dtype(cfg.activation_dtype).itemsize
        one_pool = 2 * math.prod(pool) * itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < one_pool


# Query heads and a head gate by layer kind (6 and 9 query rows a KV head), a
# dense layer beside the pattern, and a device's HALF of a sigmoid-routed
# bank: the `laguna` block at widths that compile in seconds.
SHARE_CFG = CFG.with_(
    d_model=1536, n_heads=24, n_kv_heads=4, head_size=128, n_layers=5,
    layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    heads_per_layer=(24, 36, 36, 36, 24), sliding_window=512, attn_gate="softplus",
    d_ff=2048, n_experts=32, experts_held=16, experts_per_token=4, capacity_factor=8.0,
    n_dense_layers=1, dense_d_ff=4096, n_shared_experts=1, router_score="sigmoid",
    routed_scaling=2.5, vocab_size=8192, remat=False,
    rope_parameters={FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
                            "original_max_position_embeddings": 8192,
                            "partial_rotary_factor": 0.5}},
)


@pytest.mark.parametrize("name", ["decode_steps", "chunk_prefill"])
def test_a_share_of_the_bank_moves_no_pool_slab_or_bank(name, v5e, no_compile_cache):
    """The paged programs of a model whose stacks of `wq`, `wo` and the gate
    go by layer kind, scanned one period a step (here ONE step, which XLA
    unrolls), with a share of the expert bank and the pair counters in the
    carry: nothing of the pool's, a slab's or one layer's bank's size is
    copied or cut out, and the counters come back beside the tokens."""
    cfg = SHARE_CFG
    fn, args = _paged_program(name, cfg, "pallas" if v5e is not None else "lax_ragged")
    if v5e is not None:
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), args)
    compiled = fn.lower(*args).compile()
    if name == "decode_steps":
        assert compiled.out_info[3].shape == (2,)          # [pairs, pairs held here]
    pool = (cfg.n_layers, POOL_BLOCKS, BLOCK) + cfg.kv_row_shapes()[0]
    bank = args[0]["layers"]["we_gate"].shape
    sizes = {math.prod(pool): "pool", math.prod(pool[1:]): "slab",
             math.prod(bank): "bank", math.prod(bank[1:]): "a layer's bank"}
    assert len(sizes) == 4 and bank[1] == 16
    moved = [
        f"{m.group(2)} of the {sizes[n]} [{m.group(1)}]"
        for m in _MOVES.finditer(compiled.as_text())
        if (n := math.prod(int(d) for d in m.group(1).split(","))) in sizes
    ]
    assert not moved, moved
    if v5e is not None:
        one_bank = math.prod(bank[1:]) * jnp.dtype(cfg.activation_dtype).itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < one_bank


# State-space layers beside attention layers (one period mmam, twice), 10
# query heads on one KV head of 128, at widths that compile in seconds.
MAMBA_CFG = CFG.with_(
    d_model=1280, n_heads=10, n_kv_heads=1, d_ff=2048, n_layers=8, vocab_size=8192,
    attn_layer_period=4, attn_layer_offset=2, mamba_dt_rank=64, use_rope=False,
    tie_embeddings=True, remat=False,
)


@pytest.mark.parametrize("name,chunk", [
    ("decode_steps", 0), ("chunk_prefill", 64),
    # the shortest bucket: with no loop left around its recurrence XLA copied
    # the whole pool a layer to write one slot's state back (PR 33)
    ("chunk_prefill", 8),
])
def test_state_pool_is_updated_in_place(name, chunk, v5e, no_compile_cache, monkeypatch):
    """The recurrent-state pool rides the layer loop as a carry, like the KV
    pool: the optimized HLO of the decode and chunk programs copies no array
    of the state pool's size or of one layer's share of it, and the program's
    scratch is smaller than the pool (it is not held twice). The KV pool's
    layer axis counts the attention layers only."""
    if chunk:
        monkeypatch.setitem(globals(), "CHUNK", chunk)
    if v5e is not None:   # what a TPU backend would choose
        monkeypatch.setattr(kv_blocks, "scan_impl", lambda n, di: "pallas")
    fn, args = _paged_program(
        name, MAMBA_CFG, "pallas" if v5e is not None else "lax_ragged"
    )
    st = args[1]
    assert st.k.shape[0] == 2 and st.ssm.shape[:2] == (6, SLOTS)
    assert st.ssm.dtype == jnp.float32 and st.conv.shape == (6, SLOTS, 3 * 2560)
    if v5e is not None:
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), args
        )
    compiled = fn.lower(*args).compile()
    sizes = {
        math.prod(st.ssm.shape): "state pool",
        math.prod(st.ssm.shape[1:]): "one layer of the state pool",
    }
    # (float32 arrays only: the state is the one float32 array of its size)
    floats = [a for a in jax.tree.leaves(args[0]) if a.dtype == jnp.float32]
    weights = {math.prod(a.shape) for a in floats}
    weights |= {math.prod(a.shape[1:]) for a in floats}
    assert not weights & set(sizes)
    copied = [
        f"copy of the {sizes[n]} [{m.group(1)}]"
        for m in re.finditer(r"= f32\[([\d,]+)\]\S* copy\(", compiled.as_text())
        if (n := math.prod(int(d) for d in m.group(1).split(","))) in sizes
    ]
    assert not copied, copied
    if v5e is not None:
        kernel = "selective_scan_" + ("chunk" if chunk else "decode")
        assert "tpu_custom_call" in compiled.as_text() and kernel in compiled.as_text()
        pool_bytes = 4 * math.prod(st.ssm.shape)
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.parametrize("axes", [{}, {"model": 2}], ids=["fsdp4", "model2"])
def test_sharded_train_loss_lowers_for_tpu(axes, monkeypatch):
    """The train step's forward+backward on a multi-device mesh, with the
    TPU's dispatch decisions. A Pallas call left to GSPMD is refused at
    lowering ("Mosaic kernels cannot be automatically partitioned"), so
    the flash path must arrive wrapped in shard_map."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = CFG.with_(n_layers=1, remat=False)
    mesh = make_mesh(jax.devices()[:4], **axes)
    attention_fn = make_attention_fn(mesh)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {
        k: jax.ShapeDtypeStruct((4, cfg.max_seq_len), jnp.int32)
        for k in ("inputs", "targets")
    }
    step = jax.jit(
        jax.grad(lambda p, b: loss_fn(cfg, p, b, attention_fn, mesh)[0]),
        in_shardings=(
            param_shardings(mesh, params),
            {k: NamedSharding(mesh, BATCH_SPEC) for k in batch},
        ),
    )
    text = step.trace(params, batch).lower(lowering_platforms=("tpu",)).as_text()
    assert attention_fn.traced_paths == {"flash"}
    assert "tpu_custom_call" in text


# ------------------------------------------------ the routed expert bank
#
# moe.plan hands a long prefill chunk and a train row to the routed path
# (expert-sorted rows through megablox's grouped matmul); the programs
# must then hold nothing of the capacity dispatch's sizes.

_ARRAYS = re.compile(r"(?:\w+\[([\d,]+)\]|tensor<((?:\d+x)+)\w+>)")


def _array_sizes(text):
    """Element counts of every array type in HLO or StableHLO text."""
    return {
        math.prod(int(d) for d in re.split("[,x]", (a or b).strip("x")))
        for a, b in _ARRAYS.findall(text)
    }


def _wide_expert_cfg(kind):
    """The latent cell's and mellum's test models widened to their 64
    experts (top-4 sigmoid with a shared expert; top-8 softmax), narrow
    experts and few layers to build quickly."""
    if kind == "latent":
        return LATENT_CFG.with_(
            n_experts=64, experts_per_token=4, capacity_factor=16.0,
            d_ff=384, dense_d_ff=512, n_layers=3, vocab_size=2048, remat=False,
        )
    return WINDOW_CFG.with_(
        n_experts=64, experts_per_token=8, capacity_factor=8.0, d_ff=384,
        vocab_size=2048, remat=False,
    )


@pytest.mark.parametrize("kind", ["latent", "window"])
def test_long_chunk_program_multiplies_only_routed_rows(kind, v5e, no_compile_cache, monkeypatch):
    """The 512-token chunk program of a 64-expert model: no array of the
    dispatch tensor's size (S x E x C), none of the expert slots'
    (E x C x d_model, E x C x d_ff) — so no dot over E x C slots — and the
    grouped matmul's kernels in their place, reading each layer's experts
    out of the stacked bank without a copy of it. The same reading finds all
    three in the capacity path's program. Compiled for the v5e where
    libtpu describes one (Mosaic's verdict on the kernels' tiles)."""
    chunk = 512
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(globals(), "CHUNK", chunk)
    cfg = _wide_expert_cfg(kind)
    assert moe.plan(cfg, 1, chunk)[0]
    E, C = cfg.n_experts, moe.expert_capacity(cfg, chunk)
    capacity_sizes = {chunk * E * C, E * C * cfg.d_model, E * C * cfg.d_ff}
    fn, args = _paged_program(
        "chunk_prefill", cfg, "pallas" if v5e is not None else "lax_ragged"
    )
    assert not capacity_sizes & {
        math.prod(a.shape) for a in jax.tree.leaves(args)
    }
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert not capacity_sizes & _array_sizes(text)
    kernels = text.count("tpu_custom_call")

    with monkeypatch.context() as held:
        held.setattr(moe, "plan", lambda c, rows, row_len, whole=True: (
            False, E * rows * moe.expert_capacity(c, row_len), 0))
        fn, _ = _paged_program(
            "chunk_prefill", cfg, "pallas" if v5e is not None else "lax_ragged"
        )
        at_capacity = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert capacity_sizes <= _array_sizes(at_capacity)
    # the grouped matmul's kernel (equal calls share one lowered function)
    assert kernels > at_capacity.count("tpu_custom_call")

    if v5e is not None:
        fn, args = _paged_program("chunk_prefill", cfg, "pallas")
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            args,
        )
        compiled = fn.lower(*args).compile().as_text()
        assert not capacity_sizes & _array_sizes(compiled)
        # ... and no layer's bank is cut out of the stack for the kernel:
        # it reads the layer's experts in place (kv_blocks._layer_loop).
        one_bank = E * cfg.d_model * cfg.d_ff
        cut_out = [
            f"{m.group(2)} makes a layer's bank [{m.group(1)}]"
            for m in _PRODUCES.finditer(compiled)
            if math.prod(int(d) for d in m.group(1).split(",")) == one_bank
            and m.group(2) not in ("parameter", "get-tuple-element", "bitcast")
        ]
        assert not cut_out, cut_out


def test_sharded_train_step_lowers_with_the_routed_bank(monkeypatch):
    """fsdp=4, one 2,048-token row a device, 8 experts top-2 at the
    no-drop factor: the rule takes the routed path, the bank runs under
    shard_map (a Pallas call left to GSPMD is refused at lowering), and
    every row gather is over ONE device's rows — no gather reads rows of
    the whole batch, which would move tokens between devices."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, seq = 4, CFG.max_seq_len
    cfg = CFG.with_(
        n_experts=8, experts_per_token=2, capacity_factor=4.0, n_layers=1,
        remat="full",
    )
    assert moe.plan(cfg, 1, seq)[0] and not moe.plan(cfg, rows, seq, False)[0]
    traced = []
    monkeypatch.setattr(
        moe, "_routed_bank",
        lambda *a, _f=moe._routed_bank: traced.append(a[1].shape) or _f(*a),
    )
    mesh = make_mesh(jax.devices()[:4])
    attention_fn = make_attention_fn(mesh)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {
        k: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
        for k in ("inputs", "targets")
    }
    step = jax.jit(
        jax.grad(lambda p, b: loss_fn(cfg, p, b, attention_fn, mesh)[0]),
        in_shardings=(
            param_shardings(mesh, params),
            {k: NamedSharding(mesh, BATCH_SPEC) for k in batch},
        ),
    )
    text = step.trace(params, batch).lower(lowering_platforms=("tpu",)).as_text()
    assert traced and set(traced) == {(1, seq, cfg.d_model)}  # a device's row
    # the grouped matmuls forward and backward (`gmm`, its transpose and
    # `tgmm`; equal calls share one lowered function) beside flash's three
    assert text.count("tpu_custom_call") >= 6
    gathers = re.findall(r'"stablehlo\.gather"\(.*?\) -> tensor<[^>]*>', text)
    assert gathers
    k = cfg.experts_per_token
    across = [
        g for g in gathers
        if re.search(rf"tensor<({rows * seq}|{rows * seq * k})x", g)
    ]
    assert not across, across


# ----------------------------------- programs of models of one kind of layer
#
# PR 31 taught the layer loop periods, windows and a rotary embedding by
# layer kind. A model whose layers are all of one kind must trace the
# program it traced before: the text below is `fn.lower(*shapes).as_text()`
# on the lax path (it carries no locations), hashed, of the parent of PR 31
# (commit aab1542). A hash that moves says the PROGRAM of every serving cell
# moved: where that is meant, say so in CHANGES.md and replace the hash; the
# Pallas path's text holds the kernel's body, which PR 31 did change (one
# more scalar-prefetch operand, the walk's first column).

_PARENT_OF_PR31 = {
    ("tiny", "decode_steps"): "b6faa3ca85dbf3e0",
    ("tiny", "chunk_prefill"): "0b5ae91847d50fc5",
    ("tiny", "spec_draft"): "bb75eb0ff223a429",
    ("tiny", "spec_verify"): "cb768ce55c23e2cb",
    ("tiny-moe", "decode_steps"): "f359b0d73180d8f9",
    ("tiny-moe", "chunk_prefill"): "f798bae2ba8ee94a",
    ("tiny-latent", "decode_steps"): "a3b2c76260615d85",
    ("tiny-latent", "chunk_prefill"): "ec059df1427815aa",
    # PR 33 gave the decode state a recurrent-state pool, the layer loop a
    # stack a kind of mixer and the head a tied form. A model without
    # state-space layers has none of them (no leaf, no operand): the hashes
    # above still hold, and the model of mixed attention layers traces what it
    # traced at the parent of PR 33 (commit 1e1737b), hashed the same way.
    ("tiny-window", "decode_steps"): "c112128a7170a2c1",
    ("tiny-window", "chunk_prefill"): "664264e23acfa788",
}


@pytest.mark.parametrize("preset,name", sorted(_PARENT_OF_PR31))
def test_programs_of_one_kind_of_layer_lower_as_before_pr31(preset, name, monkeypatch):
    """(... and, since PR 33, programs of models without state-space layers.)"""
    monkeypatch.setitem(globals(), "SLOTS", 4)
    monkeypatch.setitem(globals(), "CHUNK", 32)
    cfg = PRESETS[preset]
    monkeypatch.setitem(globals(), "MAX_BLOCKS", cfg.max_seq_len // BLOCK)
    monkeypatch.setitem(globals(), "POOL_BLOCKS", 4 * cfg.max_seq_len // BLOCK)
    fn, args = _paged_program(name, cfg, "lax_ragged")
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _PARENT_OF_PR31[preset, name]
