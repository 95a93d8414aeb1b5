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

import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from dstack_tpu.workloads import flash_attention as fa
from dstack_tpu.workloads.attention import make_attention_fn
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.paged_attention import _ragged_attention_pallas
from dstack_tpu.workloads.serving import ServingEngine
from dstack_tpu.workloads.sharding import BATCH_SPEC, make_mesh, param_shardings
from dstack_tpu.workloads.train import loss_fn
from dstack_tpu.workloads.transformer import init_params

CFG = PRESETS["smol-1b"]
H, KV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
# ServingEngine / native server defaults.
SLOTS, CHUNK, BLOCK, MAX_DRAFT = 8, 128, 16, 4
MAX_BLOCKS = CFG.max_seq_len // BLOCK
POOL_BLOCKS = SLOTS * MAX_BLOCKS
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
            [((b, s, H, HD), bf16), ((POOL_BLOCKS, BLOCK, KV, HD), bf16),
             ((POOL_BLOCKS, BLOCK, KV, HD), bf16), ((b, MAX_BLOCKS), i32),
             ((b, s), i32)],
        ))
    # The trainer's shape (bench.py: S=2048) and one ring step's shard.
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
