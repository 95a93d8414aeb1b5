"""GPipe-style pipeline parallelism over a "pipe" mesh axis, TPU-native.

The layer stack is cut into P equal stages; microbatches stream through a
`lax.scan` tick schedule and activations rotate stage->stage with
`lax.ppermute` over the ICI ring — no sends/recvs, no host scheduling, one
XLA program (the scaling-book pipelining recipe, not a torch-RPC
translation). Composes with data parallelism over the "data" axis:

    mesh = make_pipeline_mesh(data=2, pipe=4)
    step = make_pipeline_train_step(config, mesh, n_microbatches=8)

Differentiation happens *inside* `shard_map` (local value_and_grad +
explicit collectives): stage parameters and their grads/optimizer moments
stay resident on their stage's devices (out_specs P("pipe")) — pipeline
parallelism is what shards the model, so nothing here materializes the
full layer stack on one device. Tensor/sequence parallelism inside a stage
is intentionally out of scope for this schedule (use the fsdp/seq/model
axes of workloads.train for that); dp x pp covers the classic
inter-host-pipeline regime.

Schedule correctness: microbatch m is injected at stage 0 on tick m,
reaches stage s at tick m+s, and is collected from stage P-1 at tick
m+P-1; ticks run 0..M+P-2 so every microbatch drains exactly once and the
wrap-around of the ppermute ring never lands in the collected range.
"""

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dstack_tpu.workloads.attention import make_attention_fn
from dstack_tpu.workloads.config import ModelConfig
from dstack_tpu.workloads.train import TrainState, ce_from_logits, make_optimizer
from dstack_tpu.workloads.transformer import (
    _block,
    apply_remat,
    init_params,
    rms_norm,
)

PIPE_AXES = ("data", "pipe")


def make_pipeline_mesh(devices=None, *, data: int = 1, pipe: int = 2) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if data * pipe != len(devices):
        raise ValueError(f"data*pipe = {data * pipe} != {len(devices)} devices")
    return Mesh(np.array(devices).reshape(data, pipe), PIPE_AXES)


def stage_params(config: ModelConfig, params: Dict, n_stages: int) -> Dict:
    """Reshape the (L, ...) layer stacks into (P, L/P, ...) stage stacks."""
    if config.n_dense_layers or config.layer_types:
        raise ValueError(
            "pipeline stages cut ONE stack of layers of ONE kind: a model"
            " with leading dense layers has two stacks, one with"
            " layer_types more than one kind"
        )
    L = config.n_layers
    if L % n_stages:
        raise ValueError(f"n_layers={L} not divisible by {n_stages} stages")

    def cut(x):
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return {
        "embed": params["embed"],
        "layers": jax.tree_util.tree_map(cut, params["layers"]),
        "final_norm": params["final_norm"],
        "lm_head": params["lm_head"],
    }


def _param_specs(params_like: Dict) -> Dict:
    """Stage stacks shard over "pipe" (leading dim); the rest replicate."""

    def spec_for(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "layers" in keys:
            return P("pipe")
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params_like)


def _run_stage(config: ModelConfig, x, layers, positions, n_ticks: int = 1):
    """Apply this device's L/P layers (leading local dim is 1 after
    shard_map slicing; the scan runs over the per-stage layer stack).

    n_ticks: how many invocations the surrounding tick scan makes — its
    backward holds every tick's stage residuals simultaneously, so the
    remat estimate must charge all of them, not one microbatch."""
    # make_attention_fn(None) is the single-device path: the Pallas flash
    # kernel when shapes qualify, plain fused attention otherwise — same
    # choice the dense trainer makes within one shard.
    attention = make_attention_fn(None)

    def body(x, layer_p):
        x, _aux = _block(config, x, layer_p, positions, attention)
        return x, None

    # x here is one microbatch on one stage — already per-device. The
    # estimate must see the stage's slice of the model, not the whole
    # stack: n_layers/stage for activations, pipe-sharded weights for the
    # state bytes, and the actual attention path's score memory.
    n_local = jax.tree_util.tree_leaves(layers)[0].shape[1]
    stage_cfg = config.with_(n_layers=max(n_local, 1))
    quadratic = getattr(attention, "memory_is_quadratic", None)
    body = apply_remat(
        body, stage_cfg, x.shape[0] * x.shape[1] * n_ticks,
        seq_len=x.shape[1],
        attn_scores=bool(
            quadratic
            and quadratic(x.shape[1], config.head_dim, config.dtype_bytes)
        ),
    )
    x, _ = lax.scan(body, x, jax.tree_util.tree_map(lambda a: a[0], layers))
    return x


def _pipeline_loss(
    config: ModelConfig,
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    n_micro: int,
    n_stages: int,
) -> jnp.ndarray:
    """Per-(data,pipe)-shard loss. Runs inside shard_map: batch is this
    data-group's shard, params["layers"] is this stage's (1, L/P, ...)."""
    inputs, targets = batch["inputs"], batch["targets"]
    B, S = inputs.shape
    assert B % n_micro == 0, (B, n_micro)
    Bm = B // n_micro
    positions = jnp.arange(S, dtype=jnp.int32)
    p_idx = lax.axis_index("pipe")

    # Embedding is only consumed where microbatches are injected (stage 0);
    # other ranks' embed output is dead code with zero cotangent, so the
    # psum over "pipe" at the end yields exactly stage 0's embed grad.
    x = jnp.take(params["embed"], inputs, axis=0)
    x_micro = x.reshape(n_micro, Bm, S, config.d_model)

    state0 = jnp.zeros((Bm, S, config.d_model), dtype=x.dtype)
    out0 = jnp.zeros_like(x_micro)

    def tick(carry, t):
        state, outputs = carry
        inject = lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
        )
        cur = jnp.where(p_idx == 0, inject, state)
        cur = _run_stage(
            config, cur, params["layers"], positions,
            n_ticks=n_micro + n_stages - 1,
        )
        out_idx = t - (n_stages - 1)
        collect = (p_idx == n_stages - 1) & (out_idx >= 0)
        slot = jnp.clip(out_idx, 0, n_micro - 1)
        prev = lax.dynamic_index_in_dim(outputs, slot, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(collect, cur, prev), slot, 0
        )
        nxt = lax.ppermute(
            cur, "pipe", [(i, (i + 1) % n_stages) for i in range(n_stages)]
        )
        return (nxt, outputs), None

    (_, outputs), _ = lax.scan(
        tick, (state0, out0), jnp.arange(n_micro + n_stages - 1)
    )

    # Only the last stage holds real outputs; mask the rest to zero so the
    # head/final-norm grads are nonzero only there (psum over "pipe"
    # recovers the true totals, loss included).
    is_last = (p_idx == n_stages - 1).astype(x.dtype)
    h = outputs.reshape(B, S, config.d_model) * is_last
    h = rms_norm(h, params["final_norm"], config.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", h, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    loss = ce_from_logits(logits, targets, batch.get("loss_mask"))
    return loss * is_last.astype(jnp.float32)


def init_pipeline_state(
    config: ModelConfig,
    key: jax.Array,
    mesh: Mesh,
    learning_rate: float = 3e-4,
) -> TrainState:
    n_stages = mesh.shape["pipe"]
    params = stage_params(config, init_params(config, key), n_stages)
    opt_state = make_optimizer(learning_rate).init(params)
    state = TrainState(jnp.zeros((), jnp.int32), params, opt_state)
    shardings = pipeline_shardings(mesh, state)
    return jax.device_put(state, shardings)


def pipeline_shardings(mesh: Mesh, state: TrainState) -> TrainState:
    def to_named(tree):
        specs = _param_specs(tree)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    return TrainState(
        NamedSharding(mesh, P()), to_named(state.params), to_named(state.opt_state)
    )


def make_pipeline_train_step(
    config: ModelConfig,
    mesh: Mesh,
    n_microbatches: int = 4,
    learning_rate: float = 3e-4,
):
    """Returns `step(state, batch) -> (state, metrics)`, jitted over the
    (data, pipe) mesh. batch rows shard over "data"."""
    n_stages = mesh.shape["pipe"]
    optimizer = make_optimizer(learning_rate)

    def local_grads(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: _pipeline_loss(config, p, batch, n_microbatches, n_stages)
        )(params)
        # Stage grads are stage-local (no collective). Shared params (embed/
        # norm/head) contribute from exactly one stage each -> psum over
        # "pipe" totals them; everything averages over "data".
        shared = {"embed", "final_norm", "lm_head"}
        grads = {
            k: lax.psum(v, "pipe") if k in shared else v
            for k, v in grads.items()
        }
        grads = lax.pmean(grads, "data")
        loss = lax.pmean(lax.psum(loss, "pipe"), "data")
        # Global grad norm: stage-grad square sums are per-rank partials
        # (psum over "pipe"); shared grads are already replicated — count
        # them once.
        def sumsq(tree):
            return sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(tree)
            )

        gnorm = jnp.sqrt(
            lax.psum(sumsq(grads["layers"]), "pipe")
            + sumsq({k: v for k, v in grads.items() if k in shared})
        )
        return loss, grads, gnorm

    def step(state: TrainState, batch):
        loss, grads, gnorm = local_grads(state.params, batch)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        return (
            TrainState(state.step + 1, params, opt_state),
            {"loss": loss, "grad_norm": gnorm},
        )

    _cache = {}

    def sharded_step(state: TrainState, batch):
        key = (
            jax.tree_util.tree_structure(state),
            tuple(sorted(batch.keys())),
        )
        if key not in _cache:
            state_specs = TrainState(
                P(), _param_specs(state.params), _param_specs(state.opt_state)
            )
            batch_specs = {k: P("data") for k in batch}
            inner = jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(state_specs, batch_specs),
                out_specs=(state_specs, {"loss": P(), "grad_norm": P()}),
                check_vma=False,
            )
            _cache[key] = jax.jit(inner, donate_argnums=0)
        return _cache[key](state, batch)

    return sharded_step


def pipeline_batch(
    config: ModelConfig,
    batch_size: int,
    seq_len: int,
    mesh: Mesh,
    seed: int = 0,
) -> Dict[str, jnp.ndarray]:
    """train.synthetic_batch, laid out for the (data, pipe) mesh."""
    from dstack_tpu.workloads.train import synthetic_batch

    batch = synthetic_batch(config, batch_size, seq_len, seed=seed)
    sh = NamedSharding(mesh, P("data"))
    return {k: jax.device_put(v, sh) for k, v in batch.items()}
