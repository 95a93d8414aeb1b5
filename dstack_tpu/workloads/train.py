"""Sharded training step for the flagship workload.

`make_train_step(config, mesh)` returns a jitted function whose inputs and
outputs carry NamedShardings — donate the state, constrain the batch, let
XLA lay in the all-gathers/reduce-scatters (fsdp), psums (model) and
ppermutes (seq ring attention). Optimizer is AdamW with f32 moments sharded
exactly like their params, so optimizer memory scales down with fsdp.
"""

import functools
import signal as _signal
import sys as _sys
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dstack_tpu.utils.stagemarkers import auto_stage, emit_stage  # noqa: F401
from dstack_tpu.workloads import compile_cache
from dstack_tpu.workloads.attention import make_attention_fn
from dstack_tpu.workloads.config import ModelConfig
from dstack_tpu.workloads.sharding import (
    BATCH_SPEC,
    param_shardings,
)
from dstack_tpu.workloads.transformer import (
    forward,
    head_weights,
    init_params,
    logits_linear,
)


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any


def make_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    *,
    warmup_steps: int = 0,
    decay_steps: int = 0,
):
    """AdamW with f32 moments; optional linear-warmup + cosine decay (the
    standard LLM schedule) when warmup_steps/decay_steps are set."""
    if warmup_steps or decay_steps:
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=learning_rate,
            warmup_steps=max(warmup_steps, 1),
            decay_steps=max(decay_steps, warmup_steps + 1),
            end_value=learning_rate * 0.1,
        )
    else:
        lr = learning_rate
    return optax.adamw(
        lr, b1=0.9, b2=0.95, weight_decay=weight_decay,
        mu_dtype=jnp.float32,
    )


@functools.lru_cache(maxsize=16)
def _make_init(
    config: ModelConfig,
    mesh: Optional[Mesh],
    learning_rate: float,
    warmup_steps: int,
    decay_steps: int,
):
    """`init(key) -> TrainState` for one (config, mesh, schedule): built
    once and memoized, so a process that initializes the same state
    again (a resize, a test) re-traces nothing.

    With a mesh the state is BORN SHARDED: the init is jitted with the
    state's target `out_shardings`, so each device materializes only its
    own shard of the params and Adam moments. Building the state
    unsharded and then device_put-ing it parks the whole train state on
    device 0 first — most of a 16 GB chip for smol-1b at full depth.
    Threefry's partitionable mode (JAX's default) makes the values
    independent of the layout."""
    optimizer = make_optimizer(
        learning_rate, warmup_steps=warmup_steps, decay_steps=decay_steps
    )

    def init(key):
        params = init_params(config, key)
        return TrainState(
            jnp.zeros((), jnp.int32), params, optimizer.init(params)
        )

    if mesh is None:
        return init
    shardings = param_shardings(
        mesh, jax.eval_shape(init, jax.random.PRNGKey(0))
    )
    return jax.jit(init, out_shardings=shardings)


def init_train_state(
    config: ModelConfig,
    key: jax.Array,
    mesh: Optional[Mesh] = None,
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 0,
    decay_steps: int = 0,
) -> TrainState:
    # Schedule args must match make_train_step's: a scheduled optimizer has
    # a different opt-state structure than a constant-lr one.
    # First touch of the accelerator in a typical trainer: the timeline's
    # env_ready -> tpu_init gap is import + device-discovery cost.
    # The persistent cache must be live before anything compiles, so the
    # train_step build below can be a disk retrieval on a repeat boot.
    compile_cache.enable()
    auto_stage("tpu_init")
    return _make_init(
        config, mesh, learning_rate, warmup_steps, decay_steps
    )(key)


def ce_from_logits(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    mask: Optional[jnp.ndarray],
) -> jnp.ndarray:
    """Masked-mean softmax cross-entropy from (…, V) f32 logits.

    lse-form: log_softmax(logits)[target] == logits[target] - lse, but
    the lse form never materializes the normalized (…, V) f32 log-prob
    tensor beside the logits — one fewer vocab-wide intermediate
    (measured +1.3% step throughput on v5e, docs/design/perf.md). The
    single CE used by the data-parallel trainer AND the pipeline
    trainer, so a loss change (z-loss, label smoothing) lands in both.
    """
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = lse - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def _chunked_ce(
    hidden: jnp.ndarray,
    lm_head,
    targets: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Softmax cross-entropy over sequence chunks -> (nll_sum, denom).

    hidden (B, S, D) are the final-norm states; the lm-head matmul and
    the per-token logsumexp run inside a rematerialized lax.scan over
    S/chunk slices, so only one (B, chunk, V) f32 logits buffer is ever
    live and nothing vocab-sized is saved for backward (jax.checkpoint
    recomputes the chunk in the grad pass — one extra head matmul, paid
    to keep vocab_size*(4+dtype_bytes) bytes/token out of the remat
    budget; see config.resolve_remat and docs/design/perf.md). The math
    is the dense path's exactly, f32-accumulated; only the token-sum
    association differs.

    Sharding note: the scan axis comes from the sequence dimension, so
    under sequence parallelism (sp > 1) GSPMD must gather each chunk off
    the seq shards before its head matmul — the dense head keeps that
    axis parallel. Another reason this is an opt-in memory lever: use it
    when logits memory binds, not on sp meshes for speed."""
    b, s, d = hidden.shape
    n = s // chunk
    xs = jnp.moveaxis(hidden.reshape(b, n, chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)
    if mask is None:
        ms = jnp.ones((n, b, chunk), jnp.float32)
    else:
        ms = jnp.moveaxis(mask.reshape(b, n, chunk), 1, 0).astype(jnp.float32)

    @jax.checkpoint
    def body(carry, inp):
        xi, ti, mi = inp
        logits = logits_linear(xi, lm_head)  # (B, chunk, V) f32, transient
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        return carry + jnp.sum((lse - tgt) * mi), None

    total, _ = lax.scan(body, jnp.float32(0.0), (xs, ts, ms))
    return total, jnp.sum(ms)


def loss_fn(
    config: ModelConfig,
    params: Any,
    batch: Dict[str, jnp.ndarray],
    attention_fn=None,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Next-token cross-entropy -> (loss, router_aux).

    batch: inputs (B, S) int32, targets (B, S) int32, optional loss_mask
    (B, S). inputs/targets are pre-shifted so both shard evenly over the
    "seq" mesh axis. For MoE configs the router load-balance aux term is
    folded into the loss with `router_aux_coef`.
    """
    inputs, targets = batch["inputs"], batch["targets"]
    mask = batch.get("loss_mask")
    if config.ce_chunk > 0 and inputs.shape[1] % config.ce_chunk == 0:
        hidden, aux = forward(
            config, params, inputs, attention_fn=attention_fn, mesh=mesh,
            return_aux=True, return_hidden=True,
        )
        total, denom = _chunked_ce(
            hidden, head_weights(params), targets, mask, config.ce_chunk
        )
        ce = total / jnp.maximum(denom, 1.0)
        return ce + config.router_aux_coef * aux, aux
    logits, aux = forward(
        config, params, inputs, attention_fn=attention_fn, mesh=mesh,
        return_aux=True,
    )
    ce = ce_from_logits(logits, targets, mask)
    return ce + config.router_aux_coef * aux, aux


def make_train_step(
    config: ModelConfig,
    mesh: Optional[Mesh] = None,
    learning_rate: float = 3e-4,
    *,
    accum_steps: int = 1,
    warmup_steps: int = 0,
    decay_steps: int = 0,
):
    """Returns `train_step(state, batch) -> (state, metrics)`, jitted.

    With a mesh the returned fn is committed to NamedShardings (in/out) and
    the state buffer is donated; without one it is a plain single-device jit.
    accum_steps > 1 cuts the batch into that many microbatches and
    accumulates grads in a lax.scan before ONE optimizer update — the
    standard way to run a bigger effective batch than activations allow
    (activation memory is one microbatch; grads/params unchanged).
    """
    optimizer = make_optimizer(
        learning_rate, warmup_steps=warmup_steps, decay_steps=decay_steps
    )
    attention_fn = make_attention_fn(mesh)

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(config, p, batch, attention_fn, mesh),
            has_aux=True,
        )(params)

    def accumulated_grads(params, batch):
        # (B, ...) -> (accum, B/accum, ...): scan keeps one microbatch of
        # activations live; grads average across microbatches.
        b = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if b % accum_steps:
            raise ValueError(
                f"batch size {b} is not divisible by accum_steps"
                f" {accum_steps}; gradient accumulation needs equal"
                " microbatches"
            )
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                *x.shape[1:]),
            batch,
        )

        def body(carry, mb):
            (loss, aux), grads = grads_of(params, mb)
            loss_sum, aux_sum, grads_sum = carry
            grads_sum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), grads_sum, grads
            )
            return (loss_sum + loss, aux_sum + aux, grads_sum), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss, aux, grads), _ = lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0), zeros), micro
        )
        n = jnp.float32(accum_steps)
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / n).astype(p.dtype), grads, params
        )
        return (loss / n, aux / n), grads

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        if accum_steps > 1:
            (loss, aux), grads = accumulated_grads(state.params, batch)
        else:
            (loss, aux), grads = grads_of(state.params, batch)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new_state = TrainState(state.step + 1, params, opt_state)
        return new_state, {"loss": loss, "grad_norm": gnorm, "router_aux": aux}

    if mesh is None:
        return _staged_step(
            jax.jit(train_step, donate_argnums=0), attention_fn.traced_paths
        )

    def shardings_of(tree):
        return param_shardings(mesh, tree)

    # Build sharding pytrees lazily from the first state's structure to pin
    # in/out layouts (opt-state structure depends on the optimizer).
    replicated = NamedSharding(mesh, P())
    data_sharding = NamedSharding(mesh, BATCH_SPEC)
    _cache = {}

    def jitted(state: TrainState, batch):
        key = (
            jax.tree_util.tree_structure(state),
            tuple(sorted(batch.keys())),
        )
        if key not in _cache:
            state_sh = TrainState(
                replicated, shardings_of(state.params), shardings_of(state.opt_state)
            )
            batch_sh = {k: data_sharding for k in batch}
            _cache[key] = jax.jit(
                train_step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(
                    state_sh,
                    {"loss": replicated, "grad_norm": replicated,
                     "router_aux": replicated},
                ),
                donate_argnums=0,
            )
        return _cache[key](state, batch)

    return _staged_step(jitted, attention_fn.traced_paths)


def _staged_step(step_fn, attention_paths):
    """Bracket the FIRST invocation with compile_start/compile_end and
    first_step timeline markers (no-ops outside an orchestrated run). The
    first call is synced with block_until_ready so compile_end measures the
    actual compile+first-execute wall, not async dispatch; later calls go
    through untouched. The returned step carries `attention_paths`, the
    attention implementations its traces took (attention.make_attention_fn)
    — empty until the first call has traced."""
    holder = {"first": True}

    def stepped(state, batch):
        if not holder["first"]:
            return step_fn(state, batch)
        holder["first"] = False
        auto_stage("compile_start")
        out = step_fn(state, batch)
        jax.block_until_ready(out)
        auto_stage("compile_end")
        auto_stage("first_step")
        return out

    stepped.attention_paths = attention_paths
    return stepped


class DrainHandler:
    """Graceful-preemption hook for training loops.

    When the provider announces a maintenance/preemption event, the runner
    agent SIGTERMs the job group and waits a grace window before killing it
    (agents/runner.py `Executor.drain`). A training loop that installs this
    handler turns that window into a durable checkpoint:

        handler = install_drain_handler()
        for _ in range(start, steps):
            state, metrics = train_step(state, batch)
            if handler.draining:
                handler.checkpoint_and_exit(ckpt_dir, state)

    `checkpoint_and_exit` saves through workloads/checkpoint.py (blocking
    until durable) and exits with DRAIN_EXIT_CODE so the runner reports a
    *clean* drain — the resubmitted gang resumes from this step instead of
    the last periodic checkpoint (or step 0). `exec` the trainer from the
    job command so the exit code reaches the runner unwrapped by bash.
    """

    def __init__(self, signals=(_signal.SIGTERM,)):
        self._draining = False
        self._prior = {}
        for sig in signals:
            try:
                self._prior[sig] = _signal.signal(sig, self._on_signal)
            except ValueError as e:
                # signal.signal only works on the main thread; failing half
                # installed would leave the loop believing it has drain
                # coverage it does not. Surface the contract loudly.
                raise RuntimeError(
                    "DrainHandler must be installed from the main thread"
                    " (signal handlers are process-global); install it"
                    " before spawning data-loader/metric threads"
                ) from e

    def _on_signal(self, signum, frame) -> None:
        self._draining = True
        # Chain whatever was installed before us (a framework's own SIGTERM
        # hook, a prior DrainHandler): replacing it silently would disable
        # someone else's cleanup.
        prior = self._prior.get(signum)
        if callable(prior):
            prior(signum, frame)

    @property
    def draining(self) -> bool:
        return self._draining

    def checkpoint_and_exit(
        self,
        directory,
        state: TrainState,
        grace_seconds: Optional[float] = None,
    ) -> None:
        """Save a durable checkpoint and exit DRAIN_EXIT_CODE.

        `grace_seconds` is the drain window the runner allows (the server's
        SCHEDULER_PREEMPTION_GRACE for scheduler preemptions, the provider
        notice for maintenance events). When the blocking save overruns it,
        a loud warning is printed: the checkpoint WAS durable by the time we
        got here, but the runner may already have SIGKILLed siblings — size
        the grace to your checkpoint time, not the other way round.
        """
        import time as _time

        from dstack_tpu.agents.protocol import DRAIN_EXIT_CODE
        from dstack_tpu.workloads import checkpoint as ckpt

        t0 = _time.monotonic()
        step = ckpt.save(directory, state, wait=True)
        ckpt.close_all()
        elapsed = _time.monotonic() - t0
        if grace_seconds is not None and elapsed > grace_seconds:
            print(
                f"WARNING: drain checkpoint took {elapsed:.1f}s, over the"
                f" {grace_seconds:.0f}s grace window — the runner may have"
                " hard-killed this job before the save completed; raise the"
                " drain grace or shrink the checkpoint",
                file=_sys.stderr, flush=True,
            )
        print(f"drain: checkpoint saved at step {step}; exiting", flush=True)
        _sys.exit(DRAIN_EXIT_CODE)


def install_drain_handler() -> DrainHandler:
    """Install SIGTERM-drain handling for the calling training process."""
    return DrainHandler()


def read_resize_notice(path: Optional[str] = None) -> Optional[Dict[str, int]]:
    """The pending elastic-resize notice from the runner, or None.

    The runner agent writes `{"width": W, "total": N}` atomically to
    DSTACK_TPU_RESIZE_FILE when the server resizes an elastic gang
    (agents/runner.py `write_resize`). An elastic training loop polls this
    once per step; on a change it checkpoints, re-forms its mesh at the new
    data-parallel width (rescaling accum_steps via
    parallel.mesh.rescale_accum_steps to keep the global batch), and keeps
    stepping. Malformed/partial content reads as None — the write is atomic
    (tmp + rename), so that only means "no notice yet".
    """
    import json as _json
    import os as _os

    p = path or _os.environ.get("DSTACK_TPU_RESIZE_FILE")
    if not p:
        return None
    try:
        data = _json.loads(open(p).read())
        return {"width": int(data["width"]), "total": int(data.get("total", 0))}
    except (OSError, ValueError, KeyError, TypeError):
        return None


def synthetic_batch(
    config: ModelConfig,
    batch_size: int,
    seq_len: Optional[int] = None,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
) -> Dict[str, jnp.ndarray]:
    """Deterministic fake pre-shifted token batch: inputs/targets (B, S)."""
    s = (seq_len or config.max_seq_len) + 1
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(
        key, (batch_size, s), 0, config.vocab_size, dtype=jnp.int32
    )
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    if mesh is not None:
        sh = NamedSharding(mesh, BATCH_SPEC)
        batch = {k: jax.device_put(v, sh) for k, v in batch.items()}
    return batch
