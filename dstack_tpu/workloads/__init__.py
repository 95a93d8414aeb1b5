"""Bundled TPU-native example workloads.

The reference ships its training/serving examples as user YAML + shell
commands (reference: examples/fine-tuning/*, examples/accelerators/tpu/*);
the orchestrator itself never touches model code. Here the example workload
is a first-class library so that (a) the driver's `__graft_entry__` contract
has a flagship model to compile, (b) the benchmark (`benchmarks/`,
PERF.md) has a trainer and a serving engine to measure, and (c) users get a
known-good sharded JAX fine-tune to launch via `dstack-tpu apply`.

Everything is pure JAX: bf16 matmuls on the MXU with f32 accumulation,
`lax.scan` over layers, `jax.checkpoint` rematerialisation, sharding via
`jax.sharding.Mesh` + NamedSharding, and ring attention (collective
`ppermute` over a "seq" mesh axis) for long-context sequence parallelism.
"""

from dstack_tpu.workloads.config import ModelConfig, PRESETS
from dstack_tpu.workloads.transformer import init_params, forward
from dstack_tpu.workloads.train import TrainState, make_train_step, init_train_state

__all__ = [
    "ModelConfig",
    "PRESETS",
    "init_params",
    "forward",
    "TrainState",
    "make_train_step",
    "init_train_state",
]
