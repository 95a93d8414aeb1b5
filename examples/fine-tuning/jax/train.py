"""Fine-tune a llama-family model with dstack-tpu's workloads library.

Runs unmodified from a single chip to a 32-host v5p-256 pod slice: the
orchestrator injects `JAX_COORDINATOR_ADDRESS` / `JAX_PROCESS_ID` /
`JAX_NUM_PROCESSES` (parallel/env.py), and `jax.distributed.initialize()`
with no arguments consumes exactly those — there is no torchrun/mpirun
equivalent to wire up.

Parity note: the reference's examples/fine-tuning pass MASTER_ADDR +
torchrun flags by hand from DSTACK_* env; here distributed bootstrap is
zero lines of user code.
"""

import argparse
import os

import jax

from dstack_tpu.workloads import checkpoint as ckpt
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.sharding import make_mesh
from dstack_tpu.workloads.train import (
    init_train_state,
    make_train_step,
    synthetic_batch,
)


def _print_device_memory(when: str) -> None:
    """bytes_in_use per local device — shows at a glance whether the
    train state is spread over the mesh or parked on one chip. Backends
    without memory stats (CPU) print nothing."""
    used = [
        (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.local_devices()
    ]
    if any(u is not None for u in used):
        gib = " ".join(f"{(u or 0) / 2**30:.2f}" for u in used)
        print(f"device memory {when}: {gib} GiB in use per device")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="smol-1b", choices=sorted(PRESETS))
    parser.add_argument(
        "--layers", type=int, default=0,
        help="train the preset cut to this many layers (0 = the preset's"
             " depth): full width on a chip whose memory the full depth's"
             " train state does not fit",
    )
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=2048)
    parser.add_argument("--model-parallel", type=int, default=1)
    parser.add_argument("--seq-parallel", type=int, default=1)
    parser.add_argument("--expert-parallel", type=int, default=1)
    parser.add_argument(
        "--lora-rank", type=int, default=0,
        help="train low-rank adapters over the frozen base (0 = full fine-tune)",
    )
    parser.add_argument(
        "--data", default="",
        help="flat int32 token .npy (workloads/data.py); synthetic if unset",
    )
    parser.add_argument(
        "--checkpoint-dir",
        default=os.environ.get("CHECKPOINT_DIR", ""),
        help="directory on a mounted volume for periodic checkpoints",
    )
    args = parser.parse_args()

    # Multi-host: the orchestrator injected the coordinator env; single
    # host: skip (jax.distributed would wait for peers).
    if int(os.environ.get("JAX_NUM_PROCESSES", "1")) > 1:
        jax.distributed.initialize()
    print(
        f"process {jax.process_index()}/{jax.process_count()} sees"
        f" {jax.local_device_count()} local / {jax.device_count()} global"
        f" devices (platform {jax.devices()[0].platform},"
        f" {jax.devices()[0].device_kind})"
    )

    config = PRESETS[args.preset]
    if args.layers > 0:
        config = config.with_(n_layers=args.layers)
    if args.seq_len > config.max_seq_len:
        raise SystemExit(f"--seq-len > {config.max_seq_len} for {args.preset}")
    if args.expert_parallel > 1 and config.n_experts % args.expert_parallel:
        raise SystemExit("--expert-parallel must divide the preset's n_experts")
    mesh = make_mesh(
        jax.devices(), model=args.model_parallel, seq=args.seq_parallel,
        expert=args.expert_parallel,
    )
    if jax.process_index() == 0:
        print(
            f"model {args.preset}: layers={config.n_layers}"
            f" d_model={config.d_model} heads={config.n_heads}x{config.head_dim}"
            f" kv_heads={config.n_kv_heads} d_ff={config.d_ff}"
            f" vocab={config.vocab_size} dtype={config.dtype};"
            f" batch={args.batch_size} seq={args.seq_len}"
            f" mesh={dict(mesh.shape)}"
        )
    # One state + one step either way; LoRA swaps in the tiny adapter
    # state and a step closed over the frozen base — data, checkpoints,
    # and the loop below are shared.
    if args.lora_rank > 0:
        from dstack_tpu.workloads.lora import (
            init_lora_state,
            make_lora_train_step,
            merge_lora,
        )
        from dstack_tpu.workloads.sharding import shard_tree
        from dstack_tpu.workloads.train import TrainState
        from dstack_tpu.workloads.transformer import init_params

        base = shard_tree(mesh, init_params(config, jax.random.PRNGKey(0)))
        state = init_lora_state(
            config, base, jax.random.PRNGKey(1), rank=args.lora_rank, mesh=mesh
        )
        _lora_step = make_lora_train_step(config, mesh, rank=args.lora_rank)

        def step(s, b):
            return _lora_step(s, base, b)

        def export(final_state):
            # Serve the merged model; checkpoints stored the adapters only.
            merged = merge_lora(base, final_state.lora, rank=args.lora_rank)
            ckpt.export_params(
                args.checkpoint_dir,
                TrainState(final_state.step, merged, None),
            )
    else:
        state = init_train_state(config, jax.random.PRNGKey(0), mesh=mesh)
        step = make_train_step(config, mesh)

        def export(final_state):
            ckpt.export_params(args.checkpoint_dir, final_state)

    if args.checkpoint_dir:
        # Resume from the mounted volume: a retried gang continues at the
        # last saved step instead of step 0 (dstack_tpu.workloads.checkpoint).
        restored = ckpt.restore_latest(args.checkpoint_dir, state)
        if restored is not None:
            state = restored
            if jax.process_index() == 0:
                print(f"resumed from step {int(state.step)}")

    # The global batch shards over the data+fsdp axes; round up so every
    # device gets at least one row.
    dp = mesh.shape["data"] * mesh.shape["fsdp"]
    batch_size = ((args.batch_size + dp - 1) // dp) * dp
    if batch_size != args.batch_size and jax.process_index() == 0:
        print(f"batch size {args.batch_size} -> {batch_size} (divisible by {dp})")
    loader = None
    if args.data:
        from dstack_tpu.workloads.data import BatchLoader, TokenDataset

        # The loader yields the GLOBAL batch; every host derives the same
        # order and materializes only its devices' shards (workloads/data.py).
        loader = BatchLoader(
            TokenDataset(args.data, args.seq_len),
            batch_size,
            mesh=mesh,
            start_step=int(state.step),
            vocab_size=config.vocab_size,
        )
    else:
        batch = synthetic_batch(config, batch_size, args.seq_len, mesh=mesh)

    _print_device_memory("after init")
    start = int(state.step)  # nonzero after a resume
    for i in range(start, args.steps):
        if loader is not None:
            batch = next(loader)
        state, metrics = step(state, batch)
        if i == start:
            # What the first step's trace actually ran, not a prediction.
            paths = getattr(step, "attention_paths", None)
            if paths and jax.process_index() == 0:
                print(f"attention path: {','.join(sorted(paths))}")
            _print_device_memory("after first step")
        if i % 10 == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            if jax.process_index() == 0:
                print(f"step {i}: loss {loss:.4f}")
        ckpt_due = (i + 1) % 100 == 0 or i == args.steps - 1
        if args.checkpoint_dir and ckpt_due:
            # Every process participates (Orbax coordinates global arrays);
            # block on the final step so the job ends durable.
            ckpt.save(args.checkpoint_dir, state, wait=i == args.steps - 1)
    if args.checkpoint_dir:
        # Params-only export for serving (deployment/native/server.py reads
        # this without materializing optimizer moments).
        export(state)
        ckpt.close_all()  # drain async writers before the job exits
    if loader is not None:
        loader.close()
    print("training complete")


if __name__ == "__main__":
    main()
