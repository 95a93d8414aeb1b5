"""The training cell's child: the job a user gets from
examples/fine-tuning/jax/train.py with no flags — `make_mesh()` over every
chip (data=1, fsdp=n), `init_train_state`, `make_train_step`,
`synthetic_batch`, called exactly as that script calls them — inside a timed
loop. The example script itself prints no time and stops on a step count,
so it cannot be the child.

Set-up: state and batch on the devices from `--seed`, the correctness checks,
the first step (which builds the program, from the persistent cache after a
checkout's first run) and the mix's warm-up steps. Then `run` from the
parent: steps with `block_until_ready` on each, for `seconds`; with a trace,
the profiler around a few of them.

Checks, all before the window (reference/<family>.py has the tolerances):
the trainer's own forward (`transformer.forward` with the step's attention
function and mesh) against the plain reference's logits on the batch's
first rows; the first step's loss and load-balance term against the
reference's on the whole batch.
"""

import argparse
import importlib
import json
import math
import sys
import time

import jax

from benchmarks import cellfiles
from benchmarks.children import common

SAMPLE_TAIL = 512  # positions of each sampled row whose logits are compared


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, default=0)  # unused: same arguments as serve
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    cell = cellfiles.Cell(args.cell, rehearsal=args.rehearsal)

    from dstack_tpu.workloads import compile_cache
    from dstack_tpu.workloads.attention import make_attention_fn
    from dstack_tpu.workloads.config import ModelConfig
    from dstack_tpu.workloads.sharding import make_mesh
    from dstack_tpu.workloads.train import (
        init_train_state, make_train_step, synthetic_batch,
    )
    from dstack_tpu.workloads.transformer import forward, logits_linear

    device = common.require_chips(cell.chips, args.rehearsal)
    compile_cache.enable()
    job = dict(cell.mix)
    if cell.rehearsal is not None:
        job.update(cell.rehearsal["train"])
    fields = dict(cell.model_fields)
    fields["max_seq_len"] = max(fields["max_seq_len"], job["seq_len"])
    config = ModelConfig(**fields)

    timings = {}
    t0 = time.monotonic()
    mesh = make_mesh(jax.devices())
    state = init_train_state(config, jax.random.PRNGKey(args.seed), mesh=mesh)
    step = make_train_step(config, mesh)
    dp = mesh.shape["data"] * mesh.shape["fsdp"]
    rows = ((job["rows"] + dp - 1) // dp) * dp
    batch = synthetic_batch(config, rows, job["seq_len"], seed=args.seed, mesh=mesh)
    jax.block_until_ready((state, batch))
    timings["state_s"] = time.monotonic() - t0

    # The reference reads the weights before the first step donates them.
    reference = importlib.import_module(
        f"benchmarks.reference.{cell.family['reference']}")
    t0 = time.monotonic()
    tail = min(SAMPLE_TAIL, job["seq_len"])
    ref_loss, ref_aux, ref_sample = reference.loss(
        config, state.params, batch, sample_rows=dp, sample_tail=tail)
    attention_fn = make_attention_fn(mesh)

    def trainer_logits(params, tokens):
        hidden = forward(config, params, tokens, attention_fn=attention_fn,
                         mesh=mesh, return_hidden=True)
        return logits_linear(hidden[:, -tail:], params["lm_head"])

    sys_logits = jax.jit(trainer_logits)(state.params, batch["inputs"][:dp])
    checks = {"forward_logits": reference.check_logits(
        jax.device_get(sys_logits), jax.device_get(ref_sample["logits"]),
        jax.device_get(ref_sample["margin"]))}
    ref_loss, ref_aux = float(ref_loss), float(ref_aux)
    del sys_logits, ref_sample
    timings["reference_s"] = time.monotonic() - t0

    snap0 = compile_cache.snapshot()
    t0 = time.monotonic()
    state, metrics = step(state, batch)
    loss0, aux0 = float(metrics["loss"]), float(metrics["router_aux"])
    timings["first_step_s"] = time.monotonic() - t0
    checks["first_loss"] = {
        "step": loss0, "reference": ref_loss, "tolerance": reference.LOSS_TOL,
        "ok": math.isfinite(loss0) and abs(loss0 - ref_loss) <= reference.LOSS_TOL}
    checks["router_aux"] = {
        "step": aux0, "reference": ref_aux, "tolerance": reference.AUX_TOL,
        "ok": abs(aux0 - ref_aux) <= reference.AUX_TOL}
    for _ in range(job["warmup_steps"]):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    snap1 = compile_cache.snapshot()
    common.emit(
        "ready", device=device, checks=checks, timings=timings,
        mesh=dict(mesh.shape), attention_paths=sorted(step.attention_paths),
        warmup={"compiles_total": snap1["compiles"] - snap0["compiles"],
                "compile_cache_hits_total": snap1["cache_hits"] - snap0["cache_hits"],
                "compile_seconds_total": snap1["compile_seconds"] - snap0["compile_seconds"],
                "warmup_seconds": timings["first_step_s"]},
    )

    cmd = json.loads(sys.stdin.readline())
    if cmd["cmd"] != "run":
        common.fail(f"expected `run`, got {cmd!r}")
    seconds = float(cmd["seconds"])
    traced = range(0, 0)
    if cmd["trace"]:
        first = 4
        traced = range(first, first + job["traced_steps"])
    steps = []
    tracing = False
    t_open = time.monotonic()
    i = 0
    while time.monotonic() - t_open < seconds:
        if len(traced) and i == traced.start:
            common.start_trace(cmd["trace_dir"])
            tracing = True
        t0 = time.monotonic()
        state, metrics = step(state, batch)
        loss = float(jax.block_until_ready(metrics["loss"]))
        steps.append({"t": t0 - t_open, "seconds": time.monotonic() - t0,
                      "loss": loss, "finite": math.isfinite(loss),
                      "traced": i in traced})
        if tracing and i == traced.stop - 1:
            common.stop_trace()
            tracing = False
        i += 1
    if tracing:
        common.stop_trace()
    snap2 = compile_cache.snapshot()
    common.emit(
        "result", steps=steps,
        job={"rows": rows, "seq_len": job["seq_len"], "tokens_per_step": rows * job["seq_len"]},
        compiles_in_window=snap2["compiles"] - snap1["compiles"],
        **common.memory(),
    )


if __name__ == "__main__":
    main()
