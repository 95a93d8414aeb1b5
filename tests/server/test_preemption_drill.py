"""The north-star failure story in ONE drill (round-4 VERDICT #6).

A gang fine-tune training to a volume loses a worker mid-training
(simulated preemption: the worker's runner dies and the server notices via
the disconnect grace). Under `retry.on_events: [interruption]` the server
must resubmit the WHOLE replica, re-attach the SAME volume, and the second
incarnation must restore the Orbax checkpoint and finish from step N — not
from scratch.

Pieces previously proven separately (test_retry.py gang rule,
test_backfill.py volume FSM, test_checkpoint.py Orbax round-trip) run here
as one story on the local backend with real runner processes and a real
tiny JAX training loop inside the job.

Parity: reference retry FSM (process_runs.py:129-182, `retry.on_events`
with INTERRUPTED_BY_NO_CAPACITY) + checkpoint-via-volumes guidance
(SURVEY §5: orchestrator guarantees re-provisioning + same mounts + same
rank env; checkpoints are user-level Orbax on the mounted disk).
"""

import asyncio

from dstack_tpu.server import settings
from dstack_tpu.server.http import response_json
from tests.server.conftest import make_server, task_body as _body, wait_run as _wait_run

TRAIN_SCRIPT = """
import os, sys, time
vol = sys.argv[1]
import jax
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.train import (
    init_train_state, make_train_step, synthetic_batch,
)
from dstack_tpu.workloads import checkpoint as ckpt

cfg = PRESETS["tiny"]
state = init_train_state(cfg, jax.random.PRNGKey(0))
restored = ckpt.restore_latest(vol + "/ckpts", state)
start = 0
if restored is not None:
    state = restored
    start = int(state.step)
step = make_train_step(cfg)
batch = synthetic_batch(cfg, 2, 32)
for _ in range(start, 8):
    state, m = step(state, batch)
    ckpt.save(vol + "/ckpts", state, wait=True)
    with open(vol + "/progress", "w") as f:
        f.write(str(int(state.step)))
    time.sleep(1)  # keep a window open for the preemption
with open(vol + "/final", "w") as f:
    f.write(f"resumed_from={start} final={int(state.step)}")
"""


async def test_preemption_resume_drill(tmp_path, monkeypatch):
    monkeypatch.setattr(settings, "RETRY_PENDING_RUN_DELAY", 0)
    # Fast-fail disconnect detection (the knob VERDICT #10 asked for).
    monkeypatch.setattr(settings, "RUNNER_DISCONNECT_GRACE", 1.0)

    script = tmp_path / "train.py"
    script.write_text(TRAIN_SCRIPT)
    preempted_marker = tmp_path / "preempted-once"
    mount_path = tmp_path / "mnt" / "checkpoints"

    fx = await make_server()
    fx.ctx.overrides["local_backend_config"] = {"tpu_sim": ["v5litepod-16"]}
    try:
        # 1. A named volume (local backend: directory-backed, FSM-provisioned).
        resp = await fx.client.post(
            "/api/project/main/volumes/create",
            json_body={"configuration": {
                "type": "volume", "name": "ckpt-vol", "backend": "local",
                "region": "local", "size": "1GB",
            }},
        )
        assert resp.status == 200, resp.body

        # 2. A 4-host gang (v5litepod-16): rank 0 trains to the volume; the
        # first non-zero rank to grab the marker simulates a host preemption
        # ONCE by killing its own runner (the server sees a dead agent,
        # exactly like a reclaimed spot VM); the rest wait for training to
        # finish.
        # The drill exercises orchestration (preempt -> gang resubmit ->
        # volume -> Orbax resume), not the accelerator: the tiny model
        # trains on CPU.
        rank0 = (
            f"JAX_PLATFORMS=cpu PYTHONPATH=/root/repo:$PYTHONPATH"
            f" python {script} {mount_path}"
        )
        rank1 = (
            f"while [ ! -s {mount_path}/progress ]; do sleep 0.2; done; "
            f"if [ ! -f {preempted_marker} ]; then"
            f" touch {preempted_marker}; kill -9 $PPID; sleep 60; fi; "
            f"while [ ! -f {mount_path}/final ]; do sleep 0.2; done; echo rank1 done"
        )
        cmd = f'if [ "$JAX_PROCESS_ID" = "0" ]; then {rank0}; else {rank1}; fi'
        body = _body(
            [cmd], "drill",
            retry={"on_events": ["interruption"], "duration": 600},
            resources={"tpu": "v5litepod-16"},
        )
        body["run_spec"]["configuration"]["volumes"] = [
            {"name": "ckpt-vol", "path": str(mount_path)}
        ]
        resp = await fx.client.post(
            "/api/project/main/runs/submit", json_body=body
        )
        assert resp.status == 200, resp.body

        run = await _wait_run(
            fx, "drill", {"done", "failed", "terminated"}, timeout=180.0
        )
        assert run["status"] == "done", run

        # 3. Every gang job got exactly two incarnations, and the first
        # died for interruption-shaped reasons (the preempted worker as
        # no-capacity, its siblings as gang kills).
        assert len(run["jobs"]) == 4
        reasons = set()
        for job in run["jobs"]:
            subs = job["job_submissions"]
            assert len(subs) == 2, (job["job_spec"]["job_num"], subs)
            reasons.add(subs[0]["termination_reason"])
            assert subs[1]["status"] == "done"
        assert "interrupted_by_no_capacity" in reasons, reasons

        # 4. The second incarnation resumed from a real checkpoint on the
        # re-attached volume — training continued from step N >= 1, not 0.
        final = (mount_path / "final").read_text()
        resumed = int(final.split("resumed_from=")[1].split()[0])
        last = int(final.split("final=")[1].split()[0])
        assert resumed >= 1, final  # restored, not from scratch
        assert last == 8, final     # and finished the full plan
    finally:
        await fx.app.shutdown()
