"""Bundled chaos scenarios: an in-process server + local backend + the chaos
engine, with pass/fail expectations — the headless face of the subsystem
(`python -m dstack_tpu.chaos --scenario NAME`) and the fixture behind the
tier-1 chaos tests.

Each scenario boots a fresh in-memory server with background FSMs running,
installs a seeded `ChaosEngine`, submits a run on the local backend (real
runner subprocesses), and asserts the recovery story end to end. The report
is plain data so the CLI can render it and CI can gate on `ok`.
"""

import asyncio
import os
import tempfile
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from dstack_tpu import chaos
from dstack_tpu.chaos.engine import ChaosEngine

REPO_ROOT = str(Path(__file__).resolve().parent.parent.parent)

SCENARIOS: Dict[str, Callable] = {}


def scenario(name: str):
    def deco(fn):
        SCENARIOS[name] = fn
        return fn

    return deco


def list_scenarios() -> List[str]:
    return sorted(SCENARIOS)


async def run_scenario(name: str, seed: int = 0) -> Dict[str, Any]:
    """Run one scenario; returns {name, seed, ok, failures, details}."""
    if name not in SCENARIOS:
        raise ValueError(f"unknown scenario {name!r}; have {list_scenarios()}")
    from dstack_tpu.server import settings

    saved = {
        k: getattr(settings, k)
        for k in ("RETRY_PENDING_RUN_DELAY", "RUNNER_DISCONNECT_GRACE")
    }
    report: Dict[str, Any] = {"name": name, "seed": seed, "failures": [], "details": {}}
    try:
        with tempfile.TemporaryDirectory(prefix=f"dstack-chaos-{name}-") as tmp:
            await SCENARIOS[name](report, seed, Path(tmp))
    finally:
        for k, v in saved.items():
            setattr(settings, k, v)
        chaos.uninstall()
    report["ok"] = not report["failures"]
    return report


def _expect(report: Dict[str, Any], cond: bool, what: str) -> None:
    if not cond:
        report["failures"].append(what)


async def _make_server(
    tpu_sim: Optional[List[str]] = None, **backend_overrides
):
    from dstack_tpu.server.app import create_app
    from dstack_tpu.server.http import TestClient

    app = create_app(db_path=":memory:", run_background_tasks=True)
    await app.startup()
    ctx = app.state["ctx"]
    if tpu_sim or backend_overrides:
        conf = dict(backend_overrides)
        if tpu_sim:
            conf["tpu_sim"] = tpu_sim
        ctx.overrides["local_backend_config"] = conf
    client = TestClient(app, token=app.state["admin_token"])
    return app, ctx, client


async def _wait_run(client, run_name: str, targets, timeout: float):
    from dstack_tpu.server.http import response_json

    deadline = asyncio.get_event_loop().time() + timeout
    while True:
        resp = await client.post(
            "/api/project/main/runs/get", json_body={"run_name": run_name}
        )
        run = response_json(resp)
        if run and run.get("status") in targets:
            return run
        if asyncio.get_event_loop().time() > deadline:
            return run
        await asyncio.sleep(0.2)


def _task_body(commands, run_name, resources=None, retry=None, nodes=1, **conf_extra):
    conf: Dict[str, Any] = {
        "type": "task",
        "commands": commands,
        "nodes": nodes,
        "resources": resources or {"cpu": "1..", "memory": "0.1.."},
        **conf_extra,
    }
    if retry is not None:
        conf["retry"] = retry
    return {
        "run_spec": {
            "run_name": run_name,
            "configuration": conf,
            "ssh_key_pub": "ssh-rsa CHAOS",
        }
    }


# ---- scenarios -------------------------------------------------------------


@scenario("runner-flap")
async def _runner_flap(report, seed, tmp: Path) -> None:
    """Transient agent flakes: two consecutive /api/pull failures injected
    mid-run must be absorbed by the disconnect grace — the run finishes on
    its FIRST submission, no resubmit."""
    from dstack_tpu.server import settings

    settings.RETRY_PENDING_RUN_DELAY = 0
    engine = chaos.install(
        ChaosEngine(
            [
                {
                    "hook": "runner.http",
                    "action": "error",
                    "match": {"path": "/api/pull"},
                    "at_call": 2,
                    "calls": 2,
                    "message": "chaos: dropped heartbeat",
                }
            ],
            seed=seed,
            name="runner-flap",
        )
    )
    app, ctx, client = await _make_server()
    try:
        await engine.start()
        body = _task_body(
            ["sleep 2; echo flap-survived"],
            "chaos-flap",
            retry={"on_events": ["interruption"], "duration": 600},
        )
        resp = await client.post("/api/project/main/runs/submit", json_body=body)
        _expect(report, resp.status == 200, f"submit failed: {resp.body!r}")
        run = await _wait_run(client, "chaos-flap", {"done", "failed", "terminated"}, 60)
        _expect(report, run["status"] == "done", f"run ended {run['status']}, want done")
        subs = run["jobs"][0]["job_submissions"]
        _expect(
            report,
            len(subs) == 1,
            f"{len(subs)} submissions, want 1 (grace should absorb the flap)",
        )
        _expect(
            report,
            len(engine.injected) >= 2,
            f"engine injected {len(engine.injected)} faults, want >= 2",
        )
        report["details"]["injected"] = engine.injected
        report["details"]["submissions"] = len(subs)
    finally:
        await engine.stop()
        await app.shutdown()


@scenario("hard-preempt")
async def _hard_preempt(report, seed, tmp: Path) -> None:
    """A reclaimed VM with no notice: SIGKILL one worker's runner of a
    2-worker gang mid-run. The server must classify the dead agent as an
    interruption, kill the sibling, and resubmit the gang once."""
    from dstack_tpu.server import settings

    settings.RETRY_PENDING_RUN_DELAY = 0
    settings.RUNNER_DISCONNECT_GRACE = 1.0
    started = tmp / "started"
    crash_done = tmp / "crash-done"
    engine = chaos.install(
        ChaosEngine(
            [
                {
                    "hook": "tick",
                    "action": "crash",
                    "worker": 1,
                    "when_path_exists": str(started),
                    "message": "chaos: VM reclaimed",
                }
            ],
            seed=seed,
            name="hard-preempt",
        )
    )
    app, ctx, client = await _make_server(tpu_sim=["v5p-16"])
    try:
        await engine.start()
        # Both ranks check the crash marker ONCE at startup: the first
        # incarnation (marker absent) parks until the server tears it down
        # after the crash; the resubmitted gang (marker present — written
        # below once the injection is observed) finishes fast. Rank 0 also
        # opens the chaos window by touching the `started` gate.
        cmd = (
            f'[ "$JAX_PROCESS_ID" = "0" ] && touch {started};'
            f" if [ -f {crash_done} ]; then sleep 1; echo retried rank done;"
            f" else sleep 300; fi"
        )
        body = _task_body(
            [cmd],
            "chaos-hard",
            resources={"tpu": "v5p-16"},
            retry={"on_events": ["interruption"], "duration": 600},
        )
        resp = await client.post("/api/project/main/runs/submit", json_body=body)
        _expect(report, resp.status == 200, f"submit failed: {resp.body!r}")
        for _ in range(300):  # release the retried gang once the crash fired
            if engine.injected:
                await asyncio.to_thread(crash_done.write_text, "crashed")
                break
            await asyncio.sleep(0.2)
        _expect(report, engine.injected != [], "crash event never fired")
        run = await _wait_run(client, "chaos-hard", {"done", "failed", "terminated"}, 120)
        _expect(report, run["status"] == "done", f"run ended {run['status']}, want done")
        reasons = set()
        for job in run["jobs"]:
            subs = job["job_submissions"]
            _expect(
                report,
                len(subs) == 2,
                f"job {job['job_spec']['job_num']}: {len(subs)} submissions, want 2",
            )
            reasons.add(subs[0]["termination_reason"])
        _expect(
            report,
            "interrupted_by_no_capacity" in reasons,
            f"first-incarnation reasons {reasons} lack interrupted_by_no_capacity",
        )
        report["details"]["injected"] = engine.injected
        report["details"]["first_reasons"] = sorted(r for r in reasons if r)
    finally:
        await engine.stop()
        await app.shutdown()


_DRAIN_TRAIN = """
import os, sys, time
vol = sys.argv[1]
import jax
jax.config.update("jax_platforms", "cpu")
# Synchronous dispatch: these sim trainers churn buffers (resize /
# drain-restore) while the host is oversubscribed by the whole drill
# fleet; CPU async dispatch can still touch freed buffers from its
# dispatch thread (observed SIGSEGV / malloc corruption under load).
jax.config.update("jax_cpu_enable_async_dispatch", False)
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.train import (
    init_train_state, make_train_step, synthetic_batch, install_drain_handler,
)
from dstack_tpu.workloads import checkpoint as ckpt

drain = install_drain_handler()
cfg = PRESETS["tiny"]
state = init_train_state(cfg, jax.random.PRNGKey(0))
restored = ckpt.restore_latest(vol + "/ckpts", state)
start = 0
if restored is not None:
    state = restored
    start = int(state.step)
step = make_train_step(cfg)
batch = synthetic_batch(cfg, 2, 32)
for _ in range(start, 6):
    state, m = step(state, batch)
    with open(vol + "/progress", "w") as f:
        f.write(str(int(state.step)))
    if drain.draining:
        drain.checkpoint_and_exit(vol + "/ckpts", state)
    time.sleep(0.5)
    if drain.draining:
        drain.checkpoint_and_exit(vol + "/ckpts", state)
with open(vol + "/final", "w") as f:
    f.write(f"resumed_from={start} final={int(state.step)}")
"""


@scenario("preempt-resume")
async def _preempt_resume(report, seed, tmp: Path) -> None:
    """The flagship drill: a maintenance notice preempts ONE worker of a
    2-worker gang mid-training. The agent drains the job (SIGTERM), the
    workload checkpoints and exits DRAIN_EXIT_CODE, the server resubmits the
    gang exactly once, the retry resumes at step > 0, and /metrics reports
    1 preemption + 1 restart + 1 clean drain."""
    from dstack_tpu.server import settings

    settings.RETRY_PENDING_RUN_DELAY = 0
    script = tmp / "train.py"
    await asyncio.to_thread(script.write_text, _DRAIN_TRAIN)
    mount = tmp / "mnt" / "ckpt"
    engine = chaos.install(
        ChaosEngine(
            [
                {
                    "hook": "tick",
                    "action": "preempt",
                    "worker": 0,
                    "when_path_exists": str(mount / "progress"),
                    "message": "chaos: host maintenance",
                }
            ],
            seed=seed,
            name="preempt-resume",
        )
    )
    app, ctx, client = await _make_server(tpu_sim=["v5p-16"])
    try:
        await engine.start()
        resp = await client.post(
            "/api/project/main/volumes/create",
            json_body={"configuration": {
                "type": "volume", "name": "chaos-ckpt", "backend": "local",
                "region": "local", "size": "1GB",
            }},
        )
        _expect(report, resp.status == 200, f"volume create failed: {resp.body!r}")
        # Rank 0 execs the trainer so SIGTERM + the drain exit code reach the
        # runner unwrapped by bash; rank 1 waits for the final marker.
        rank0 = (
            f"PYTHONPATH={REPO_ROOT}:$PYTHONPATH exec python {script} {mount}"
        )
        rank1 = (
            f"while [ ! -f {mount}/final ]; do sleep 0.2; done; echo rank1 done"
        )
        cmd = f'if [ "$JAX_PROCESS_ID" = "0" ]; then {rank0}; else {rank1}; fi'
        body = _task_body(
            [cmd],
            "chaos-drill",
            resources={"tpu": "v5p-16"},
            retry={"on_events": ["interruption"], "duration": 600},
        )
        body["run_spec"]["configuration"]["volumes"] = [
            {"name": "chaos-ckpt", "path": str(mount)}
        ]
        resp = await client.post("/api/project/main/runs/submit", json_body=body)
        _expect(report, resp.status == 200, f"submit failed: {resp.body!r}")
        run = await _wait_run(client, "chaos-drill", {"done", "failed", "terminated"}, 180)
        _expect(report, run["status"] == "done", f"run ended {run['status']}, want done")

        reasons = set()
        for job in run["jobs"]:
            subs = job["job_submissions"]
            _expect(
                report,
                len(subs) == 2,
                f"job {job['job_spec']['job_num']}: {len(subs)} submissions,"
                " want 2 (gang resubmitted exactly once)",
            )
            reasons.add(subs[0]["termination_reason"])
        _expect(
            report,
            "preempted_by_provider" in reasons,
            f"first-incarnation reasons {reasons} lack preempted_by_provider",
        )

        final_path = mount / "final"
        resumed = -1
        if final_path.exists():
            final = await asyncio.to_thread(final_path.read_text)
            resumed = int(final.split("resumed_from=")[1].split()[0])
            report["details"]["final"] = final.strip()
        _expect(
            report,
            resumed > 0,
            f"resumed step {resumed}, want > 0 (checkpoint-resumed, not from scratch)",
        )

        resp = await client.get("/metrics", token="")
        text = resp.body.decode()
        for metric, want in [
            ("dstack_tpu_run_preemptions_total", 1),
            ("dstack_tpu_run_restarts_total", 1),
            ("dstack_tpu_run_clean_drains_total", 1),
        ]:
            line = next(
                (
                    ln
                    for ln in text.splitlines()
                    if ln.startswith(metric + "{") and 'run="chaos-drill"' in ln
                ),
                None,
            )
            val = float(line.rsplit(" ", 1)[1]) if line else None
            _expect(report, val == want, f"/metrics {metric} = {val}, want {want}")
        stage_buckets = [
            ln for ln in text.splitlines()
            if ln.startswith("dstack_tpu_run_stage_seconds_bucket{") and 'stage="' in ln
        ]
        _expect(
            report,
            bool(stage_buckets),
            "/metrics lacks dstack_tpu_run_stage_seconds_bucket series",
        )

        # The victim's persisted timeline must tell the preemption story in
        # order: notice (runner), graceful drain (runner), resubmit (FSM).
        from dstack_tpu.server.http import response_json

        resp = await client.get("/api/project/main/runs/chaos-drill/timeline")
        _expect(report, resp.status == 200, f"timeline fetch failed: {resp.body!r}")
        timeline = response_json(resp) or {"events": []}
        stages = [e["stage"] for e in timeline["events"]]
        report["details"]["timeline_stages"] = stages
        order = [stages.index(s) if s in stages else -1
                 for s in ("preempt", "drain", "resume")]
        _expect(
            report,
            -1 not in order and order[0] < order[1] < order[2],
            f"timeline stages {stages} lack ordered preempt -> drain -> resume",
        )
        _expect(
            report,
            timeline.get("trace_context") is None
            or timeline["trace_context"].startswith("00-"),
            f"timeline trace_context malformed: {timeline.get('trace_context')!r}",
        )
        report["details"]["injected"] = engine.injected
        report["details"]["first_reasons"] = sorted(r for r in reasons if r)
    finally:
        await engine.stop()
        await app.shutdown()


_VICTIM_TRAIN = """
import os, sys, time
vol = sys.argv[1]
import jax
jax.config.update("jax_platforms", "cpu")
# Synchronous dispatch: these sim trainers churn buffers (resize /
# drain-restore) while the host is oversubscribed by the whole drill
# fleet; CPU async dispatch can still touch freed buffers from its
# dispatch thread (observed SIGSEGV / malloc corruption under load).
jax.config.update("jax_cpu_enable_async_dispatch", False)
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.train import (
    init_train_state, make_train_step, synthetic_batch, install_drain_handler,
)
from dstack_tpu.workloads import checkpoint as ckpt

drain = install_drain_handler()
cfg = PRESETS["tiny"]
state = init_train_state(cfg, jax.random.PRNGKey(0))
restored = ckpt.restore_latest(vol + "/ckpts", state)
start = 0
if restored is not None:
    state = restored
    start = int(state.step)
step = make_train_step(cfg)
batch = synthetic_batch(cfg, 2, 32)
for _ in range(start, 400):
    state, m = step(state, batch)
    with open(vol + "/progress", "w") as f:
        f.write(str(int(state.step)))
    if drain.draining:
        drain.checkpoint_and_exit(vol + "/ckpts", state, grace_seconds=30.0)
    if os.path.exists(vol + "/stop"):
        break
    time.sleep(0.3)
    if drain.draining:
        drain.checkpoint_and_exit(vol + "/ckpts", state, grace_seconds=30.0)
with open(vol + "/final", "w") as f:
    f.write(f"resumed_from={start} final={int(state.step)}")
"""


@scenario("priority-preempt")
async def _priority_preempt(report, seed, tmp: Path) -> None:
    """Cluster-level priority preemption: the local fleet holds exactly ONE
    TPU slice (max_slices=1) and a priority-0 training run occupies it. A
    priority-50 run arrives, cannot place, and the scheduler reclaims
    capacity: the victim is cleanly drained (checkpoint + DRAIN_EXIT_CODE,
    reason preempted_by_scheduler), the high-priority run places on the
    freed slice and finishes, and the victim resumes from its drain
    checkpoint once capacity frees again. No chaos engine — the only
    "fault" is the scheduler doing its job."""
    from dstack_tpu.server import settings

    settings.RETRY_PENDING_RUN_DELAY = 0
    script = tmp / "train.py"
    await asyncio.to_thread(script.write_text, _VICTIM_TRAIN)
    mount = tmp / "mnt" / "ckpt"
    app, ctx, client = await _make_server(tpu_sim=["v5litepod-4"], max_slices=1)
    try:
        resp = await client.post(
            "/api/project/main/volumes/create",
            json_body={"configuration": {
                "type": "volume", "name": "chaos-ckpt", "backend": "local",
                "region": "local", "size": "1GB",
            }},
        )
        _expect(report, resp.status == 200, f"volume create failed: {resp.body!r}")
        body = _task_body(
            [f"PYTHONPATH={REPO_ROOT}:$PYTHONPATH exec python {script} {mount}"],
            "chaos-victim",
            resources={"tpu": "v5litepod-4"},
            retry={"on_events": ["interruption"], "duration": 600},
        )
        body["run_spec"]["configuration"]["volumes"] = [
            {"name": "chaos-ckpt", "path": str(mount)}
        ]
        resp = await client.post("/api/project/main/runs/submit", json_body=body)
        _expect(report, resp.status == 200, f"victim submit failed: {resp.body!r}")
        # The victim must be mid-training (checkpointable) before the
        # high-priority run shows up.
        progress = mount / "progress"
        for _ in range(600):
            if progress.exists():
                break
            await asyncio.sleep(0.2)
        _expect(report, progress.exists(), "victim never made training progress")

        body = _task_body(
            ["echo high-priority work done"],
            "chaos-highpri",
            resources={"tpu": "v5litepod-4"},
            priority=50,
        )
        resp = await client.post("/api/project/main/runs/submit", json_body=body)
        _expect(report, resp.status == 200, f"high-pri submit failed: {resp.body!r}")
        run = await _wait_run(
            client, "chaos-highpri", {"done", "failed", "terminated"}, 120
        )
        _expect(
            report, run["status"] == "done",
            f"high-pri run ended {run['status']}, want done (preemption placed it)",
        )

        # Let the resumed victim finish.
        await asyncio.to_thread((mount / "stop").write_text, "done")
        victim = await _wait_run(
            client, "chaos-victim", {"done", "failed", "terminated"}, 120
        )
        _expect(
            report, victim["status"] == "done",
            f"victim ended {victim['status']}, want done (resumed after preemption)",
        )
        subs = victim["jobs"][0]["job_submissions"]
        _expect(
            report, len(subs) == 2,
            f"victim has {len(subs)} submissions, want 2 (drained exactly once)",
        )
        _expect(
            report,
            subs[0]["termination_reason"] == "preempted_by_scheduler",
            f"victim first incarnation ended {subs[0]['termination_reason']},"
            " want preempted_by_scheduler",
        )
        final_path = mount / "final"
        resumed = -1
        if final_path.exists():
            final = await asyncio.to_thread(final_path.read_text)
            resumed = int(final.split("resumed_from=")[1].split()[0])
            report["details"]["final"] = final.strip()
        _expect(
            report, resumed > 0,
            f"victim resumed at step {resumed}, want > 0 (from the drain checkpoint)",
        )

        resp = await client.get("/metrics", token="")
        text = resp.body.decode()
        for metric, want in [
            ("dstack_tpu_run_scheduler_preemptions_total", 1),
            ("dstack_tpu_run_clean_drains_total", 1),
            ("dstack_tpu_run_restarts_total", 1),
            ("dstack_tpu_run_steps_lost_total", 0),
        ]:
            line = next(
                (
                    ln
                    for ln in text.splitlines()
                    if ln.startswith(metric + "{") and 'run="chaos-victim"' in ln
                ),
                None,
            )
            val = float(line.rsplit(" ", 1)[1]) if line else None
            _expect(report, val == want, f"/metrics {metric} = {val}, want {want}")
    finally:
        await app.shutdown()


_ELASTIC_TRAIN = """
import json, os, sys, time
vol = sys.argv[1]
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
)
import jax
jax.config.update("jax_platforms", "cpu")
# Synchronous dispatch: these sim trainers churn buffers (resize /
# drain-restore) while the host is oversubscribed by the whole drill
# fleet; CPU async dispatch can still touch freed buffers from its
# dispatch thread (observed SIGSEGV / malloc corruption under load).
jax.config.update("jax_cpu_enable_async_dispatch", False)
from dstack_tpu.parallel.mesh import rescale_accum_steps
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.sharding import make_mesh
from dstack_tpu.workloads.train import (
    init_train_state, make_train_step, read_resize_notice, synthetic_batch,
)
from dstack_tpu.workloads import checkpoint as ckpt

GLOBAL_BATCH = 12
cfg = PRESETS["tiny"]
devices = jax.devices()


_built = {}


def build(width, accum):
    # Cache per-width artifacts: re-expanding to a width already seen reuses
    # the mesh and compiled step (no recompile on rejoin).
    if (width, accum) not in _built:
        mesh = make_mesh(devices[:width], data=width)
        step = make_train_step(cfg, mesh, accum_steps=accum)
        batch = synthetic_batch(cfg, GLOBAL_BATCH, 32, mesh=mesh)
        _built[(width, accum)] = (mesh, step, batch)
    return _built[(width, accum)]


width, accum = 4, 3
mesh, step_fn, batch = build(width, accum)
state = init_train_state(cfg, jax.random.PRNGKey(0), mesh)
widths = [width]
steps_since_full = 0
for _ in range(200):
    notice = read_resize_notice()
    if notice and notice["width"] != width:
        # Shrink or re-expand: checkpoint, re-form the mesh at the new dp
        # width, reshard the state back in, rescale grad accumulation so
        # accum * width (the global batch) is invariant.
        ckpt.save(vol + "/ckpts", state, wait=True)
        ckpt.close_all()
        accum = rescale_accum_steps(accum, width, notice["width"])
        width = notice["width"]
        widths.append(width)
        mesh, step_fn, batch = build(width, accum)
        template = init_train_state(cfg, jax.random.PRNGKey(0), mesh)
        state = ckpt.restore_latest(vol + "/ckpts", template)
        steps_since_full = 0
    state, m = step_fn(state, batch)
    with open(vol + "/progress", "w") as f:
        f.write(str(int(state.step)))
    if width == 4 and len(widths) >= 3:
        steps_since_full += 1
        if steps_since_full >= 2:
            break
    time.sleep(0.3)
with open(vol + "/final", "w") as f:
    f.write(json.dumps({"widths": widths, "final_step": int(state.step)}))
"""


@scenario("elastic-resize")
async def _elastic_resize(report, seed, tmp: Path) -> None:
    """Elastic data-parallel recovery: a 4-host v5p-32 gang trains with
    elastic: true; chaos preempts worker 1 mid-run. Instead of restarting
    the gang, the server keeps the drained host's instance, notifies the
    survivors to re-form at width 3 (the rank-0 trainer reshards from its
    drain checkpoint and rescales grad accumulation to preserve the global
    batch), resubmits the lost rank in place, and re-expands to width 4
    when it rejoins. Rank 0 never restarts; no steps are lost."""
    from dstack_tpu.server import settings

    settings.RETRY_PENDING_RUN_DELAY = 0
    script = tmp / "train.py"
    await asyncio.to_thread(script.write_text, _ELASTIC_TRAIN)
    mount = tmp / "mnt" / "ckpt"
    engine = chaos.install(
        ChaosEngine(
            [
                {
                    "hook": "tick",
                    "action": "preempt",
                    "worker": 1,
                    "when_path_exists": str(mount / "progress"),
                    "message": "chaos: host maintenance",
                }
            ],
            seed=seed,
            name="elastic-resize",
        )
    )
    app, ctx, client = await _make_server(tpu_sim=["v5p-32"])
    try:
        await engine.start()
        resp = await client.post(
            "/api/project/main/volumes/create",
            json_body={"configuration": {
                "type": "volume", "name": "chaos-ckpt", "backend": "local",
                "region": "local", "size": "1GB",
            }},
        )
        _expect(report, resp.status == 200, f"volume create failed: {resp.body!r}")
        # Rank 0 execs the elastic trainer; other ranks model checkpointing
        # workers: exit DRAIN_EXIT_CODE on SIGTERM (a clean drain), park
        # until the trainer finishes otherwise.
        rank0 = f"PYTHONPATH={REPO_ROOT}:$PYTHONPATH exec python {script} {mount}"
        workers = (
            f"trap 'exit 113' TERM;"
            f" while [ ! -f {mount}/final ]; do sleep 0.2; done; echo rank done"
        )
        cmd = f'if [ "$JAX_PROCESS_ID" = "0" ]; then {rank0}; else {workers}; fi'
        body = _task_body(
            [cmd],
            "chaos-elastic",
            resources={"tpu": "v5p-32"},
            retry={"on_events": ["interruption"], "duration": 600},
            elastic=True,
        )
        body["run_spec"]["configuration"]["volumes"] = [
            {"name": "chaos-ckpt", "path": str(mount)}
        ]
        resp = await client.post("/api/project/main/runs/submit", json_body=body)
        _expect(report, resp.status == 200, f"submit failed: {resp.body!r}")
        run = await _wait_run(
            client, "chaos-elastic", {"done", "failed", "terminated"}, 240
        )
        _expect(report, run["status"] == "done", f"run ended {run['status']}, want done")
        _expect(report, engine.injected != [], "preempt event never fired")

        report["details"]["submissions"] = [
            {
                "job_num": job["job_spec"]["job_num"],
                "subs": [
                    {
                        "status": s["status"],
                        "reason": s.get("termination_reason"),
                        "exit": s.get("exit_status"),
                        "msg": s.get("termination_reason_message"),
                    }
                    for s in job["job_submissions"]
                ],
            }
            for job in run["jobs"]
        ]
        # Rank 0 must have survived on its FIRST submission — the whole
        # point of elastic mode is no full-gang restart.
        for job in run["jobs"]:
            subs = job["job_submissions"]
            num = job["job_spec"]["job_num"]
            want = 2 if num == 1 else 1
            _expect(
                report, len(subs) == want,
                f"job {num}: {len(subs)} submissions, want {want}",
            )

        final_path = mount / "final"
        widths = []
        if final_path.exists():
            import json as _json

            final = _json.loads(await asyncio.to_thread(final_path.read_text))
            widths = final["widths"]
            report["details"]["final"] = final
        _expect(
            report, widths == [4, 3, 4],
            f"trainer width history {widths}, want [4, 3, 4]"
            " (shrink on preemption, re-expand on rejoin)",
        )

        resp = await client.get("/metrics", token="")
        text = resp.body.decode()
        for metric, want in [
            ("dstack_tpu_run_elastic_resizes_total", 1),
            ("dstack_tpu_run_steps_lost_total", 0),
            ("dstack_tpu_run_restarts_total", 0),
        ]:
            line = next(
                (
                    ln
                    for ln in text.splitlines()
                    if ln.startswith(metric + "{") and 'run="chaos-elastic"' in ln
                ),
                None,
            )
            val = float(line.rsplit(" ", 1)[1]) if line else None
            _expect(report, val == want, f"/metrics {metric} = {val}, want {want}")
        report["details"]["injected"] = engine.injected
    finally:
        await engine.stop()
        await app.shutdown()


# ---- PR 9: failure-isolated serving tier drills ----------------------------
#
# Three drills proving the multi-replica control plane and the standalone
# data-plane workers fail independently: (a) kill -9 a server replica and
# watch the survivor take over its expired leases with zero double-claims;
# (b) kill -9 a data-plane worker mid-SSE and verify the other worker's
# streams are byte-intact while the killed streams end promptly; (c) cut
# the data plane off from the control-plane DB and verify it serves
# last-known routes flagged stale, then re-syncs epochs within one poll
# interval of recovery.


async def _seed_service_rows(ctx, run_name: str, port: int) -> str:
    """Insert a RUNNING service run + replica job pointing at
    127.0.0.1:port (same row shapes bench_proxy.py seeds). Returns run_id."""
    import json

    from dstack_tpu.models.runs import JobProvisioningData, JobSpec, RunSpec
    from dstack_tpu.server.security import generate_id
    from dstack_tpu.utils.common import utcnow_iso

    project = await ctx.db.fetchone("SELECT * FROM projects WHERE name='main'")
    user = await ctx.db.fetchone("SELECT * FROM users LIMIT 1")
    run_id, now = generate_id(), utcnow_iso()
    spec = RunSpec.model_validate(
        {"run_name": run_name, "repo_id": "local",
         "configuration": {"type": "service", "name": run_name, "port": port,
                           "commands": ["serve"]}}
    )
    await ctx.db.execute(
        "INSERT INTO runs (id, project_id, user_id, run_name, submitted_at,"
        " last_processed_at, status, run_spec, service_spec)"
        " VALUES (?, ?, ?, ?, ?, ?, 'running', ?, ?)",
        (run_id, project["id"], user["id"], run_name, now, now,
         spec.model_dump_json(),
         json.dumps({"url": f"/proxy/services/main/{run_name}/", "model": None})),
    )
    await ctx.db.execute(
        "INSERT INTO jobs (id, project_id, run_id, run_name, job_num, replica_num,"
        " submitted_at, last_processed_at, status, job_spec, job_provisioning_data)"
        " VALUES (?, ?, ?, ?, 0, 0, ?, ?, 'running', ?, ?)",
        (generate_id(), project["id"], run_id, run_name, now, now,
         _service_job_spec(run_name, port), _service_jpd()),
    )
    return run_id


def _service_job_spec(run_name: str, port: int) -> str:
    from dstack_tpu.models.runs import JobSpec

    return JobSpec.model_validate(
        {"job_name": f"{run_name}-0-0", "commands": ["serve"],
         "requirements": {"resources": {}},
         "app_specs": [{"app_name": "app", "port": port}]}
    ).model_dump_json()


def _service_jpd() -> str:
    from dstack_tpu.models.runs import JobProvisioningData

    return JobProvisioningData.model_validate(
        {"backend": "local",
         "instance_type": {"name": "local",
                           "resources": {"cpus": 1, "memory_mib": 1024}},
         "instance_id": "i-0", "hostname": "127.0.0.1", "internal_ip": "127.0.0.1",
         "region": "local", "price": 0.0, "username": "root", "dockerized": False}
    ).model_dump_json()


_REPLICA_WORKER = """
import asyncio, json, sys, time

from dstack_tpu.server.app import create_app
from dstack_tpu.server.http import Server


async def main():
    db_path, mode, keys_csv = sys.argv[1:4]
    keys = keys_csv.split(",")
    app = create_app(db_path=db_path, admin_token="chaos-admin",
                     run_background_tasks=True)
    server = Server(app, "127.0.0.1", 0)
    await server.start()
    ctx = app.state["ctx"]
    print(json.dumps({"event": "up", "port": server.port,
                      "replica": ctx.replica_id}), flush=True)
    if mode == "holder":
        held = []
        for k in keys:
            if await ctx.claims.try_claim("jobs", k):
                held.append(k)
                await ctx.db.execute(
                    "INSERT INTO chaos_claims (key, owner, acquired_at)"
                    " VALUES (?, ?, ?)", (k, ctx.replica_id, time.time()),
                )
        print(json.dumps({"event": "held", "keys": held}), flush=True)
        await asyncio.sleep(300)  # killed from outside; heartbeat renews
    else:  # contender: spin until every key is stolen from the corpse
        acquired = []
        while len(acquired) < len(keys):
            for k in keys:
                if k not in acquired and await ctx.claims.try_claim("jobs", k):
                    acquired.append(k)
                    await ctx.db.execute(
                        "INSERT INTO chaos_claims (key, owner, acquired_at)"
                        " VALUES (?, ?, ?)", (k, ctx.replica_id, time.time()),
                    )
            await asyncio.sleep(0.1)
        print(json.dumps({"event": "acquired", "keys": sorted(acquired)}),
              flush=True)
        await asyncio.sleep(300)  # parent scrapes /metrics, then kills us


asyncio.run(main())
"""


async def _read_event(proc, want: str, timeout: float = 60.0):
    """Next {"event": want} JSON line from a worker's stdout."""
    import json

    while True:
        line = await asyncio.wait_for(proc.stdout.readline(), timeout)
        if not line:
            raise RuntimeError(f"worker exited before event {want!r}")
        try:
            msg = json.loads(line)
        except ValueError:
            continue  # log noise on stdout
        if msg.get("event") == want:
            return msg


def _drill_env(tmp: Path, **extra: str) -> Dict[str, str]:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO_ROOT,
        # Keep subprocess servers away from the operator's real config.
        "DSTACK_TPU_SERVER_CONFIG": str(tmp / "config.yml"),
    }
    env.update(extra)
    return env


@scenario("replica-kill-takeover")
async def _replica_kill_takeover(report, seed, tmp: Path) -> None:
    """kill -9 one of two server replicas mid-claim: the survivor must
    take over the corpse's leases within TTL, with zero double-claims
    (no acquisition before the dead replica's lease expiry), and the
    takeover must be visible on the survivor's /metrics."""
    import json as _json
    import sys
    import time

    import httpx

    ttl = 2.0
    keys = [f"drill-job-{i}" for i in range(4)]
    db = tmp / "replicas.db"

    # Parent-side control app: migrates the DB, creates the audit table,
    # and is our read handle on resource_leases / chaos_claims.
    from dstack_tpu.server.app import create_app

    app = create_app(db_path=str(db), admin_token="chaos-admin",
                     run_background_tasks=False)
    await app.startup()
    ctx = app.state["ctx"]
    await ctx.db.execute(
        "CREATE TABLE IF NOT EXISTS chaos_claims ("
        " key TEXT NOT NULL, owner TEXT NOT NULL, acquired_at REAL NOT NULL)"
    )

    script = tmp / "replica_worker.py"
    await asyncio.to_thread(script.write_text, _REPLICA_WORKER)

    def _spawn(replica_id: str, mode: str):
        # stderr to a file, not a pipe: nobody drains it, and a chatty FSM
        # filling the pipe buffer would deadlock the worker.
        errlog = open(tmp / f"{replica_id}.stderr", "wb")
        return asyncio.create_subprocess_exec(
            sys.executable, str(script), str(db), mode, ",".join(keys),
            stdout=asyncio.subprocess.PIPE, stderr=errlog,
            env=_drill_env(
                tmp,
                DSTACK_TPU_MULTI_REPLICA="1",
                DSTACK_TPU_REPLICA_ID=replica_id,
                DSTACK_TPU_LEASE_TTL=str(ttl),
            ),
        )

    proc_a = await _spawn("replica-a", "holder")
    proc_b = None
    try:
        held = await _read_event(proc_a, "held")
        _expect(report, sorted(held["keys"]) == sorted(keys),
                f"holder claimed {held['keys']}, want all of {keys}")

        proc_b = await _spawn("replica-b", "contender")
        up_b = await _read_event(proc_b, "up")
        b_port = up_b["port"]

        # Readiness gate: the contender's HTTP plane answers.
        async with httpx.AsyncClient(timeout=5) as hc:
            deadline = time.monotonic() + 15
            while True:
                try:
                    r = await hc.get(f"http://127.0.0.1:{b_port}/metrics")
                    if r.status_code == 200:
                        break
                except httpx.HTTPError:
                    pass
                _expect(report, time.monotonic() < deadline,
                        "contender /metrics never came up")
                if time.monotonic() >= deadline:
                    return
                await asyncio.sleep(0.1)

        # Let the contender demonstrably contend (and fail) while the
        # holder is alive, then snapshot the holder's lease expiries and
        # kill it without ceremony.
        await asyncio.sleep(2 * ttl / 4)
        pre_kill = await ctx.db.fetchall(
            "SELECT key, expires_at FROM resource_leases"
            " WHERE owner = 'replica-a' AND namespace = 'jobs'"
        )
        _expect(report, len(pre_kill) == len(keys),
                f"holder had {len(pre_kill)} leases at kill time, want {len(keys)}")
        expiry = {r["key"]: r["expires_at"] for r in pre_kill}
        stolen_early = await ctx.db.fetchall(
            "SELECT * FROM chaos_claims WHERE owner = 'replica-b'"
        )
        _expect(report, not stolen_early,
                "contender acquired keys while the holder was alive")
        t_kill = time.time()
        proc_a.kill()

        acquired = await _read_event(proc_b, "acquired",
                                     timeout=ttl + 20)
        _expect(report, acquired["keys"] == sorted(keys),
                f"contender acquired {acquired['keys']}, want {sorted(keys)}")
        rows = await ctx.db.fetchall(
            "SELECT key, acquired_at FROM chaos_claims WHERE owner = 'replica-b'"
        )
        takeover_at = {r["key"]: r["acquired_at"] for r in rows}
        double_claims = [
            k for k in keys
            if takeover_at.get(k, float("inf")) < expiry.get(k, 0) - 0.05
        ]
        _expect(report, not double_claims,
                f"double-claimed before lease expiry: {double_claims}")
        worst = max(takeover_at.values()) - t_kill if takeover_at else None
        _expect(report, worst is not None and worst <= ttl + 3,
                f"takeover took {worst}s after kill -9, want <= ttl+3")
        report["details"]["takeover_after_kill_s"] = round(worst, 3) if worst else None

        # The steal is observable: lease_takeovers ticked on the survivor.
        takeovers = 0.0
        async with httpx.AsyncClient(timeout=5) as hc:
            r = await hc.get(f"http://127.0.0.1:{b_port}/metrics")
            for ln in r.text.splitlines():
                if ln.startswith("dstack_tpu_lease_takeovers_total") and \
                        'namespace="jobs"' in ln:
                    takeovers = float(ln.rsplit(" ", 1)[1])
        _expect(report, takeovers >= 1,
                f"survivor /metrics lease_takeovers_total = {takeovers}, want >= 1")
        report["details"]["lease_takeovers_total"] = takeovers
    finally:
        for p in (proc_a, proc_b):
            if p is not None and p.returncode is None:
                p.kill()
                try:
                    await asyncio.wait_for(p.wait(), 10)
                except asyncio.TimeoutError:
                    pass
        await app.shutdown()


@scenario("dataplane-worker-kill")
async def _dataplane_worker_kill(report, seed, tmp: Path) -> None:
    """kill -9 one of two data-plane workers mid-SSE: the surviving
    worker's stream must arrive byte-intact, the killed worker's streams
    must end promptly (not hang), and the survivor stays ready."""
    import sys
    import time

    import httpx

    from dstack_tpu.server.app import create_app

    db = tmp / "dataplane.db"
    events = [f"event {i:03d}\n".encode() for i in range(30)]
    expected = b"".join(events)

    # Slow SSE-ish upstream: headers immediately, then one event every
    # 120 ms — long enough for a mid-stream kill, short enough for CI.
    async def _handle(reader, writer):
        try:
            await reader.readuntil(b"\r\n\r\n")
            writer.write(
                b"HTTP/1.1 200 OK\r\ncontent-type: text/event-stream\r\n"
                + b"content-length: %d\r\n\r\n" % len(expected)
            )
            await writer.drain()
            for e in events:
                writer.write(e)
                await writer.drain()
                await asyncio.sleep(0.12)
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            writer.close()

    upstream = await asyncio.start_server(_handle, "127.0.0.1", 0)
    uport = upstream.sockets[0].getsockname()[1]

    # Control plane: migrate + seed the service, then get out of the way
    # (the whole point is that workers need no live server process).
    app = create_app(db_path=str(db), admin_token="chaos-admin",
                     run_background_tasks=False)
    await app.startup()
    await _seed_service_rows(app.state["ctx"], "chaos-sse", uport)
    await app.shutdown()

    async def _spawn_worker(idx: int):
        errlog = await asyncio.to_thread(open, tmp / f"worker-{idx}.stderr", "wb")
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "dstack_tpu.dataplane",
            "--db", str(db), "--port", "0", "--poll-interval", "0.2",
            stdout=asyncio.subprocess.PIPE, stderr=errlog,
            env=_drill_env(tmp),
        )
        line = await asyncio.wait_for(proc.stdout.readline(), 30)
        port = int(line.decode().rsplit(":", 1)[1])
        return proc, port

    async def _wait_ready(hc, port, deadline=15.0) -> bool:
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline:
            try:
                r = await hc.get(f"http://127.0.0.1:{port}/readyz")
                if r.status_code == 200:
                    return True
            except httpx.HTTPError:
                pass
            await asyncio.sleep(0.1)
        return False

    proc1 = proc2 = None
    hc = httpx.AsyncClient(timeout=httpx.Timeout(30, connect=5))
    try:
        (proc1, port1), (proc2, port2) = await asyncio.gather(
            _spawn_worker(1), _spawn_worker(2)
        )
        ready = await asyncio.gather(
            _wait_ready(hc, port1), _wait_ready(hc, port2)
        )
        _expect(report, all(ready), f"workers ready: {ready}, want both")
        if not all(ready):
            return

        progress = {1: 0, 2: 0}
        body: Dict[int, bytes] = {}
        errors: Dict[int, str] = {}

        async def _consume(idx: int, port: int) -> None:
            buf = b""
            try:
                async with hc.stream(
                    "GET",
                    f"http://127.0.0.1:{port}/proxy/services/main/chaos-sse/stream",
                    headers={"X-Request-ID": f"chaos-stream-{idx}"},
                ) as r:
                    async for chunk in r.aiter_raw():
                        buf += chunk
                        progress[idx] = len(buf)
            except Exception as e:  # the killed stream ends however it ends
                errors[idx] = repr(e)
            body[idx] = buf

        t1 = asyncio.create_task(_consume(1, port1))
        t2 = asyncio.create_task(_consume(2, port2))
        # Both streams demonstrably mid-flight (>= 5 events each), then
        # SIGKILL worker 1 — no shutdown hooks, no connection draining.
        five = 5 * len(events[0])
        deadline = time.monotonic() + 15
        while min(progress.values()) < five:
            _expect(report, time.monotonic() < deadline,
                    f"streams never reached mid-flight: {progress}")
            if time.monotonic() >= deadline:
                return
            await asyncio.sleep(0.05)
        t_kill = time.monotonic()
        proc1.kill()
        try:
            await asyncio.wait_for(t1, 10)
            killed_end = time.monotonic() - t_kill
        except asyncio.TimeoutError:
            t1.cancel()
            killed_end = None
        _expect(report, killed_end is not None,
                "killed worker's stream hung instead of ending")
        try:
            await asyncio.wait_for(t2, 30)
        except asyncio.TimeoutError:
            t2.cancel()
        _expect(report, body.get(2) == expected,
                f"surviving stream not byte-intact: got {len(body.get(2) or b'')}"
                f" bytes, want {len(expected)}")
        _expect(report, body.get(1) != expected,
                "killed stream implausibly completed after SIGKILL")
        r = await hc.get(f"http://127.0.0.1:{port2}/readyz")
        _expect(report, r.status_code == 200,
                f"survivor /readyz = {r.status_code} after the kill, want 200")
        # Trace continuity through the chaos: the survivor's flight
        # recorder must still serve its stream's trace after the sibling
        # died — observability that evaporates under failure is not
        # observability.
        tr = await hc.get(
            f"http://127.0.0.1:{port2}/v1/requests/chaos-stream-2/trace"
        )
        trace_ok = (
            tr.status_code == 200
            and tr.json().get("x_request_id") == "chaos-stream-2"
            and tr.json().get("status") == "ok"
            and [p["phase"] for p in tr.json().get("phases", [])] == ["proxy"]
        )
        _expect(report, trace_ok,
                f"survivor trace lookup failed: {tr.status_code}"
                f" {tr.text[:200]}")
        report["details"]["survivor_trace"] = (
            tr.json() if tr.status_code == 200 else None
        )
        report["details"]["killed_stream_ended_after_s"] = (
            round(killed_end, 3) if killed_end is not None else None
        )
        report["details"]["killed_stream_bytes"] = len(body.get(1) or b"")
        report["details"]["surviving_stream_bytes"] = len(body.get(2) or b"")
    finally:
        await hc.aclose()
        for p in (proc1, proc2):
            if p is not None and p.returncode is None:
                p.kill()
                try:
                    await asyncio.wait_for(p.wait(), 10)
                except asyncio.TimeoutError:
                    pass
        upstream.close()
        await upstream.wait_closed()


@scenario("dataplane-outage")
async def _dataplane_outage(report, seed, tmp: Path) -> None:
    """Control-plane outage: the data plane must keep serving last-known
    routes (flagged `x-dstack-route-stale`), stay ready, and re-sync
    epochs within ~one poll interval of the control plane returning —
    including a topology change that happened during the outage."""
    import time

    from dstack_tpu.dataplane.app import (
        create_dataplane_app, route_staleness_seconds,
    )
    from dstack_tpu.server.app import create_app
    from dstack_tpu.server.http import TestClient

    db = tmp / "outage.db"
    poll = 0.25

    async def _make_upstream(payload: bytes):
        async def _handle(reader, writer):
            try:
                while True:
                    await reader.readuntil(b"\r\n\r\n")
                    writer.write(
                        b"HTTP/1.1 200 OK\r\ncontent-length: %d\r\n\r\n"
                        % len(payload) + payload
                    )
                    await writer.drain()
            except (ConnectionError, asyncio.IncompleteReadError):
                pass
            finally:
                writer.close()

        srv = await asyncio.start_server(_handle, "127.0.0.1", 0)
        return srv, srv.sockets[0].getsockname()[1]

    up_a, port_a = await _make_upstream(b"alpha")
    up_b, port_b = await _make_upstream(b"bravo")

    app = create_app(db_path=str(db), admin_token="chaos-admin",
                     run_background_tasks=False)
    await app.startup()
    ctx = app.state["ctx"]
    run_id = await _seed_service_rows(ctx, "outage-svc", port_a)

    dp = create_dataplane_app(str(db), poll_interval=poll, routing_ttl=0.4)
    await dp.startup()
    dpc = dp.state["ctx"]
    client = TestClient(dp)

    async def _get(path):
        resp = await client.get(path)
        if resp.stream is not None:
            chunks = []
            async for c in resp.stream:
                chunks.append(c)
            resp.body = b"".join(chunks)
        return resp

    class _DeadDB:
        """Every query raises — the worker's view of a down control
        plane. Real db object kept so non-query attributes still work."""

        def __init__(self, real):
            self._real = real

        def __getattr__(self, name):
            if name in ("fetchone", "fetchall", "execute", "executemany",
                        "run_sync"):
                async def _fail(*a, **k):
                    raise RuntimeError("control plane unreachable (chaos)")
                return _fail
            return getattr(self._real, name)

    try:
        deadline = time.monotonic() + 15
        while not dpc.synced_once and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        _expect(report, dpc.synced_once, "worker never achieved epoch sync")
        r = await _get("/proxy/services/main/outage-svc/data")
        _expect(report, r.status == 200 and r.body == b"alpha",
                f"pre-outage request: {r.status} {r.body[:40]!r}")
        _expect(report, r.headers.get("x-dstack-route-stale") is None,
                "fresh route wrongly flagged stale")

        # --- outage: cut the worker off from the DB entirely.
        real_db = dpc.db
        dpc.db = _DeadDB(real_db)
        await asyncio.sleep(0.6)  # routing TTL expires; epoch polls fail
        r = await _get("/proxy/services/main/outage-svc/data")
        _expect(report, r.status == 200 and r.body == b"alpha",
                f"during outage: {r.status} {r.body[:40]!r}, want cached 200")
        _expect(report, r.headers.get("x-dstack-route-stale") == "1",
                "degraded-mode response missing x-dstack-route-stale: 1")
        ready = await _get("/readyz")
        _expect(report, ready.status == 200,
                f"/readyz {ready.status} during outage, want 200 (stays ready)")
        await asyncio.sleep(poll)
        staleness = route_staleness_seconds(dpc)
        _expect(report, staleness > 0,
                f"staleness gauge {staleness} during outage, want > 0")
        report["details"]["outage_staleness_s"] = round(staleness, 3)
        report["details"]["stale_serves"] = dpc.routing_cache.stats()["stale_serves"]

        # While the worker is cut off, the FSM moves the service to a new
        # replica (port flip + epoch bump) — exactly what the worker must
        # pick up on recovery.
        await ctx.db.execute(
            "UPDATE jobs SET job_spec = ? WHERE run_id = ?",
            (_service_job_spec("outage-svc", port_b), run_id),
        )
        await ctx.db.execute(
            "UPDATE runs SET routing_epoch = routing_epoch + 1 WHERE id = ?",
            (run_id,),
        )

        # --- recovery: reconnect and measure epoch re-sync latency.
        dpc.db = real_db
        t0 = time.monotonic()
        resynced = None
        while time.monotonic() - t0 < poll * 4 + 2:
            r = await _get("/proxy/services/main/outage-svc/data")
            if r.status == 200 and r.body == b"bravo":
                resynced = time.monotonic() - t0
                _expect(report, r.headers.get("x-dstack-route-stale") is None,
                        "post-recovery response still flagged stale")
                break
            await asyncio.sleep(0.05)
        _expect(report, resynced is not None,
                "worker never picked up the epoch bump after recovery")
        _expect(report, resynced is None or resynced <= poll + 1.0,
                f"epoch re-sync took {resynced}s, want <= poll + 1.0")
        if resynced is not None:
            report["details"]["resync_after_recovery_s"] = round(resynced, 3)
        await asyncio.sleep(poll + 0.1)
        _expect(report, route_staleness_seconds(dpc) < poll + 1.0,
                "staleness gauge did not recover after reconnection")
    finally:
        await dp.shutdown()
        await app.shutdown()
        for srv in (up_a, up_b):
            srv.close()
            await srv.wait_closed()


_SHARD_WORKER = """
import asyncio, json, sys, time

from dstack_tpu.server.app import create_app
from dstack_tpu.server.http import Server


async def main():
    db_path = sys.argv[1]
    app = create_app(db_path=db_path, admin_token="chaos-admin",
                     run_background_tasks=True)
    server = Server(app, "127.0.0.1", 0)
    await server.start()
    ctx = app.state["ctx"]
    print(json.dumps({"event": "up", "port": server.port,
                      "replica": ctx.replica_id}), flush=True)
    # Audit trail: every shard acquisition gets a wall-clock row. The
    # parent compares these against the victim's snapshotted lease
    # expiries to prove no survivor stole a shard early. Polling lags
    # the lease write by <= 50ms, which only makes the recorded time
    # LATER -- it can never fake a pre-expiry steal.
    owned = frozenset()
    while True:
        now_owned = ctx.shard_map.owned()
        for n in sorted(now_owned - owned):
            await ctx.db.execute(
                "INSERT INTO chaos_shards (shard, owner, acquired_at)"
                " VALUES (?, ?, ?)", (n, ctx.replica_id, time.time()),
            )
        owned = now_owned
        await asyncio.sleep(0.05)


asyncio.run(main())
"""


@scenario("shard-kill")
async def _shard_kill(report, seed, tmp: Path) -> None:
    """kill -9 one of four sharded replicas mid-probe: the survivors
    must absorb the corpse's FSM shards within one lease TTL of expiry,
    with zero pre-expiry steals (the lease boundary is the only handoff
    authority), and every in-flight run still reaches `done` -- the
    blast radius of a replica death is one TTL of latency on its
    shards, never a stuck run."""
    import json as _json
    import signal
    import sys
    import time

    import httpx

    from dstack_tpu.server.services.shard_map import NS_SHARD

    ttl = 2.0
    n_replicas = 4
    n_shards = 16
    n_runs = 12
    db = tmp / "shards.db"

    # Parent-side control app (not multi-replica, no background tasks):
    # migrates the DB, owns the audit table, reads leases and run rows.
    from dstack_tpu.server.app import create_app

    app = create_app(db_path=str(db), admin_token="chaos-admin",
                     run_background_tasks=False)
    await app.startup()
    ctx = app.state["ctx"]
    await ctx.db.execute(
        "CREATE TABLE IF NOT EXISTS chaos_shards ("
        " shard INTEGER NOT NULL, owner TEXT NOT NULL,"
        " acquired_at REAL NOT NULL)"
    )

    script = tmp / "shard_worker.py"
    await asyncio.to_thread(script.write_text, _SHARD_WORKER)

    def _spawn(replica_id: str):
        errlog = open(tmp / f"{replica_id}.stderr", "wb")
        return asyncio.create_subprocess_exec(
            sys.executable, str(script), str(db),
            stdout=asyncio.subprocess.PIPE, stderr=errlog,
            env=_drill_env(
                tmp,
                DSTACK_TPU_MULTI_REPLICA="1",
                DSTACK_TPU_REPLICA_ID=replica_id,
                DSTACK_TPU_LEASE_TTL=str(ttl),
                DSTACK_TPU_FSM_SHARDS=str(n_shards),
            ),
        )

    names = [f"replica-{i}" for i in range(n_replicas)]
    procs = {}
    try:
        for name in names:
            procs[name] = await _spawn(name)
        ports = {}
        for name in names:
            up = await _read_event(procs[name], "up")
            ports[name] = up["port"]

        async def _lease_map():
            now = time.time()
            rows = await ctx.db.fetchall(
                "SELECT key, owner, expires_at FROM resource_leases"
                " WHERE namespace = ? AND expires_at > ?", (NS_SHARD, now),
            )
            return {int(r["key"]): (r["owner"], r["expires_at"]) for r in rows}

        # Convergence gate: all shards leased, perfectly fair (4 each).
        deadline = time.monotonic() + 30
        while True:
            leases = await _lease_map()
            per_owner = {}
            for owner, _ in leases.values():
                per_owner[owner] = per_owner.get(owner, 0) + 1
            if len(leases) == n_shards and \
                    sorted(per_owner.values()) == [4] * n_replicas:
                break
            _expect(report, time.monotonic() < deadline,
                    f"shards never balanced: {per_owner}")
            if time.monotonic() >= deadline:
                return
            await asyncio.sleep(0.1)
        report["details"]["balanced_assignment"] = {
            o: n for o, n in sorted(per_owner.items())
        }

        # Mid-probe load: real runs through the sharded FSM, submitted
        # to replica-0's API (which stays alive).
        api = f"http://127.0.0.1:{ports['replica-0']}"
        hdrs = {"Authorization": "Bearer chaos-admin"}
        run_names = [f"shardkill-{i:02d}" for i in range(n_runs)]
        async with httpx.AsyncClient(timeout=30) as hc:
            for rn in run_names:
                r = await hc.post(f"{api}/api/project/main/runs/submit",
                                  headers=hdrs, json=_task_body(["true"], rn))
                _expect(report, r.status_code == 200,
                        f"submit {rn} -> {r.status_code}: {r.text[:200]}")

        # Snapshot the victim's lease expiries, then kill it mid-flight.
        victim = "replica-3"
        leases = await _lease_map()
        victim_shards = {n: exp for n, (o, exp) in leases.items() if o == victim}
        _expect(report, len(victim_shards) == 4,
                f"victim held {len(victim_shards)} shards at kill, want 4")
        t_kill = time.time()
        procs[victim].kill()

        # Survivors must own ALL shards again within one TTL of the
        # victim's last lease expiry (tick cadence is ttl/4; generous
        # slack for a 1-core box mid run-churn).
        reassigned_at = None
        deadline = time.monotonic() + 3 * ttl + 30
        while time.monotonic() < deadline:
            leases = await _lease_map()
            owners = {o for o, _ in leases.values()}
            if len(leases) == n_shards and victim not in owners:
                reassigned_at = time.time()
                break
            await asyncio.sleep(0.1)
        _expect(report, reassigned_at is not None,
                "survivors never absorbed the victim's shards")
        if reassigned_at is not None:
            report["details"]["reassigned_after_kill_s"] = round(
                reassigned_at - t_kill, 3)

        # Zero pre-expiry steals: every takeover row for a victim shard
        # is stamped at or after that shard's snapshotted lease expiry.
        rows = await ctx.db.fetchall(
            "SELECT shard, owner, acquired_at FROM chaos_shards"
            " WHERE acquired_at > ? AND owner != ?", (t_kill, victim),
        )
        early = [
            (r["shard"], r["owner"])
            for r in rows
            if r["shard"] in victim_shards
            and r["acquired_at"] < victim_shards[r["shard"]] - 0.05
        ]
        _expect(report, not early,
                f"shards stolen before the victim's lease expired: {early}")

        # The kill must not strand a single run: shards moved, rows kept
        # flowing (per-row claims stay the correctness backstop).
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            rows = await ctx.db.fetchall(
                "SELECT run_name, status FROM runs WHERE deleted = 0")
            status = {r["run_name"]: r["status"] for r in rows
                      if r["run_name"] in set(run_names)}
            if len(status) == n_runs and \
                    all(s in ("done", "failed", "terminated")
                        for s in status.values()):
                break
            await asyncio.sleep(0.5)
        not_done = {n: s for n, s in status.items() if s != "done"}
        missing = [n for n in run_names if n not in status]
        _expect(report, not not_done and not missing,
                f"runs not done after takeover: {not_done or missing}")
        report["details"]["runs_done"] = sum(
            1 for s in status.values() if s == "done")

        # Observability: the rebalance is visible on survivor /metrics --
        # the owned-shards gauges sum to the full shard space and at
        # least one survivor counted an `acquired` rebalance post-kill.
        owned_total, acquired_total = 0.0, 0.0
        async with httpx.AsyncClient(timeout=10) as hc:
            for name in names:
                if name == victim:
                    continue
                r = await hc.get(f"http://127.0.0.1:{ports[name]}/metrics")
                for ln in r.text.splitlines():
                    if ln.startswith("dstack_tpu_fsm_shards_owned"):
                        owned_total += float(ln.rsplit(" ", 1)[1])
                    if ln.startswith("dstack_tpu_fsm_shard_rebalances_total") \
                            and 'action="acquired"' in ln:
                        acquired_total += float(ln.rsplit(" ", 1)[1])
        _expect(report, owned_total == n_shards,
                f"survivor shards_owned gauges sum to {owned_total},"
                f" want {n_shards}")
        _expect(report, acquired_total >= n_shards,
                f"rebalance counters show {acquired_total} acquisitions,"
                f" want >= {n_shards}")
        report["details"]["survivor_shards_owned_sum"] = owned_total
    finally:
        for p in procs.values():
            if p is not None and p.returncode is None:
                p.kill()
                try:
                    await asyncio.wait_for(p.wait(), 10)
                except asyncio.TimeoutError:
                    pass
        await app.shutdown()
