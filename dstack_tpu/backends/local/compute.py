"""Local backend: provisions "instances" as processes on the server host.

Parity: src/dstack/_internal/core/backends/local/ (114 LoC dev backend), but
substantially more capable: it spawns a real runner agent per "host", so the
entire submit→provision→run→logs pipeline executes end-to-end in tests and
dev setups with zero cloud access — including *gang-scheduled multi-host TPU
slices*, which it simulates by advertising TPU offers (`tpu_sim`) and
spawning one runner process per worker host.
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Dict, List, Optional

from dstack_tpu.backends.base.catalog import tpu_offer
from dstack_tpu.backends.base.compute import Compute
from dstack_tpu.backends.base.offers import filter_offers
from dstack_tpu.errors import NoCapacityError
from dstack_tpu.models.backends import BackendType
from dstack_tpu.models.common import CoreModel
from pydantic import model_validator
from dstack_tpu.models.instances import (
    InstanceAvailability,
    InstanceOfferWithAvailability,
    InstanceType,
    Resources,
)
from dstack_tpu.models.runs import JobProvisioningData, Requirements
from dstack_tpu.models.topology import TpuTopology
from dstack_tpu.models.volumes import (
    Volume,
    VolumeAttachmentData,
    VolumeProvisioningData,
)


class LocalBackendConfig(CoreModel):
    type: str = "local"
    # TPU accelerator types to advertise as simulated offers (e.g.
    # ["v5litepod-16"]); each worker host becomes a local runner process.
    tpu_sim: List[str] = []
    cpu_offers: bool = True
    price_per_hour: float = 0.0
    # Path to the C++ runner binary (agents/native/build/dstack-tpu-runner)
    # to spawn instead of the Python twin — the same --host/--port/--port-file
    # contract, so the whole control plane can be e2e'd against the native
    # agent stack.
    runner_binary: Optional[str] = None
    # Path to the C++ shim binary. When set, each worker "host" is a shim
    # in `--runtime process` mode (dockerized path): the server submits a
    # task to the shim, the shim spawns the runner — the exact chain real
    # hosts use, minus docker.
    shim_binary: Optional[str] = None
    # Production semantics for restart drills: real hosts are remote
    # machines whose agents SURVIVE a server crash/restart. When true,
    # skip the PDEATHSIG/--parent-pid death-link so local agents model
    # that (the restart-reconciliation test depends on it). Default off:
    # abruptly-killed dev servers must not leak agent processes.
    detach_agents: bool = False
    # Finite fleet: at most this many TPU slices may be live at once;
    # further slice provisions raise NoCapacityError exactly like a real
    # region with no free nodes. None = unlimited (the historical default).
    # The priority-preemption chaos drill uses max_slices=1 to force the
    # scheduler to reclaim capacity instead of provisioning fresh.
    max_slices: Optional[int] = None

    @model_validator(mode="after")
    def _shim_needs_runner(self):
        if self.shim_binary and not self.runner_binary:
            raise ValueError("shim_binary requires runner_binary (the shim execs it)")
        return self


# Loaded at import, NOT inside the preexec hook: dlopen between fork and
# exec in a threaded parent can deadlock on loader/malloc locks.
try:
    import ctypes as _ctypes

    _LIBC = _ctypes.CDLL("libc.so.6", use_errno=True)
except OSError:  # non-glibc platform
    _LIBC = None

_PR_SET_PDEATHSIG = 1


def _exit_with_parent_preexec() -> None:
    """In the child, pre-exec: deliver SIGTERM when the parent dies
    (Linux PR_SET_PDEATHSIG). There is a window where the parent died
    between fork and prctl — detect it and exit immediately."""
    if _LIBC is None:
        return  # the --parent-pid watchdog still covers python runners
    import signal as _signal

    _LIBC.prctl(_PR_SET_PDEATHSIG, _signal.SIGTERM)
    if os.getppid() == 1:
        os._exit(0)


class LocalCompute(Compute):
    BACKEND_TYPE = "local"

    def __init__(self, config: Optional[LocalBackendConfig] = None):
        self.config = config or LocalBackendConfig()
        self._procs: Dict[str, subprocess.Popen] = {}
        self._preempt_files: Dict[tuple, str] = {}  # (instance_name, worker)
        self._slices: Dict[str, List[int]] = {}  # instance_name -> worker pids

    def _live_slices(self) -> int:
        """Active TPU slices, pruning entries whose workers all exited —
        a drained/crashed slice frees its capacity slot without waiting
        for the FSM's terminate to round-trip."""
        for name in list(self._slices):
            alive = False
            for pid in self._slices[name]:
                try:
                    os.kill(pid, 0)  # PermissionError would still mean alive
                    alive = True
                    break
                except ProcessLookupError:
                    continue
            if not alive:
                del self._slices[name]
        return len(self._slices)

    async def get_offers(
        self, requirements: Requirements
    ) -> List[InstanceOfferWithAvailability]:
        offers: List[InstanceOfferWithAvailability] = []
        if self.config.cpu_offers:
            offers.append(
                InstanceOfferWithAvailability(
                    backend=BackendType.LOCAL,
                    instance=InstanceType(
                        name="local",
                        resources=Resources(
                            cpus=os.cpu_count() or 1,
                            memory_mib=16 * 1024,
                            description="local process",
                        ),
                    ),
                    region="local",
                    price=self.config.price_per_hour,
                    hosts=1,
                    availability=InstanceAvailability.AVAILABLE,
                )
            )
        for acc_type in self.config.tpu_sim:
            topo = TpuTopology.parse(acc_type)
            offer = tpu_offer(topo, "local", "local-a", spot=False, backend=BackendType.LOCAL)
            offer.price = self.config.price_per_hour
            offer.availability = InstanceAvailability.AVAILABLE
            offers.append(offer)
        return filter_offers(offers, requirements)

    async def run_job(
        self,
        project_name: str,
        run_name: str,
        offer: InstanceOfferWithAvailability,
        ssh_public_key: str,
        instance_name: str,
        env: Optional[Dict[str, str]] = None,
    ) -> List[JobProvisioningData]:
        is_tpu = offer.instance.resources.tpu is not None
        if (
            is_tpu
            and self.config.max_slices is not None
            and self._live_slices() >= self.config.max_slices
        ):
            raise NoCapacityError(
                f"local fleet full: {self.config.max_slices} TPU slice(s) live"
            )
        out: List[JobProvisioningData] = []
        # -S skips site init, which the runner agent does not need (on
        # real hosts the C++ runner starts in milliseconds). PYTHONPATH
        # re-adds what site would have provided.
        pythonpath = os.pathsep.join(p for p in sys.path if p)
        spawned = []
        # Race-free port allocation: each runner binds :0 and reports the
        # kernel-chosen port through a file — no pick-then-bind window for
        # another process to steal the port (the cause of rare parallel-boot
        # failures with up-front find_free_ports).
        # Private temp dir so port-file paths are not predictable/pre-creatable
        # by other local users (mktemp would be).
        port_dir = tempfile.mkdtemp(prefix="dstack-local-runner-")
        # Per-worker preemption-notice files: the runner's preemption watcher
        # polls DSTACK_TPU_PREEMPTION_FILE (the local stand-in for the GCP
        # maintenance-event metadata endpoint); the chaos engine "preempts" a
        # worker by writing its file. Outlives port_dir — notices can arrive
        # any time in the worker's life.
        preempt_dir = tempfile.mkdtemp(prefix="dstack-local-preempt-")
        for worker in range(offer.hosts):
            port_file = os.path.join(port_dir, f"w{worker}.port")
            preempt_file = os.path.join(preempt_dir, f"w{worker}.preempt")
            if self.config.shim_binary:
                argv = [
                    self.config.shim_binary,
                    "--host", "127.0.0.1", "--port", "0", "--port-file", port_file,
                    "--runtime", "process",
                    "--runner-binary", self.config.runner_binary or "",
                ]
            elif self.config.runner_binary:
                argv = [
                    self.config.runner_binary,
                    "--host", "127.0.0.1", "--port", "0", "--port-file", port_file,
                ]
            else:
                argv = [
                    sys.executable, "-S", "-m", "dstack_tpu.agents.runner",
                    "--host", "127.0.0.1", "--port", "0", "--port-file", port_file,
                ]
                if not self.config.detach_agents:
                    # Belt-and-braces with PDEATHSIG below: the explicit
                    # pid makes the watchdog race-free even if the parent
                    # dies during interpreter startup.
                    argv += ["--parent-pid", str(os.getpid())]
            proc = await asyncio.to_thread(
                subprocess.Popen,
                argv,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
                env={**os.environ, **(env or {}), "PYTHONPATH": pythonpath,
                     # Jobs run as raw host processes here; bootstrap steps
                     # that would mutate the environment (pip installs) are
                     # gated on this marker.
                     "DSTACK_TPU_LOCAL": "1",
                     "DSTACK_TPU_PREEMPTION_FILE": preempt_file},
                start_new_session=True,
                # Local "hosts" are children of the server process and must
                # die with it — abruptly-killed servers (tests, probes)
                # otherwise leave agent processes around forever (observed:
                # hundreds, hours old). PDEATHSIG covers every spawn branch
                # (python, C++ runner, shim) and survives exec — unless
                # detach_agents models production hosts that outlive the
                # server (restart-reconciliation drill).
                preexec_fn=(None if self.config.detach_agents
                            else _exit_with_parent_preexec),
            )
            instance_id = f"local-{proc.pid}"
            self._procs[instance_id] = proc
            spawned.append((worker, port_file, proc, instance_id))
            self._preempt_files[(instance_name, worker)] = preempt_file
        # All workers of the slice boot in parallel — the real GCP path
        # provisions one TPU node object whose workers come up together.
        try:
            ports = await asyncio.gather(
                *(self._wait_port_file(f, p) for _, f, p, _i in spawned)
            )
        finally:
            import shutil

            shutil.rmtree(port_dir, ignore_errors=True)
        spawned = [
            (worker, port, proc, instance_id)
            for (worker, _f, proc, instance_id), port in zip(spawned, ports)
        ]
        # The FSM issues ONE terminate per slice (worker 0 — the real TPU
        # API deletes the whole node object); locally that must fan out to
        # every worker's process, so each jpd carries the gang's pids.
        slice_pids = [proc.pid for _w, _p, proc, _i in spawned]
        if is_tpu:
            self._slices[instance_name] = list(slice_pids)
        # Hand the gang to an installed chaos engine so tick-scheduled
        # preempt/crash events can target it by instance name/worker index.
        from dstack_tpu import chaos

        engine = chaos.get_engine()
        if engine is not None:
            for worker, _port, proc, _iid in spawned:
                engine.register_worker(
                    instance_name,
                    worker,
                    preemption_file=self._preempt_files[(instance_name, worker)],
                    pids=[proc.pid],
                )
        for worker, port, proc, instance_id in spawned:
            out.append(
                JobProvisioningData(
                    backend=BackendType.LOCAL,
                    instance_type=offer.instance,
                    instance_id=instance_id,
                    hostname="127.0.0.1",
                    internal_ip="127.0.0.1",
                    region=offer.region,
                    availability_zone=offer.zone,
                    # offer.price covers the whole slice; each worker carries
                    # its share so per-job cost sums correctly.
                    price=offer.price / offer.hosts,
                    username="root",
                    ssh_port=None,
                    # shim mode follows the real host chain (shim creates the
                    # task, reports the runner port); otherwise the server
                    # talks to the runner directly.
                    dockerized=bool(self.config.shim_binary),
                    backend_data=json.dumps(
                        {"shim_port": port, "pid": proc.pid, "slice_pids": slice_pids}
                        if self.config.shim_binary
                        else {"port": port, "pid": proc.pid, "slice_pids": slice_pids}
                    ),
                    tpu_node_id=instance_name if offer.hosts > 1 else None,
                    tpu_worker_index=worker,
                )
            )
        return out

    @staticmethod
    async def _wait_port_file(
        port_file: str, proc: subprocess.Popen, timeout: float = 30.0
    ) -> int:
        """The runner's reported port, once it has bound :0 and is serving."""
        deadline = asyncio.get_event_loop().time() + timeout
        port = None
        while True:
            if port is None:
                try:
                    port = int(await asyncio.to_thread(Path(port_file).read_text))
                    Path(port_file).unlink(missing_ok=True)
                except (OSError, ValueError):
                    port = None
            if port is not None:
                try:
                    _, writer = await asyncio.open_connection("127.0.0.1", port)
                    writer.close()
                    return port
                except OSError:
                    pass
            if proc.poll() is not None:
                raise RuntimeError(
                    f"local runner exited with {proc.returncode} before serving"
                )
            if asyncio.get_event_loop().time() > deadline:
                raise TimeoutError("local runner did not start in time")
            await asyncio.sleep(0.05)

    async def terminate_instance(
        self, instance_id: str, region: str, backend_data: Optional[str] = None
    ) -> None:
        proc = self._procs.pop(instance_id, None)
        data = json.loads(backend_data) if backend_data else {}
        pids = data.get("slice_pids") or []
        # Free the capacity slot as soon as the slice is torn down (not on
        # the next provision's liveness prune — reaped zombies still ping).
        for name, spids in list(self._slices.items()):
            if set(spids) & set(pids) or (proc is not None and proc.pid in spids):
                del self._slices[name]
        if proc is not None and proc.pid not in pids:
            pids.append(proc.pid)
        if not pids and data.get("pid"):
            pids = [data["pid"]]
        def _kill(sig) -> int:
            alive = 0
            for pid in pids:
                try:
                    os.killpg(os.getpgid(pid), sig)
                    alive += 1
                except (ProcessLookupError, PermissionError):
                    pass
            return alive

        if self.config.shim_binary:
            # Shim mode: TERM first so the shim tears its tasks down (its
            # runner children setsid out of the process group — killpg
            # alone would leak them). Poll up to 6s (the shim's own
            # teardown allows 2s per task) before escalating.
            if _kill(signal.SIGTERM):
                for _ in range(24):
                    await asyncio.sleep(0.25)
                    if not _kill(0):
                        break
        # Direct runners sit in the group killpg reaches; KILL is exact.
        _kill(signal.SIGKILL)
        # Reap every slice member (not just this instance's Popen) so no
        # zombies or dict entries accumulate across slices.
        for iid in [f"local-{p}" for p in pids]:
            sibling = self._procs.pop(iid, None)
            if sibling is not None:
                try:
                    sibling.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    pass
        if proc is not None:
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass

    # Volumes: directory-backed fakes so the volume FSM is testable.
    async def create_volume(self, volume: Volume) -> VolumeProvisioningData:
        import tempfile

        path = tempfile.mkdtemp(prefix=f"dstack-vol-{volume.name}-")
        return VolumeProvisioningData(
            backend=BackendType.LOCAL,
            volume_id=path,
            size_gb=int(volume.configuration.size or 1),
        )

    async def delete_volume(self, volume: Volume) -> None:
        import shutil

        if volume.volume_id and os.path.isdir(volume.volume_id):
            shutil.rmtree(volume.volume_id, ignore_errors=True)

    async def attach_volume(
        self, volume: Volume, provisioning_data: JobProvisioningData
    ) -> VolumeAttachmentData:
        return VolumeAttachmentData(device_name=volume.volume_id)

    async def detach_volume(
        self, volume: Volume, provisioning_data: JobProvisioningData
    ) -> None:
        return None
