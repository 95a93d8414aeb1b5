#!/usr/bin/env python3
"""chip_smoke.py — apply -> train -> serve on the accelerator, end to end.

The quickest proof that the system still starts on the chip. It walks the
main path a user walks, through the entry points a user calls, at the full
width of smol-1b (d_model 2048, 16x128 heads, 8 KV heads, d_ff 5632, vocab
32,768, S=2048, bf16; depth cut to what one chip's memory holds; random
weights from a seed), and checks what comes out by the repo's own means:

  a. device   (child)   what JAX sees: platform, kind, count, versions, HBM
  b. kernels  (child)   every Pallas kernel compiled — not interpreted — at
                        the engine's and trainer's shapes, against the
                        repo's plain/lax path
  c. train    (job)     in-process control plane + local backend + a real
                        runner; `apply` a task running the unmodified
                        examples/fine-tuning/jax/train.py; params exported
  d. serve    (service) `apply` a service running
                        examples/deployment/native/server.py on THAT
                        checkpoint; OpenAI chat completions through
                        /proxy/models/main/chat/completions
  with >= 4 devices, also:
  e. sharded train      all 16 layers under fsdp=4, then model-parallel 2,
                        then seq-parallel 2 (ring attention): first-step
                        losses agree across layouts
  f. sharded serve      --mesh-model 4 on phase c's checkpoint; temp-0
                        tokens against the one-chip server's

A chip belongs to one process at a time, so THIS process never imports JAX
(checked at exit): every phase is a child or an orchestrated job, one holder
of the chip at a time, each with JAX_PLATFORMS naming the chip's platform —
a missing chip is JAX's own hard error, never a slide to the CPU. All of
them share one persistent compile cache (workloads/compile_cache.py), so a
second run retrieves instead of compiling.

    python chip_smoke.py              # on a machine with a chip; anywhere
                                      # else it fails
    python chip_smoke.py --rehearsal  # the same control flow at `tiny` on
                                      # JAX_PLATFORMS=cpu, kernels
                                      # interpreted; labelled a rehearsal

Exit code 0 only if every phase passed; then the last two lines of stdout
are `SUMMARY {..., "claim": null}` (what each phase saw) and, last, exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
with the device as JAX reported it. A failure prints neither. It measures
nothing: no claim, no metric.
"""

import argparse
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / ".chip_smoke"  # git-ignored: checkpoints, nothing else
TRAIN_SCRIPT = REPO / "examples" / "fine-tuning" / "jax" / "train.py"
SERVER_SCRIPT = REPO / "examples" / "deployment" / "native" / "server.py"

# The whole script, compilation included, must end inside 1200 s; every
# wait below is also capped by what is left of this.
BUDGET_S = 1140.0
CHILD_TIMEOUT_S = 420.0   # device report / kernel phase
TRAIN_TIMEOUT_S = 600.0   # smol-1b train-step compile + steps + export
READY_TIMEOUT_S = 600.0   # service RUNNING + weights + warmup compiles
REQUEST_TIMEOUT_S = 180.0

# Written tolerances. Kernels: max |kernel - reference| over max |reference|,
# bf16 storage with f32 accumulation on both sides (the references round
# probabilities to bf16; the kernels keep them f32 or round them per block).
KERNEL_REL_TOL = 3e-2
# First-step loss (~ln 32768 = 10.4) of the same seed and batch under
# different layouts: only bf16 reduction order differs.
LOSS_LAYOUT_TOL = 5e-2

MODEL_NAME = "chip-smoke"


class PhaseFailed(Exception):
    pass


def first_divergence(a: str, b: str):
    """Index of the first position where two outputs differ (the shorter
    one's length when it is a prefix of the other); None when equal."""
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None if len(a) == len(b) else min(len(a), len(b))


def sizes(rehearsal: bool) -> dict:
    if rehearsal:
        # Control flow only: the smallest engine that still has several
        # prefill buckets and fewer slots than the burst has requests.
        return dict(
            preset="tiny", layers=2, batch=4, seq=64, steps=4,
            sharded_layers=2, sharded_batch=4, sharded_seq=64,
            max_new_tokens=8, mesh_model=2, platform="cpu",
            serve_flags="--slots 2 --prefill-chunk-tokens 32",
        )
    # The one-chip shape: 8 of smol-1b's 16 layers leave room for
    # the 12 B/param train state on a 16 GB chip; B=6, S=2048. Four chips
    # take all 16 layers; B=4 there because the seq-parallel layout's ring
    # backward (jnp recompute of each step's logits) needs 16.2 GiB of a
    # chip's 15.75 at B=8 (compiler's memory analysis) and 10.6 at B=4.
    # The server runs with its defaults: 8 slots, 128-token chunks.
    return dict(
        preset="smol-1b", layers=8, batch=6, seq=2048, steps=6,
        sharded_layers=16, sharded_batch=4, sharded_seq=2048,
        max_new_tokens=16, mesh_model=4, platform="tpu",
        serve_flags="",
    )


# --------------------------------------------------------------- children
# Everything below this line up to "parent" runs only in a child process
# (`--child ...`): the only code in this file that may import JAX.


def child_device() -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    stats = devices[0].memory_stats() or {}
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "bytes_limit": stats.get("bytes_limit"),
    }


def child_kernels(rehearsal: bool) -> dict:
    """Compile every Pallas kernel at the shapes the engine and trainer
    use and compare it with the repo's own plain/lax path. Returns
    {name: max_abs_err}; raises on a tolerance miss."""
    import functools
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, str(REPO))
    from dstack_tpu.workloads import compile_cache
    from dstack_tpu.workloads.attention import _block_attend, plain_attention
    from dstack_tpu.workloads.config import PRESETS
    from dstack_tpu.workloads.flash_attention import (
        flash_attention,
        flash_block_attend,
    )
    from dstack_tpu.workloads.paged_attention import (
        _ragged_attention_lax,
        _ragged_attention_pallas,
        ragged_attention,
    )
    from dstack_tpu.workloads.serving import ServingEngine

    compile_cache.enable()
    interpret = rehearsal  # the chip compiles; only the rehearsal interprets
    cfg = PRESETS["smol-1b"]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if rehearsal:
        h, kv = 4, 2
        train_b, train_s, ring_s = 1, 128, 128
        slots, chunk, block, max_len, max_draft = 2, 16, 8, 32, 1
    else:
        # The train phase's shape is B=6; two rows are enough to cross the
        # batch*head grid axis. Engine geometry = ServingEngine defaults.
        train_b, train_s, ring_s = 2, 2048, 1024
        slots, chunk, block, max_len, max_draft = 8, 128, 16, cfg.max_seq_len, 4
    dt = jnp.bfloat16
    errs, failed = {}, []

    def check(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            raise PhaseFailed(f"kernel {name}: bad output {got.shape}")
        err = float(np.max(np.abs(got - want)))
        rel = err / max(float(np.max(np.abs(want))), 1e-6)
        errs[name] = round(err, 5)
        print(f"  kernel {name}: max abs err {err:.4g} (rel {rel:.3g})",
              flush=True)
        if rel > KERNEL_REL_TOL:
            failed.append(f"{name} rel {rel:.3g} > {KERNEL_REL_TOL}")

    def rand(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(dt)

    keys = jax.random.split(jax.random.PRNGKey(0), 8)

    # -- flash forward + backward vs plain_attention ----------------------
    q = rand(keys[0], (train_b, train_s, h, hd))
    k = rand(keys[1], (train_b, train_s, kv, hd))
    v = rand(keys[2], (train_b, train_s, kv, hd))
    w = rand(keys[3], (train_b, train_s, h, hd))

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)
        )

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret
    )
    plain = lambda q, k, v: plain_attention(q, k, v, causal=True)
    check("flash_fwd", jax.jit(flash)(q, k, v), jax.jit(plain)(q, k, v))
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(plain), argnums=(0, 1, 2)))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        check(f"flash_bwd_{name}", g, r)

    # -- one ring step's block kernel vs attention._block_attend ----------
    qr = rand(keys[4], (train_b, ring_s, h, hd))
    kr = rand(keys[5], (train_b, ring_s, h, hd))  # kv already GQA-expanded
    vr = rand(keys[6], (train_b, ring_s, h, hd))
    tril = jnp.tril(jnp.ones((ring_s, ring_s), bool))
    for causal in (True, False):
        o, m, l = jax.jit(
            lambda q, k, v: flash_block_attend(
                q, k, v, causal=causal, interpret=interpret
            )
        )(qr, kr, vr)
        ro, rm, rl = jax.jit(
            lambda q, k, v: _block_attend(q, k, v, tril if causal else None)
        )(qr, kr, vr)
        norm = lambda o, l: o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        tag = "causal" if causal else "full"
        check(f"flash_block_{tag}_o", norm(o, l), norm(ro, rl))
        check(f"flash_block_{tag}_lse", m + jnp.log(l), rm + jnp.log(rl))

    # -- ragged paged kernel vs the lax path ------------------------------
    # The kernel addresses one layer inside the stacked pool: three
    # layers, the middle one attended.
    nb, mb = slots * (max_len // block), max_len // block
    n_pool_layers, layer = 3, jnp.int32(1)
    k_pool = rand(keys[7], (n_pool_layers, nb, block, kv, hd))
    v_pool = rand(
        jax.random.fold_in(keys[7], 1), (n_pool_layers, nb, block, kv, hd)
    )
    rng = np.random.default_rng(0)

    def tables_for(b):
        """Ragged per-row tables over disjoint blocks, pad sentinel nb."""
        t = np.full((b, mb), nb, np.int32)
        n_blk = rng.integers(1, mb + 1, b)
        ids = rng.permutation(nb)[: int(n_blk.sum())]
        at = 0
        for i in range(b):
            t[i, : n_blk[i]] = ids[at: at + n_blk[i]]
            at += n_blk[i]
        return t, n_blk

    def paged(name, b, s, *, causal_chunk):
        t, n_blk = tables_for(b)
        if causal_chunk:  # row i of a prefill chunk sees start + 1 + i
            start = rng.integers(0, n_blk * block - s + 1)
            vlen = (start[:, None] + 1 + np.arange(s)[None]).astype(np.int32)
        else:
            vlen = np.stack(
                [rng.integers(1, n_blk[i] * block + 1, s) for i in range(b)]
            ).astype(np.int32)
        qq = rand(jax.random.fold_in(keys[0], b * 1000 + s), (b, s, h, hd))
        args = (qq, k_pool, v_pool, layer, jnp.asarray(t), jnp.asarray(vlen))
        check(
            name,
            _ragged_attention_pallas(*args, interpret=interpret),
            jax.jit(_ragged_attention_lax)(*args),
        )

    paged(f"paged_decode_b{slots}", slots, 1, causal_chunk=False)
    # The engine's own bucketing rule, so this list cannot drift from it.
    pad = types.SimpleNamespace(prefill_chunk_tokens=chunk)
    buckets = sorted(
        {ServingEngine._pad_chunk(pad, n) for n in range(1, chunk + 1)}
    )
    for s in buckets:
        if mb * block >= s:
            paged(f"paged_prefill_s{s}", 1, s, causal_chunk=True)
    for draft in range(1, max_draft + 1):
        paged(f"paged_verify_k{draft}", slots, draft + 1, causal_chunk=False)

    # -- latent paged kernel vs the lax path -------------------------------
    # One row a position for all heads, the values its leading columns
    # (GLM-4.7-Flash: 512 + 64 padded to 640, 20 heads); the engine's two
    # shapes for it, decode and a prefill chunk.
    latent_pool = rand(keys[7], (n_pool_layers, nb, block, 1, 640))
    no_v = jnp.zeros((n_pool_layers, nb, block, 1, 0), latent_pool.dtype)

    def latent(name, b, s):
        t, n_blk = tables_for(b)
        start = rng.integers(0, n_blk * block - s + 1)
        vlen = (start[:, None] + 1 + np.arange(s)[None]).astype(np.int32)
        qq = rand(jax.random.fold_in(keys[1], b * 1000 + s), (b, s, 20, 640))
        args = (qq, latent_pool, no_v, layer, jnp.asarray(t), jnp.asarray(vlen))
        kw = {"latent_values": 512, "scale": 256 ** -0.5}
        check(
            name,
            ragged_attention(*args, impl="pallas", interpret=interpret, **kw),
            jax.jit(functools.partial(_ragged_attention_lax, **kw))(*args),
        )

    latent(f"latent_decode_b{slots}", slots, 1)
    latent(f"latent_prefill_s{buckets[-1]}", 1, buckets[-1])

    if failed:
        raise PhaseFailed("kernel tolerance: " + "; ".join(failed))
    return {
        "mode": "interpreted (rehearsal)" if interpret else "compiled",
        "max_abs_err": errs,
        "compile_cache": compile_cache.snapshot(),
    }


def child_main(which: str, rehearsal: bool) -> int:
    try:
        result = child_device() if which == "device" else child_kernels(rehearsal)
    except PhaseFailed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print("RESULT " + json.dumps(result), flush=True)
    return 0


# ----------------------------------------------------------------- parent


class Smoke:
    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.sz = sizes(rehearsal)
        self.t0 = time.monotonic()
        self.results = {}
        # Children and orchestrated jobs alike: the chip's platform by
        # name, this checkout on the path, one shared compile cache (an
        # exported JAX_COMPILATION_CACHE_DIR is inherited as it is;
        # without one every process resolves the same fixed default).
        self.child_env = {
            "JAX_PLATFORMS": self.sz["platform"],
            "PYTHONPATH": os.pathsep.join(
                p for p in (str(REPO), os.environ.get("PYTHONPATH")) if p
            ),
        }
        self.srv = None
        self.client = None
        self.live_runs = []

    # -- plumbing ---------------------------------------------------------

    def left(self, cap: float) -> float:
        rest = BUDGET_S - (time.monotonic() - self.t0)
        if rest <= 1:
            raise PhaseFailed("out of time: the 1200 s budget is spent")
        return min(cap, rest)

    def say(self, msg: str) -> None:
        print(f"[{time.monotonic() - self.t0:6.1f}s] {msg}", flush=True)

    def run_child(self, which: str) -> dict:
        cmd = [sys.executable, str(Path(__file__).resolve()), "--child", which]
        if self.rehearsal:
            cmd.append("--rehearsal")
        try:
            proc = subprocess.run(
                cmd, env={**os.environ, **self.child_env}, text=True,
                capture_output=True, timeout=self.left(CHILD_TIMEOUT_S),
            )
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{which} child timed out")
        lines = proc.stdout.splitlines()
        for line in lines:
            if not line.startswith("RESULT "):
                print(line, flush=True)
        if proc.returncode != 0 or not lines or not lines[-1].startswith("RESULT "):
            raise PhaseFailed(
                f"{which} child exited {proc.returncode}:\n"
                + proc.stderr[-3000:]
            )
        return json.loads(lines[-1][len("RESULT "):])

    def start_orchestrator(self) -> None:
        sys.path.insert(0, str(REPO))
        from latency_probe import ProbeServer

        from dstack_tpu.api import Client

        srv = ProbeServer(polling=False).start()
        self.client = Client(
            server_url=srv.url, token=srv.token, project_name="main"
        )
        self.srv = srv

    def stop_orchestrator(self) -> None:
        if self.srv is None:
            return
        from dstack_tpu.models.runs import RunStatus

        for run in self.live_runs:  # a service still up after a failure
            try:
                run.stop()
                run.wait([RunStatus.TERMINATED, RunStatus.DONE,
                          RunStatus.FAILED], timeout=60, poll=0.5)
            except Exception as e:  # teardown must reach srv.stop()
                print(f"stopping {run.name}: {e!r}", file=sys.stderr)
        self.client.api.close()
        self.srv.stop()

    def job_config(self, kind: str, cmd: str, **extra) -> dict:
        return {
            "type": kind,
            # exec: the runner's stop signal reaches python, not a shell.
            "commands": ["exec " + cmd],
            "env": dict(self.child_env),
            "resources": {"cpu": "1..", "memory": "0.1.."},
            **extra,
        }

    def log_of(self, run) -> str:
        text = b"".join(run.logs()).decode(errors="replace")
        # XLA:CPU's cache loader logs a screenful of machine-feature
        # flags per retrieved program; keep it out of failure tails.
        return "\n".join(
            l for l in text.splitlines() if "cpu_aot_loader" not in l
        )

    # -- phases -----------------------------------------------------------

    def phase_device(self) -> dict:
        dev = self.run_child("device")
        self.say(f"device: {json.dumps(dev)}")
        if dev["platform"] != self.sz["platform"]:
            raise PhaseFailed(
                f"platform is {dev['platform']!r}, wanted"
                f" {self.sz['platform']!r}"
            )
        return dev

    def train_job(self, name: str, *, layers: int, batch: int, seq: int,
                  steps: int, flags: str = "", ckpt: str = "") -> dict:
        from dstack_tpu.models.runs import RunStatus

        cmd = (
            f"{sys.executable} {TRAIN_SCRIPT} --preset {self.sz['preset']}"
            f" --layers {layers} --steps {steps} --batch-size {batch}"
            f" --seq-len {seq} {flags}"
        )
        if ckpt:
            cmd += f" --checkpoint-dir {ckpt}"
        plan = self.client.runs.get_plan(
            self.job_config("task", cmd), run_name=name
        )
        run = self.client.runs.exec_plan(plan)
        t0 = time.monotonic()
        try:
            run.wait(
                [RunStatus.DONE, RunStatus.FAILED, RunStatus.TERMINATED],
                timeout=self.left(TRAIN_TIMEOUT_S), poll=0.5,
            )
        except TimeoutError as e:
            run.stop(abort=True)
            raise PhaseFailed(f"{name}: {e}\n{self.log_of(run)[-3000:]}")
        log = self.log_of(run)
        for line in log.splitlines():
            if re.match(r"(process \d|model |attention path|device memory"
                        r"|step \d|batch size)", line):
                print("  " + line, flush=True)
        if run.status != RunStatus.DONE or "training complete" not in log:
            raise PhaseFailed(f"{name}: {run.status.value}\n{log[-3000:]}")
        losses = [
            (int(m.group(1)), float(m.group(2)))
            for m in re.finditer(r"step (\d+): loss ([0-9.naninf]+)", log)
        ]
        if not losses or any(l != l or abs(l) == float("inf") for _, l in losses):
            raise PhaseFailed(f"{name}: losses not finite: {losses}")
        device_line = next(
            (l for l in log.splitlines() if "local /" in l and "platform" in l),
            "",
        )
        if f"platform {self.sz['platform']}" not in device_line:
            raise PhaseFailed(f"{name}: job log's device line: {device_line!r}")
        path = re.search(r"attention path: (\S+)", log)
        return {
            "status": run.status.value,
            "wall_s": round(time.monotonic() - t0, 1),
            "shape": next((l for l in log.splitlines()
                           if l.startswith("model ")), None),
            "first_loss": losses[0][1],
            "last_loss": losses[-1][1],
            "attention_path": path.group(1) if path else None,
            "device_line": device_line,
            "memory": [l for l in log.splitlines()
                       if l.startswith("device memory")],
        }

    def phase_train(self, ckpt: str) -> dict:
        sz = self.sz
        out = self.train_job(
            "smoke-train", layers=sz["layers"], batch=sz["batch"],
            seq=sz["seq"], steps=sz["steps"], ckpt=ckpt,
        )
        if not out["last_loss"] < out["first_loss"]:
            raise PhaseFailed(f"loss did not fall: {out}")
        if not (Path(ckpt) / "export").exists():
            raise PhaseFailed(f"no params export under {ckpt}")
        want = "plain" if self.rehearsal else "flash"
        if out["attention_path"] != want:
            raise PhaseFailed(
                f"train step traced attention path"
                f" {out['attention_path']!r}, wanted {want!r}"
            )
        return out

    def phase_sharded_train(self) -> dict:
        sz = self.sz
        layouts = (
            ("fsdp4", "", "plain" if self.rehearsal else "flash"),
            ("model2", "--model-parallel 2",
             "plain" if self.rehearsal else "flash"),
            ("seq2", "--seq-parallel 2",
             "ring_jnp" if self.rehearsal else "ring_flash"),
        )
        out = {}
        for name, flags, want_path in layouts:
            r = self.train_job(
                f"smoke-train-{name}", layers=sz["sharded_layers"],
                batch=sz["sharded_batch"], seq=sz["sharded_seq"], steps=2,
                flags=flags,
            )
            if r["attention_path"] != want_path:
                raise PhaseFailed(
                    f"{name}: traced attention path {r['attention_path']!r},"
                    f" wanted {want_path!r}"
                )
            out[name] = r
        first = {n: r["first_loss"] for n, r in out.items()}
        spread = max(first.values()) - min(first.values())
        self.say(f"first-step loss by layout: {first} (spread {spread:.4f})")
        if spread > LOSS_LAYOUT_TOL:
            raise PhaseFailed(
                f"first-step losses differ across layouts by {spread:.4f}"
                f" > {LOSS_LAYOUT_TOL}: {first}"
            )
        out["first_loss_spread"] = round(spread, 5)
        return out

    def http(self, url, body=None, timeout=60.0, stream=False):
        """(status, parsed JSON | list of SSE data strings)."""
        req = urllib.request.Request(
            url,
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Authorization": f"Bearer {self.srv.token}",
                     "Content-Type": "application/json"},
            method="POST" if body is not None else "GET",
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                if not stream:
                    return resp.status, json.loads(resp.read())
                events = [
                    line[len(b"data: "):].decode().strip()
                    for line in resp if line.startswith(b"data: ")
                ]
                return resp.status, events
        except urllib.error.HTTPError as e:
            return e.code, {"error": e.read().decode(errors="replace")[-500:]}

    def phase_serve(self, name: str, ckpt: str, *, mesh_model: int = 1) -> dict:
        from dstack_tpu.models.runs import RunStatus

        sz = self.sz
        with socket.socket() as s:
            # Kernel-assigned port: a fixed pick could collide with a
            # leftover process and silently proxy to stale code.
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        cmd = (
            f"{sys.executable} {SERVER_SCRIPT} --preset {sz['preset']}"
            f" --layers {sz['layers']} --port {port}"
            f" --model-name {MODEL_NAME}"
            f" --max-new-tokens {sz['max_new_tokens']}"
            f" --checkpoint-dir {ckpt} {sz['serve_flags']}"
        )
        if mesh_model > 1:
            cmd += f" --mesh-model {mesh_model}"
        config = self.job_config(
            "service", cmd, name=name, port=port, model=MODEL_NAME, auth=False
        )
        run = self.client.runs.exec_plan(
            self.client.runs.get_plan(config, run_name=name)
        )
        self.live_runs.append(run)
        replica = f"http://127.0.0.1:{port}"
        chat = f"{self.srv.url}/proxy/models/main/chat/completions"

        def fail(msg):
            return PhaseFailed(f"{name}: {msg}\n{self.log_of(run)[-3000:]}")

        # RUNNING, then the replica's own warmup-gated /readyz, then the
        # proxy's model listing.
        t0 = time.monotonic()
        deadline = t0 + self.left(READY_TIMEOUT_S)
        ready = None
        while ready is None:
            if time.monotonic() > deadline:
                raise fail("never became ready")
            if run.refresh().status in (RunStatus.FAILED, RunStatus.TERMINATED,
                                        RunStatus.DONE):
                raise fail(f"service ended: {run.status.value}")
            try:
                code, body = self.http(f"{replica}/readyz", timeout=5)
                if code == 200:
                    ready = body
            except (urllib.error.URLError, OSError):
                pass
            if ready is None:
                time.sleep(0.5)
        while True:
            code, models = self.http(f"{self.srv.url}/proxy/models/main/models")
            if code == 200 and any(
                m["id"] == MODEL_NAME for m in models.get("data", [])
            ):
                break
            if time.monotonic() > deadline:
                raise fail(f"model never listed by the proxy: {models}")
            time.sleep(0.5)
        ready_s = time.monotonic() - t0
        # /readyz answers 200 only once warmup has finished; what it and
        # /metrics report is the engine's own state, not a log scrape.
        _, before = self.http(f"{replica}/metrics")
        if ready.get("weights_via") in (None, "init"):
            raise fail(f"weights did not come from the checkpoint: {ready}")
        if not before.get("warmup_done") or not before.get("warmup_programs"):
            raise fail("/readyz is 200 but /metrics shows no finished warmup")

        # Requests through the proxy, all after /readyz.
        n = sz["max_new_tokens"]

        def body(text, **kw):
            return {"model": MODEL_NAME, "max_tokens": n, "temperature": 0,
                    "messages": [{"role": "user", "content": text}], **kw}

        def completion(text):
            code, resp = self.http(chat, body(text),
                                   timeout=self.left(REQUEST_TIMEOUT_S))
            got = (resp.get("usage") or {}).get("completion_tokens")
            if code != 200 or got != n:
                raise fail(f"completion: HTTP {code}, {got}/{n} tokens: {resp}")
            return resp["choices"][0]["message"]["content"]

        prompt = "hello tpu"
        first = completion(prompt)
        if completion(prompt) != first:
            raise fail("same prompt at temperature 0 gave different tokens")
        code, events = self.http(chat, body(prompt, stream=True), stream=True,
                                 timeout=self.left(REQUEST_TIMEOUT_S))
        if code != 200 or not events or events[-1] != "[DONE]":
            raise fail(f"stream: HTTP {code}, events {events[-3:]}")
        streamed = "".join(
            json.loads(e)["choices"][0]["delta"].get("content", "")
            for e in events[:-1]
        )
        if streamed != first:
            raise fail(f"streamed text {streamed!r} != completion {first!r}")
        # A burst of four whose prompts land in the server's 32-, 64-,
        # 128- and 256-token buckets, so their prefills cross the engine's
        # pow-2 chunk buckets up to (and past) the 128-token chunk budget;
        # the repeated prompt above already took the small remainders a
        # prefix-cache hit leaves.
        burst = [None] * 4

        def one(i):
            try:
                burst[i] = completion(chr(ord("a") + i) * (8, 60, 150, 300)[i])
            except Exception as e:
                burst[i] = e

        threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in burst:
            if isinstance(r, Exception):
                raise r

        _, after = self.http(f"{replica}/metrics")
        want_path = (
            "lax_ragged" if self.rehearsal or mesh_model > 1 else "pallas"
        )
        other = "pallas" if want_path == "lax_ragged" else "lax_ragged"
        if (after.get("attn_path") != want_path
                or not after.get(f"attn_dispatch_{want_path}_total")
                or after.get(f"attn_dispatch_{other}_total")):
            raise fail(
                f"attention path: wanted {want_path}, /metrics says"
                f" {after.get('attn_path')} (pallas"
                f" {after.get('attn_dispatch_pallas_total')}, lax_ragged"
                f" {after.get('attn_dispatch_lax_ragged_total')})"
            )
        if after["compiles_total"] != before["compiles_total"]:
            raise fail(
                f"compiles after ready: {before['compiles_total']} ->"
                f" {after['compiles_total']}"
            )
        run.stop()
        run.wait([RunStatus.TERMINATED, RunStatus.DONE, RunStatus.FAILED],
                 timeout=60, poll=0.5)
        self.live_runs.remove(run)
        return {
            "ready_s": round(ready_s, 1),
            "weights_via": ready["weights_via"],
            "attn_path": after["attn_path"],
            "attn_dispatch_total": after[f"attn_dispatch_{want_path}_total"],
            "requests_ok": 3 + len(burst),
            "compiles_after_ready": 0,
            # Built vs retrieved at /readyz, from compile_cache.snapshot()
            # (process-wide: warmup's programs plus the weight load's).
            "warmup": {
                "programs": before["warmup_programs"],
                "seconds": before["warmup_seconds"],
                "builds": before["compiles_total"],
                "retrieved": before["compile_cache_hits_total"],
                "compiled": before["compile_cache_misses_total"],
                "compile_seconds": before["compile_seconds_total"],
                "cache_dir": before["compile_cache_dir"],
            },
            "temp0_text": first,
        }

    # -- the run ----------------------------------------------------------

    def run(self) -> dict:
        r = self.results
        r["device"] = dev = self.phase_device()
        self.say("phase b: kernels")
        r["kernels"] = self.run_child("kernels")
        if WORK.exists():
            shutil.rmtree(WORK)
        WORK.mkdir(parents=True)
        ckpt = str(WORK / "ckpt")
        self.start_orchestrator()
        self.say("phase c: train through the orchestrator")
        r["train"] = self.phase_train(ckpt)
        self.say("phase d: serve through the orchestrator")
        r["serve"] = self.phase_serve("smoke-serve", ckpt)
        if dev["count"] >= 4:
            self.say("phase e: sharded train (fsdp=4, model=2, seq=2)")
            r["sharded_train"] = self.phase_sharded_train()
            self.say(f"phase f: sharded serve (--mesh-model"
                     f" {self.sz['mesh_model']})")
            r["sharded_serve"] = sh = self.phase_serve(
                "smoke-serve-tp", ckpt, mesh_model=self.sz["mesh_model"]
            )
            diverge = first_divergence(
                r["serve"]["temp0_text"], sh["temp0_text"]
            )
            # Reported, not failed: the two engines run different
            # attention paths (pallas vs lax_ragged) on flat post-init
            # logits. ROADMAP S6 tracks the contract.
            sh["temp0_vs_one_chip"] = (
                "equal" if diverge is None
                else f"first divergence at output char {diverge}"
            )
            self.say(f"one chip vs sharded: {sh['temp0_vs_one_chip']}")
        return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="same control flow at `tiny` on JAX_PLATFORMS=cpu")
    ap.add_argument("--child", choices=("device", "kernels"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_main(args.child, args.rehearsal)

    smoke = Smoke(args.rehearsal)
    if args.rehearsal:
        print("REHEARSAL on cpu at `tiny`: control flow only, nothing here"
              " is a device result", flush=True)
    failure = None
    try:
        dev = smoke.run()
    except PhaseFailed as e:
        failure = str(e)
    finally:
        smoke.stop_orchestrator()
    if failure is None and "jax" in sys.modules:
        failure = "the parent process imported jax"
    if failure is not None:
        print(f"chip_smoke FAILED after {time.monotonic() - smoke.t0:.0f}s"
              f" (phases passed: {list(smoke.results)}): {failure}",
              file=sys.stderr, flush=True)
        return 1
    device = {k: dev[k] for k in ("platform", "kind", "count")}
    summary = {
        "ok": True,
        "device": device,
        "rehearsal": args.rehearsal,
        "parent_imported_jax": False,  # checked above
        "versions": {k: dev[k] for k in ("jax", "jaxlib", "libtpu")},
        "bytes_limit": dev["bytes_limit"],
        "wall_s": round(time.monotonic() - smoke.t0, 1),
        "phases": {k: v for k, v in smoke.results.items() if k != "device"},
        "claim": None,
    }
    print("SUMMARY " + json.dumps(summary), flush=True)
    # The last line is the contract's object and holds nothing else.
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
