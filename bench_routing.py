"""Prefix-affinity fleet routing benchmark: cache-aware replica selection
vs plain least-outstanding, measured on REAL serving engines.

A CPU COUNT-CHECK, NOT A DEVICE MEASUREMENT: every replica and the
data-plane worker are children pinned to JAX_PLATFORMS=cpu, so it
refuses to start unless JAX_PLATFORMS=cpu is exported
(utils/devices.py). What carries over to a chip are its counts (prefill
tokens computed, forced adapter loads); its TTFTs are XLA's CPU backend.
It needs per-chip process placement — one replica per chip — before it
can become a benchmark cell (ROADMAP D7, R4c).

An N-replica fleet of native model servers (tiny preset, real prefix
caches) sits behind one real `python -m dstack_tpu.dataplane` worker.
Each arm runs twice — affinity routing on (the shipped default) and off
(`DSTACK_TPU_ROUTING_AFFINITY=0`, the pre-PR-18 least-outstanding
policy) — and reads cluster prefill compute straight off the engines'
`prefill_tokens_computed_total` counters, so the headline number is
device work actually avoided, not a proxy-side estimate.

Arms:

1. shared_prefix — G prompt groups sharing a long fixed prefix with
   fixed-width unique tails. Least-outstanding smears every group over
   all replicas (each replica re-prefills each prefix); affinity pins a
   group to the replica that already holds its blocks.
2. multi_session — S chat sessions, each with a fixed persona block and
   fixed-width per-turn questions. Same shape as production multi-turn
   traffic: per-session reuse only pays on the replica that served the
   session before.
3. adapter_skew — 2 replicas each preloading a different LoRA adapter,
   traffic split across `base:adapter` ids. Affinity routes to the
   adapter-resident replica; the baseline misroutes ~half the traffic,
   and every misroute the client must heal with a forced
   `POST /v1/adapters` is counted.
4. cache_cold — unique prompts, zero overlap. Affinity scores all-zero
   and must fall through to the identical least-outstanding path: the
   guardrail arm (TTFT p95 within noise of baseline).

Emits ONE JSON document (BENCH_routing_r18.json via --out) with per-arm
prefill-compute totals, TTFT quantiles, forced-load counts, and a
summary block of speedup ratios + pass/fail booleans (exit nonzero on
regression).

Run: JAX_PLATFORMS=cpu python bench_routing.py [--out BENCH_routing_r18.json]
"""

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

import httpx

from dstack_tpu.utils.devices import require_cpu_request

REPO = Path(__file__).resolve().parent
MODEL = "tiny-rt"

# The tiny preset's byte tokenizer keeps the NEWEST `prompt_limit` (248)
# bytes then buckets DOWN to a power of two — so every prompt below 256
# bytes lands in the 128-token bucket, and reuse only exists between
# prompts whose newest-128-byte windows align. All bench prompts are
# therefore exactly PROMPT_LEN bytes: the retained window starts at the
# same offset for every request, shared cores line up block-for-block,
# and the unique 4-byte tail rides in the final (never-hashed) partial
# block so same-group requests share ALL full blocks.
PROMPT_LEN = 300
TAIL = 4


def _prompt(core: str, tail: str) -> str:
    """PROMPT_LEN-byte prompt: `core` repeated, `tail` (TAIL bytes) last.
    Cores carry their group id in every 16-byte window so distinct
    groups share zero chain blocks."""
    body = (core * (PROMPT_LEN // len(core) + 2))[: PROMPT_LEN - TAIL]
    return body + f"{tail:>{TAIL}}"[:TAIL]


# ------------------------------------------------------------ fleet setup


async def _wait_http(url: str, timeout: float = 90.0) -> None:
    deadline = time.perf_counter() + timeout
    async with httpx.AsyncClient(timeout=5.0) as hc:
        while True:
            try:
                r = await hc.get(url)
                if r.status_code == 200:
                    return
            except httpx.HTTPError:
                pass
            if time.perf_counter() > deadline:
                raise RuntimeError(f"{url} never became ready")
            await asyncio.sleep(0.25)


async def _spawn_engine(port: int, adapters=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    cmd = [
        sys.executable, str(REPO / "examples/deployment/native/server.py"),
        "--preset", "tiny", "--port", str(port), "--model-name", MODEL,
        "--max-new-tokens", "4", "--slots", "8",
        # 16-token prefill chunks: a cold 128-token prompt costs eight
        # chunk steps where a prefix hit's 16-token remainder costs one,
        # so avoided prefill compute shows up as avoided engine STEPS —
        # i.e. as TTFT — even on a host where a single tiny-model matmul
        # is dispatch-overhead-bound.
        "--prefill-chunk-tokens", "16",
    ]
    for name in adapters:
        cmd += ["--adapter", f"{name}=random"]
    if adapters:
        cmd += ["--lora-max-adapters", "4"]
    proc = await asyncio.create_subprocess_exec(
        *cmd, stdout=asyncio.subprocess.DEVNULL,
        stderr=asyncio.subprocess.DEVNULL, env=env,
    )
    return proc


async def _seed_fleet(db_path: str, run_name: str, ports, adapters=()):
    """Migrate a DB and seed one RUNNING service with one replica per
    engine port, model entry included (adapters listed so `base:adapter`
    composite ids resolve through the model route)."""
    from dstack_tpu.models.runs import JobProvisioningData, JobSpec, RunSpec
    from dstack_tpu.server.app import create_app
    from dstack_tpu.server.security import generate_id
    from dstack_tpu.utils.common import utcnow_iso

    app = create_app(
        db_path=db_path, admin_token="bench-admin",
        run_background_tasks=False,
    )
    await app.startup()
    ctx = app.state["ctx"]
    project = await ctx.db.fetchone("SELECT * FROM projects WHERE name='main'")
    user = await ctx.db.fetchone("SELECT * FROM users LIMIT 1")
    run_id, now = generate_id(), utcnow_iso()
    spec = RunSpec.model_validate(
        {"run_name": run_name, "repo_id": "local",
         "configuration": {"type": "service", "name": run_name,
                           "port": ports[0], "commands": ["serve"]}}
    )
    model = {"name": MODEL, "format": "openai", "prefix": "/v1"}
    if adapters:
        model["adapters"] = list(adapters)
    await ctx.db.execute(
        "INSERT INTO runs (id, project_id, user_id, run_name, submitted_at,"
        " last_processed_at, status, run_spec, service_spec)"
        " VALUES (?, ?, ?, ?, ?, ?, 'running', ?, ?)",
        (run_id, project["id"], user["id"], run_name, now, now,
         spec.model_dump_json(),
         json.dumps({"url": f"/proxy/services/main/{run_name}/",
                     "model": model})),
    )
    for replica_num, port in enumerate(ports):
        job_spec = JobSpec.model_validate(
            {"job_name": f"{run_name}-0-{replica_num}", "commands": ["serve"],
             "requirements": {"resources": {}},
             "app_specs": [{"app_name": "app", "port": port}]}
        )
        jpd = JobProvisioningData.model_validate(
            {"backend": "local",
             "instance_type": {"name": "local",
                               "resources": {"cpus": 1, "memory_mib": 1024}},
             "instance_id": f"i-{replica_num}", "hostname": "127.0.0.1",
             "internal_ip": "127.0.0.1", "region": "local", "price": 0.0,
             "username": "root", "dockerized": False}
        )
        await ctx.db.execute(
            "INSERT INTO jobs (id, project_id, run_id, run_name, job_num,"
            " replica_num, submitted_at, last_processed_at, status, job_spec,"
            " job_provisioning_data)"
            " VALUES (?, ?, ?, ?, 0, ?, ?, ?, 'running', ?, ?)",
            (generate_id(), project["id"], run_id, run_name, replica_num,
             now, now, job_spec.model_dump_json(), jpd.model_dump_json()),
        )
    await app.shutdown()


async def _spawn_worker(db_path: str, affinity: bool):
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        DSTACK_TPU_ROUTING_AFFINITY="1" if affinity else "0",
        DSTACK_TPU_ROUTING_SKETCH_MAX_AGE="30",
    )
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "dstack_tpu.dataplane",
        "--db", db_path, "--port", "0",
        "--poll-interval", os.environ.get("BENCH_ROUTING_POLL", "1.0"),
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.DEVNULL, env=env,
    )
    line = await asyncio.wait_for(proc.stdout.readline(), 30)
    port = int(line.decode().rsplit(":", 1)[1])
    await _wait_http(f"http://127.0.0.1:{port}/readyz", 30)
    return proc, port


async def _kill(procs):
    for p in procs:
        if p.returncode is None:
            p.kill()
    for p in procs:
        try:
            await asyncio.wait_for(p.wait(), 10)
        except asyncio.TimeoutError:
            pass


# ------------------------------------------------------------- measurement


async def _engine_counter(hc, port: int, key: str) -> float:
    r = await hc.get(f"http://127.0.0.1:{port}/metrics")
    return float(r.json()[key])


async def _chat_ttft(hc, worker_port: int, body) -> tuple:
    """(status, seconds to first SSE byte) through the worker."""
    t0 = time.perf_counter()
    async with hc.stream(
        "POST", f"http://127.0.0.1:{worker_port}/proxy/models/main/chat/completions",
        json={**body, "stream": True},
    ) as resp:
        if resp.status_code != 200:
            await resp.aread()
            return resp.status_code, None
        async for _ in resp.aiter_raw():
            return 200, time.perf_counter() - t0
    return 200, time.perf_counter() - t0


def _user(content: str):
    return [{"role": "user", "content": content}]


def _arm_requests(arm: str, tag: str):
    """Deterministic request list per arm; `tag` varies content across
    affinity/baseline passes so the second pass never free-rides on KV
    the first pass left behind on shared engines."""
    reqs = []
    if arm == "shared_prefix":
        # 9 prompt families x 8 requests: shared core, unique tail. The
        # family count is COPRIME with the replica count so the
        # baseline's round-robin rotation cannot resonate into
        # accidentally pinning a family to one replica.
        for i in range(72):
            g = i % 9
            core = f"{tag[0]}g{g:02d} docs "
            reqs.append({"model": MODEL,
                         "messages": _user(_prompt(core, f"q{i}"))})
    elif arm == "multi_session":
        # 9 chat sessions x 8 turns, interleaved: fixed persona block
        # per session, the turn number as the only varying content.
        for turn in range(8):
            for s in range(9):
                core = f"{tag[0]}s{s:02d} chat "
                reqs.append({"model": MODEL,
                             "messages": _user(_prompt(core, f"t{turn}"))})
    elif arm == "adapter_skew":
        # Fully unique prompts — this arm isolates adapter residency.
        for i in range(48):
            name = ("fr", "de")[i % 2]
            core = f"{tag[0]}{name}{i:03d} "
            reqs.append({"model": f"{MODEL}:{name}",
                         "messages": _user(_prompt(core, f"a{i}"))})
    elif arm == "cache_cold":
        # Unique request id in every 16-byte window: zero shared blocks.
        for i in range(96):
            core = f"{tag[0]}x{i:03d} "
            reqs.append({"model": MODEL,
                         "messages": _user(_prompt(core, f"c{i}"))})
    return reqs


async def _force_adapter_load(hc, engine_ports, name: str) -> int:
    """The heal a misrouted `base:adapter` request forces on the
    baseline: load the adapter everywhere it is missing. Returns the
    number of loads performed."""
    forced = 0
    for port in engine_ports:
        r = await hc.get(f"http://127.0.0.1:{port}/v1/affinity")
        if name not in r.json().get("adapters", []):
            r = await hc.post(f"http://127.0.0.1:{port}/v1/adapters",
                              json={"name": name, "path": "random"})
            if r.status_code == 200:
                forced += 1
    return forced


async def _run_arm_mode(arm: str, affinity: bool, engine_ports, tmpdir,
                        rep: int = 0) -> dict:
    tag = "aff" if affinity else "base"
    adapters = ("fr", "de") if arm == "adapter_skew" else ()
    db_path = str(Path(tmpdir) / f"{arm}-{tag}{rep}.db")
    await _seed_fleet(db_path, "rt-svc", engine_ports, adapters=adapters)
    worker, wport = await _spawn_worker(db_path, affinity)
    hc = httpx.AsyncClient(timeout=60.0)
    try:
        # Prime routes (and, with affinity on, let one gossip pass land)
        # with a throwaway prompt outside every measured prefix family.
        prime = {"model": MODEL,
                 "messages": _user(_prompt(f"{tag[0]}prime ", "p0"))}
        status, _ = await _chat_ttft(hc, wport, prime)
        assert status == 200, f"prime request failed: {status}"
        # Two poll cycles: the first gossip pass after the route exists
        # is what populates every replica's sketch.
        await asyncio.sleep(2.5 if affinity else 1.0)

        # Unmeasured burn-in shaped like the measured traffic (unique
        # prompts on the no-reuse arms so block-pool eviction churn is
        # warm too, prompt families on the reuse arms). Whichever mode
        # runs first otherwise pays a system-warm-up tax (page cache,
        # scheduler) that the tight cold-arm gate would read as a
        # routing regression.
        burn_sem = asyncio.Semaphore(4)
        burn_family = arm in ("shared_prefix", "multi_session")

        async def burn_one(j):
            core = f"{tag[0]}b{j % 3} " if burn_family else f"{tag[0]}bu{j:03d} "
            async with burn_sem:
                await _chat_ttft(hc, wport, {
                    "model": MODEL,
                    "messages": _user(_prompt(core, f"b{j}")),
                })

        await asyncio.gather(*[burn_one(j) for j in range(24)])
        await asyncio.sleep(0.5)

        before = sum([
            await _engine_counter(hc, p, "prefill_tokens_computed_total")
            for p in engine_ports
        ])
        reqs = _arm_requests(arm, tag)
        ttfts, failures, forced_loads = [], 0, 0

        async def run_wave(wave, conc, stagger):
            sem = asyncio.Semaphore(conc)

            async def one(body, idx):
                nonlocal failures, forced_loads
                await asyncio.sleep(idx * stagger)
                async with sem:
                    status, ttft = await _chat_ttft(hc, wport, body)
                    if status != 200 and ":" in body["model"]:
                        # Misroute to a non-resident replica: heal +
                        # retry, exactly the operator dance affinity
                        # routing exists to avoid.
                        forced_loads += await _force_adapter_load(
                            hc, engine_ports, body["model"].split(":", 1)[1]
                        )
                        status, ttft = await _chat_ttft(hc, wport, body)
                    if status == 200 and ttft is not None:
                        ttfts.append(ttft)
                    else:
                        failures += 1

            await asyncio.gather(*[one(b, i) for i, b in enumerate(wave)])

        if arm in ("shared_prefix", "multi_session"):
            # Plant/harvest: the first request of each prompt family
            # lands first (all cold in BOTH modes — identical work),
            # then one gossip interval passes so every planted family
            # is in the sketches, then the remaining requests run at
            # saturating concurrency. Sketch staleness is bounded by
            # one epoch poll, so without the settle a family's 2nd
            # request would measure cold-start staleness instead of
            # steady-state routing; with it, the harvest wave is free
            # to saturate the fleet — which is where the baseline's
            # re-prefill bill turns into queueing and the TTFT gap
            # affinity exists to close actually shows up.
            await run_wave(reqs[:9], 3, 0.08)
            await asyncio.sleep(1.7)
            await run_wave(reqs[9:], 6, 0.012)
        else:
            # Light fixed-rate load on the control arms: the cold arm's
            # tight 5% gate wants a service-time-bound p95, not a
            # queueing-noise-bound one.
            await run_wave(reqs, {"adapter_skew": 4}.get(arm, 2), 0.012)
        after = sum([
            await _engine_counter(hc, p, "prefill_tokens_computed_total")
            for p in engine_ports
        ])
        ttfts.sort()

        def pct(p):
            return ttfts[min(len(ttfts) - 1, int(p * len(ttfts)))] if ttfts else None

        return {
            "requests": len(reqs),
            "failures": failures,
            "forced_adapter_loads": forced_loads,
            "prefill_tokens_computed": after - before,
            "ttft_p50_ms": round(pct(0.50) * 1000, 2),
            "ttft_p95_ms": round(pct(0.95) * 1000, 2),
        }
    finally:
        await hc.aclose()
        await _kill([worker])


async def _warm_engine(hc, port: int, model: str) -> None:
    """Pay every XLA compile the measured window will need: the cold
    prompt's 16-token prefill chunks (+ decode) first, then the
    16-token hit-remainder path via the same prompt with a different
    tail (112 cached tokens, 16 computed)."""
    core = f"warm{port % 100:02d} "
    for tail in ("w1", "w2"):
        r = await hc.post(
            f"http://127.0.0.1:{port}/v1/chat/completions",
            json={"model": model, "max_tokens": 2,
                  "messages": _user(_prompt(core, tail))},
        )
        assert r.status_code == 200, (port, r.status_code, r.text)


async def _run_arm(arm: str, tmpdir) -> dict:
    # 5 replicas on the prefix-reuse arms: the baseline's spread (and so
    # its re-prefill bill) grows with fleet width, which is exactly the
    # 1/N fleet-hit-rate effect affinity routing removes. 2 replicas
    # isolate adapter residency; 3 keep the cold control arm light.
    n_engines = {"adapter_skew": 2, "cache_cold": 3}.get(arm, 5)
    base_port = {"shared_prefix": 19400, "multi_session": 19430,
                 "adapter_skew": 19460, "cache_cold": 19470}[arm]
    out = {}
    modes = (True, False)
    if os.environ.get("BENCH_ROUTING_BASELINE_FIRST"):
        modes = (False, True)
    # Single-run p95 on a small shared box carries order bias (whichever
    # mode runs first measures a colder system) and one-off scheduler
    # noise, so every TTFT-gated arm runs each mode TWICE on fresh
    # fleets in interleaved order (A B B A — neither mode systematically
    # goes first) and scores each mode by its better p95: a repeat-min
    # estimate of the steady-state tail, applied identically to both
    # modes. The adapter arm's gate is a deterministic forced-load
    # count, so one pass per mode suffices there.
    mode_seq = modes if arm == "adapter_skew" else modes + tuple(reversed(modes))
    reps = {}
    for run_i, affinity in enumerate(mode_seq):
        # Fresh engines per mode run: prefix caches and adapter pools
        # must not leak between passes.
        ports = [base_port + run_i * n_engines + i for i in range(n_engines)]
        per_engine_adapters = (
            [("fr",), ("de",)] if arm == "adapter_skew" else [()] * n_engines
        )
        engines = [
            await _spawn_engine(p, adapters=a)
            for p, a in zip(ports, per_engine_adapters)
        ]
        try:
            await asyncio.gather(*[
                _wait_http(f"http://127.0.0.1:{p}/v1/models") for p in ports
            ])
            async with httpx.AsyncClient(timeout=180.0) as hc:
                for i, p in enumerate(ports):
                    warm_model = (
                        f"{MODEL}:{per_engine_adapters[i][0]}"
                        if per_engine_adapters[i] else MODEL
                    )
                    await _warm_engine(hc, p, warm_model)
            mode = "affinity" if affinity else "baseline"
            res = await _run_arm_mode(arm, affinity, ports, tmpdir,
                                      rep=len(reps.get(mode, [])))
            reps.setdefault(mode, []).append(res)
            print(f"  {arm}/{mode}: {res}", flush=True)
        finally:
            await _kill(engines)
    for mode, runs in reps.items():
        best = min(runs, key=lambda r: r["ttft_p95_ms"])
        if len(runs) > 1:
            best = dict(best)
            best["reps_ttft_p95_ms"] = [r["ttft_p95_ms"] for r in runs]
        out[mode] = best
    return out


def _summary(results: dict) -> dict:
    def ratio(arm, key):
        b = results[arm]["baseline"][key]
        a = results[arm]["affinity"][key]
        return round(b / a, 2) if a else None

    s = {
        "shared_prefix_prefill_drop": ratio("shared_prefix",
                                            "prefill_tokens_computed"),
        "multi_session_prefill_drop": ratio("multi_session",
                                            "prefill_tokens_computed"),
        "shared_prefix_ttft_p95_speedup": ratio("shared_prefix", "ttft_p95_ms"),
        "multi_session_ttft_p95_speedup": ratio("multi_session", "ttft_p95_ms"),
        "adapter_forced_loads_affinity":
            results["adapter_skew"]["affinity"]["forced_adapter_loads"],
        "adapter_forced_loads_baseline":
            results["adapter_skew"]["baseline"]["forced_adapter_loads"],
        "cache_cold_ttft_p95_ratio": round(
            results["cache_cold"]["affinity"]["ttft_p95_ms"]
            / results["cache_cold"]["baseline"]["ttft_p95_ms"], 3),
    }
    s["prefill_drop_at_least_2x"] = (
        (s["shared_prefix_prefill_drop"] or 0) >= 2.0
        and (s["multi_session_prefill_drop"] or 0) >= 2.0
    )
    s["ttft_p95_better_on_affinity_arms"] = (
        (s["shared_prefix_ttft_p95_speedup"] or 0) > 1.0
        and (s["multi_session_ttft_p95_speedup"] or 0) > 1.0
    )
    s["zero_forced_adapter_loads_with_affinity"] = (
        s["adapter_forced_loads_affinity"] == 0
        and results["adapter_skew"]["affinity"]["failures"] == 0
    )
    s["cache_cold_within_5pct"] = s["cache_cold_ttft_p95_ratio"] <= 1.05
    return s


async def _run_all(args) -> dict:
    import tempfile

    results = {}
    arms = args.arms.split(",") if args.arms else [
        "shared_prefix", "multi_session", "adapter_skew", "cache_cold",
    ]
    with tempfile.TemporaryDirectory() as tmpdir:
        for arm in arms:
            print(f"arm: {arm}", flush=True)
            results[arm] = await _run_arm(arm, tmpdir)
    if not args.arms:
        results["summary"] = _summary(results)
    return results


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="BENCH_routing_r18.json")
    parser.add_argument("--arms", default="",
                        help="comma-separated arm subset (skips summary)")
    args = parser.parse_args()
    require_cpu_request("bench_routing.py")
    results = asyncio.get_event_loop().run_until_complete(_run_all(args))
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    if "summary" not in results:
        raise SystemExit(0)
    print(json.dumps(results["summary"], indent=2))
    ok = (results["summary"]["prefill_drop_at_least_2x"]
          and results["summary"]["ttft_p95_better_on_affinity_arms"]
          and results["summary"]["zero_forced_adapter_loads_with_affinity"]
          and results["summary"]["cache_cold_within_5pct"])
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
