"""Native model server over real HTTP: admission control + metrics.

Boots examples/deployment/native/server.py as an OS process (tiny preset,
CPU-pinned) and drives the OpenAI surface: a request on an idle engine
with max_pending=0 serves; a concurrent burst beyond slot capacity sheds
with 429 + Retry-After; /metrics reports the shed counter and queue
shape. This pins over the wire what tests/test_serving.py pins at the
engine API (VERDICT r4 #3 acceptance).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import Counter
from pathlib import Path

import pytest

from tests.conftest import free_port

REPO = Path(__file__).resolve().parents[1]
SERVER = REPO / "examples" / "deployment" / "native" / "server.py"


def _post(port, body, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _boot_server(tmp_path, *flags, warmup=False):
    """Start the example model server (CPU-pinned) and wait for /v1/models.
    Returns (proc, log_handle, port); raises with the log tail if the
    process dies or never binds.

    Boots `--no-warmup` by default: these tests target the HTTP surface,
    and even a cache-warm warmup pass pays several seconds of Python
    tracing per boot — across every boot in this file that would
    dominate the suite's budget. The readiness tests, whose subject IS
    the warmup gate, opt in with warmup=True."""
    port = free_port()
    if not warmup and "--no-warmup" not in flags:
        flags = (*flags, "--no-warmup")
    # CPU-pinned: these tests are about the HTTP surface. The suite's
    # JAX_COMPILATION_CACHE_DIR (tests/conftest.py) rides along in
    # os.environ: the server warms up before admitting traffic, and a
    # cold warmup would add ~30s of XLA compilation to EVERY boot here.
    env = {**os.environ, "PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"}
    log = open(tmp_path / "server.log", "ab")
    proc = subprocess.Popen(
        [sys.executable, str(SERVER), "--preset", "tiny", "--port", str(port),
         *flags],
        stdout=log, stderr=subprocess.STDOUT, env=env,
    )
    deadline = time.time() + 120
    while time.time() < deadline:
        if proc.poll() is not None:
            raise AssertionError(
                "server died: "
                + (tmp_path / "server.log").read_bytes().decode()[-2000:]
            )
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/models", timeout=2
            )
            return proc, log, port
        except (urllib.error.URLError, ConnectionError, OSError):
            time.sleep(0.5)
    raise AssertionError(
        "server never came up: "
        + (tmp_path / "server.log").read_bytes().decode()[-2000:]
    )


def test_native_server_sheds_with_retry_after(tmp_path):
    proc, log, port = _boot_server(
        tmp_path, "--max-new-tokens", "16", "--max-pending", "0"
    )
    try:
        body = {"messages": [{"role": "user", "content": "hello there"}]}
        # idle engine with max_pending=0 must SERVE (free slots count)
        resp = _post(port, body)
        assert resp.status == 200
        content = json.load(resp)["choices"][0]["message"]["content"]
        assert isinstance(content, str)

        # burst of 2x slots: part admitted, overflow shed with the hint
        statuses, retry_afters = [], []
        lock = threading.Lock()

        def fire():
            try:
                r = _post(port, body)
                json.load(r)
                with lock:
                    statuses.append(r.status)
            except urllib.error.HTTPError as e:
                with lock:
                    statuses.append(e.code)
                    if e.code == 429:
                        retry_afters.append(e.headers.get("Retry-After"))
            except (urllib.error.URLError, ConnectionError, OSError) as e:
                # Connection-level failure (backlog overflow, reset): a
                # silently-dead thread would skew every count below.
                with lock:
                    statuses.append(f"conn: {e}")

        threads = [threading.Thread(target=fire) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        counts = Counter(statuses)
        assert counts[200] >= 2, counts   # free slots admitted part of it
        assert counts[429] >= 1, counts   # and the overflow was shed
        assert set(counts) <= {200, 429}, counts  # no conn-level failures
        assert all(ra and int(ra) >= 1 for ra in retry_afters), retry_afters

        m = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ))
        assert m["rejected_total"] == counts[429]
        assert m["max_pending"] == 0 and m["slots"] == 8
        assert m["slot_turn_seconds_ewma"] > 0
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()


def test_native_server_honors_max_tokens(tmp_path):
    """The OpenAI `max_tokens` field bounds the generation per request,
    clamped to the server's --max-new-tokens cap."""
    proc, log, port = _boot_server(tmp_path, "--max-new-tokens", "32")
    try:
        def chat(extra):
            r = _post(port, {"messages": [{"role": "user", "content": "hi"}],
                             **extra})
            return json.load(r)["choices"][0]["message"]["content"]

        # The toy tokenizer is byte-level: generated bytes ~= tokens, so
        # a 3-token budget must come back far shorter than the 32 cap.
        short = chat({"max_tokens": 3})
        capped = chat({"max_tokens": 10_000})  # clamped to server cap
        default = chat({})
        assert len(short.encode()) <= 3 * 4  # <=3 tokens (utf-8 replacement slack)
        assert len(capped.encode()) <= 32 * 4
        assert len(default.encode()) > len(short.encode())
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()


def test_native_server_paged_kv_flags_and_prometheus(tmp_path):
    """--prefill-chunk-tokens / --kv-block-size ride through to the
    engine, /metrics stays JSON for existing consumers, and the same
    endpoint serves Prometheus text when asked via ?format=prometheus
    or an Accept header."""
    proc, log, port = _boot_server(
        tmp_path, "--max-new-tokens", "16",
        "--prefill-chunk-tokens", "32", "--kv-block-size", "8",
    )
    try:
        r = _post(port, {"messages": [{"role": "user", "content": "hi"}]})
        assert r.status == 200

        m = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ))
        assert m["prefill_chunk_tokens"] == 32
        assert m["kv_block_size"] == 8
        assert m["admitted_total"] >= 1
        assert m["prefill_chunks_total"] >= 1
        # untouched legacy keys existing dashboards scrape
        assert m["rejected_total"] == 0 and m["slots"] == 8

        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics?format=prometheus", timeout=5
        ).read().decode()
        assert "# TYPE dstack_tpu_serving_kv_blocks_in_use gauge" in text
        assert "dstack_tpu_serving_admitted_total 1" in text
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/metrics",
            headers={"Accept": "text/plain"},
        )
        via_accept = urllib.request.urlopen(req, timeout=5)
        assert via_accept.headers["Content-Type"].startswith("text/plain")
        assert "dstack_tpu_serving_prefix_cache_hits_total" in (
            via_accept.read().decode()
        )
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()


def test_native_server_rejects_bad_paged_kv_flags(tmp_path):
    """Invalid paged-KV flags fail fast with a clear message, not a
    late traceback (tiny's max_seq_len is 256: 24 does not divide it)."""
    env = {**os.environ, "PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"}
    for flags, needle in (
        (["--kv-block-size", "24"], "must divide"),
        (["--kv-block-size", "0"], "must be positive"),
        (["--prefill-chunk-tokens", "-4"], "must be positive"),
    ):
        out = subprocess.run(
            [sys.executable, str(SERVER), "--preset", "tiny",
             "--port", str(free_port()), *flags],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode != 0, flags
        assert needle in out.stderr, (flags, out.stderr[-500:])


def test_native_server_rejects_bad_spec_flags(tmp_path):
    """The speculation flags fail fast with clear messages: a
    non-positive draft ceiling, an unknown drafter preset, and a KV
    budget that fits the target pool but cannot also fit the drafter
    pool (tiny at server defaults needs exactly 1 MiB per pool, so
    --kv-budget-mb 1 admits plain serving but rejects speculation)."""
    env = {**os.environ, "PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"}
    for flags, needle in (
        (["--spec-enable", "--spec-max-draft", "0"], "must be positive"),
        (["--spec-enable", "--spec-draft-preset", "nope"],
         "not a known preset"),
        (["--spec-enable", "--kv-budget-mb", "1"], "drafter KV pool"),
    ):
        out = subprocess.run(
            [sys.executable, str(SERVER), "--preset", "tiny",
             "--port", str(free_port()), *flags],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode != 0, flags
        assert needle in out.stderr, (flags, out.stderr[-500:])


@pytest.mark.slow
def test_native_server_spec_flags_and_prometheus(tmp_path):
    """--spec-enable rides through to the engine (the same 1 MiB-per-pool
    budget that rejects speculation at 1 MiB admits it at 2), the JSON
    /metrics surface reports the speculation counters, and every
    dstack_tpu_serving_spec_* Prometheus series is declared in the
    registry with matching type."""
    from dstack_tpu.server.metrics_registry import METRICS

    proc, log, port = _boot_server(
        tmp_path, "--max-new-tokens", "16", "--spec-enable",
        "--spec-max-draft", "2", "--kv-budget-mb", "2",
    )
    try:
        r = _post(port, {"messages": [{"role": "user", "content": "hi"}],
                         "temperature": 0})
        assert r.status == 200

        m = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ))
        assert m["spec_enabled"] is True
        assert m["spec_max_draft"] == 2
        assert m["spec_rounds_total"] >= 1
        assert m["spec_tokens_proposed_total"] == (
            m["spec_tokens_accepted_total"] + m["spec_tokens_rejected_total"]
        )

        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics?format=prometheus", timeout=5
        ).read().decode()
        spec_series = set()
        for line in text.splitlines():
            if line.startswith("# TYPE dstack_tpu_serving_spec_"):
                _, _, name, mtype = line.split()
                spec_series.add(name)
                assert name in METRICS, name
                assert METRICS[name][0] == mtype, (name, mtype)
                assert METRICS[name][1] == (), name
        declared = {n for n in METRICS if n.startswith(
            "dstack_tpu_serving_spec_")}
        assert spec_series == declared, declared - spec_series
        assert "dstack_tpu_serving_spec_rounds_total" in spec_series
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()


def test_native_server_trace_surfaces(tmp_path):
    """Per-request tracing over the wire: the server echoes X-Request-ID
    and Traceparent, serves the flight-recorder trace at
    /v1/requests/<id>/trace (keyed by the caller's X-Request-ID), keeps
    the caller's trace_id end to end, and streams a phase_summary chunk
    before [DONE]. --trace-slow-ms 0 forces tail capture for everything
    so the lookup can't race ring recycling."""
    proc, log, port = _boot_server(
        tmp_path, "--max-new-tokens", "8",
        "--trace-ring", "64", "--trace-slow-ms", "0",
    )
    trace_id = "f0" * 16
    tp = f"00-{trace_id}-{'1b' * 8}-01"

    def chat(body, rid):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-ID": rid, "traceparent": tp},
        )
        return urllib.request.urlopen(req, timeout=120)

    try:
        # Non-stream: identity echoed on the response, trace retrievable.
        rid = "trace-test-1"
        resp = chat({"messages": [{"role": "user", "content": "hi"}]}, rid)
        assert resp.status == 200
        assert resp.headers["X-Request-ID"] == rid
        assert resp.headers["Traceparent"] == tp
        json.load(resp)

        trace = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/requests/{rid}/trace", timeout=5
        ))
        assert trace["x_request_id"] == rid
        assert trace["trace_id"] == trace_id  # caller's trace, not a new one
        assert trace["status"] == "ok"
        phases = [p["phase"] for p in trace["phases"]]
        assert phases[0] == "queue_wait" and "decode" in phases, phases
        assert abs(sum(p["duration_s"] for p in trace["phases"])
                   - trace["total_seconds"]) < 1e-9
        assert trace["counters"]["decode_steps"] >= 1

        # Unknown id: 404, not a stack trace.
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/requests/nope/trace", timeout=5
            )
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404

        # Stream: headers echoed on the SSE response and a phase_summary
        # chunk rides after the tokens, before the [DONE] sentinel.
        rid2 = "trace-test-2"
        resp = chat({"messages": [{"role": "user", "content": "go"}],
                     "stream": True}, rid2)
        assert resp.status == 200
        assert resp.headers["X-Request-ID"] == rid2
        assert resp.headers["Traceparent"] == tp
        raw = resp.read().decode()
        chunks = [json.loads(line[len("data: "):])
                  for line in raw.splitlines()
                  if line.startswith("data: ") and line != "data: [DONE]"]
        assert raw.rstrip().endswith("data: [DONE]")
        summaries = [c for c in chunks if "phase_summary" in c]
        assert len(summaries) == 1
        ps = summaries[-1]["phase_summary"]
        assert chunks.index(summaries[0]) == len(chunks) - 1  # last chunk
        assert ps["trace_id"] == trace_id
        assert abs(sum(p["duration_s"] for p in ps["phases"])
                   - ps["total_seconds"]) < 1e-9
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()


def test_native_server_stop_sequences(tmp_path):
    """The OpenAI `stop` field truncates the output before the stop
    string; greedy decode makes the check deterministic."""
    proc, log, port = _boot_server(tmp_path, "--max-new-tokens", "24")
    try:
        def chat(extra):
            r = _post(port, {"messages": [{"role": "user", "content": "go"}],
                             "temperature": 0, **extra})
            return json.load(r)["choices"][0]["message"]["content"]

        full = chat({})
        assert len(full) > 6
        # Stop on substrings the greedy output certainly contains — a
        # single char and a MULTI-char one (the hold-back case: partial
        # matches must not leak into the emitted text).
        for stop in (full[2], full[2:5]):
            stopped = chat({"stop": [stop]})
            assert stop not in stopped, (full, stop, stopped)
            assert stopped == full[:full.index(stop)], (full, stop, stopped)
        # malformed stop: lenient, full output
        assert chat({"stop": 5}) == full
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()


def _get_json(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return json.load(r)


def _wait_ready(port, timeout=120):
    """Poll /readyz until it answers 200; its body."""
    deadline = time.time() + timeout
    while True:
        try:
            return _get_json(port, "/readyz")
        except urllib.error.HTTPError:
            assert time.time() < deadline, "never became ready"
            time.sleep(0.5)


def test_readyz_gated_on_warmup_and_first_request_compiles_nothing(tmp_path):
    """The cold-start readiness contract over real HTTP: /healthz green
    at socket-up, /readyz 503 while warmup builds programs, and the
    first post-ready request moves the process compile counter by ZERO
    — including the host-side tokenize/convert seams a naive engine
    warmup can't see."""
    # Narrow geometry (--slots 2, 16-token chunks) keeps the warmup's
    # program set small: batch width and bucket count scale CPU
    # trace+compile time and the gate's semantics depend on neither.
    proc, log, port = _boot_server(
        tmp_path, "--max-new-tokens", "8", "--slots", "2",
        "--prefill-chunk-tokens", "16", warmup=True,
    )
    try:
        # _boot_server returns at socket-up, which is before the warmup
        # thread (several seconds even cache-warm) finishes: liveness
        # green, readiness 503 + Retry-After.
        assert _get_json(port, "/healthz") == {"ok": True}
        try:
            _get_json(port, "/readyz")
            raise AssertionError("/readyz answered 200 before warmup_end")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert e.headers["Retry-After"]
            assert json.load(e)["ready"] is False

        ready = _wait_ready(port)
        assert ready["ready"] is True
        assert ready["warmup_seconds"] > 0
        assert ready["weights_via"] == "init"

        before = _get_json(port, "/metrics")
        assert before["warmup_done"] is True
        assert before["compiles_total"] > 0
        r = _post(port, {"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 4})
        assert json.load(r)["choices"][0]["message"]["content"]
        after = _get_json(port, "/metrics")
        assert after["compiles_total"] == before["compiles_total"], (
            "first post-ready request built XLA programs"
        )
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()


def test_warm_boot_retrieves_every_program_from_the_cache(tmp_path, monkeypatch):
    """Scale-from-zero: a second boot against the first boot's
    `--compile-cache-dir` builds the same programs and finds every one
    of them on disk (`compile_cache_hits_total` == `compiles_total` at
    /readyz), so a warm replica pays tracing and no XLA compile."""
    # The flag places the cache only when no JAX_COMPILATION_CACHE_DIR is
    # exported (compile_cache.enable); the suite's would win over it.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    flags = ("--max-new-tokens", "8", "--slots", "2",
             "--prefill-chunk-tokens", "16",
             "--compile-cache-dir", str(tmp_path / "cache"))
    at_ready = []
    for _ in range(2):
        proc, log, port = _boot_server(tmp_path, *flags, warmup=True)
        try:
            _wait_ready(port, timeout=240)
            at_ready.append(_get_json(port, "/metrics"))
        finally:
            proc.kill()
            proc.wait(timeout=10)
            log.close()
    cold, warm = at_ready
    assert cold["compiles_total"] > 0
    assert cold["compile_cache_hits_total"] < cold["compiles_total"]
    assert warm["compiles_total"] == cold["compiles_total"]
    assert warm["compile_cache_hits_total"] == warm["compiles_total"]


def test_warmup_failure_ends_the_process(tmp_path):
    """A program that does not build during warmup must take the server
    down non-zero with the traceback — not strand it at /healthz 200 +
    /readyz 503 forever (an exception that killed the warmup thread), and
    never flip it ready on an engine that cannot decode. The failure is
    a plain RuntimeError on purpose: compiler and runtime errors
    (XlaRuntimeError: VMEM limit, Mosaic refusal) ARE RuntimeErrors, and
    only the engine's own EngineBusyError may take the benign
    "a request raced warmup" branch."""
    port = free_port()
    src = f"""
import runpy, sys
from dstack_tpu.workloads import serving

def refuse(self):
    raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem (injected)")

serving.ServingEngine.warmup = refuse
sys.argv = ["server.py", "--preset", "tiny", "--port", "{port}"]
runpy.run_path({str(SERVER)!r}, run_name="__main__")
"""
    env = {**os.environ, "PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, "-c", src], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        out, err = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise AssertionError("server outlived a failed warmup")
    assert proc.returncode not in (0, None), (out, err)
    assert "Traceback" in err and "scoped vmem (injected)" in err, err
    assert "warmup skipped" not in out


def test_no_warmup_flag_skips_the_gate(tmp_path):
    """--no-warmup trades the zero-compile guarantee for instant
    readiness (dev loops): /readyz is green with no warmup stats."""
    proc, log, port = _boot_server(tmp_path, "--no-warmup")
    try:
        deadline = time.time() + 30
        while True:
            try:
                ready = _get_json(port, "/readyz")
                break
            except urllib.error.HTTPError:
                assert time.time() < deadline
                time.sleep(0.2)
        assert ready["ready"] is True
        assert ready["warmup_seconds"] is None
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()
