"""Full-stack serving e2e: every byte of this path is this repo's code.

submit(service) -> run FSM -> local backend provisions a runner -> the
runner launches examples/deployment/native/server.py (workloads.generate
behind an OpenAI API) -> the replica registers with the in-server proxy ->
a chat completion through /proxy/models returns REAL generated tokens.
The reference can orchestrate this shape but always delegates the engine
to a user container (SURVEY §2.7) — here orchestrator AND engine are ours.
"""

import asyncio
import json
import os
import sys
from pathlib import Path

from dstack_tpu.server.http import response_json
from tests.server.conftest import make_server

REPO = Path(__file__).resolve().parent.parent.parent
PORT = 18431


async def test_native_model_serving_end_to_end():
    fx = await make_server()
    try:
        resp = await fx.client.post(
            "/api/project/main/runs/submit",
            json_body={
                "run_spec": {
                    "run_name": "native-svc",
                    "configuration": {
                        "type": "service",
                        "name": "native-svc",
                        "port": PORT,
                        "model": "tiny-native",
                        "auth": False,
                        "commands": [
                            f"{sys.executable} {REPO}/examples/deployment/native/server.py"
                            f" --preset tiny --port {PORT}"
                            " --model-name tiny-native --max-new-tokens 8"
                            # Warmup-less boot: this test's subject is the
                            # orchestration path, and the readiness gate
                            # pays seconds of tracing per boot either way
                            # (tests/test_serving_http.py covers the gate).
                            " --no-warmup"
                        ],
                        "env": {
                            "PYTHONPATH": str(REPO),
                            "JAX_PLATFORMS": "cpu",
                            # Boot from the suite's shared compile cache
                            # (tests/conftest.py exports it).
                            "JAX_COMPILATION_CACHE_DIR":
                                os.environ["JAX_COMPILATION_CACHE_DIR"],
                        },
                        "resources": {"cpu": "1..", "memory": "0.1.."},
                    },
                    "ssh_key_pub": "ssh-rsa TEST",
                }
            },
        )
        assert resp.status == 200, resp.body

        # Wait for the replica to be RUNNING and registered.
        deadline = asyncio.get_event_loop().time() + 60
        while True:
            resp = await fx.client.post(
                "/api/project/main/runs/get", json_body={"run_name": "native-svc"}
            )
            run = response_json(resp)
            if run["status"] == "running":
                break
            assert run["status"] not in ("failed", "terminated"), run
            assert asyncio.get_event_loop().time() < deadline, run["status"]
            await asyncio.sleep(0.3)

        # Model discoverable on the OpenAI-compatible endpoint.
        deadline = asyncio.get_event_loop().time() + 30
        while True:
            resp = await fx.client.get("/proxy/models/main/models")
            models = response_json(resp)["data"]
            if any(m["id"] == "tiny-native" for m in models):
                break
            assert asyncio.get_event_loop().time() < deadline, models
            await asyncio.sleep(0.3)

        # Chat completion through the in-server proxy to OUR engine. First
        # request also compiles the tiny model on CPU — give it time.
        deadline = asyncio.get_event_loop().time() + 120
        while True:
            resp = await fx.client.post(
                "/proxy/models/main/chat/completions",
                json_body={
                    "model": "tiny-native",
                    "messages": [{"role": "user", "content": "hello tpu"}],
                },
            )
            if resp.status == 200:
                break
            assert asyncio.get_event_loop().time() < deadline, resp.body
            await asyncio.sleep(1.0)
        body = json.loads(resp.body)
        assert body["object"] == "chat.completion"
        content = body["choices"][0]["message"]["content"]
        assert isinstance(content, str) and len(content) >= 1
        assert body["model"] == "tiny-native"

        # Streaming (SSE) through the proxy: one delta chunk per token from
        # the continuous-batching engine, [DONE] terminated.
        resp = await fx.client.post(
            "/proxy/models/main/chat/completions",
            json_body={
                "model": "tiny-native", "stream": True,
                "messages": [{"role": "user", "content": "stream me"}],
            },
        )
        assert resp.status == 200, resp.body
        raw = resp.body
        if resp.stream is not None:  # streamed responses arrive as chunks
            async for chunk in resp.stream:
                raw += chunk
        events = [
            line for line in raw.decode().split("\n\n")
            if line.startswith("data: ")
        ]
        assert events[-1] == "data: [DONE]"
        chunks = [json.loads(e[len("data: "):]) for e in events[:-1]]
        assert len(chunks) >= 2  # multiple tokens streamed
        assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
        streamed = "".join(
            c["choices"][0]["delta"].get("content", "") for c in chunks
        )
        assert len(streamed) >= 1

        # Stop the service; the run terminates cleanly.
        await fx.client.post(
            "/api/project/main/runs/stop", json_body={"runs_names": ["native-svc"]}
        )
        deadline = asyncio.get_event_loop().time() + 30
        while True:
            resp = await fx.client.post(
                "/api/project/main/runs/get", json_body={"run_name": "native-svc"}
            )
            run = response_json(resp)
            if run["status"] in ("terminated", "done", "failed"):
                break
            assert asyncio.get_event_loop().time() < deadline
            await asyncio.sleep(0.3)
        assert run["status"] == "terminated"
    finally:
        await fx.app.shutdown()
