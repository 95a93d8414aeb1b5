"""The serving cells' child: holds the chip and runs the native server's own
`main()` — the normal entry point, with warmup-gated `/readyz`, SSE
streaming and `/metrics` — on the cell's configuration.

What it does around that call, and why each is not the program's to do:

- registers the configuration in `PRESETS` under the cell's name (the server
  takes `--preset` from that table only);
- makes the weights on the device in ONE jitted call from `--seed`
  (`transformer.init_params` itself, jitted) and hands that tree to the
  server in place of its leaf-by-leaf `init_params(PRNGKey(0))`: the server
  has no `--seed`, and its eager init holds two float32 copies of a leaf at
  once, which Mixtral's expert banks (7.5 GB a copy at four layers) do not
  survive on a 16 GB chip. PERF.md lists both under "Program limits";
- runs the plain reference on those weights BEFORE the server takes the
  memory, then sends the probe prompts through `ServingEngine.submit` at
  temperature 0 once `/readyz` is green and holds the tokens to the
  reference's logits (reference/<family>.py says how). The engine is the one
  `main()` built: the child's `Engine` subclass only remembers the instance;
- starts and stops `jax.profiler` when the parent says so. Only the process
  that holds the chip can trace it.

Protocol: JSON lines on stdin (`trace_start`, `trace_stop`, `finish`), lines
that start with `@bench ` on stdout. Everything else on stdout is the
server's own.
"""

import argparse
import importlib
import importlib.util
import json
import os
import random
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import cellfiles
from benchmarks.children import common
from benchmarks.generators import prompts

SERVER_PY = cellfiles.REPO / "examples" / "deployment" / "native" / "server.py"
READY_TIMEOUT_S = 1100.0
PROBE_TIMEOUT_S = 120.0


def load_server_module():
    spec = importlib.util.spec_from_file_location("native_server", SERVER_PY)
    module = importlib.util.module_from_spec(spec)
    sys.modules["native_server"] = module
    spec.loader.exec_module(module)
    return module


def probe_prompts(cell, seed: int):
    """The cut's probe: `prompts` prompts that encode to exactly
    `prompt_tokens` tokens through the server's own rule."""
    spec = cell.cut["probe"]
    n_tokens = spec["prompt_tokens"]
    if cell.rehearsal is not None:
        n_tokens = max(cell.rehearsal["min_prompt_tokens"],
                       n_tokens // cell.rehearsal["length_divisor"])
    rng = random.Random(f"{seed}:probe")
    limits = prompts.Limits.of(cell)
    rows = []
    for _ in range(spec["prompts"]):
        content = prompts.ascii_text(rng, n_tokens - prompts.TEMPLATE_TOKENS)
        rows.append(prompts.encode(prompts.render(content), limits))
    return rows, spec["max_tokens"]


def run_probe(engine, rows, max_tokens, reference, ref_out):
    outs = [engine.serving.submit(list(row), max_new_tokens=max_tokens,
                                  temperature=0.0) for row in rows]
    got = []
    for out in outs:
        tokens = []
        while True:
            tok = out.get(timeout=PROBE_TIMEOUT_S)
            if tok is None:
                break
            if isinstance(tok, BaseException):
                raise tok
            tokens.append(int(tok))
        got.append(tokens)
    ref_tokens, ref_logits, ref_margins = ref_out
    result = reference.check_tokens(got, np.asarray(ref_tokens), ref_logits,
                                    ref_margins)
    result["lengths_ok"] = all(len(t) == max_tokens for t in got)
    result["ok"] = bool(result["ok"] and result["lengths_ok"])
    return result


def http_json(port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read())


def wait_ready(port: int) -> None:
    deadline = time.monotonic() + READY_TIMEOUT_S
    while time.monotonic() < deadline:
        try:
            if http_json(port, "/readyz").get("ready"):
                return
        except (urllib.error.URLError, OSError, ValueError):
            pass
        time.sleep(0.2)
    common.fail(f"/readyz not green after {READY_TIMEOUT_S:.0f} s")


def control(args, cell, captured, probe, reference, ref_out, timings, device):
    """Everything after `main()` has been entered: readiness, the probe, then
    the parent's commands. Runs on its own thread; `main()` keeps the main
    one. Ends the process."""
    try:
        wait_ready(args.port)
        t0 = time.monotonic()
        result = run_probe(captured[0], probe[0], probe[1], reference, ref_out)
        timings["probe_s"] = time.monotonic() - t0
        stats = http_json(args.port, "/metrics")
        common.emit(
            "ready", device=device, probe=result, timings=timings,
            attn_path=stats.get("attn_path"),
            warmup={k: stats.get(k) for k in (
                "warmup_seconds", "warmup_programs", "compiles_total",
                "compile_cache_hits_total", "compile_cache_misses_total",
                "compile_seconds_total", "compile_cache_dir")},
        )
        for line in sys.stdin:
            cmd = json.loads(line)
            if cmd["cmd"] == "trace_start":
                common.start_trace(cmd["dir"])
                common.emit("trace_started")
            elif cmd["cmd"] == "trace_stop":
                common.stop_trace()
                common.emit("trace_stopped")
            elif cmd["cmd"] == "finish":
                common.emit("done", **common.memory())
                os._exit(0)
        os._exit(3)  # the parent went away without `finish`
    except BaseException:
        import traceback

        traceback.print_exc()
        os._exit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    cell = cellfiles.Cell(args.cell, rehearsal=args.rehearsal)

    from dstack_tpu.workloads import compile_cache
    from dstack_tpu.workloads.config import PRESETS, ModelConfig
    from dstack_tpu.workloads.transformer import init_params

    device = common.require_chips(cell.chips, args.rehearsal)
    compile_cache.enable()
    config = ModelConfig(**cell.model_fields)
    preset = f"bench:{cell.entry['config']}:{cell.cellfile['cut']}"
    PRESETS[preset] = config

    timings = {}
    t0 = time.monotonic()
    params = jax.jit(init_params, static_argnums=0)(
        config, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    timings["weights_s"] = time.monotonic() - t0

    reference = importlib.import_module(
        f"benchmarks.reference.{cell.family['reference']}")
    probe = probe_prompts(cell, args.seed)
    t0 = time.monotonic()
    ref_out = jax.device_get(reference.greedy_path(
        config, params, jnp.asarray(probe[0], jnp.int32), probe[1]))
    timings["reference_s"] = time.monotonic() - t0

    server = load_server_module()
    captured = []

    class Engine(server.Engine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            captured.append(self)

    def seeded_params(cfg, key):
        if cfg != config:
            common.fail("the server asked for weights of another configuration")
        return params

    server.Engine = Engine
    server.init_params = seeded_params
    threading.Thread(
        target=control, daemon=True, name="bench-control",
        args=(args, cell, captured, probe, reference, ref_out, timings, device),
    ).start()
    sys.argv = [str(SERVER_PY), "--preset", preset, "--port", str(args.port),
                "--model-name", cell.name, *cell.server_args()]
    server.main()


if __name__ == "__main__":
    main()
