"""What both children need: the device gate, the line protocol to the
parent, memory readings and the profiler. Imported only in a child: this is
the side of the benchmark that may import JAX."""

import json
import sys
import time
from typing import Any, Dict

import jax

PREFIX = "@bench "  # a line of the child's stdout that is for the parent


def emit(event: str, **fields: Any) -> None:
    print(PREFIX + json.dumps({"event": event, "t": time.monotonic(), **fields}),
          flush=True)


def fail(message: str) -> None:
    """No CPU fallback and no partial result: say why on stderr, exit 2."""
    print(f"benchmark child: {message}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def require_chips(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """The device as JAX reports it; exits unless it is a TPU with at least
    `chips` chips (a rehearsal asks for the CPU by name instead)."""
    from dstack_tpu.utils.devices import require_device

    info = require_device("benchmarks/run.py")
    if rehearsal:
        if info["platform"] != "cpu":
            fail("--rehearsal runs on the CPU: export JAX_PLATFORMS=cpu")
    elif info["platform"] != "tpu":
        fail(f"JAX found platform {info['platform']!r}, not a TPU")
    if info["device_count"] < chips:
        fail(f"the cell asks for {chips} chips, JAX sees {info['device_count']}")
    return {"platform": info["platform"], "kind": info["device_kind"],
            "count": info["device_count"]}


def memory() -> Dict[str, Any]:
    """Peak and limit on the fullest chip (None where the backend has no
    memory statistics, as on the CPU)."""
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    limits = [s["bytes_limit"] for s in stats if "bytes_limit" in s]
    return {"memory_peak_bytes": max(peaks) if peaks else None,
            "memory_limit_bytes": min(limits) if limits else None}


def start_trace(trace_dir: str) -> None:
    # Python frames on the host lines are what names a device gap
    # (trace_reduce.py); they cost host time, which is why end-to-end numbers
    # come from runs without a trace.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_trace() -> None:
    jax.profiler.stop_trace()
