"""Name the device a result came from, and refuse the wrong one.

A time, a rate or a utilization means something only together with the
device it was taken on, and a benchmark that slides to the CPU when it
finds no chip produces numbers nobody deploys. Two rules, used by every
bench and drill script at the repo root:

- a measuring entry point names `platform`, `device_kind` and the device
  count on its result, and refuses the cpu platform unless the caller
  exported `JAX_PLATFORMS=cpu` — an explicit request is not a fallback
  (`require_device`);
- a CPU count-check script — one whose parent process holds the JAX
  device while its children are pinned to `JAX_PLATFORMS=cpu` — says so
  and refuses any other platform, because a local chip belongs to one
  process at a time (`require_cpu_request`).
"""

import os
from typing import Dict, Union


def cpu_requested() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def device_info() -> Dict[str, Union[str, int]]:
    """The device as JAX reports it (initializes the backend)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_device(what: str) -> Dict[str, Union[str, int]]:
    """`device_info()` for a measuring entry point; exits when JAX fell
    back to the cpu platform that nobody asked for."""
    info = device_info()
    if info["platform"] == "cpu" and not cpu_requested():
        raise SystemExit(
            f"{what}: JAX found no accelerator and resolved to the cpu"
            " platform. Refusing to measure a fallback — run on a machine"
            " with a chip, or export JAX_PLATFORMS=cpu to ask for a CPU"
            " run explicitly (correctness and counts only)."
        )
    return info


def require_cpu_request(what: str) -> None:
    """Gate for a CPU count-check script. Reads only the environment, so
    the check itself never takes a device."""
    if not cpu_requested():
        raise SystemExit(
            f"{what} is a CPU count-check, not a device measurement: its"
            " parent process holds the JAX device and its children are"
            " pinned to JAX_PLATFORMS=cpu, which a real chip (one process"
            " at a time) cannot serve. Run it with JAX_PLATFORMS=cpu"
            f" exported (found {os.environ.get('JAX_PLATFORMS')!r}); it"
            " needs per-chip process placement before it can run on an"
            " accelerator (ROADMAP D7)."
        )
