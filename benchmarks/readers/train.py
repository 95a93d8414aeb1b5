"""Numbers of a training run from the child's per-step host clock (each step
ends in `block_until_ready`). Steps taken under the profiler are left out of
rates: tracing slows the host.

op `tokens_per_s`: batch tokens x steps / the time of those steps.
op `mfu`: tokens_per_s x model FLOPs per token (opsbytes/train_step.py)
over chips x the device's published bf16 peak, in %."""

from typing import Any, Dict, Optional

from benchmarks.opsbytes import train_step


def tokens_per_s(obs: Dict[str, Any]) -> Optional[float]:
    steps = [s for s in obs["steps"] if not s["traced"] and s["finite"]]
    if not steps:
        return None
    return obs["job"]["tokens_per_step"] * len(steps) / sum(s["seconds"] for s in steps)


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    if obs["kind"] != "train":
        return None
    rate = tokens_per_s(obs)
    if rate is None or args["op"] == "tokens_per_s":
        return rate
    if args["op"] == "mfu":
        if "peaks" not in obs:   # a rehearsal: no device, no utilisation
            return None
        flops = train_step.flops_per_token(obs["model_fields"], obs["job"]["seq_len"])
        peak = obs["peaks"]["bf16_flops_per_s"] * obs["device"]["count"]
        return 100.0 * rate * flops / peak
    raise ValueError(f"unknown op {args['op']!r}")
