"""Numbers from the device trace, as trace_reduce.py reduced it. A pattern
that matches nothing raises: a renamed program or kernel must not read as a
zero. Patterns are data, in the metric's file.

ops:
  idle_share        100 x (1 - busy_s / window_s)
  module_median_ms  median device duration of the modules matching `module`,
                    divided by the server stat named in `per` (a decode
                    module runs `steps_per_sync` steps)
  op_share          self time of the operations matching `op` (inside
                    modules matching `module`, if given) over window_s, in %
  kernel_roofline   least time the chip could take for the matched kernel
                    calls (opsbytes/<opsbytes>.py, the larger of FLOPs over
                    peak FLOP/s and bytes over peak bytes/s) over their
                    measured self time, in %
"""

import importlib
from typing import Any, Dict, Optional

from benchmarks import trace_reduce


def read(obs: Dict[str, Any], args: Dict[str, Any]) -> Optional[float]:
    reduced = obs.get("trace")
    if reduced is None:
        return None
    op = args["op"]
    if op == "idle_share":
        return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    if op == "module_median_ms":
        durations = sorted(
            d for m in trace_reduce.matching_modules(reduced, args["module"]).values()
            for d in m["durations_s"]
        )
        per = obs["stats"]["after"][args["per"]] if "per" in args else 1
        return durations[len(durations) // 2] * 1e3 / per
    if op == "op_share":
        found = trace_reduce.matching_ops(reduced, args["op_pattern"], args.get("module"))
        return 100.0 * found["self_s"] / reduced["window_s"]
    if op == "kernel_roofline":
        found = trace_reduce.matching_ops(reduced, args["op_pattern"], args.get("module"))
        model = importlib.import_module(f"benchmarks.opsbytes.{args['opsbytes']}")
        need = model.needed(obs, reduced, found, args)
        if need is None:
            return None
        peaks = obs["peaks"]
        least_s = max(need["flops"] / peaks["bf16_flops_per_s"],
                      need["bytes"] / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / found["self_s"]
    raise ValueError(f"unknown op {op!r}")
