"""A training job: the child runs the steps and times them; the parent waits.
The mix gives the job's shape (rows, sequence length), the cell nothing."""

import time
from typing import Any, Dict

READY_TIMEOUT_S = 1150.0


async def run(ctx) -> Dict[str, Any]:
    ready = await ctx.child.wait_event("ready", READY_TIMEOUT_S)
    setup_s = time.monotonic() - ctx.t_process_start
    ctx.child.send("run", seconds=ctx.seconds, trace=bool(ctx.trace),
                   trace_dir=str(ctx.trace_dir))
    result = await ctx.child.wait_event("result", ctx.seconds + 300)
    steps = result["steps"]
    checks = ready["checks"]
    reasons = (
        [f"{name}: {c}" for name, c in checks.items() if not c["ok"]]
        + ([] if all(s["finite"] for s in steps) else ["a step's loss was not finite"])
        + ([f"{result['compiles_in_window']} programs were built inside the window"]
           if result["compiles_in_window"] else [])
        + ([] if steps else ["no step finished"])
    )
    return {
        "kind": "train", "ready": ready, "done": result, "setup_s": setup_s,
        "window": {"seconds": float(ctx.seconds)},
        "steps": steps, "job": result["job"],
        "attempted": len(steps), "failed": sum(not s["finite"] for s in steps),
        "compiles_in_window": result["compiles_in_window"],
        "reasons": reasons, "correct": not reasons,
    }
