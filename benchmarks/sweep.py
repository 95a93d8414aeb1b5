"""Find a serving cell's knee once, when the cell is defined: several loads,
one after another, on ONE server (one set-up), a lead-in, a window and a
drain for each. A benchmark PR runs this on the chip and writes 0.8 x the
knee into cells/<cell>.json as a number; run.py never searches.

    python3 benchmarks/sweep.py --workload mistral-7b.chat --seed 1 \
        --seconds 30 --loads 1,1.5,2,2.5,3

`--loads` are requests per second for an open-loop mix and callers for a
closed-loop one. The knee is the highest load at which at least 90% of the
window's requests finish and no backlog is left growing at its end
(`pending` and `active` in the table are the server's gauges at the end of
the window). Prints a table row per load and writes the same to
benchmarks/out/<cell>/sweep.json. Not a measurement of record: windows are
short and follow each other on a warm server.
"""

import argparse
import asyncio
import importlib
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks import cellfiles, run as runner  # noqa: E402
from benchmarks.childproc import ChildFailed  # noqa: E402
from benchmarks.generators import serving  # noqa: E402
from benchmarks.readers import client  # noqa: E402


def row_of(load: float, obs: dict) -> dict:
    def stat(field, stat, population):
        return client.read(obs, {"field": field, "stat": stat, "population": population})

    mine = [r for r in obs["requests"] if r["in_window"]]
    after = obs["stats"]["after"]
    return {
        "load": load, "attempted": obs["attempted"], "failed": obs["failed"],
        "finished_share": (sum(r["ok"] for r in mine) / len(mine)) if mine else None,
        "refused": sum(r["status"] in (429, 503) for r in mine),
        "ttft_p50_ms": stat("ttft_ms", "p50", "due_in_window"),
        "ttft_p90_ms": stat("ttft_ms", "p90", "due_in_window"),
        "tpot_p50_ms": stat("tpot_ms", "p50", "finished_in_window"),
        "tok_s": client.token_rate(obs),
        "late_p99_ms": stat("late_ms", "p99", "due_in_window"),
        "pending_at_end": after["pending"], "active_at_end": after["active"],
        "rejected_total": after["rejected_total"],
        "correct": obs["correct"], "reasons": obs["reasons"],
    }


async def sweep(args, cell) -> list:
    ctx = runner.open_child(args, cell, "sweep_child.log", trace=False)
    child = ctx.child
    generator = importlib.import_module(f"benchmarks.generators.{cell.generator}")
    key = next(iter(cell.load))
    rows = []
    await child.start()
    try:
        flow = serving.Run(ctx)
        ready = await flow.ready()
        print("READY " + json.dumps({k: ready[k] for k in ("probe", "timings", "warmup")}),
              flush=True)
        for i, load in enumerate(args.loads):
            _, issue = generator.issuer(flow, {key: load}, args.seed + i)
            obs = serving.finish_observation(await flow.window(issue),
                                             generator.in_window(flow))
            rows.append(row_of(load, obs))
            print("ROW " + json.dumps(rows[-1]), flush=True)
        await flow.finish()
    finally:
        await child.close()
    (ctx.out_dir / "sweep.json").write_text(json.dumps(rows, indent=1))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--loads", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    try:
        cell = cellfiles.Cell(args.workload, rehearsal=args.rehearsal)
        asyncio.run(sweep(args, cell))
    except (cellfiles.CellError, ChildFailed) as e:
        print(f"benchmarks/sweep.py: {e}", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
