"""Closed loop: a fixed number of callers, each of which sends its next
request when its last one has ended. RL actors, evaluation harnesses and
batch jobs call this way; a slow server receives less load, so the number to
read is the work completed per second. The cell gives the number of callers.

A caller works through SESSIONS of `sessions.requests` requests that share
the first `shared_head_tokens` tokens of their prompts (a document and its
questions), one session after another. As in the open loop, the shape of the
traffic (lengths, a cycle of them per caller) comes from the mix's
`trace_seed` and what the requests say from `--seed`.
"""

import asyncio
import random
from typing import Any, Dict

from benchmarks.generators import prompts, serving

LENGTH_CYCLE = 16  # a caller's lengths repeat after this many requests


def caller_request(mix: Dict[str, Any], seed: int, caller: int, k: int,
                   limits: prompts.Limits) -> Dict[str, Any]:
    """The k-th request of one caller. Pure: same arguments, same request."""
    shape = random.Random(f"{mix['trace_seed']}:caller:{caller}")
    total = prompts.stratified(mix["prompt"]["total_tokens"], LENGTH_CYCLE,
                               shape)[k % LENGTH_CYCLE]
    output = prompts.stratified(mix["output_tokens"], LENGTH_CYCLE,
                                shape)[k % LENGTH_CYCLE]
    if not prompts.is_bucket(total, limits):
        raise ValueError(f"{total} prompt tokens is not one of the server's buckets")
    session = k // int(mix["sessions"]["requests"])
    head = prompts.shared_head(
        int(mix["prompt"]["shared_head_tokens"]),
        random.Random(f"{seed}:caller:{caller}:session:{session}"))
    text = random.Random(f"{seed}:caller:{caller}:{k}")
    return {
        "prompt_tokens": total, "max_tokens": output, "session": session,
        "content": prompts.content_for(total, head, text),
    }


def issuer(flow: serving.Run, load: Dict[str, Any], seed: int):
    """-> (what is offered, the coroutine function that sends it)."""
    callers = int(load["callers"])

    async def one_caller(flow: serving.Run, caller: int) -> None:
        k, due = 0, flow.t_lead
        while flow.now() < flow.seconds:
            r = caller_request(flow.mix, seed, caller, k, flow.limits)
            rec = await flow.request(
                due=due, content=r["content"], prompt_tokens=r["prompt_tokens"],
                max_tokens=r["max_tokens"], caller=caller, session=r["session"],
                turn=k)
            if rec["done"] is None:
                return  # refused or broken: this caller stops, and it counts
            k, due = k + 1, rec["done"]

    async def issue(flow: serving.Run) -> None:
        await flow.sleep_until(flow.t_lead)
        await asyncio.gather(*(one_caller(flow, c) for c in range(callers)))

    return {"callers": callers}, issue


def in_window(flow: serving.Run):
    return lambda r: 0.0 <= r["sent"] < flow.seconds


async def run(ctx) -> Dict[str, Any]:
    flow = serving.Run(ctx)
    offered, issue = issuer(flow, ctx.cell.load, ctx.seed)
    obs = await flow.run(issue)
    obs["offered"] = offered
    return serving.finish_observation(obs, in_window(flow))
