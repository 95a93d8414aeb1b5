"""Open loop: requests are sent on a schedule drawn from the seed, whether or
not earlier ones have finished. Independent users make such traffic.

One general generator for every open-loop mix. A mix describes SESSIONS:
how they arrive (gaps gamma-distributed with the mix's coefficient of
variation: 1 is Poisson, above 1 is bursty), how many requests each makes
and how far apart, how much of a prompt the session's requests share, and
the length distributions. The cell gives the rate in requests per second;
the mix never does.

Steadiness. A mix is replayed as a TRACE: arrival times, session structure
and lengths come from the mix's own `trace_seed`, the same in every run;
`--seed` draws what the requests SAY (prompt and document text) and the
weights. The reason is arithmetic: a window holds some tens of requests
whose service times span 32:1, and with arrivals redrawn per seed the
90th-percentile TTFT of such a window swings by 25-50% between seeds
(simulated on the engine's own admission rule, PERF.md), where a cell is
admitted only under 5%. Redrawing the text still defeats anything that
remembers a prompt. Another trace of the same mix is a data file: a copy
with another `trace_seed`. Within the trace, the lead-in and the window each
get exactly round(rate x length) session starts, with gaps drawn and scaled
to fill the stretch, and the lengths are a fixed multiset in a shuffled
order (prompts.stratified), so a trace is not a lucky or unlucky draw of how
many long requests it holds.
"""

import random
from typing import Any, Dict, List

from benchmarks.generators import prompts, serving


def _starts(n: int, t_from: float, length: float, cv: float,
            rng: random.Random) -> List[float]:
    """`n` arrival times in [t_from, t_from + length): n + 1 gamma gaps scaled
    to sum to the stretch; for cv 1 these are a Poisson process's arrivals
    given their count."""
    if n <= 0:
        return []
    shape = 1.0 / (cv * cv)
    gaps = [rng.gammavariate(shape, 1.0) for _ in range(n + 1)]
    scale = length / sum(gaps)
    out, t = [], t_from
    for g in gaps[:n]:
        t += g * scale
        out.append(t)
    return out


def schedule(mix: Dict[str, Any], rate_rps: float, seed: int, seconds: float,
             limits: prompts.Limits) -> List[Dict[str, Any]]:
    """Every request of one run, sorted by due time (seconds from the start
    of the window; the lead-in is negative). Pure: same arguments, same list;
    another `seed` changes the contents only."""
    sess = mix["sessions"]
    per_session = int(sess["requests"])
    lead_in = float(mix["lead_in_s"])
    session_rate = rate_rps / per_session
    arrivals = random.Random(f"{mix['trace_seed']}:arrivals")
    lengths = random.Random(f"{mix['trace_seed']}:lengths")
    text = random.Random(f"{seed}:text")
    head_tokens = int(mix["prompt"]["shared_head_tokens"])
    requests = []
    session = 0
    # The lead-in and the window are drawn apart, so that the window's own
    # sessions are the same multiset of lengths under every seed.
    for t_from, length in ((-lead_in, lead_in), (0.0, seconds)):
        starts = _starts(round(session_rate * length), t_from, length,
                         sess["arrival_cv"], arrivals)
        n = len(starts) * per_session
        totals = prompts.stratified(mix["prompt"]["total_tokens"], n, lengths)
        outputs = prompts.stratified(mix["output_tokens"], n, lengths)
        for s, start in enumerate(starts):
            head = prompts.shared_head(head_tokens, text)
            due = start
            for k in range(per_session):
                if k:
                    due += arrivals.expovariate(1.0 / sess["reask_gap_mean_s"])
                i = s * per_session + k
                if not prompts.is_bucket(totals[i], limits):
                    raise ValueError(
                        f"{totals[i]} prompt tokens is not one of the server's"
                        " buckets: the tokenizer would cut or pad the prompt"
                    )
                requests.append({
                    "due": due, "session": session, "turn": k,
                    "prompt_tokens": totals[i], "max_tokens": outputs[i],
                    "content": prompts.content_for(totals[i], head, text),
                })
            session += 1
    requests = [r for r in requests if r["due"] < seconds]
    requests.sort(key=lambda r: r["due"])
    return requests


def issuer(flow: serving.Run, load: Dict[str, Any], seed: int):
    """-> (what is offered, the coroutine function that sends it)."""
    plan = schedule(flow.mix, load["rate_rps"], seed, flow.seconds, flow.limits)

    async def issue(flow: serving.Run) -> None:
        for r in plan:
            await flow.sleep_until(r["due"])
            flow.request(due=r["due"], content=r["content"],
                         prompt_tokens=r["prompt_tokens"],
                         max_tokens=r["max_tokens"], session=r["session"],
                         turn=r["turn"])

    return {"rate_rps": load["rate_rps"], "planned": len(plan)}, issue


def in_window(flow: serving.Run):
    return lambda r: 0.0 <= r["due"] < flow.seconds


async def run(ctx) -> Dict[str, Any]:
    flow = serving.Run(ctx)
    offered, issue = issuer(flow, ctx.cell.load, ctx.seed)
    obs = await flow.run(issue)
    obs["offered"] = offered
    return serving.finish_observation(obs, in_window(flow))
