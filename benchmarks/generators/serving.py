"""The measured flow of a serving cell, shared by the open-loop and the
closed-loop generator: ready -> lead-in -> window -> drain -> finish.

The parent is the client. It talks HTTP to the server the child runs
(`POST /v1/chat/completions`, streamed) from ONE thread (an asyncio loop),
stamps every event on its own monotonic clock, and reads `GET /metrics`
before and after the window. Nothing here imports JAX.

What a request's record holds (all times are seconds on the parent's clock,
relative to the start of the window):

  due     when the schedule wanted it sent (open loop), or when its caller's
          previous request ended (closed loop)
  sent    when the client began to send it
  first   first SSE chunk (the first token, unless it was the lead byte of a
          multi-byte character: the server holds that back until the next)
  last    last SSE chunk that carried text
  done    end of the stream
  asked   max_tokens;  tokens  what the server's own summary chunk counted
          (the stream carries text, and one chunk is not always one token)
  status  HTTP status, or a word for what went wrong
"""

import asyncio
import json
import time
from typing import Any, Awaitable, Callable, Dict, List, Optional

from benchmarks.childproc import Child
from benchmarks.generators import prompts

READY_TIMEOUT_S = 1150.0
TRACE_SECONDS = 3.0      # traced stretch in the middle of the window
TRACE_AT = 0.4           # where in the window it starts
SAMPLE_EVERY_S = 1.0
SAMPLED_KEYS = ("active", "pending", "kv_blocks_in_use", "kv_blocks_cached",
                "kv_blocks_total", "slots")


async def _http(port: int, method: str, path: str, body: Optional[bytes],
                on_data: Optional[Callable[[bytes], None]] = None):
    """One HTTP/1.0-style exchange with the server on localhost. Returns
    (status, headers, body); with `on_data`, hands it every SSE `data:` line
    as it arrives instead of collecting a body."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        head = [f"{method} {path} HTTP/1.1", "Host: 127.0.0.1", "Connection: close"]
        if body is not None:
            head += ["Content-Type: application/json", f"Content-Length: {len(body)}"]
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + (body or b""))
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, value = line.decode().partition(":")
            headers[key.strip().lower()] = value.strip()
        if on_data is None or "text/event-stream" not in headers.get("content-type", ""):
            return status, headers, await reader.read()
        while True:
            line = await reader.readline()
            if not line:
                break
            if line.startswith(b"data: "):
                on_data(line[6:].rstrip())
        return status, headers, b""
    finally:
        writer.close()


async def get_metrics(port: int) -> Dict[str, Any]:
    status, _, body = await _http(port, "GET", "/metrics", None)
    if status != 200:
        raise RuntimeError(f"GET /metrics answered {status}")
    return json.loads(body)


class Run:
    """One run of a serving cell. `issue(run)` is the generator's part: it
    sends requests with `run.request(...)` from `run.t_lead` on and returns
    when it has nothing more to send."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.cell = ctx.cell
        self.port = ctx.port
        self.child: Child = ctx.child
        self.mix = self.cell.mix if self.cell.rehearsal is None else \
            prompts.scaled_for_rehearsal(self.cell.mix, self.cell.rehearsal)
        self.limits = prompts.Limits.of(self.cell)
        self.records: List[Dict[str, Any]] = []
        self.tasks: List[asyncio.Task] = []
        self.t0 = 0.0            # window start, parent's monotonic clock
        self.seconds = float(ctx.seconds)
        self.lead_in = float(self.mix["lead_in_s"])
        self.trace_span = None   # the traced stretch, on the window's clock

    def now(self) -> float:
        return time.monotonic() - self.t0

    @property
    def t_lead(self) -> float:
        return -self.lead_in

    async def sleep_until(self, t: float) -> None:
        delay = t - self.now()
        if delay > 0:
            await asyncio.sleep(delay)

    def request(self, *, due: float, content: str, prompt_tokens: int,
                max_tokens: int, **labels: Any) -> asyncio.Task:
        """Send one request now; returns the task that ends with its record."""
        rec = {"due": due, "sent": self.now(), "first": None, "last": None,
               "done": None, "asked": max_tokens, "tokens": None,
               "prompt_tokens": prompt_tokens, "chunks": 0, "status": None,
               **labels}
        self.records.append(rec)
        task = asyncio.create_task(self._stream(rec, content, max_tokens))
        self.tasks.append(task)
        return task

    async def _stream(self, rec: Dict[str, Any], content: str, max_tokens: int):
        body = json.dumps({
            "model": self.cell.name, "stream": True, "max_tokens": max_tokens,
            "messages": [{"role": "user", "content": content}],
        }).encode()

        def on_data(data: bytes) -> None:
            t = self.now()
            if data == b"[DONE]":
                rec["done"] = t
            elif b'"phase_summary"' in data:
                counters = json.loads(data)["phase_summary"]["counters"]
                # The first token comes out of the final prefill chunk; the
                # recorder counts the decode chunks' tokens.
                rec["tokens"] = 1 + counters.get("decode_tokens", 0)
                rec["prefill_tokens_computed"] = counters.get("prefill_tokens", 0)
            else:
                if rec["first"] is None:
                    rec["first"] = t
                rec["last"] = t
                rec["chunks"] += 1

        try:
            status, _, _ = await _http(self.port, "POST", "/v1/chat/completions",
                                       body, on_data)
            rec["status"] = status
        except asyncio.CancelledError:
            rec["status"] = "unfinished"
            raise
        except (OSError, ValueError, IndexError) as e:
            rec["status"] = f"error: {type(e).__name__}: {e}"
        return rec

    async def _sample(self, samples: List[Dict[str, Any]]) -> None:
        while True:
            stats = await get_metrics(self.port)
            samples.append({"t": self.now(), **{k: stats.get(k) for k in SAMPLED_KEYS}})
            await asyncio.sleep(SAMPLE_EVERY_S)

    async def _trace(self) -> None:
        await self.sleep_until(self.seconds * TRACE_AT)
        self.child.send("trace_start", dir=str(self.ctx.trace_dir))
        await self.child.wait_event("trace_started", 60)
        started = self.now()
        await asyncio.sleep(min(TRACE_SECONDS, self.seconds * 0.4))
        self.trace_span = (started, self.now())
        self.child.send("trace_stop")
        await self.child.wait_event("trace_stopped", 300)

    async def ready(self) -> Dict[str, Any]:
        self.ready_event = await self.child.wait_event("ready", READY_TIMEOUT_S)
        return self.ready_event

    async def window(self, issue: Callable[["Run"], Awaitable[None]]) -> Dict[str, Any]:
        """Lead-in, window and drain of one load; may be called again on the
        same server (sweep.py does, one rate after another)."""
        self.records, self.tasks, self.trace_span = [], [], None
        self.t0 = time.monotonic() + self.lead_in
        issuer = asyncio.create_task(issue(self))
        await self.sleep_until(0.0)
        setup_s = time.monotonic() - self.ctx.t_process_start
        before = await get_metrics(self.port)
        samples: List[Dict[str, Any]] = []
        side = []
        if self.ctx.trace:
            side = [asyncio.create_task(self._sample(samples)),
                    asyncio.create_task(self._trace())]
        await self.sleep_until(self.seconds)
        after = await get_metrics(self.port)
        t_after = self.now()
        if side:
            side[0].cancel()
            await side[1]
        # Drain: what is in flight gets drain_s to finish, then is dropped
        # (and counts as failed where it belongs to the window).
        deadline = self.seconds + float(self.mix["drain_s"])
        while self.now() < deadline and not (
                issuer.done() and all(t.done() for t in self.tasks)):
            await asyncio.sleep(0.05)
        late = [t for t in [issuer, *self.tasks] if not t.done()]
        for t in late:
            t.cancel()
        await asyncio.gather(*late, return_exceptions=True)
        if issuer.done() and not issuer.cancelled() and issuer.exception():
            raise issuer.exception()
        await asyncio.gather(*side, return_exceptions=True)
        return {
            "kind": "serve", "ready": self.ready_event, "setup_s": setup_s,
            "window": {"seconds": self.seconds, "stats_span_s": t_after},
            "requests": self.records, "stats": {"before": before, "after": after,
                                                "samples": samples},
            "probe_ok": bool(self.ready_event["probe"]["ok"]),
            "trace_span": self.trace_span,
        }

    async def finish(self) -> Dict[str, Any]:
        self.child.send("finish")
        return await self.child.wait_event("done", 60)

    async def run(self, issue: Callable[["Run"], Awaitable[None]]) -> Dict[str, Any]:
        await self.ready()
        obs = await self.window(issue)
        obs["done"] = await self.finish()
        return obs


def finish_observation(obs: Dict[str, Any], in_window: Callable[[Dict[str, Any]], bool]):
    """Count what was attempted and what failed, and decide `correct`.

    attempted: requests that belong to the window (`in_window`). failed: those
    that got anything but a complete 200 stream with exactly the tokens asked
    for. correct: the probe held, no program was built inside the window, and
    every stream that finished (in or out of the window) had exactly
    max_tokens tokens."""
    for r in obs["requests"]:
        r["in_window"] = bool(in_window(r))
        r["ok"] = (r["status"] == 200 and r["done"] is not None
                   and r["tokens"] == r["asked"])
    mine = [r for r in obs["requests"] if r["in_window"]]
    obs["attempted"] = len(mine)
    obs["failed"] = sum(not r["ok"] for r in mine)
    finished = [r for r in obs["requests"] if r["status"] == 200 and r["done"] is not None]
    wrong_length = [r for r in finished if r["tokens"] != r["asked"]]
    stats = obs["stats"]
    compiles = stats["after"]["compiles_total"] - stats["before"]["compiles_total"]
    obs["compiles_in_window"] = compiles
    obs["reasons"] = (
        ([] if obs["probe_ok"] else ["the probe's tokens are outside the reference's tolerance"])
        + ([f"{len(wrong_length)} finished streams had another number of tokens than asked"]
           if wrong_length else [])
        + ([f"{compiles} programs were built inside the window"] if compiles else [])
        + ([] if finished else ["no request finished"])
    )
    obs["correct"] = not obs["reasons"]
    return obs
