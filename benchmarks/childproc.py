"""The parent's side of a child process: start it, follow its `@bench` lines,
send it commands, and make sure nothing outlives the run. No JAX here."""

import asyncio
import json
import os
import signal
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

PREFIX = "@bench "


class ChildFailed(Exception):
    pass


class Child:
    def __init__(self, module: str, args: List[str], env: Dict[str, str],
                 cwd: Path, log_path: Path):
        self.cmd = [sys.executable, "-m", module, *args]
        self.env = env
        self.cwd = cwd
        self.log_path = log_path
        self.proc: Optional[asyncio.subprocess.Process] = None
        self.events: "asyncio.Queue[Optional[Dict[str, Any]]]" = asyncio.Queue()
        self._reader: Optional[asyncio.Task] = None

    async def start(self) -> None:
        self.log_path.parent.mkdir(parents=True, exist_ok=True)
        self._log = open(self.log_path, "w")
        self.proc = await asyncio.create_subprocess_exec(
            *self.cmd, cwd=self.cwd, env=self.env,
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            stderr=self._log, start_new_session=True, limit=1 << 22,
        )
        self._reader = asyncio.create_task(self._follow())

    async def _follow(self) -> None:
        assert self.proc is not None and self.proc.stdout is not None
        while True:
            line = await self.proc.stdout.readline()
            if not line:
                break
            text = line.decode(errors="replace")
            if text.startswith(PREFIX):
                await self.events.put(json.loads(text[len(PREFIX):]))
            self._log.write(text)
            self._log.flush()
        await self.events.put(None)

    async def wait_event(self, name: str, timeout: float) -> Dict[str, Any]:
        """The next `@bench` event, which must be `name`."""
        try:
            event = await asyncio.wait_for(self.events.get(), timeout)
        except asyncio.TimeoutError:
            raise ChildFailed(f"no {name!r} from the child within {timeout:.0f} s")
        if event is None:
            code = await self.proc.wait()
            raise ChildFailed(
                f"the child ended (exit {code}) before {name!r}; its output is"
                f" in {self.log_path}:\n{self.tail()}"
            )
        if event["event"] != name:
            raise ChildFailed(f"expected {name!r} from the child, got {event!r}")
        return event

    def send(self, cmd: str, **fields: Any) -> None:
        assert self.proc is not None and self.proc.stdin is not None
        self.proc.stdin.write((json.dumps({"cmd": cmd, **fields}) + "\n").encode())

    def tail(self, n: int = 30) -> str:
        self._log.flush()
        try:
            return "".join(open(self.log_path).readlines()[-n:])
        except OSError:
            return ""

    async def close(self, grace: float = 20.0) -> Optional[int]:
        """Wait for the child to end; kill its whole process group if it does
        not, or if anything it started is still there."""
        if self.proc is None:
            return None
        try:
            code = await asyncio.wait_for(self.proc.wait(), grace)
        except asyncio.TimeoutError:
            code = None
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        if code is None:
            code = await self.proc.wait()
        if self._reader is not None:
            await self._reader
        self._log.close()
        return code
