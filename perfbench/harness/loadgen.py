"""Open-loop HTTP load generator over loopback, on a thread of its own.

A copy of the program's ``serving/loadgen.py::drive_http`` with three
repairs: every time is taken against the request's SCHEDULED send (the
program's copy started TTFT after the connect and the write, so a late
generator read as a fast server); each request records how late the
generator actually sent it; and the generator runs its own event loop
on its own thread, so a serve tick that holds the server's event loop
does not hold back the sends (in the server's loop they ran 1-2 s late
at p50). Times are ``time.monotonic()`` seconds, the clock both event
loops keep.

Each planned request is POSTed to ``/v1/completions`` as a streaming
completion at its scheduled time, whether or not earlier ones have
finished. The SSE stream is read to its end; every token's id and its
arrival time are kept, with the server's request id, so the tokens can
be checked against the reference afterwards.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import json
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

from .traffic import Planned


@dataclass
class Record:
    plan: Planned
    late: Optional[float] = None      # actual send - scheduled send (s)
    server_id: Optional[str] = None
    tokens: List[int] = field(default_factory=list)
    recv: List[float] = field(default_factory=list)  # s from window start
    state: str = "pending"            # pending|sent|done|error|dropped
    error: Optional[str] = None

    @property
    def ttft(self) -> Optional[float]:
        """First streamed token minus the scheduled send."""
        return self.recv[0] - self.plan.t if self.recv else None

    @property
    def tpot(self) -> Optional[float]:
        """(last token - first token) / (tokens - 1)."""
        if len(self.recv) < 2:
            return None
        return (self.recv[-1] - self.recv[0]) / (len(self.recv) - 1)


async def _one(host: str, port: int, rec: Record, anchor: float) -> None:
    p = rec.plan
    delay = anchor + p.t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({"prompt_tokens": p.prompt_len,
                           "max_tokens": p.max_tokens, "tier": p.tier,
                           "stream": True}).encode()
        writer.write((
            "POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await writer.drain()
        rec.late = time.monotonic() - (anchor + p.t)
        rec.state = "sent"
        status = await reader.readline()
        if b" 200 " not in status:
            rec.state = "error"
            rec.error = status.decode("latin1").strip()
            return
        while True:
            line = await reader.readline()
            if not line:
                rec.state = "dropped"
                return
            if not line.startswith(b"data: "):
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                return
            ev = json.loads(payload)
            if "token" in ev:
                rec.recv.append(time.monotonic() - anchor)
                rec.tokens.append(int(ev["token"]))
                rec.server_id = ev["id"]
            else:
                fin = ev["choices"][0].get("finish_reason")
                rec.server_id = ev.get("id", rec.server_id)
                rec.state = "done" if fin == "stop" else f"error:{fin}"
    except (ConnectionError, OSError) as e:
        rec.state = "error"
        rec.error = str(e)
    finally:
        if writer is not None:
            writer.close()


async def _all(host: str, port: int, recs: Sequence[Record],
               anchor: float) -> None:
    await asyncio.gather(*(_one(host, port, r, anchor) for r in recs))


class Generator:
    """The load generator's thread and event loop, for one server."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       name="perfbench-loadgen",
                                       daemon=True)
        self.thread.start()

    def start(self, planned: Sequence[Planned], anchor: float) -> tuple:
        """Send every planned request at ``anchor + plan.t`` (monotonic
        seconds). Returns (records, job); the job is a future that is
        done once every request has ended."""
        recs = [Record(plan=p) for p in planned]
        job = asyncio.run_coroutine_threadsafe(
            _all(self.host, self.port, recs, anchor), self.loop)
        return recs, job

    @staticmethod
    def done(recs: Sequence[Record]) -> bool:
        return all(r.state not in ("pending", "sent") for r in recs)

    def cancel(self, job: concurrent.futures.Future) -> None:
        """Stop a job: requests not yet sent are not sent, and streams
        still open are closed (the client goes away)."""
        job.cancel()
        try:
            job.result(timeout=10)
        except (concurrent.futures.CancelledError,
                concurrent.futures.TimeoutError):
            pass

    def close(self) -> None:
        """Stop the thread's loop and wait for the thread to end."""
        async def _drain():
            tasks = [t for t in asyncio.all_tasks()
                     if t is not asyncio.current_task()]
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        asyncio.run_coroutine_threadsafe(_drain(), self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=10)
        self.loop.close()
