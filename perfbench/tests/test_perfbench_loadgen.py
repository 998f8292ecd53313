"""The load generator sends on time from its own thread, while the
server's event loop is held by a synchronous tick, and reads each
stream's tokens and their arrival times."""
import asyncio
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import loadgen  # noqa: E402
from harness.traffic import Planned  # noqa: E402


async def _handle(reader, writer):
    body_len = 0
    while True:
        line = await reader.readline()
        if line.lower().startswith(b"content-length:"):
            body_len = int(line.split(b":")[1])
        if line in (b"\r\n", b""):
            break
    req = json.loads(await reader.readexactly(body_len))
    writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/event-stream"
                 b"\r\n\r\n")
    for i in range(req["max_tokens"]):
        ev = {"id": "r", "token": 7 + i}
        writer.write(b"data: " + json.dumps(ev).encode() + b"\n\n")
    tail = {"id": "r", "choices": [{"finish_reason": "stop"}]}
    writer.write(b"data: " + json.dumps(tail).encode()
                 + b"\n\ndata: [DONE]\n\n")
    await writer.drain()
    writer.close()


def test_sends_are_on_time_while_the_server_loop_is_held():
    async def main():
        srv = await asyncio.start_server(_handle, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        gen = loadgen.Generator("127.0.0.1", port)
        plan = [Planned(rid=f"r{i}", t=0.1 * i, tier="standard",
                        prompt_len=16, max_tokens=3) for i in range(8)]
        anchor = time.monotonic() + 0.2
        recs, _job = gen.start(plan, anchor)
        t_end = anchor + 2.0
        while not gen.done(recs) and time.monotonic() < t_end + 5:
            time.sleep(0.4)             # a serve tick holding the loop
            await asyncio.sleep(0)
        gen.close()
        srv.close()
        await srv.wait_closed()
        return recs

    recs = asyncio.run(main())
    assert all(r.state == "done" for r in recs)
    assert all(r.tokens == [7, 8, 9] for r in recs)
    assert max(r.late for r in recs) < 0.2
    # the tokens were read after the send, on the generator's clock
    assert all(r.recv[0] >= r.plan.t for r in recs)
