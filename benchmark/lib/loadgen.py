"""The load generator: one process, one thread, asyncio over plain
sockets. Streams `/v1/generate` and stamps every token as it arrives (the
server writes one JSON line a token in a chunked body).

Open loop: each request is sent when it is due and timed from when it was
due, so a stall shows in the requests behind it; how late the generator
itself ran is recorded.
"""

from __future__ import annotations

import asyncio
import json
import time
from urllib.parse import urlparse

now = time.monotonic


def new_record(req, due: float) -> dict:
    return {"index": req.index, "prompt_len": len(req.prompt),
            "max_new_tokens": req.max_new_tokens, "due": due, "sent": None,
            "status": None, "stamps": [], "tokens": [], "done": None,
            "error": None, "ended": None}


async def stream_request(host: str, port: int, req, rec: dict) -> None:
    body = json.dumps({"prompt": req.prompt, "stream": True,
                       "max_new_tokens": req.max_new_tokens}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: replica\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Connection: close\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
        rec["sent"] = now()
        await writer.drain()
        status = await reader.readline()
        rec["status"] = int(status.split()[1])
        while (await reader.readline()).strip():
            pass                                    # headers
        while True:
            line = await reader.readline()
            if not line:
                break
            if line[:1] != b"{":
                continue                            # chunk sizes, CRLFs
            t = now()
            obj = json.loads(line)
            if "token" in obj:
                rec["stamps"].append(t)
                rec["tokens"].append(obj["token"])
            elif obj.get("done"):
                rec["done"] = obj
            elif "error" in obj:
                rec["error"] = obj["error"]
        rec["ended"] = now()
    except (OSError, ValueError, IndexError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["ended"] = now()
    finally:
        if writer is not None:
            writer.close()


async def get_json(host: str, port: int, path: str) -> dict | None:
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(f"GET {path} HTTP/1.1\r\nHost: replica\r\n"
                     f"Connection: close\r\n\r\n".encode())
        await writer.drain()
        raw = await reader.read()
        writer.close()
        return json.loads(raw.split(b"\r\n\r\n", 1)[1])
    except (OSError, ValueError, IndexError):
        return None


def complete(rec: dict) -> bool:
    return (rec["status"] == 200 and rec["done"] is not None
            and len(rec["tokens"]) == rec["max_new_tokens"])


def judged(client: dict) -> list:
    """The records a window judges: the requests due inside it."""
    return [r for r in client["records"]
            if client["t0"] <= r["due"] < client["t1"]]


async def sample_gauges(host, port, out: list, stop: asyncio.Event,
                        every_s: float = 0.5) -> None:
    while not stop.is_set():
        load = await get_json(host, port, "/v1/load")
        if load is not None:
            out.append({"t": now(), "active_slots": load.get("active_slots"),
                        "n_slots": load.get("n_slots"),
                        "queue_depth": load.get("queue_depth")})
        try:
            await asyncio.wait_for(stop.wait(), every_s)
        except asyncio.TimeoutError:
            pass


async def warm_up(host, port, lengths, make_request) -> list:
    """One short request of every prompt length the mix holds, in turn:
    each compiles (or loads) its prefill program, the first also the
    decode step and the eager admission ops."""
    recs = []
    for n in lengths:
        req = make_request(n)
        rec = new_record(req, now())
        await stream_request(host, port, req, rec)
        recs.append(rec)
    return recs


async def open_loop(host, port, requests, seconds: float, ramp_s: float,
                    drain_s: float, hooks) -> dict:
    """Send each request at t_first + due_s. The window is
    [t0, t0 + seconds) with t0 = t_first + ramp_s: the requests due before
    it fill the replica to its steady state, those due in it are the ones
    measured, and they are waited for after it closes."""
    t_first = now() + 0.05
    t0 = t_first + ramp_s
    t1 = t0 + seconds
    hooks.window_open(t0)
    records, tasks = [], []
    for req in requests:
        due = t_first + req.due_s
        if due >= t1:
            break
        delay = due - now()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = new_record(req, due)
        records.append(rec)
        tasks.append(asyncio.create_task(
            stream_request(host, port, req, rec)))
    delay = t1 - now()
    if delay > 0:
        await asyncio.sleep(delay)
    hooks.window_closed()
    if tasks:
        _, pending = await asyncio.wait(tasks, timeout=drain_s)
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
    return {"t0": t0, "t1": t1, "records": records}


def endpoint_hostport(url: str) -> tuple:
    u = urlparse(url)
    return u.hostname, u.port
