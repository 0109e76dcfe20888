"""The HTTP client of the serve cells: one asyncio loop in the driver
process, every response streamed, every time read from one clock.

Open loop: a request is sent when it is due, whether or not earlier ones
have come back, and its times count from when it was *due*; how late the
generator sent it is kept beside them.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import List

from .traffic import Request


def _body(request: Request) -> bytes:
    return json.dumps({"prompt_ids": list(request.prompt_ids),
                       "max_tokens": request.max_tokens,
                       "temperature": request.temperature,
                       "stream": True}).encode()


async def _one(session, url: str, request: Request, body: bytes, t0: float,
               tag: str, timeout_s: float) -> dict:
    """Send one request; times are seconds after ``t0``."""
    import aiohttp

    rid = f"{tag}-{request.index}"
    record = {"index": request.index, "rid": rid, "counted": request.counted,
              "due": request.due_s, "sent": time.perf_counter() - t0,
              "first": None, "last": None, "tokens": [], "times": [],
              "ok": False,
              "error": None, "asked": request.max_tokens,
              "prompt_tokens": len(request.prompt_ids)}
    try:
        async with session.post(
                url, data=body, headers={
                    "Content-Type": "application/json",
                    "X-Request-ID": rid},
                timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
            if resp.status != 200:
                record["error"] = f"HTTP {resp.status}: " \
                                  f"{(await resp.text())[:200]}"
                return record
            finished = False
            async for line in resp.content:
                line = line.strip()
                if not line.startswith(b"data:"):
                    continue
                now = time.perf_counter() - t0
                chunk = json.loads(line[len(b"data:"):])
                if record["first"] is None:
                    record["first"] = now
                record["last"] = now
                record["times"].append(now)
                record["tokens"].append(chunk["token"])
                finished = chunk["finished"]
            record["ok"] = finished
            if not finished:
                record["error"] = "stream ended without a finished chunk"
    except asyncio.CancelledError:
        record["error"] = "unfinished when the run ended"
        raise
    except Exception as e:  # noqa: BLE001 - a failed request is a result
        record["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        record["done"] = time.perf_counter() - t0
    return record


async def _session():
    import aiohttp

    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0))


async def sequential(url: str, requests: List[Request], tag: str,
                     timeout_s: float = 600.0) -> List[dict]:
    """One request at a time (warm-up and probes)."""
    t0 = time.perf_counter()
    async with await _session() as session:
        return [await _one(session, url, r, _body(r), t0, tag, timeout_s)
                for r in requests]


async def open_loop(url: str, schedule: List[Request], window_s: float,
                    drain_s: float, tag: str) -> dict:
    """Run ``schedule`` (due times relative to the window's start, the
    lead-in below zero). Returns once every counted request has ended, or
    ``drain_s`` after the window closed; what is still in flight then is
    cancelled and counts as failed. ``{"t0": perf_counter at the window's
    start, "records": [...]}``."""
    bodies = [_body(r) for r in schedule]
    lead = -min(0.0, schedule[0].due_s) + 0.05
    t0 = time.perf_counter() + lead
    records: List[dict] = []
    tasks: List[asyncio.Task] = []
    counted_tasks: List[asyncio.Task] = []
    stop = asyncio.Event()

    async with await _session() as session:

        async def sender() -> None:
            for request, body in zip(schedule, bodies):
                delay = t0 + request.due_s - time.perf_counter()
                if delay > 0:
                    try:
                        await asyncio.wait_for(stop.wait(), delay)
                        return
                    except asyncio.TimeoutError:
                        pass
                if stop.is_set():
                    return
                task = asyncio.ensure_future(_one(
                    session, url, request, body, t0, tag,
                    window_s + drain_s + lead))
                tasks.append(task)
                if request.counted:
                    counted_tasks.append(task)

        send_task = asyncio.ensure_future(sender())
        # the window: every counted request is created by its end
        await asyncio.sleep(max(0.0, t0 + window_s - time.perf_counter())
                            + 0.01)
        deadline = t0 + window_s + drain_s
        while time.perf_counter() < deadline and not all(
                t.done() for t in counted_tasks):
            await asyncio.sleep(0.02)
        stop.set()
        await send_task
        for task in tasks:
            if not task.done():
                task.cancel()
        for task, request in zip(tasks, schedule):
            try:
                records.append(await task)
            except asyncio.CancelledError:
                records.append({
                    "index": request.index, "counted": request.counted,
                    "due": request.due_s, "ok": False, "first": None,
                    "last": None, "tokens": [], "times": [], "sent": None,
                    "asked": request.max_tokens,
                    "error": "unfinished when the run ended"})
    return {"t0": t0, "records": records,
            "ended": time.perf_counter() - t0}
