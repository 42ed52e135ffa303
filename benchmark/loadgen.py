#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX.

Plain ``asyncio`` + ``aiohttp`` over real sockets, so the client's coroutines
do not share the server's interpreter lock. It replays a schedule written by
``traffic.write_schedule`` (one JSON line per request, ``due_s`` relative to
``--t0`` on ``time.monotonic()``, which all processes of one machine share),
open loop: a request is sent when it is due, whatever the server is doing,
and the result says how late each send ran.

Every SSE frame must be ``data: <json>``, the stream must end with
``data: [DONE]`` and nothing may follow it (the frame checks of
``chip_smoke.py::_post_stream``, copied). With the benchmark's tokenizer a
content frame carries ``len(text.split())`` tokens, and each token is stamped
with the arrival time of its frame. The empty ``{"role": "assistant"}`` frame
carries none.

A line ``stop`` on standard input stops the sending of requests that are not
yet due; those in flight are read to their end.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time


async def one_request(session, url: str, rec: dict, t0: float, stop: asyncio.Event, gate: dict) -> dict:
    due = t0 + rec["due_s"]
    out = {"rid": rec["rid"], "counted": rec["counted"], "due": due, "max_tokens": rec["max_tokens"],
           "prompt_tokens": rec["prompt_tokens"], "sent": None, "frames": [], "error": None,
           "usage": None, "finish_reason": None, "done": False}
    delay = due - time.monotonic()
    if delay > 0:
        sleeper = asyncio.ensure_future(asyncio.sleep(delay))
        stopper = asyncio.ensure_future(stop.wait())
        await asyncio.wait([sleeper, stopper], return_when=asyncio.FIRST_COMPLETED)
        sleeper.cancel()
        stopper.cancel()
    if stop.is_set() and time.monotonic() < due:
        out["error"] = "not_sent"
        return out
    out["sent"] = time.monotonic()
    if gate["max"] and gate["inflight"] >= gate["max"]:
        out["error"] = "shed"  # warm-up only: a stalled server is not offered a growing backlog
        return out
    gate["inflight"] += 1
    try:
        async with session.post(url, json=rec["body"]) as resp:
            if resp.status != 200:
                out["error"] = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return out
            if not resp.headers.get("Content-Type", "").startswith("text/event-stream"):
                out["error"] = "not an SSE response"
                return out
            async for raw in resp.content:
                now = time.monotonic()
                line = raw.decode("utf-8").rstrip("\r\n")
                if not line:
                    continue
                if out["done"]:
                    out["error"] = f"SSE frame after [DONE]: {line[:80]}"
                    return out
                if not line.startswith("data: "):
                    out["error"] = f"malformed SSE line: {line[:80]!r}"
                    return out
                payload = line[len("data: "):]
                if payload == "[DONE]":
                    out["done"] = True
                    continue
                frame = json.loads(payload)
                choice = frame["choices"][0]
                text = (choice.get("delta") or {}).get("content")
                if text:
                    n = len(text.split())
                    if n:
                        out["frames"].append([now, n])
                if choice.get("finish_reason"):
                    out["finish_reason"] = choice["finish_reason"]
                    out["usage"] = frame.get("usage")
    except Exception as e:  # noqa: BLE001 - any failure is a failed request, reported
        out["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        gate["inflight"] -= 1
    return out


async def watch_stdin(stop: asyncio.Event) -> None:
    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)
    while True:
        line = await reader.readline()
        if not line or line.strip() == b"stop":
            stop.set()
            return


REQUEST_TIMEOUT_S = 600.0  # a request that hangs fails the run; the runner watches the engine meanwhile


async def amain(args) -> int:
    import aiohttp

    with open(args.schedule) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    stop = asyncio.Event()
    watcher = asyncio.ensure_future(watch_stdin(stop))
    timeout = aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        gate = {"max": args.max_inflight, "inflight": 0}
        results = await asyncio.gather(*[one_request(session, args.url, r, args.t0, stop, gate) for r in recs])
    watcher.cancel()
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"t0": args.t0, "finished": time.monotonic(), "requests": results}, f)
    os.replace(tmp, args.out)
    return 0


def main() -> int:
    p = argparse.ArgumentParser(allow_abbrev=False)
    p.add_argument("--url", required=True)
    p.add_argument("--schedule", required=True)
    p.add_argument("--t0", type=float, required=True, help="time.monotonic() at which due_s = 0")
    p.add_argument("--out", required=True)
    p.add_argument("--max-inflight", type=int, default=0, help="0 = open loop; else shed what is due beyond it")
    args = p.parse_args()
    assert "jax" not in sys.modules
    return asyncio.run(amain(args))


if __name__ == "__main__":
    sys.exit(main())
