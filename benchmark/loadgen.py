#!/usr/bin/env python3
"""The load generator: a process of its own, standard library only.

A corrected copy of ``deepspeed_tpu/serving/client.py``'s generator.  What
was wrong there for a benchmark, and what this one does:

- Arrivals there are ``sleep(expovariate)`` chained, so every late wake-up
  moves the rest of the schedule.  Here every request has an **absolute**
  due time; a late generator catches up and the schedule does not move.
- TTFT there starts when the request is fully written, and a
  ``concurrency`` semaphore holds arrivals back, so a stalled server or a
  late generator shortens what is measured (coordinated omission).  Here
  every time is taken **from the due time**, no semaphore, and how late
  each request was really sent is reported (``send_t - due_t``).
- It ran inside the engine's process and shared its GIL.  This file never
  imports jax or the program: it speaks HTTP/1.1 + SSE over the socket with
  its own parsing, reads its plan from a file and writes its records to
  standard output as one JSON object.

Two loops.  ``open``: each request is sent at ``t0 + due``.  ``closed``:
``clients`` workers, each sending the next request of the list (in order,
round again at the end) as soon as its last one completed, from
``t0 - warmup_s`` until ``t0 + window_s``.

All times are ``time.monotonic()`` (CLOCK_MONOTONIC: the same clock in the
benchmark's process).
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Any, Dict, List, Optional

now = time.monotonic


class SSEParser:
    """``feed(bytes)`` -> completed ``(event, data)`` pairs."""

    def __init__(self) -> None:
        self._buf = b""

    def feed(self, chunk: bytes) -> List[tuple]:
        self._buf += chunk
        out = []
        while b"\n\n" in self._buf:
            block, self._buf = self._buf.split(b"\n\n", 1)
            event, data = "message", []
            for line in block.decode("utf-8").split("\n"):
                if line.startswith("event:"):
                    event = line[6:].strip()
                elif line.startswith("data:"):
                    data.append(line[5:].strip())
            if data:
                out.append((event, "\n".join(data)))
        return out


async def generate(host: str, port: int, prompt: List[int],
                   max_new_tokens: int, keep_tokens: bool = False
                   ) -> Dict[str, Any]:
    """One streamed ``POST /v1/generate``; the record of what came back.

    ``ok`` only if the stream ended in a ``done`` event whose tokens are
    the prompt followed by exactly ``max_new_tokens`` tokens, equal to the
    tokens streamed."""
    rec: Dict[str, Any] = {"send_t": now(), "first_t": None, "last_t": None,
                           "done_t": None, "streamed": 0, "ok": False,
                           "error": None}
    body = json.dumps({"prompt": prompt, "max_new_tokens": max_new_tokens,
                       "stream": True}).encode("utf-8")
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: " +
                     host.encode("latin-1") +
                     b"\r\nContent-Type: application/json\r\n"
                     b"Content-Length: " + str(len(body)).encode() +
                     b"\r\nConnection: close\r\n\r\n" + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split(b"\r\n", 1)[0].split(b" ")[1])
        if status != 200 or b"text/event-stream" not in head.lower():
            rec["error"] = f"http_{status}"
            return rec
        parser = SSEParser()
        streamed: List[int] = []
        while True:
            chunk = await reader.read(65536)
            if not chunk:
                rec["error"] = "truncated_stream"
                return rec
            for event, data in parser.feed(chunk):
                t = now()
                if event == "tokens":
                    toks = json.loads(data)["tokens"]
                    if toks:
                        if rec["first_t"] is None:
                            rec["first_t"] = t
                        rec["last_t"] = t
                        streamed.extend(int(x) for x in toks)
                elif event == "done":
                    final = [int(x) for x in json.loads(data)["tokens"]]
                    rec["done_t"] = t
                    rec["streamed"] = len(streamed)
                    n = len(prompt)
                    if final[:n] != prompt:
                        rec["error"] = "prompt_not_echoed"
                    elif len(final) != n + max_new_tokens:
                        rec["error"] = (f"generated_{len(final) - n}_of_"
                                        f"{max_new_tokens}")
                    elif streamed != final[n:]:
                        rec["error"] = "streamed_differs_from_final"
                    else:
                        rec["ok"] = True
                    if keep_tokens:
                        rec["generated"] = final[n:]
                    return rec
                elif event == "error":
                    rec["error"] = str(json.loads(data).get("error"))
                    return rec
    except asyncio.CancelledError:
        rec["error"] = "unfinished_at_drain_end"
        return rec
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        rec["error"] = f"conn: {type(e).__name__}: {e}"
        return rec
    finally:
        rec["end_t"] = now()
        if writer is not None:
            writer.transport.abort()


async def _until(tasks: List["asyncio.Future"], end: float) -> None:
    """Wait for ``tasks`` until the drain ends at ``end``; what is still
    unfinished then is cancelled, and records itself as such."""
    _, pending = await asyncio.wait(tasks, timeout=max(end - now(), 0.0))
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending)


async def _run_open(plan: Dict[str, Any]) -> List[Dict[str, Any]]:
    t0 = plan["t0"]
    records: List[Dict[str, Any]] = []

    async def one(i: int, req: Dict[str, Any]) -> None:
        due_t = t0 + req["due"]
        delay = due_t - now()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = await generate(plan["host"], plan["port"], req["prompt"],
                             req["max_new_tokens"],
                             bool(plan.get("keep_tokens")))
        rec.update(i=i, due_t=due_t, prompt_tokens=len(req["prompt"]),
                   max_new_tokens=req["max_new_tokens"])
        records.append(rec)

    await _until([asyncio.ensure_future(one(i, r))
                  for i, r in enumerate(plan["requests"])],
                 t0 + plan["window_s"] + plan["drain_s"])
    return records


async def _run_closed(plan: Dict[str, Any]) -> List[Dict[str, Any]]:
    t0 = plan["t0"]
    start, stop = t0 - plan["warmup_s"], t0 + plan["window_s"]
    reqs = plan["requests"]
    records: List[Dict[str, Any]] = []
    nxt = [0]

    async def client() -> None:
        delay = start - now()
        if delay > 0:
            await asyncio.sleep(delay)
        while now() < stop:
            i = nxt[0]
            nxt[0] += 1
            req = reqs[i % len(reqs)]
            due_t = now()           # a closed loop is due when it is free
            rec = await generate(plan["host"], plan["port"], req["prompt"],
                                 req["max_new_tokens"])
            rec.update(i=i, due_t=due_t, prompt_tokens=len(req["prompt"]),
                       max_new_tokens=req["max_new_tokens"])
            records.append(rec)

    await _until([asyncio.ensure_future(client())
                  for _ in range(int(plan["clients"]))],
                 stop + plan["drain_s"])
    return records


def run_plan(plan: Dict[str, Any]) -> Dict[str, Any]:
    loop = {"open": _run_open, "closed": _run_closed}[plan["loop"]]
    started = now()
    records = asyncio.run(loop(plan))
    records.sort(key=lambda r: r["i"])
    return {"started_t": started, "ended_t": now(), "records": records}


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        plan = json.load(f)
    json.dump(run_plan(plan), sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
