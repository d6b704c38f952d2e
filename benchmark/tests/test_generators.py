"""The generators are pure functions of the seed; every seed gets the same
set of sizes; the load generator times from due and reports lateness."""
import asyncio
import json

import numpy as np

from benchmark import harness as H
from benchmark import loadgen
from benchmark.generators import independent_requests, packed_documents


def _chat():
    return H.load_json(H.HERE + "/traffic/chat-open.json")


def test_schedule_is_a_pure_function_of_the_seed():
    a = independent_requests.make(_chat(), 2 ** 31 + 5, 20.0, 32000)
    b = independent_requests.make(_chat(), 2 ** 31 + 5, 20.0, 32000)
    c = independent_requests.make(_chat(), 7, 20.0, 32000)
    assert a == b
    assert a != c


def test_every_seed_gets_the_same_schedule_and_other_tokens():
    t = _chat()
    a = independent_requests.make(t, 1, 20.0, 32000)["requests"]
    b = independent_requests.make(t, 2, 20.0, 32000)["requests"]

    def sizes(reqs, window):
        return [(r["due"], len(r["prompt"]), r["max_new_tokens"])
                for r in reqs if (r["due"] >= 0) == window]

    assert sizes(a, True) == sizes(b, True)
    assert sizes(a, False) == sizes(b, False)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    n = round(t["rate_per_s"] * 20.0)
    assert len(sizes(a, True)) == n
    dues = [r["due"] for r in a if r["due"] >= 0]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 20.0
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)


def test_packed_documents_are_seeded_and_fresh_each_step():
    t = H.load_json(H.HERE + "/traffic/pretrain-4k.json")
    g = packed_documents.make(t, 3, 32000, 4, 256)
    x0, x1 = g.batch(0)["input_ids"], g.batch(1)["input_ids"]
    assert x0.shape == (4, 256) and x0.dtype == np.int32
    assert not np.array_equal(x0, x1)
    assert np.array_equal(x0, packed_documents.make(
        t, 3, 32000, 4, 256).batch(0)["input_ids"])
    assert (x0 == t["separator_id"]).any() and x0.max() < 32000


class _Server:
    """A loopback SSE server that answers after ``delay`` seconds with the
    prompt echoed and ``max_new_tokens`` tokens in two events."""

    def __init__(self, delay):
        self.delay = delay

    async def handle(self, reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        n = int([l for l in head.split(b"\r\n")
                 if l.lower().startswith(b"content-length")][0].split(b":")[1])
        body = json.loads(await reader.readexactly(n))
        await asyncio.sleep(self.delay)
        toks = list(range(100, 100 + body["max_new_tokens"]))
        writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/event-stream"
                     b"\r\n\r\n")
        for part in (toks[:1], toks[1:]):
            writer.write(f"event: tokens\ndata: "
                         f"{json.dumps({'tokens': part})}\n\n".encode())
            await writer.drain()
            await asyncio.sleep(0.02)
        writer.write(f"event: done\ndata: "
                     f"{json.dumps({'tokens': body['prompt'] + toks})}"
                     f"\n\n".encode())
        await writer.drain()
        writer.close()


def test_loadgen_times_from_due_and_reports_lateness():
    async def main():
        srv = await asyncio.start_server(_Server(0.05).handle,
                                         "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        # the first request was due 0.2 s BEFORE the generator started: it
        # is sent at once, 0.2 s late, and its TTFT still counts from due
        plan = {"host": "127.0.0.1", "port": port, "loop": "open",
                "t0": loadgen.now(), "window_s": 0.5, "drain_s": 5.0,
                "requests": [
                    {"due": -0.2, "prompt": [1, 2, 3], "max_new_tokens": 4},
                    {"due": 0.3, "prompt": [4, 5], "max_new_tokens": 3}]}
        recs = await loadgen._run_open(plan)
        srv.close()
        return plan, sorted(recs, key=lambda r: r["i"])

    plan, (late, ontime) = asyncio.run(main())
    assert late["ok"] and ontime["ok"]
    assert late["send_t"] - late["due_t"] >= 0.2        # lateness reported
    assert late["first_t"] - late["due_t"] >= 0.25      # TTFT from due
    assert 0 <= ontime["send_t"] - ontime["due_t"] < 0.05
    assert ontime["streamed"] == 3 and late["streamed"] == 4
    assert ontime["last_t"] > ontime["first_t"]


def test_loadgen_marks_a_wrong_answer():
    async def main():
        async def bad(reader, writer):
            await reader.readuntil(b"\r\n\r\n")
            writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: "
                         b"text/event-stream\r\n\r\n"
                         b"event: tokens\ndata: {\"tokens\": [9]}\n\n"
                         b"event: done\ndata: {\"tokens\": [1, 9]}\n\n")
            await writer.drain()
            writer.close()
        srv = await asyncio.start_server(bad, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        rec = await loadgen.generate("127.0.0.1", port, [1], 2)
        srv.close()
        return rec

    rec = asyncio.run(main())
    assert not rec["ok"] and rec["error"] == "generated_1_of_2"
