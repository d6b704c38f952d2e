"""Network front door e2e: HTTP/SSE serving over real tiny engines.

The contracts under test, each at the socket (a real TCP client
against a listening server, never an in-process shortcut):

- **streaming bit-parity**: tokens streamed over SSE equal the
  generated suffix of the final output, and the final output is
  bit-identical to in-process single-engine serving;
- **disconnect cancellation**: a client that vanishes mid-stream
  triggers engine-level teardown — pool pages return to baseline and
  ``audit_kv_sharing()`` stays clean;
- **deadlines**: a burned deadline is a typed 429 at the front door; a
  deadline expiring in the queue surfaces as an SSE ``error`` event;
- **graceful drain**: SIGTERM stops admission (503 + Retry-After)
  while in-flight streams finish with ZERO dropped tokens, then the
  handoff callback runs;
- **observability**: ``/metrics`` serves the dstpu_http_* series and
  the ``cat="http"`` trace events pass ``trace_summarize``'s schema
  gate.
"""
import asyncio
import json
import os
import signal
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                     # noqa: E402

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2  # noqa: E402
from deepspeed_tpu.models.llama import (LlamaForCausalLM,       # noqa: E402
                                        get_config)
from deepspeed_tpu.resilience import faults                      # noqa: E402
from deepspeed_tpu.serving import (FrontDoorServer, ReplicaSet,  # noqa: E402
                                   Router)
from deepspeed_tpu.serving.client import LoadGenerator, sse_generate  # noqa: E402
from deepspeed_tpu.telemetry import (flight,                     # noqa: E402
                                     read_flight_record,
                                     tracer as tracer_mod)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                ".."))
from scripts.trace_summarize import validate_events              # noqa: E402

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=True, remat=False,
                 use_flash_attention=False)


@pytest.fixture(scope="module")
def params():
    model = LlamaForCausalLM(CFG)
    return jax.jit(model.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))


def _engine(params):
    return RaggedInferenceEngineV2(
        LlamaForCausalLM(CFG), params=params, pipeline=True,
        rng=jax.random.PRNGKey(11), max_seqs=4, max_seq_len=128,
        prefill_chunk=8, decode_block_size=4, harvest_interval=3)


def _prompts(sizes, seed=3):
    r = np.random.default_rng(seed)
    return [r.integers(1, 64, size=(s,), dtype=np.int32) for s in sizes]


def _reference(params, prompts, max_new):
    eng = _engine(params)
    order = {eng.put_request(p, max_new_tokens=max_new): i
             for i, p in enumerate(prompts)}
    outs = {}
    while eng.has_work():
        eng.step()
        for uid, toks in eng.get_outputs():
            outs[order[uid]] = toks
    eng.sync()
    for uid, toks in eng.get_outputs():
        outs[order[uid]] = toks
    eng.close()
    return outs


@pytest.fixture(scope="module")
def served(params):
    """Two live replicas behind a listening front door (shared by the
    non-drain tests; the drain test builds its own server)."""
    rs = ReplicaSet(lambda i: _engine(params), 2)
    router = Router(rs, policy="least_tokens")
    srv = FrontDoorServer(router, port=0).start()
    yield srv, router, rs
    srv.close()
    rs.close()


async def _raw(host, port, request: bytes) -> bytes:
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(request)
    await writer.drain()
    data = await reader.read(-1)
    writer.close()
    return data


def _get(srv, path) -> bytes:
    return asyncio.run(_raw(
        srv.host, srv.port,
        f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode()))


def _post(srv, body: bytes, path="/v1/generate") -> bytes:
    return asyncio.run(_raw(
        srv.host, srv.port,
        (f"POST {path} HTTP/1.1\r\nHost: x\r\n"
         f"Content-Type: application/json\r\n"
         f"Content-Length: {len(body)}\r\n\r\n").encode() + body))


def _quiesce(router, timeout=15.0):
    """Wait until the router (pump thread) has nothing outstanding —
    only then is it safe to read engine state from the test thread."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if router.outstanding == 0 and router.queued == 0:
            time.sleep(0.1)       # let in-flight step ops fold
            if router.outstanding == 0:
                return
        time.sleep(0.02)
    raise AssertionError("router never quiesced")


class TestRoutesAndValidation:
    def test_healthz(self, served):
        srv, _, _ = served
        raw = _get(srv, "/healthz")
        assert raw.startswith(b"HTTP/1.1 200")
        body = json.loads(raw.split(b"\r\n\r\n", 1)[1])
        assert body == {"status": "ok", "replicas": 2}

    def test_unknown_path_404_and_bad_method_405(self, served):
        srv, _, _ = served
        assert _get(srv, "/nope").startswith(b"HTTP/1.1 404")
        assert _get(srv, "/v1/generate").startswith(b"HTTP/1.1 405")

    def test_malformed_bodies_400(self, served):
        srv, _, _ = served
        assert _post(srv, b"{not json").startswith(b"HTTP/1.1 400")
        assert _post(srv, b'{"prompt": []}').startswith(b"HTTP/1.1 400")
        assert _post(srv, b'{"prompt": [1], "wat": 1}').startswith(
            b"HTTP/1.1 400")
        # never-schedulable surfaces as a typed 400 too
        big = json.dumps({"prompt": [1] * 120,
                          "max_new_tokens": 120}).encode()
        raw = _post(srv, big)
        assert raw.startswith(b"HTTP/1.1 400"), raw[:200]
        assert b"NeverSchedulableRejection" in raw

    def test_burned_deadline_is_typed_429(self, served):
        srv, router, _ = served
        res = asyncio.run(sse_generate(
            srv.host, srv.port,
            {"prompt": [1, 2, 3], "max_new_tokens": 4,
             "deadline_ms": 0.0}))
        assert res["status"] == 429
        assert res["error"] == "DeadlineRejection"
        assert router.stats_counters["rejected_deadline"] >= 1
        # the Retry-After header rides the 429
        raw = _post(srv, json.dumps(
            {"prompt": [1, 2, 3], "deadline_ms": -1}).encode())
        assert b"Retry-After:" in raw


class TestStreaming:
    def test_sse_bit_parity_with_inprocess(self, served, params):
        srv, router, _ = served
        prompts = _prompts((5, 9, 13, 7, 11, 6, 8, 10))
        ref = _reference(params, prompts, max_new=12)
        gen = LoadGenerator(
            srv.host, srv.port,
            lambda i: {"prompt": prompts[i].tolist(),
                       "max_new_tokens": 12},
            requests=len(prompts), concurrency=8)
        summary = gen.run()
        assert summary["completed"] == len(prompts), summary
        for r in gen.results:
            i = r["i"]
            np.testing.assert_array_equal(
                r["final"], ref[i],
                err_msg=f"request {i} diverged over the socket")
            # streamed tokens are exactly the generated suffix
            assert r["tokens"] == list(ref[i][len(prompts[i]):]), i
            # harvest granularity: more than one tokens event per
            # stream (harvest_interval 3 over 12 new tokens)
            assert r["events"] >= 3, (i, r["events"])
        assert summary["ttft_ms_p50"] > 0

    def test_buffered_mode_matches(self, served, params):
        srv, _, _ = served
        (p,) = _prompts((6,), seed=9)
        ref = _reference(params, [p], max_new=8)[0]
        res = asyncio.run(sse_generate(
            srv.host, srv.port,
            {"prompt": p.tolist(), "max_new_tokens": 8,
             "stream": False}))
        assert res["status"] == 200 and res["error"] is None
        np.testing.assert_array_equal(res["final"], ref)

    def test_metrics_endpoint_serves_http_series(self, served):
        srv, _, _ = served
        raw = _get(srv, "/metrics")
        assert raw.startswith(b"HTTP/1.1 200")
        text = raw.split(b"\r\n\r\n", 1)[1].decode()
        assert "dstpu_http_requests_total" in text
        assert "dstpu_http_ttft_ms" in text
        assert "dstpu_http_active_streams" in text

    def test_disconnect_mid_stream_reclaims_pages(self, served):
        srv, router, rs = served
        _quiesce(router)
        free0 = [h.engine.allocator.free_pages for h in rs.handles]
        cancels0 = sum(h.engine.cancels for h in rs.handles)
        (p,) = _prompts((8,), seed=17)
        res = asyncio.run(sse_generate(
            srv.host, srv.port,
            {"prompt": p.tolist(), "max_new_tokens": 64},
            abort_after_events=1))
        assert res["error"] == "client_abort"
        assert len(res["tokens"]) < 64, "aborted before completion"
        # the disconnect must propagate: engine cancel, pages home
        t0 = time.monotonic()
        while time.monotonic() - t0 < 20.0:
            if (sum(h.engine.cancels for h in rs.handles) > cancels0
                    and router.outstanding == 0
                    and [h.engine.allocator.free_pages
                         for h in rs.handles] == free0):
                break
            time.sleep(0.05)
        assert sum(h.engine.cancels for h in rs.handles) == cancels0 + 1
        assert ([h.engine.allocator.free_pages for h in rs.handles]
                == free0), (
            "pool pages not reclaimed after client disconnect")
        _quiesce(router)
        for h in rs.handles:
            h.engine.audit_kv_sharing()
        assert router.stats_counters["cancelled"] >= 1

    def test_8_concurrent_streams(self, served):
        # tier-1 sibling of the slow 64-stream case
        srv, _, _ = served
        prompts = _prompts((6,) * 8, seed=21)
        gen = LoadGenerator(
            srv.host, srv.port,
            lambda i: {"prompt": prompts[i].tolist(),
                       "max_new_tokens": 6},
            requests=8, concurrency=8)
        summary = gen.run()
        assert summary["completed"] == 8, summary

    @pytest.mark.slow
    def test_64_concurrent_streams(self, params):
        # a router provisioned for the burst (queue_cap 40 x 2
        # replicas): all 64 simultaneous streams must be admitted,
        # stream to completion, and leave the router empty
        rs = ReplicaSet(lambda i: _engine(params), 2)
        router = Router(rs, policy="least_tokens", queue_cap=40)
        srv = FrontDoorServer(router, port=0).start()
        try:
            prompts = _prompts((6,) * 64, seed=22)
            gen = LoadGenerator(
                srv.host, srv.port,
                lambda i: {"prompt": prompts[i].tolist(),
                           "max_new_tokens": 6},
                requests=64, concurrency=64)
            summary = gen.run()
            assert summary["completed"] == 64, summary
            assert summary["tokens_streamed"] == 64 * 6
            assert router.outstanding == 0
        finally:
            srv.close()
            rs.close()


class TestReplicaDeathMidStream:
    def test_greedy_streams_survive_death_no_duplicates(
            self, params, tmp_path, monkeypatch):
        # a replica dies mid-serve: greedy requests re-dispatch on the
        # survivor and replay behind the stream watermark — every
        # client sees the exact generated suffix once, bit-identical
        # to the no-fault reference
        monkeypatch.setenv("DSTPU_FLIGHT_DIR", str(tmp_path))
        prompts = _prompts((7, 9, 6, 8), seed=41)
        ref = _reference(params, prompts, max_new=16)
        rs = ReplicaSet(lambda i: _engine(params), 2)
        router = Router(rs, policy="least_tokens")
        srv = FrontDoorServer(router, port=0).start()
        try:
            with faults.FaultInjector(seed=11) as inj:
                inj.io_error("replica.step", after=6, count=1)
                gen = LoadGenerator(
                    srv.host, srv.port,
                    lambda i: {"prompt": prompts[i].tolist(),
                               "max_new_tokens": 16},
                    requests=4, concurrency=4)
                summary = gen.run()
            assert [f[0] for f in inj.fired] == ["replica.step"]
            assert summary["completed"] == 4, summary
            for r in gen.results:
                i = r["i"]
                np.testing.assert_array_equal(
                    r["final"], ref[i],
                    err_msg=f"request {i} diverged across the death")
                assert r["tokens"] == list(ref[i][len(prompts[i]):]), (
                    f"request {i}: mid-stream re-dispatch replayed or "
                    f"dropped streamed tokens")
            s = router.stats()
            assert s["replica_deaths"] == 1 and s["replicas_alive"] == 1
            header, _events = read_flight_record(flight.last_dump_path())
            assert header["reason"].startswith("replica_death_")
        finally:
            srv.close()
            rs.close()

    def test_sampled_stream_gets_typed_replica_death_error(
            self, params, tmp_path, monkeypatch):
        # a SAMPLED stream cannot be replayed after tokens are on the
        # wire (a survivor would sample a different continuation): the
        # death must surface as a typed SSE error, never a silent
        # truncation or a contradictory resumption
        monkeypatch.setenv("DSTPU_FLIGHT_DIR", str(tmp_path))
        rs = ReplicaSet(lambda i: _engine(params), 2)
        router = Router(rs, policy="least_tokens")
        srv = FrontDoorServer(router, port=0).start()
        try:
            async def scenario():
                from deepspeed_tpu.serving import protocol as proto
                body = json.dumps({"prompt": [1, 2, 3, 4, 5, 6, 7],
                                   "max_new_tokens": 64,
                                   "do_sample": True,
                                   "temperature": 0.9}).encode()
                ra, wa = await asyncio.open_connection(srv.host,
                                                       srv.port)
                wa.write((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                          f"Content-Length: {len(body)}\r\n\r\n"
                          ).encode() + body)
                await wa.drain()
                head = await ra.readuntil(b"\r\n\r\n")
                assert b"200" in head.split(b"\r\n")[0]
                parser = proto.SSEParser()
                events = []
                while not any(e == "tokens" for e, _ in events):
                    events += parser.feed(await ra.read(4096))
                # tokens are on the wire: NOW the replica dies
                with faults.FaultInjector(seed=13) as inj:
                    inj.io_error("replica.step", count=1)
                    while not any(e == "error" for e, _ in events):
                        chunk = await ra.read(4096)
                        assert chunk, ("stream closed without the "
                                       "typed error event")
                        events += parser.feed(chunk)
                    assert inj.fired, "fault never fired"
                wa.close()
                return events

            events = asyncio.run(scenario())
            err = next(json.loads(d) for e, d in events if e == "error")
            assert err["error"] == "replica_death"
            assert not any(e == "done" for e, _ in events)
            s = router.stats()
            assert s["failed_replica_death"] == 1, s
            assert s["replica_deaths"] == 1
        finally:
            srv.close()
            rs.close()


@pytest.fixture
def http_trace():
    tr = tracer_mod.trace
    prev = (tr.enabled, tr.buffer_size, tr.clock)
    tr.clear()
    tr.configure(enabled=True)
    yield tr
    tr.configure(enabled=prev[0], buffer_size=prev[1], clock=prev[2])
    tr.clear()


class TestDrainAndTrace:
    def test_sigterm_drain_zero_dropped_tokens(self, params, tmp_path,
                                               http_trace):
        (p,) = _prompts((7,), seed=31)
        ref = _reference(params, [p], max_new=24)[0]
        rs = ReplicaSet(lambda i: _engine(params), 1)
        router = Router(rs, policy="rr")
        srv = FrontDoorServer(
            router, port=0,
            handoff=lambda r: {"finished":
                               r.stats_counters["finished"]}).start()
        srv.install_signal_handlers()
        try:
            async def scenario():
                from deepspeed_tpu.serving import protocol as proto
                body = json.dumps({"prompt": p.tolist(),
                                   "max_new_tokens": 24}).encode()
                ra, wa = await asyncio.open_connection(srv.host,
                                                       srv.port)
                wa.write((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                          f"Content-Length: {len(body)}\r\n\r\n"
                          ).encode() + body)
                await wa.drain()
                head = await ra.readuntil(b"\r\n\r\n")
                assert b"200" in head.split(b"\r\n")[0]
                parser = proto.SSEParser()
                events = []
                # wait for the FIRST streamed token, then drain
                while not any(e == "tokens" for e, _ in events):
                    events += parser.feed(await ra.read(4096))
                os.kill(os.getpid(), signal.SIGTERM)   # -> begin_drain
                # draining: a NEW request gets 503 + Retry-After while
                # the in-flight stream keeps going
                t0 = time.monotonic()
                while not srv.draining:
                    assert time.monotonic() - t0 < 5.0
                    await asyncio.sleep(0.01)
                raw = await _raw(srv.host, srv.port,
                                 (f"POST /v1/generate HTTP/1.1\r\n"
                                  f"Host: x\r\n"
                                  f"Content-Length: {len(body)}\r\n"
                                  f"\r\n").encode() + body)
                assert raw.startswith(b"HTTP/1.1 503"), raw[:200]
                assert b"Retry-After:" in raw
                assert b"DrainingRejection" in raw
                # the in-flight stream finishes with every token
                while not any(e == "done" for e, _ in events):
                    chunk = await ra.read(4096)
                    assert chunk, "stream truncated during drain"
                    events += parser.feed(chunk)
                wa.close()
                return events

            events = asyncio.run(scenario())
            streamed = [t for e, d in events if e == "tokens"
                        for t in json.loads(d)["tokens"]]
            done = next(json.loads(d) for e, d in events if e == "done")
            # zero dropped tokens: the done event carries the full
            # sequence, the streamed tokens are its exact suffix, and
            # both match the in-process reference bit-for-bit
            np.testing.assert_array_equal(done["tokens"], ref)
            assert streamed == list(ref[len(p):])
            assert done["streamed"] == len(streamed)
            assert srv.wait_drained(30.0), "drain never completed"
            assert srv.handoff_result == {"finished": 1}
        finally:
            srv.close()
            rs.close()
        # the http span schema holds end-to-end
        path = str(tmp_path / "frontdoor_trace.json")
        http_trace.export(path)
        with open(path) as f:
            evs = json.load(f)["traceEvents"]
        assert validate_events(evs) == []
        names = {e["name"] for e in evs if e.get("cat") == "http"}
        assert {"http_accept", "http_parse", "http_admit",
                "http_stream", "http_flush",
                "http_close"} <= names, names