"""Unit tests for the unified telemetry substrate.

Covers the tracer (span nesting, per-thread bounded rings, the
disabled-path zero-allocation contract, Chrome-trace export schema),
the nearest-rank percentile math against hand-computed fixtures, the
per-request latency tracker, and the flight recorder (ring bounds,
dump-on-fault per hard-failure exception class, truncation detection,
per-destination dedupe).
"""
import json
import os
import threading

import pytest

from deepspeed_tpu.telemetry import (RequestLatencyTracker, flight,
                                     percentile, read_flight_record)
from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.telemetry.tracer import Tracer


class ManualClock:
    """Injectable monotonic source the tests advance by hand."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture
def global_trace():
    """The process singleton, restored to its prior configuration."""
    tr = tracer_mod.trace
    prev = (tr.enabled, tr.buffer_size, tr.clock)
    tr.clear()
    yield tr
    tr.configure(enabled=prev[0], buffer_size=prev[1], clock=prev[2])
    tr.clear()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_disabled_span_is_shared_singleton(self):
        """The disabled fast path allocates nothing: every span() call
        returns the SAME no-op object, events/add_complete are no-ops."""
        tr = Tracer(enabled=False)
        s1 = tr.span("a")
        s2 = tr.span("b", big_attr="x" * 1000)
        assert s1 is s2
        assert s1 is tracer_mod._NULL_SPAN
        with s1:
            pass
        tr.event("never", uid=1)
        tr.add_complete("never", 0.0, 1.0)
        assert tr.snapshot() == []

    def test_span_records_complete_event(self):
        clk = ManualClock()
        tr = Tracer(enabled=True, clock=clk)
        clk.t = 2.0
        with tr.span("swap_in_wait", cat="swap", bucket=3):
            clk.t = 2.5
        (ev,) = tr.snapshot()
        assert ev["ph"] == "X"
        assert ev["name"] == "swap_in_wait"
        assert ev["cat"] == "swap"
        assert ev["ts"] == pytest.approx(2.0e6)       # us since epoch=0
        assert ev["dur"] == pytest.approx(0.5e6)
        assert ev["args"] == {"bucket": 3}
        assert ev["tid"] == threading.get_ident()

    def test_span_nesting_is_contained(self):
        clk = ManualClock()
        tr = Tracer(enabled=True, clock=clk)
        clk.t = 1.0
        with tr.span("outer"):
            clk.t = 2.0
            with tr.span("inner"):
                clk.t = 3.0
            clk.t = 5.0
        inner, outer = tr.snapshot()    # ts-sorted: outer@1.0 first
        assert (inner["name"], outer["name"]) == ("outer", "inner")
        inner, outer = outer, inner
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert inner["dur"] == pytest.approx(1.0e6)
        assert outer["dur"] == pytest.approx(4.0e6)

    def test_span_exception_tags_error_and_propagates(self):
        tr = Tracer(enabled=True, clock=ManualClock())
        with pytest.raises(KeyError):
            with tr.span("doomed", cat="swap"):
                raise KeyError("boom")
        (ev,) = tr.snapshot()
        assert ev["args"]["error"] == "KeyError"

    def test_event_is_instant(self):
        clk = ManualClock(t=0.0)
        tr = Tracer(enabled=True, clock=clk)
        clk.t = 1.5
        tr.event("request_submit", cat="request", uid=7)
        (ev,) = tr.snapshot()
        assert ev["ph"] == "i"
        assert ev["cat"] == "request"
        assert ev["args"] == {"uid": 7}
        assert ev["ts"] == pytest.approx(1.5e6)

    def test_add_complete_shares_clock(self):
        """Adapters hand in externally bracketed (t0, dt) pairs read
        from the SAME clock; ts/dur must line up with span() output."""
        clk = ManualClock()
        tr = Tracer(enabled=True, clock=clk)
        tr.add_complete("bucket_update", start=4.0, dur_s=0.25,
                        cat="swap", bytes=123)
        (ev,) = tr.snapshot()
        assert ev["ts"] == pytest.approx(4.0e6)
        assert ev["dur"] == pytest.approx(0.25e6)
        assert ev["args"]["bytes"] == 123

    def test_per_thread_rings_are_bounded_and_isolated(self):
        tr = Tracer(enabled=True, buffer_size=16, clock=ManualClock())
        # keep every worker alive until all have recorded — a finished
        # thread's ident can be reused, which would merge two timelines
        barrier = threading.Barrier(3)

        def work(i):
            for k in range(30):
                with tr.span(f"thread{i}", idx=k):
                    pass
            barrier.wait()

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = tr.snapshot()
        by_tid = {}
        for ev in evs:
            by_tid.setdefault(ev["tid"], []).append(ev)
        assert len(by_tid) == 3
        for tid, tevs in by_tid.items():
            assert len(tevs) == 16          # ring dropped the oldest 14
            names = {ev["name"] for ev in tevs}
            assert len(names) == 1          # no cross-thread bleed
            # the ring keeps the most RECENT events
            assert {ev["args"]["idx"] for ev in tevs} == set(range(14, 30))

    def test_configure_mutates_singleton_in_place(self, global_trace):
        """Modules import ``trace`` by value at import time; configure
        must mutate that same object, never rebind it."""
        tr = global_trace
        before = id(tr)
        clk = ManualClock()
        assert tracer_mod.configure(enabled=True, buffer_size=4,
                                    clock=clk) is tr
        assert id(tracer_mod.trace) == before
        for i in range(9):
            tr.event("e", i=i)
        assert len(tr.snapshot()) == 4
        tr.configure(enabled=False)
        tr.event("after_disable")
        assert len(tr.snapshot()) == 4

    def test_export_chrome_trace_schema(self, tmp_path):
        clk = ManualClock()
        tr = Tracer(enabled=True, clock=clk)
        clk.t = 1.0
        with tr.span("apply", cat="swap", buckets=2):
            clk.t = 1.75
        tr.event("request_submit", cat="request", uid=1)
        path = str(tmp_path / "trace.json")
        assert tr.export(path) == path
        with open(path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert isinstance(evs, list)
        phs = {"X": 0, "i": 0, "M": 0}
        for ev in evs:
            assert ev["ph"] in phs
            phs[ev["ph"]] += 1
            assert isinstance(ev["name"], str) and ev["name"]
            if ev["ph"] == "M":
                continue
            assert ev["pid"] == os.getpid()
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["tid"], int)
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
        assert phs == {"X": 1, "i": 1, "M": 2}   # process + 1 thread name
        names = {ev["name"]: ev for ev in evs}
        assert names["apply"]["args"] == {"buckets": 2}
        assert names["process_name"]["args"]["name"].startswith(
            "deepspeed_tpu")


# ---------------------------------------------------------------------------
# Percentiles + request latency
# ---------------------------------------------------------------------------


class TestPercentile:
    def test_nearest_rank_hand_fixture(self):
        vals = [50.0, 15.0, 35.0, 20.0, 40.0]     # sorted: 15 20 35 40 50
        assert percentile(vals, 50) == 35.0       # rank ceil(2.5) = 3
        assert percentile(vals, 90) == 50.0       # rank ceil(4.5) = 5
        assert percentile(vals, 99) == 50.0
        assert percentile(vals, 1) == 15.0        # rank floors at 1
        decade = list(range(10, 101, 10))
        assert percentile(decade, 50) == 50
        assert percentile(decade, 90) == 90
        assert percentile(decade, 99) == 100      # rank ceil(9.9) = 10

    def test_edge_cases(self):
        assert percentile([], 50) is None
        assert percentile([7.0], 50) == 7.0
        assert percentile([7.0], 99) == 7.0
        assert percentile([3.0, 1.0], 100) == 3.0


class TestRequestLatencyTracker:
    def test_hand_computed_percentiles(self):
        clk = ManualClock()
        tk = RequestLatencyTracker(clock=clk)
        # uid 1: queue 10ms, ttft 50ms, 5 tokens -> tpot (130-50)/4 = 20ms
        clk.t = 0.000
        tk.on_submit(1)
        clk.t = 0.010
        tk.on_admit(1)
        clk.t = 0.050
        tk.on_tokens(1, 1)
        clk.t = 0.130
        tk.on_tokens(1, 5)
        tk.on_finish(1)
        # uid 2: queue 0ms, ttft 20ms, 2 tokens -> tpot 20ms, one spill
        # stalling 30ms
        clk.t = 0.200
        tk.on_submit(2)
        tk.on_admit(2)
        clk.t = 0.220
        tk.on_tokens(2, 1)
        tk.on_spill(2)
        tk.on_restore_stall(2, 0.030)
        clk.t = 0.240
        tk.on_tokens(2, 2)
        tk.on_finish(2)
        s = tk.summary()
        assert s["completed"] == 2
        assert s["submitted"] == 2
        assert s["in_flight"] == 0
        # n=2: p50 rank 1 (min), p99 rank 2 (max)
        assert s["ttft_ms_p50"] == pytest.approx(20.0)
        assert s["ttft_ms_p99"] == pytest.approx(50.0)
        assert s["queue_wait_ms_p50"] == pytest.approx(0.0)
        assert s["queue_wait_ms_p99"] == pytest.approx(10.0)
        assert s["tpot_ms_p50"] == pytest.approx(20.0)
        assert s["tpot_ms_p99"] == pytest.approx(20.0)
        # only the spilled request contributes a stall sample
        assert s["spill_stall_ms_p50"] == pytest.approx(30.0)

    def test_summary_is_flat_and_none_safe(self):
        """write_serving_health flattens one level and keeps numeric
        scalars; an empty tracker must be flat with None percentiles."""
        s = RequestLatencyTracker().summary()
        assert all(not isinstance(v, dict) for v in s.values())
        assert s["ttft_ms_p50"] is None
        assert s["completed"] == 0

    def test_token_hook_idempotent_and_first_admit_wins(self):
        clk = ManualClock()
        tk = RequestLatencyTracker(clock=clk)
        tk.on_submit(1)
        clk.t = 0.005
        tk.on_admit(1)
        clk.t = 0.500
        tk.on_admit(1)                 # re-admit after evict: not queue wait
        clk.t = 0.600
        tk.on_tokens(1, 3)
        clk.t = 0.700
        tk.on_tokens(1, 3)             # unchanged cumulative count: no-op
        clk.t = 0.800
        tk.on_tokens(1, 4)
        tk.on_finish(1)
        s = tk.summary()
        assert s["queue_wait_ms_p50"] == pytest.approx(5.0)
        assert s["ttft_ms_p50"] == pytest.approx(600.0)
        # tpot spans first->last token over 3 increments... tokens=4,
        # (0.8 - 0.6) / (4 - 1) s
        assert s["tpot_ms_p50"] == pytest.approx(200.0 / 3)

    def test_completed_window_is_bounded(self):
        clk = ManualClock()
        tk = RequestLatencyTracker(clock=clk, max_completed=8)
        for uid in range(50):
            tk.on_submit(uid)
            tk.on_finish(uid)
        s = tk.summary()
        assert s["completed"] == 8
        assert s["submitted"] == 50


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def _fault_instances():
    from deepspeed_tpu.inference.kv_tiering import KVRestoreError
    from deepspeed_tpu.resilience.distributed import CollectiveTimeout
    from deepspeed_tpu.resilience.guards import (GradientAnomalyError,
                                                 SwapCorruptionError)
    return [
        ("collective_timeout", CollectiveTimeout("all_reduce deadline")),
        ("swap_corruption", SwapCorruptionError("bucket 3 checksum")),
        ("kv_restore_error", KVRestoreError(7, 2, "page 2 digest")),
        ("gradient_anomaly", GradientAnomalyError("4 consecutive skips")),
    ]


@pytest.mark.faults
class TestFlightRecorder:
    def test_dump_roundtrip_and_ring_bound(self, tmp_path, global_trace):
        global_trace.configure(enabled=True, buffer_size=32,
                               clock=ManualClock())
        for i in range(100):
            global_trace.event("tick", i=i)
        path = flight.dump_on_fault("unit_test", dir=str(tmp_path),
                                    extra={"step": 12})
        assert path is not None and os.path.dirname(path) == str(tmp_path)
        assert flight.last_dump_path() == path
        header, events = read_flight_record(path)
        assert header["reason"] == "unit_test"
        assert header["version"] == 1
        assert header["extra"] == {"step": 12}
        assert header["exception"] is None
        assert len(events) == 32               # the ring bound, not 100
        assert [ev["args"]["i"] for ev in events] == list(range(68, 100))

    @pytest.mark.parametrize("reason,exc",
                             _fault_instances(),
                             ids=lambda v: v if isinstance(v, str) else "")
    def test_dump_per_exception_class(self, tmp_path, global_trace,
                                      reason, exc):
        global_trace.configure(enabled=True, clock=ManualClock())
        global_trace.event("before_fault", cat="swap")
        path = flight.dump_on_fault(reason, exc, dir=str(tmp_path))
        assert os.path.basename(path).startswith(f"flight_{reason}_")
        header, events = read_flight_record(path)
        assert header["reason"] == reason
        assert header["exception"]["type"] == type(exc).__name__
        assert str(exc) in header["exception"]["message"]
        assert any(ev["name"] == "before_fault" for ev in events)

    def test_dedupe_per_exception_per_destination(self, tmp_path,
                                                  global_trace):
        from deepspeed_tpu.resilience.guards import SwapCorruptionError
        err = SwapCorruptionError("once")
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        p1 = flight.dump_on_fault("swap_corruption", err, dir=a)
        p2 = flight.dump_on_fault("swap_corruption", err, dir=a)
        p3 = flight.dump_on_fault("swap_corruption", err, dir=b)
        assert p1 == p2                 # same exc + same dir: one file
        assert p3 != p1                 # engine copy next to the
        assert os.path.dirname(p3) == b     # emergency checkpoint
        assert len(os.listdir(a)) == 1

    def test_truncated_dump_is_detected(self, tmp_path, global_trace):
        global_trace.configure(enabled=True, clock=ManualClock())
        global_trace.event("tick")
        path = flight.dump_on_fault("trunc", dir=str(tmp_path))
        read_flight_record(path)        # intact: parses
        with open(path) as f:
            lines = f.read().splitlines()
        with open(path, "w") as f:
            f.write("\n".join(lines[:-1]))     # kill mid-write
        with pytest.raises(ValueError, match="truncated"):
            read_flight_record(path)
        # count mismatch is also caught
        with open(path, "w") as f:
            f.write("\n".join(lines[:1] + lines[2:]) + "\n")
        with pytest.raises(ValueError, match="count mismatch"):
            read_flight_record(path)

    def test_dump_never_raises(self, tmp_path, global_trace):
        bad = str(tmp_path / "file_not_dir")
        with open(bad, "w") as f:
            f.write("x")
        assert flight.dump_on_fault("broken", dir=bad) is None

    def test_guard_raise_leaves_parseable_dump(self, tmp_path,
                                               monkeypatch, global_trace):
        """End-to-end: the skipped-step guard's raise site dumps into
        DSTPU_FLIGHT_DIR without any engine plumbing."""
        from deepspeed_tpu.resilience.guards import (GradientAnomalyError,
                                                     SkippedStepGuard)
        monkeypatch.setenv("DSTPU_FLIGHT_DIR", str(tmp_path))
        guard = SkippedStepGuard(bound=2)
        guard.update(True, step=1)
        with pytest.raises(GradientAnomalyError):
            guard.update(True, step=2)
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_gradient_anomaly_")]
        assert len(dumps) == 1
        header, _ = read_flight_record(str(tmp_path / dumps[0]))
        assert header["extra"] == {"step": 2, "consecutive": 2}
        assert header["exception"]["type"] == "GradientAnomalyError"
