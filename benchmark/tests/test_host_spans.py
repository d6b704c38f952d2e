"""The host-span helper and the readers built on it, on a hand-built
neutral trace: device idle intervals x the worker's spans."""
import threading
import types

import pytest

from benchmark import flops
from benchmark import harness as H
from benchmark import idle_report
from benchmark.layer_metrics import (decode_roofline_counted,
                                     engine_step_host_ms, host_spans,
                                     idle_between_ops_pct, idle_feed_pct,
                                     idle_plan_pct, idle_sample_pct,
                                     idle_unnamed_pct,
                                     prefill_roofline_counted)
from benchmark.tests.test_trace_reduce import _xspace

MS = 1e6
CONFIG = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
              programs={"decode": "jit_decode", "prefill": "jit_prefill"},
              engine={"decode_block_size": 4})
NEW = {"idle_plan_pct", "idle_feed_pct", "idle_sample_pct",
       "idle_between_ops_pct", "idle_unnamed_pct", "engine_step_host_ms",
       "prefill_roofline_counted", "decode_roofline_counted"}


def device():
    # one chip, extent 0-100 ms; busy 0-10, 30-60, 70-100: idle 10-30, 60-70
    return {"/device:TPU:0": {
        "XLA Modules": [("jit_prefill(1)", 0, 10 * MS),
                        ("jit_prefill(1)", 30 * MS, 30 * MS),
                        ("jit_decode(2)", 70 * MS, 30 * MS)],
        "XLA Ops": [("fusion.1", 0, 10 * MS), ("fusion.1", 30 * MS, 30 * MS),
                    ("fusion.2", 70 * MS, 30 * MS)]}}


def worker():
    """One replica op 5-28 ms: a step 6-27 with plan 8-14, upload 14-16,
    dispatch 16-18, a fetch 19-20 and, inside a harvest 20-26, a reap
    22-24.  A second op 62-66: a step with a short dispatch and nothing
    else named."""
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    return [ev("replica.op", 5, 28, kind="step"),
            ev("engine.step", 6, 27),
            ev("engine.plan", 8, 14),
            ev("engine.upload", 14, 16),
            ev("engine.dispatch", 16, 18, program="fused",
               prefill_tokens=100, prefill_key_tokens=100 * 101 // 2,
               decode_key_tokens=50),
            ev("engine.harvest", 20, 26),
            ev("engine.reap", 22, 24),
            ev("engine.fetch", 19, 20),
            ev("replica.op", 62, 66, kind="step"),
            ev("engine.step", 62, 66),
            ev("engine.dispatch", 62, 62.5, program="decode_block", rows=3,
               ticks=4, key_tokens=300)]


def pump():
    """A round 1-2; a request taken in 27-29.5, its prompt hashed 28-29;
    a step folded 60-61."""
    return [("router.pump", 1 * MS, 1 * MS, {}),
            ("router.submit", 27 * MS, 2.5 * MS, {}),
            ("router.prefix_hash", 28 * MS, 1 * MS, {}),
            ("router.fold", 60 * MS, 1 * MS, {})]


def trace(host=None):
    return host_spans.from_planes(
        device(), {0: pump(), 1: worker()} if host is None else host)


def _ctx(ht):
    red = types.SimpleNamespace(
        window_s=ht.window_s,
        program_s={"jit_prefill": 0.040, "jit_decode": 0.030},
        program_runs={"jit_prefill": 2.0, "jit_decode": 1.0})
    return {"cell": types.SimpleNamespace(name="cell", config=CONFIG),
            "trace": red, "_host_trace": ht,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def test_idle_intervals_are_the_complement_of_busy_in_the_extent():
    extent, idle = host_spans.idle_intervals(device())
    assert extent == (0.0, 100 * MS)
    assert idle == [[(10 * MS, 30 * MS), (60 * MS, 70 * MS)]]


def test_segments_give_each_instant_to_its_innermost_classed_span():
    segs = host_spans.segments(worker())
    assert [(a / MS, b / MS, c) for a, b, c in segs] == [
        (5, 8, "unnamed"), (8, 14, "plan"), (14, 18, "feed"),
        (18, 19, "unnamed"), (19, 26, "sample"), (26, 28, "unnamed"),
        (62, 62.5, "feed"), (62.5, 66, "unnamed")]
    # disjoint and in order: a partition of the time inside replica ops
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))


def test_the_five_shares_partition_the_idle_time():
    ht = trace()
    by = host_spans.idle_by_class(ht)
    # idle 10-30: plan 10-14, feed 14-18, unnamed 18-19 + 26-28, sample
    # 19-26, between ops 28-30; idle 60-70: between 60-62 + 66-70, feed
    # 62-62.5, unnamed 62.5-66
    assert by == pytest.approx({"plan": 0.004, "feed": 0.0045,
                                "sample": 0.007, "unnamed": 0.0065,
                                "between_ops": 0.008})
    ctx = _ctx(ht)
    shares = [m.read(ctx) for m in (idle_plan_pct, idle_feed_pct,
                                    idle_sample_pct, idle_between_ops_pct,
                                    idle_unnamed_pct)]
    assert shares == pytest.approx([4.0, 4.5, 7.0, 8.0, 6.5])
    # the identity the metrics are cross-checked by: they sum to the idle
    # share that trace_reduce measures from outside
    from benchmark import trace_reduce
    assert sum(shares) == pytest.approx(
        100.0 * trace_reduce.reduce_planes(device()).idle_share)


def test_a_gap_that_straddles_two_spans_is_split_between_them():
    dev = {"/device:TPU:0": {"XLA Ops": [("a", 0, 10 * MS),
                                         ("b", 20 * MS, 10 * MS)]}}
    host = {3: [("replica.op", 0, 30 * MS, {}),
                ("engine.sample", 8 * MS, 5 * MS, {}),      # 8-13
                ("engine.plan", 13 * MS, 9 * MS, {})]}      # 13-22
    by = host_spans.idle_by_class(host_spans.from_planes(dev, host))
    assert by == pytest.approx({"sample": 0.003, "plan": 0.007, "feed": 0.0,
                                "unnamed": 0.0, "between_ops": 0.0})


def test_between_ops_is_split_by_what_the_pump_thread_was_in():
    """The worker waits 28-30, 60-62 and 66-70 while the chip idles: the
    pump thread hashes a prompt 28-29, is in the rest of ``submit`` to
    29.5, folds a step 60-61, and is in no router span otherwise."""
    ht = trace()
    by = host_spans.between_ops_by_pump(ht)
    assert by == pytest.approx({"router.prefix_hash": 0.001,
                                "router.submit": 0.0005,
                                "router.fold": 0.001, "elsewhere": 0.0055})
    assert sum(by.values()) == pytest.approx(
        host_spans.idle_by_class(ht)["between_ops"])
    # no pump thread (or no worker): nothing to split
    assert host_spans.between_ops_by_pump(trace({1: worker()})) is None
    assert host_spans.between_ops_by_pump(trace({0: pump()})) is None


def test_the_report_names_the_wait_by_pump_span_and_by_the_op_that_ends_it():
    out = idle_report.report(trace())
    assert out["idle_pct"] == pytest.approx(
        {"plan": 4.0, "feed": 4.5, "sample": 7.0, "between_ops": 8.0,
         "unnamed": 6.5})
    assert out["between_ops_by_pump"] == pytest.approx(
        {"router.prefix_hash": 1.0, "router.submit": 0.5,
         "router.fold": 1.0, "elsewhere": 5.5})
    # one wait between the two ops, 28-62, idle 28-30 and 60-62, ended by
    # a step; the edges of the slice (66-70) belong to no pair of ops
    assert out["between_ops_before"] == pytest.approx({"step": 4.0})
    (gap,) = out["largest_gaps"]
    assert (gap["ms"], gap["idle_ms"], gap["before"]) == (34.0, 4.0, "step")
    assert gap["pump_ms"] == pytest.approx(
        {"router.prefix_hash": 1.0, "router.submit": 0.5,
         "router.fold": 1.0, "elsewhere": 31.5})
    assert out["spans"]["engine.dispatch"] == {"n": 2, "ms": 2.5,
                                               "max_ms": 2.0}
    assert out["brackets_per_dispatch"] == 7.5      # 11 + 4 spans, 2 ticks
    assert idle_report.report(trace({0: pump()})) == {}


def test_a_trace_without_the_programs_spans_reads_as_nothing():
    """The parent of the PR that added the spans: every reader returns
    None and the line leaves the metric out."""
    for host in ({}, {0: pump()}):
        ctx = _ctx(trace(host))
        for m in (idle_plan_pct, idle_unnamed_pct, engine_step_host_ms,
                  prefill_roofline_counted, decode_roofline_counted):
            assert m.read(ctx) is None
    # two workers cannot be told apart by chip
    assert trace({1: worker(), 2: worker()}).worker() is None


def test_engine_step_host_ms_is_step_time_less_blocked_time_per_dispatch():
    # steps 21 + 4 ms, fetch 1 ms, two dispatches
    assert engine_step_host_ms.read(_ctx(trace())) == pytest.approx(12.0)
    # a step cut by the slice's start is not recorded, its leaves are:
    # they count neither as blocked time nor as dispatches
    orphans = [("engine.fetch", 1 * MS, 3 * MS, {}),
               ("engine.dispatch", 4 * MS, 0.5 * MS, {"program": "fused"})]
    ht = trace({0: pump(), 1: orphans + worker()})
    assert len(ht.spans("engine.fetch")) == 2
    assert len(ht.spans("engine.fetch", inside="engine.step")) == 1
    assert engine_step_host_ms.read(_ctx(ht)) == pytest.approx(12.0)


def test_counted_rooflines_use_the_spans_counts_per_run():
    ctx = _ctx(trace())
    need = flops.prefill_flops(CONFIG, 100, 50.5)
    assert prefill_roofline_counted.read(ctx) == pytest.approx(
        100.0 * need / 1e12 / 0.020)        # 40 ms over 2 runs
    need = 4 * flops.decode_step_bytes(CONFIG, 300 + 3 * 1.5)
    assert decode_roofline_counted.read(ctx) == pytest.approx(
        100.0 * need / 1e11 / 0.030)


def test_a_stale_trace_directory_gives_none(tmp_path, monkeypatch):
    """``ctx`` carries no path: the newest directory of the cell is read,
    and refused unless its device extent is the one the driver reduced."""
    monkeypatch.setattr(H, "OUT_DIR", str(tmp_path))
    cell = types.SimpleNamespace(name="cell", config=CONFIG)
    assert host_spans.load({"cell": cell, "trace": None}) is None
    red = types.SimpleNamespace(window_s=0.004)
    assert host_spans.load({"cell": cell, "trace": red}) is None  # no dir
    d = tmp_path / "trace-cell-7" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(
        "/device:TPU:0", "XLA Ops",
        [("fusion.1", 0, 2_000_000_000), ("fusion.2", 3_000_000_000,
                                          1_000_000_000)]))
    ht = host_spans.load({"cell": cell, "trace": red})
    assert ht is not None and ht.window_s == pytest.approx(0.004)
    assert ht.idle == [[(pytest.approx(ht.extent[0] + 2e6),
                         pytest.approx(ht.extent[0] + 3e6))]]
    stale = types.SimpleNamespace(window_s=2.5)
    assert host_spans.load({"cell": cell, "trace": stale}) is None
    # another cell's directory is not this cell's
    other = types.SimpleNamespace(name="other", config=CONFIG)
    assert host_spans.load({"cell": other, "trace": red}) is None
    # parsed once a run: the result is kept on ctx
    ctx = {"cell": cell, "trace": red}
    assert host_spans.load(ctx) is host_spans.load(ctx)


def test_the_loader_reads_spans_and_their_counts_from_a_real_profile(
        tmp_path):
    """A profiler session on the CPU: the program's spans are events of
    the ``/host:CPU`` plane with their counts as stats, one line a thread;
    other host events are left out."""
    import jax
    from jax.profiler import TraceAnnotation

    from benchmark import trace_reduce

    def work():
        with TraceAnnotation("replica.op", kind="step"):
            with TraceAnnotation("engine.dispatch", program="fused",
                                 prefill_tokens=512):
                pass
            with TraceAnnotation("something.else"):
                pass

    jax.profiler.start_trace(str(tmp_path))
    try:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    dev, host = host_spans.read_planes(
        trace_reduce.newest_xplane(str(tmp_path)))
    assert dev == {}                       # no TPU here
    (events,) = host.values()
    assert [e[0] for e in events] == ["replica.op", "engine.dispatch"]
    assert events[0][3] == {"kind": "step"}
    assert events[1][3] == {"program": "fused", "prefill_tokens": 512}
    assert events[0][1] <= events[1][1] and events[1][2] <= events[0][2]
    with pytest.raises(trace_reduce.TraceError):
        host_spans.from_planes(dev, host)


def test_which_cells_report_each_host_span_reader():
    """The five idle shares and the host's step time are every serving
    cell's (together the shares are its ``device_idle_pct``); a counted
    roofline is a dense serving cell's alone.  Read through
    ``Cell.metrics``, so a bare entry with a list and a suffixed entry of
    one cell count alike."""
    m = H.manifest()
    by_reader = {}
    for w in m["workloads"]:
        for met in H.Cell(w["name"]).metrics("per_layer"):
            reader = H.reader_of(met["name"])
            if reader in NEW:
                assert met["source"] == ("program_counter" if "roofline"
                                         in reader else "program_span")
                by_reader.setdefault(reader, set()).add(w["name"])
    serving = {w["name"] for w in m["workloads"]} - {"pretrain-zero3"}
    counted = {"prefill_roofline_counted": {"longdoc-closed"},
               "decode_roofline_counted": {"chat-open", "reasoning-closed"}}
    assert by_reader == {r: counted.get(r, serving) for r in NEW}
