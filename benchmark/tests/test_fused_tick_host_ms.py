"""``fused_tick_host_ms`` on a hand-built trace: the steps that dispatched a
fused tick alone, the waits inside them taken off, and the leaves nested in
``engine.plan`` leaving the five idle shares where they were."""
import json

import pytest

from benchmark.layer_metrics import (engine_step_host_ms, fused_tick_host_ms,
                                     host_spans)
from benchmark.tests.test_host_spans import MS, _ctx, device, pump

NESTED = ("engine.plan.", "engine.wait")


def ev(name, a, b, **stats):
    return (name, a * MS, (b - a) * MS, stats)


def worker():
    """Leaves 0.5-3 of a step that began before the slice (no
    ``engine.step``, no ``replica.op`` around them).  A fused step 6-27
    whose upload 14-16 holds a wait 14.5-15.5 and whose fetch is 19-20.  A
    step 31-39 that dispatched a decode block AND a tick, fetch 37-38.  A
    step 62-66 that dispatched a decode block alone."""
    return [
        ev("engine.plan", 0.5, 1.5),
        ev("engine.dispatch", 2, 3, program="fused", ahead=0),
        ev("replica.op", 5, 28, kind="step"),
        ev("engine.step", 6, 27),
        ev("engine.plan", 8, 14),
        ev("engine.plan.admit", 8, 9, waiting=2, admitted=1),
        ev("engine.plan.grow", 9, 9.5, rows=3),
        ev("engine.plan.rows", 9.5, 11, decode_rows=1, prefill_seqs=2,
           pages_granted=4, page_limited=0),
        ev("engine.plan.pack", 11, 13, rows=9, seqs=3, page_cells=48),
        ev("engine.plan.counts", 13, 13.5, rows=9),
        ev("engine.upload", 14, 16),
        ev("engine.wait", 14.5, 15.5, on="upload"),
        ev("engine.plan", 16, 16.5),
        ev("engine.plan.attrs", 16, 16.4),
        ev("engine.dispatch", 16.5, 18, program="fused", ahead=0),
        ev("engine.fetch", 19, 20),
        ev("engine.harvest", 20, 26),
        ev("engine.reap", 22, 24),
        ev("replica.op", 30, 40, kind="step"),
        ev("engine.step", 31, 39),
        ev("engine.dispatch", 32, 32.5, program="decode_block", ahead=1),
        ev("engine.plan", 33, 35),
        ev("engine.plan.pack", 33, 34.5, rows=5, seqs=1, page_cells=16),
        ev("engine.dispatch", 35, 36, program="fused", ahead=1),
        ev("engine.fetch", 37, 38),
        ev("replica.op", 62, 66, kind="step"),
        ev("engine.step", 62, 66),
        ev("engine.dispatch", 62, 62.5, program="decode_block", ahead=0)]


def trace(events=None):
    return host_spans.from_planes(
        device(), {0: pump(), 1: worker() if events is None else events})


def test_the_steps_of_fused_ticks_alone_less_the_waits_inside_them(capsys):
    ctx = _ctx(trace())
    # (21 - fetch 1 - wait 1) + (8 - fetch 1) over two ticks; the decode
    # step 62-66 and the leaves of the cut step are not in it
    assert fused_tick_host_ms.read(ctx) == pytest.approx((19 + 7) / 2)
    # the accepted reader divides every step by every dispatch in a step
    assert engine_step_host_ms.read(ctx) == pytest.approx(
        (21 + 8 + 4 - 2) / 4)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert (line["ticks"], line["steps"], line["wait_ms"]) == (2, 2, 1.5)
    assert (line["host_ms_p50"], line["host_ms_max"]) == (19.0, 19.0)
    got = line["fused_tick_leaves"]
    by = got["by_span"]
    assert by["engine.plan"] == {"ms": 4.25, "self_ms": 0.55, "n": 1.5}
    assert by["engine.plan.pack"] == {
        "ms": 1.75, "self_ms": 1.75, "n": 1.0,
        "stats": {"rows": 7.0, "seqs": 2.0, "page_cells": 32.0}}
    assert by["engine.plan.admit"]["stats"] == {"waiting": 2.0,
                                                "admitted": 1.0}
    assert by["engine.upload"] == {"ms": 1.0, "self_ms": 0.5, "n": 0.5}
    assert by["engine.wait"] == {"ms": 0.5, "self_ms": 0.5, "n": 0.5}
    # only a plan leaf's stats explain a cost; a dispatch's are read
    # elsewhere
    assert "stats" not in by["engine.dispatch"]
    # step 1: 6-8, 18-19, 26-27; step 2: 31-32, 32.5-33, 36-37, 38-39
    assert got["uncovered_ms"] == pytest.approx((4 + 3.5) / 2)
    assert got["plan_covered_pct"] == pytest.approx(
        100.0 * (1 - 1.1 / 8.5), abs=0.01)
    # logged once a run
    fused_tick_host_ms.read(ctx)
    assert capsys.readouterr().out == ""


def test_nothing_to_read_gives_none():
    assert fused_tick_host_ms.read(_ctx(trace([]))) is None   # no worker
    blocks = [e for e in worker() if e[3].get("program") != "fused"]
    assert fused_tick_host_ms.read(_ctx(trace(blocks))) is None
    ctx = _ctx(trace())
    ctx["_host_trace"] = None                   # a run without a trace
    assert fused_tick_host_ms.read(ctx) is None


def test_leaves_and_waits_leave_the_five_idle_shares_where_they_were():
    """A span nested in a stage inherits the stage's class
    (``host_spans._worker_class``)."""
    bare = [e for e in worker() if not e[0].startswith(NESTED)]
    assert len(bare) == len(worker()) - 8
    with_leaves = host_spans.idle_by_class(trace())
    assert with_leaves == pytest.approx(
        host_spans.idle_by_class(trace(bare)))
    assert with_leaves["plan"] == pytest.approx(0.0045)     # 10-14, 16-16.5
    assert sum(with_leaves.values()) == pytest.approx(0.030)
