"""Closed-loop control plane tests (deepspeed_tpu.control).

The load-bearing contracts:

- **Typed knob surface**: every write through a
  :class:`KnobRegistry` is clamped to declared bounds and cast to the
  declared kind — a policy bug can propose garbage and the subsystem
  still receives a sane value; recompile-triggering knobs are fenced
  off from the online policy entirely.
- **Deterministic convergence**: on a synthetic profile whose
  objective strictly improves toward a known optimum, the hill-climb
  reaches it within ~3x the steady-state trial length — asserted with
  an injectable clock and a fake signal feed, no engine involved.
- **Oscillation guard**: a hostile objective that punishes every
  change produces revert + freeze (never a runaway flip-flop), the
  pre-trial value is restored exactly, and cooldowns block immediate
  re-probing.
- **Attributable decisions**: every decision lands in the trace ring
  as a ``cat="control"`` event naming its driving signal, in the
  metrics registry as ``dstpu_control_*`` series, and renders through
  ``trace_summarize --control`` / passes ``--validate`` — the
  reconstruction contract the smoke gate leans on.
- **Profiles**: per-host profile round-trips through JSON, a foreign
  fingerprint is rejected at load, and the offline sweep
  (:func:`autotune_serving`, on the autotuning scheduler substrate)
  registers its experiments into the metrics registry (satellite:
  sweeps used to be JSON-only, invisible to ``--metrics``).
"""
import importlib.util
import json
import os

import pytest

from deepspeed_tpu.control import (Controller, HostProfile, Knob,
                                   KnobRegistry, Rule, autotune_serving,
                                   control_enabled, engine_signal_feed,
                                   fingerprint_key, host_fingerprint,
                                   load_profile, prefetch_rule,
                                   router_knobs, save_profile,
                                   slo_shed_rule, swapper_knobs)
from deepspeed_tpu.telemetry import metrics as metrics_mod
from deepspeed_tpu.telemetry import tracer as tracer_mod

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class ManualClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture
def registry():
    """The process metrics singleton, owned for the test."""
    reg = metrics_mod.metrics
    prev = (reg.enabled, reg.clock, reg.slo)
    reg.reset()
    reg.configure(enabled=True)
    reg.slo = None
    yield reg
    reg.reset()
    reg.configure(enabled=prev[0], clock=prev[1])
    reg.slo = prev[2]


@pytest.fixture
def global_trace():
    tr = tracer_mod.trace
    prev = (tr.enabled, tr.buffer_size, tr.clock)
    tr.clear()
    yield tr
    tr.configure(enabled=prev[0], buffer_size=prev[1], clock=prev[2])
    tr.clear()


def _int_knob(state, name="t.x", lo=1, hi=8, step=1, **kw):
    return Knob(name, lambda: state["x"],
                lambda v: state.__setitem__("x", v),
                lo=lo, hi=hi, step=step, kind="int", **kw)


def _registry(state, **kw):
    reg = KnobRegistry()
    reg.register(_int_knob(state, **kw))
    return reg


# ---------------------------------------------------------------------------
# KnobRegistry: the typed write path
# ---------------------------------------------------------------------------


class TestKnobRegistry:
    def test_set_clamps_types_and_bounds(self):
        state = {"x": 4}
        reg = _registry(state, lo=1, hi=8)
        assert reg.set("t.x", 99) == (4, 8)        # clamped to hi
        assert state["x"] == 8
        assert reg.set("t.x", -3) == (8, 1)        # clamped to lo
        assert reg.set("t.x", 3.7) == (1, 4)       # int kind rounds
        assert isinstance(state["x"], int)

    def test_bool_kind_casts(self):
        state = {"on": False}
        reg = KnobRegistry()
        reg.register(Knob("t.on", lambda: state["on"],
                          lambda v: state.__setitem__("on", v),
                          kind="bool"))
        assert reg.set("t.on", 1) == (False, True)
        assert state["on"] is True

    def test_apply_skipped_when_unchanged(self):
        calls = []
        state = {"x": 4}
        reg = KnobRegistry()
        reg.register(Knob("t.x", lambda: state["x"], calls.append,
                          lo=1, hi=8, kind="int"))
        reg.set("t.x", 4)
        assert calls == []                         # no-op write
        reg.set("t.x", 5)
        assert calls == [5]

    def test_recompiling_knob_is_fenced(self):
        state = {"x": 4}
        reg = _registry(state, recompiles=True)
        with pytest.raises(RuntimeError, match="recompiles"):
            reg.set("t.x", 5)
        assert state["x"] == 4                     # untouched
        reg.set("t.x", 5, allow_recompile=True)    # offline path
        assert state["x"] == 5
        assert reg.tunable() == []                 # online set excludes it

    def test_duplicate_register_raises(self):
        state = {"x": 1}
        reg = _registry(state)
        with pytest.raises(ValueError):
            reg.register(_int_knob(state))

    def test_merge_and_profile_seeding(self):
        a = {"x": 2}
        b = {"y": 1.0}
        reg = _registry(a)
        other = KnobRegistry()
        other.register(Knob("t.y", lambda: b["y"],
                            lambda v: b.__setitem__("y", v),
                            lo=0.0, hi=4.0, step=0.5, kind="float"))
        reg.merge(other)
        assert reg.names() == ["t.x", "t.y"]
        applied = reg.apply_profile({"t.x": 6, "t.y": 2.5,
                                     "gone.knob": 99})
        assert applied == {"t.x": 6, "t.y": 2.5}   # unknown skipped
        assert (a["x"], b["y"]) == (6, 2.5)


# ---------------------------------------------------------------------------
# Controller: hill-climb, hysteresis, guard — fake feed + manual clock
# ---------------------------------------------------------------------------


def _climb(state, optimum, *, start, objective="throughput", sign=1.0,
           **ctl_kw):
    """A controller over one int knob whose objective strictly improves
    toward ``optimum`` (quadratic peak): the synthetic stall profile."""
    state["x"] = start
    reg = _registry(state)

    def feed():
        v = 100.0 - 5.0 * (state["x"] - optimum) ** 2
        return {objective.lstrip("-"): sign * v}

    ctl_kw.setdefault("settle", 1)
    ctl_kw.setdefault("cooldown", 0)
    ctl_kw.setdefault("clock", ManualClock())
    return Controller(reg, feed, objective=objective, **ctl_kw)


class TestHillClimb:
    def test_converges_within_3x_steady_state(self):
        """Start 4 steps from the optimum; each accepted step costs one
        probe tick + ``settle`` judge ticks, so steady state is
        distance * (settle + 1) ticks — the controller must land
        within 3x that (the ISSUE's convergence budget)."""
        state = {}
        ctl = _climb(state, optimum=6, start=2, settle=1)
        budget = 3 * 4 * 2
        for _ in range(budget):
            ctl.tick()
        assert state["x"] == 6
        assert ctl.counts["accepts"] >= 4

    def test_minimize_objective_sign(self):
        """A leading ``-`` minimizes: same profile, objective negated
        (a latency-like signal)."""
        state = {}
        ctl = _climb(state, optimum=3, start=7, objective="-lat_ms",
                     sign=-1.0)
        for _ in range(3 * 4 * 2):
            ctl.tick()
        assert state["x"] == 3

    def test_no_decisions_without_objective_signal(self):
        """A feed that never carries the objective starts no trials —
        the controller idles instead of probing blind."""
        state = {"x": 4}
        ctl = Controller(_registry(state), lambda: {"other": 1.0},
                         clock=ManualClock())
        for _ in range(10):
            ctl.tick()
        assert state["x"] == 4
        assert ctl.decision_log == []


class TestOscillationGuard:
    def _hostile(self, state, **kw):
        """Every change regresses hard: the pathological profile the
        guard exists for."""
        state["x"] = 4
        base = {"x": 4}

        def feed():
            return {"throughput": 100.0 - 50.0 * abs(state["x"]
                                                     - base["x"])}

        kw.setdefault("settle", 1)
        kw.setdefault("cooldown", 2)
        kw.setdefault("guard_window", 16)
        kw.setdefault("guard_reverts", 2)
        kw.setdefault("freeze", 6)
        return Controller(_registry(state), feed,
                          clock=ManualClock(), **kw)

    def test_regressions_revert_then_freeze(self):
        state = {}
        ctl = self._hostile(state)
        for _ in range(30):
            ctl.tick()
        # every probe was undone: the knob holds its pre-trial value
        assert state["x"] == 4
        assert ctl.counts["reverts"] >= 2
        assert ctl.counts["freezes"] >= 1
        acts = [d["action"] for d in ctl.decision_log]
        # guard engaged after the configured revert budget, then
        # released after the freeze window
        assert "freeze" in acts and "unfreeze" in acts
        f = acts.index("freeze")
        assert acts[:f].count("revert") == 2

    def test_frozen_knob_is_not_probed(self):
        state = {}
        ctl = self._hostile(state, freeze=8)
        frozen_ticks = []
        for _ in range(30):
            ctl.tick()
            if ctl.frozen():
                frozen_ticks.append(ctl._tick)
        assert frozen_ticks, "guard never engaged"
        probes = [d["tick"] for d in ctl.decision_log
                  if d["action"] == "probe"]
        assert not set(probes) & set(frozen_ticks)

    def test_cooldown_blocks_immediate_reprobe(self):
        state = {}
        ctl = self._hostile(state, cooldown=4, guard_reverts=99)
        for _ in range(24):
            ctl.tick()
        log = [d for d in ctl.decision_log
               if d["action"] in ("probe", "revert", "settle")]
        last_block = None
        for d in log:
            if d["action"] == "probe":
                # blocked while tick < revert_tick + cooldown
                assert (last_block is None
                        or d["tick"] >= last_block + 4), \
                    f"probe at {d['tick']} inside cooldown"
            else:
                last_block = d["tick"]

    def test_neutral_change_settles_quietly(self):
        """Objective noise inside the hysteresis band is neither an
        accept nor a regression: quiet revert, no guard bookkeeping."""
        state = {"x": 4}
        reg = _registry(state)
        ctl = Controller(reg, lambda: {"throughput": 100.0},
                         settle=1, hysteresis=0.05, cooldown=0,
                         clock=ManualClock())
        for _ in range(8):
            ctl.tick()
        assert state["x"] == 4
        assert ctl.counts["settles"] >= 1
        assert ctl.counts["reverts"] == 0
        assert ctl.counts["freezes"] == 0


class TestRules:
    def test_prefetch_rule_fires_and_names_signal(self):
        state = {"on": False}
        reg = KnobRegistry()
        reg.register(Knob("kv.prefetch", lambda: state["on"],
                          lambda v: state.__setitem__("on", v),
                          kind="bool"))
        sig = {"tiering_spill_rate": 0.0, "throughput": 1.0}
        ctl = Controller(reg, lambda: dict(sig),
                         rules=[prefetch_rule()], clock=ManualClock())
        ctl.tick()
        assert state["on"] is False                # below threshold
        sig["tiering_spill_rate"] = 2.0
        decisions = ctl.tick()
        assert state["on"] is True
        rule_d = [d for d in decisions if d["action"] == "rule"]
        assert rule_d and rule_d[0]["signal"] == "tiering_spill_rate"
        assert rule_d[0]["knob"] == "kv.prefetch"

    def test_rule_cooldown(self):
        state = {"on": False}
        reg = KnobRegistry()
        reg.register(Knob("kv.prefetch", lambda: state["on"],
                          lambda v: state.__setitem__("on", v),
                          kind="bool"))
        rule = prefetch_rule()
        rule.cooldown = 5
        ctl = Controller(reg, lambda: {"tiering_spill_rate": 2.0},
                         rules=[rule], clock=ManualClock())
        fire_ticks = []
        for _ in range(12):
            for d in ctl.tick():
                if d["action"] == "rule":
                    fire_ticks.append(d["tick"])
            state["on"] = False                    # knock it back off
        assert fire_ticks
        assert all(b - a >= 5 for a, b in zip(fire_ticks,
                                              fire_ticks[1:]))

    def test_slo_shed_rule_lowers_router_deferral(self):
        class FakeRouter:
            burn_defer = 2.0
            burn_shed = 4.0
            queue_cap = 8

        router = FakeRouter()
        ctl = Controller(router_knobs(router),
                         lambda: {"slo_burn_max": 3.0},
                         rules=[slo_shed_rule(threshold=1.5,
                                              defer_at=1.0)],
                         clock=ManualClock())
        ctl.tick()
        assert router.burn_defer == 1.0


# ---------------------------------------------------------------------------
# Emission: trace events, metrics series, trace_summarize --control
# ---------------------------------------------------------------------------


def _load_summarize():
    path = os.path.join(REPO_ROOT, "scripts", "trace_summarize.py")
    spec = importlib.util.spec_from_file_location("_ts_mod", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestEmission:
    def test_decisions_hit_trace_and_metrics(self, registry,
                                             global_trace, tmp_path,
                                             capsys):
        global_trace.configure(enabled=True)
        state = {}
        ctl = _climb(state, optimum=6, start=4)
        for _ in range(10):
            ctl.tick()
        assert ctl.decision_log
        # metrics: per-action decision counters + tick counter
        snap = registry.scalar_summary()
        assert snap.get("dstpu_control_ticks_total") == 10
        total = sum(v for k, v in snap.items()
                    if k.startswith("dstpu_control_decisions_total"))
        assert total == len(ctl.decision_log)
        # trace: every decision is a cat="control" event naming its
        # signal; the export renders and validates through
        # trace_summarize --control / --validate
        out = tmp_path / "ctl.json"
        global_trace.export(str(out))
        doc = json.loads(out.read_text())
        evs = [e for e in doc["traceEvents"]
               if e.get("cat") == "control"
               and e.get("name") == "control_decision"]
        assert len(evs) == len(ctl.decision_log)
        assert all(e["args"].get("signal") for e in evs)
        ts = _load_summarize()
        assert ts.main(["--control", str(out)]) == 0
        rendered = capsys.readouterr().out
        assert "control decision" in rendered
        assert "t.x" in rendered
        assert ts.main(["--validate", str(out)]) == 0

    def test_validate_rejects_malformed_decision(self, tmp_path,
                                                 capsys):
        bad = {"traceEvents": [
            {"ph": "i", "name": "control_decision", "cat": "control",
             "ts": 1, "pid": 0, "tid": 0,
             "args": {"tick": 1, "action": "explode", "knob": "k",
                      "signal": "s", "old": 1, "new": 2}}]}
        p = tmp_path / "bad.json"
        p.write_text(json.dumps(bad))
        ts = _load_summarize()
        assert ts.main(["--validate", str(p)]) == 1
        assert "unknown action" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Per-host profiles + the offline sweep
# ---------------------------------------------------------------------------


class TestProfiles:
    def test_round_trip_and_fingerprint_gate(self, tmp_path):
        prof = HostProfile(knobs={"engine.harvest_interval": 4,
                                  "engine.async_depth": 2},
                           metric=123.0, metric_name="tok_per_s")
        path = save_profile(prof, str(tmp_path))
        assert os.path.basename(path) == \
            f"control_profile_{fingerprint_key()}.json"
        got = load_profile(str(tmp_path))
        assert got is not None
        assert got.knobs == prof.knobs
        assert got.metric == 123.0
        # a foreign host's profile must NOT seed this one; with an
        # explicit file path, strict=False opts into the foreign seed
        other = dict(host_fingerprint())
        other["cores"] = other["cores"] + 64
        assert load_profile(str(tmp_path), fingerprint=other) is None
        assert load_profile(path, fingerprint=other) is None
        assert load_profile(path, fingerprint=other,
                            strict=False) is not None

    def test_missing_or_garbage_is_none(self, tmp_path):
        assert load_profile(str(tmp_path)) is None
        p = tmp_path / f"control_profile_{fingerprint_key()}.json"
        p.write_text("{not json")
        assert load_profile(str(tmp_path)) is None

    def test_autotune_serving_sweeps_and_persists(self, tmp_path,
                                                  registry):
        """Grid sweep over a 2-knob space on the autotuning scheduler;
        the winner round-trips as a profile AND the experiments land in
        the metrics registry (the satellite: sweeps were JSON-only)."""
        def runner(point):
            if point["engine.async_depth"] == 3:
                raise RuntimeError("boom")        # quarantined point
            return (10.0 * point["engine.harvest_interval"]
                    - point["engine.async_depth"])

        prof = autotune_serving(
            runner,
            {"engine.harvest_interval": [2, 4],
             "engine.async_depth": [1, 3]},
            save_to=str(tmp_path))
        assert prof is not None
        assert prof.knobs == {"engine.harvest_interval": 4,
                              "engine.async_depth": 1}
        assert prof.metric == 39.0
        got = load_profile(str(tmp_path))
        assert got is not None and got.knobs == prof.knobs
        snap = registry.scalar_summary()
        assert snap.get(
            'dstpu_autotune_experiments_total{status="ok"}') == 2
        assert snap.get(
            'dstpu_autotune_experiments_total{status="error"}') == 2
        assert snap.get("dstpu_autotune_best_metric") == 39.0

    def test_swapper_knob_surface(self):
        """The moment-stream swapper exposes the uniform knob surface
        (apply defers through set_buffer_count — runtime-safe)."""
        class FakeSwapper:
            buffer_count = 2

            def set_buffer_count(self, n):
                self.buffer_count = n

        sw = FakeSwapper()
        reg = swapper_knobs(sw)
        assert reg.set("swap.buffer_count", 5) == (2, 5)
        assert sw.buffer_count == 5
        assert reg.set("swap.buffer_count", 99) == (5, 8)   # clamped


class TestKillSwitch:
    def test_env_disables(self, monkeypatch):
        monkeypatch.delenv("DSTPU_CONTROL", raising=False)
        assert control_enabled()
        monkeypatch.setenv("DSTPU_CONTROL", "0")
        assert not control_enabled()
        monkeypatch.setenv("DSTPU_CONTROL", "1")
        assert control_enabled()
