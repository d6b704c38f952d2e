"""Metrics registry, SLO burn-rate, and tail-sampling tests.

The load-bearing contracts:

- **Hand-computable histograms**: exponential bucket boundaries, bucket
  placement (inclusive upper edges), per-thread shard merge, and the
  linear-interpolation quantile are all asserted against paper-derived
  fixtures — the serve_smoke one-bucket-width agreement gate leans on
  exactly this math.
- **SLO window arithmetic**: burn rate = error_rate / error_budget over
  a rolling window under an injectable ManualClock — samples age out,
  budget health flips deterministically.
- **Deterministic tail sampling**: same seed => same 1-in-N promotion
  stream, and a breach-promoted decision still consumes the RNG so the
  sample stream stays aligned with the request stream.
- **Breach promotes a timeline** (faults-marked): an engine run whose
  every request breaches a tiny TTFT objective lands full
  submit→reap lifecycles plus ``promoted`` markers in the retained
  ring, while the staging rings stay scratch.
- **Metrics never recompile**: the zero-new-compilations guard holds
  with the registry enabled AND sampling armed — all evaluation happens
  at reap time on host, structurally outside traced dispatch code.
"""
import json
import threading
import types

import numpy as np
import pytest

from deepspeed_tpu.telemetry import metrics as metrics_mod
from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.telemetry.metrics import (MetricsRegistry,
                                             exponential_buckets,
                                             validate_metrics_doc)
from deepspeed_tpu.telemetry.slo import (SLOSet, TailSampler,
                                         parse_objective)
from deepspeed_tpu.telemetry.tracer import Tracer


class ManualClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture
def registry():
    """The process singleton, emptied and restored around each test
    (emitters all feed the singleton, so tests must own its state)."""
    reg = metrics_mod.metrics
    prev = (reg.enabled, reg.clock, reg.slo)
    reg.reset()
    reg.configure(enabled=True)
    reg.slo = None
    yield reg
    reg.reset()
    reg.configure(enabled=prev[0], clock=prev[1])
    reg.slo = prev[2]


# ---------------------------------------------------------------------------
# Histogram fixtures (hand-computed)
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_exponential_bucket_boundaries(self):
        assert exponential_buckets(1.0, 2.0, 4) == (1.0, 2.0, 4.0, 8.0)
        assert exponential_buckets(0.5, 4.0, 3) == (0.5, 2.0, 8.0)
        for bad in ((0.0, 2.0, 4), (1.0, 1.0, 4), (1.0, 2.0, 0)):
            with pytest.raises(ValueError):
                exponential_buckets(*bad)

    def test_observations_land_in_hand_computed_buckets(self):
        """Upper edges are inclusive (Prometheus ``le`` semantics)."""
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0, 8.0)).labels()
        for v in (0.5, 1.0, 1.5, 4.0, 9.0):
            h.observe(v)
        counts, hsum, n = h.merged()
        assert counts == [2, 1, 1, 0, 1]      # le=1,2,4,8,+Inf
        assert hsum == pytest.approx(16.0)
        assert n == 5

    def test_thread_shards_merge_exactly(self):
        """Each thread writes only its own shard (no lock on the record
        path); the merged read must still see every observation."""
        reg = MetricsRegistry()
        fam = reg.histogram("h", buckets=(1.0, 2.0, 4.0, 8.0))
        ctr = reg.counter("c")
        barrier = threading.Barrier(4)
        # thread i observes value (i+0.5) a hundred times: values 0.5,
        # 1.5, 2.5, 3.5 -> buckets 0, 1, 2, 2
        def work(i):
            barrier.wait()
            for _ in range(100):
                fam.observe(i + 0.5)
                ctr.inc()
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        counts, hsum, n = fam.labels().merged()
        assert counts == [100, 100, 200, 0, 0]
        assert n == 400
        assert hsum == pytest.approx(100 * (0.5 + 1.5 + 2.5 + 3.5))
        assert ctr.value() == 400

    def test_quantile_linear_interpolation(self):
        """target = q/100 * n; interpolate inside the crossing bucket by
        the fraction of its population below the target."""
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0, 8.0)).labels()
        for v in (0.5, 1.5, 3.0, 3.5):
            h.observe(v)
        # p50: target 2.0; bucket0 cum 1, bucket1 (1,2] crosses with
        # frac (2-1)/1 = 1 -> 1 + (2-1)*1 = 2.0
        assert h.quantile(50) == pytest.approx(2.0)
        # p75: target 3.0; cum after bucket1 = 2, bucket2 (2,4] holds 2,
        # frac (3-2)/2 = 0.5 -> 2 + (4-2)*0.5 = 3.0
        assert h.quantile(75) == pytest.approx(3.0)
        # p100: target 4.0 crosses in bucket2 at frac 1 -> 4.0
        assert h.quantile(100) == pytest.approx(4.0)

    def test_quantile_clamps_to_last_finite_bound(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0, 8.0)).labels()
        h.observe(100.0)                      # +Inf bucket has no width
        assert h.quantile(99) == pytest.approx(8.0)

    def test_quantile_empty_is_none(self):
        reg = MetricsRegistry()
        assert reg.histogram("h", buckets=(1.0,)).quantile(50) is None

    def test_bucket_width_at(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0, 8.0)).labels()
        assert h.bucket_width_at(0.3) == pytest.approx(1.0)
        assert h.bucket_width_at(3.0) == pytest.approx(2.0)
        assert h.bucket_width_at(50.0) == pytest.approx(4.0)  # last finite


class TestCounterGauge:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("c").labels()
        c.inc()
        c.inc(2.5)
        assert c.value() == pytest.approx(3.5)
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_set_total_is_monotonic_max(self):
        """Mirroring an external cumulative dict must never go backwards
        (a reset external counter keeps the high-water mark)."""
        reg = MetricsRegistry()
        c = reg.counter("c").labels()
        c.set_total(5)
        c.set_total(3)
        assert c.value() == 5.0
        c.set_total(9)
        assert c.value() == 9.0

    def test_gauge_last_write_wins_and_additive(self):
        reg = MetricsRegistry()
        g = reg.gauge("g").labels()
        g.set(3.0)
        g.set(1.5)
        assert g.value() == 1.5
        g2 = reg.gauge("g2").labels()
        g2.add(2.0)
        g2.add(3.0)
        assert g2.value() == 5.0

    def test_sync_counters_mirrors_numeric_entries(self):
        reg = MetricsRegistry()
        reg.sync_counters("pfx_", {"spills": 4, "ok": True, "x": "nope"})
        assert reg.get("pfx_spills_total").value() == 4.0
        assert reg.get("pfx_ok_total") is None       # bools skipped
        assert reg.get("pfx_x_total") is None
        reg.configure(enabled=False)
        reg.sync_counters("pfx_", {"spills": 9})
        assert reg.get("pfx_spills_total").value() == 4.0

    def test_kind_collision_raises(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("m")


# ---------------------------------------------------------------------------
# Export formats
# ---------------------------------------------------------------------------


class TestExport:
    def test_exposition_histogram_lines(self):
        reg = MetricsRegistry(clock=ManualClock(7.0))
        h = reg.histogram("lat", help="latency",
                          buckets=(1.0, 2.0)).labels()
        for v in (0.5, 1.5, 9.0):
            h.observe(v)
        text = reg.export_text()
        lines = text.splitlines()
        assert "# HELP lat latency" in lines
        assert "# TYPE lat histogram" in lines
        assert 'lat_bucket{le="1"} 1' in lines       # cumulative
        assert 'lat_bucket{le="2"} 2' in lines
        assert 'lat_bucket{le="+Inf"} 3' in lines    # == count
        assert "lat_sum 11" in lines          # integral floats render bare
        assert "lat_count 3" in lines

    def test_exposition_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("c", labels=("who",)).labels(who='a"b\\c\nd').inc()
        line = [ln for ln in reg.export_text().splitlines()
                if ln.startswith("c{")][0]
        assert line == 'c{who="a\\"b\\\\c\\nd"} 1'

    def test_export_json_is_schema_valid_and_round_trips(self):
        reg = MetricsRegistry(clock=ManualClock(42.0))
        reg.counter("c", labels=("k",)).labels(k="v").inc(2)
        reg.gauge("g").set(1.25)
        h = reg.histogram("h", buckets=(1.0, 2.0)).labels()
        h.observe(0.5)
        h.observe(1.5)
        doc = json.loads(json.dumps(reg.export_json()))
        assert doc["record"] == "metrics"
        assert doc["unix_time"] == 42.0
        assert validate_metrics_doc(doc) == []
        (hist,) = doc["histograms"]
        assert hist["counts"] == [1, 1, 0]
        assert hist["count"] == 2
        assert hist["p50"] == pytest.approx(1.0)

    def test_validate_metrics_doc_catches_corruption(self):
        reg = MetricsRegistry()
        reg.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
        good = reg.export_json()
        bad = json.loads(json.dumps(good))
        bad["histograms"][0]["counts"] = [1, 0]       # missing +Inf slot
        assert any("counts length" in p
                   for p in validate_metrics_doc(bad))
        bad = json.loads(json.dumps(good))
        bad["histograms"][0]["buckets"] = [2.0, 1.0]
        assert any("not increasing" in p
                   for p in validate_metrics_doc(bad))
        bad = json.loads(json.dumps(good))
        bad["histograms"][0]["count"] = 99
        assert any("sum(counts)" in p for p in validate_metrics_doc(bad))
        bad = json.loads(json.dumps(good))
        bad["record"] = "trace"
        assert any("record" in p for p in validate_metrics_doc(bad))
        assert validate_metrics_doc("nope") == [
            "metrics doc is not an object"]

    def test_scalar_summary_flattens(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(3)
        h = reg.histogram("h", labels=("stage",), buckets=(1.0, 2.0))
        h.labels(stage="plan").observe(0.5)
        s = reg.scalar_summary()
        assert s["c"] == 3.0
        assert s['h{stage="plan"}_count'] == 1
        assert s['h{stage="plan"}_p50'] == pytest.approx(0.5)

    def test_reset_drops_families(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.reset()
        assert reg.get("c") is None
        assert reg.export_json()["counters"] == []


# ---------------------------------------------------------------------------
# SLO objectives & burn rate
# ---------------------------------------------------------------------------


class TestSLO:
    def test_parse_objective(self):
        o = parse_objective("ttft_ms_p99 <= 150")
        assert (o.metric, o.target, o.threshold) == ("ttft_ms", 0.99,
                                                     150.0)
        assert o.budget == pytest.approx(0.01)
        o = parse_objective("tpot_ms_p99.9<2.5")
        assert (o.metric, o.threshold) == ("tpot_ms", 2.5)
        assert o.target == pytest.approx(0.999)
        for bad in ("ttft_ms <= 150", "ttft_ms_p99 <=", "p99 <= 1",
                    "ttft_ms_p0 <= 1", "ttft_ms_p100 <= 1"):
            with pytest.raises(ValueError):
                parse_objective(bad)

    def test_burn_rate_hand_computed(self):
        """10 samples, 2 over threshold, p90 objective: error rate 0.2
        against a 0.1 budget = burn 2.0 (unhealthy)."""
        clk = ManualClock()
        s = SLOSet(["ttft_ms_p90 <= 100"], window_s=300.0, clock=clk)
        breaches = []
        for i, v in enumerate([50] * 8 + [200, 300]):
            clk.t = float(i)
            breaches += s.record("ttft_ms", v)
        assert breaches == ["ttft_ms_p90", "ttft_ms_p90"]
        st = s.evaluate()["ttft_ms_p90"]
        assert st["samples"] == 10 and st["breaches"] == 2
        assert st["error_rate"] == pytest.approx(0.2)
        assert st["burn_rate"] == pytest.approx(2.0)
        assert st["ok"] is False
        flat = s.flat_summary()
        assert flat["ttft_ms_p90_burn_rate"] == pytest.approx(2.0)
        assert flat["ttft_ms_p90_ok"] == 0

    def test_window_ages_samples_out(self):
        clk = ManualClock()
        s = SLOSet(["ttft_ms_p90 <= 100"], window_s=300.0, clock=clk)
        clk.t = 0.0
        s.record("ttft_ms", 500.0)            # breach at t=0
        clk.t = 200.0
        s.record("ttft_ms", 50.0)             # healthy at t=200
        clk.t = 250.0
        st = s.evaluate()["ttft_ms_p90"]
        assert st["samples"] == 2 and st["burn_rate"] > 1.0
        clk.t = 350.0                         # t=0 sample leaves window
        st = s.evaluate()["ttft_ms_p90"]
        assert st["samples"] == 1 and st["breaches"] == 0
        assert st["burn_rate"] == 0.0 and st["ok"] is True

    def test_record_request_covers_each_metric_once(self):
        """Two objectives on one metric: the request summary feeds the
        metric exactly once, record() fans out to both objectives."""
        clk = ManualClock()
        s = SLOSet(["ttft_ms_p50 <= 10", "ttft_ms_p99 <= 100",
                    "tpot_ms_p90 <= 5"], clock=clk)
        breached = s.record_request(
            {"uid": 1, "ttft_ms": 200.0, "tpot_ms": 1.0,
             "queue_wait_ms": None})
        assert sorted(breached) == ["ttft_ms_p50", "ttft_ms_p99"]
        ev = s.evaluate()
        assert ev["ttft_ms_p50"]["samples"] == 1
        assert ev["ttft_ms_p99"]["samples"] == 1
        assert ev["tpot_ms_p90"]["samples"] == 1
        assert s.total_samples == 3           # one per objective

    def test_duplicate_objectives_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            SLOSet(["ttft_ms_p99 <= 1", "ttft_ms_p99 <= 2"])


# ---------------------------------------------------------------------------
# Tail sampling
# ---------------------------------------------------------------------------


class TestTailSampler:
    def test_deterministic_under_seed(self):
        a = TailSampler(n=4, seed=123)
        b = TailSampler(n=4, seed=123)
        da = [a.should_promote() for _ in range(200)]
        db = [b.should_promote() for _ in range(200)]
        assert da == db
        assert a.promoted_sample > 0
        assert a.promoted_sample + a.dropped == 200
        # roughly 1-in-4 (binomial, wide tolerance — determinism is the
        # contract, the rate is a sanity floor)
        assert 20 <= a.promoted_sample <= 90

    def test_breach_consumes_rng_stream(self):
        """Decision k must be identical across runs regardless of how
        many earlier decisions were breach-promoted."""
        plain = TailSampler(n=4, seed=9)
        mixed = TailSampler(n=4, seed=9)
        ref = [plain.should_promote() for _ in range(50)]
        got = [mixed.should_promote(breached=(i == 0))
               for i in range(50)]
        assert got[0] == (True, "slo_breach")
        assert got[1:] == ref[1:]

    def test_n_zero_promotes_only_breach_and_error(self):
        s = TailSampler(n=0, seed=1)
        assert s.should_promote() == (False, "")
        assert s.should_promote(breached=True) == (True, "slo_breach")
        assert s.should_promote(errored=True) == (True, "error")
        assert s.should_promote(breached=True, errored=True) == (
            True, "slo_breach")              # breach outranks error
        c = s.counters()
        assert c["decisions"] == 4
        assert c["promoted_breach"] == 2 and c["promoted_error"] == 1
        assert c["dropped"] == 1


class TestTracerPromotion:
    def test_promote_filters_other_uid_lifecycles(self):
        """The retained ring gets the promoted uid's lifecycle plus the
        shared serving spans in its window — neighbours' request events
        and out-of-window spans stay out."""
        clk = ManualClock()
        tr = Tracer(enabled=True, sampling=True, clock=clk)
        clk.t = 1.0
        tr.event("request_submit", cat="request", uid=1)
        tr.event("request_submit", cat="request", uid=2)
        tr.add_complete("decode_block", 1.1, 1.4, cat="request",
                        uids=[1, 2])
        tr.add_complete("prefill_chunk", 1.2, 1.3, cat="serving")
        clk.t = 2.0
        tr.event("request_reap", cat="request", uid=1)
        tr.add_complete("late_span", 5.0, 6.0, cat="serving")
        assert tr.retained_snapshot() == []   # staging is scratch
        n = tr.promote(1, 1.0, 2.0, reason="slo_breach")
        kept = tr.retained_snapshot()
        names = [ev["name"] for ev in kept]
        assert n == 4
        assert names.count("request_submit") == 1     # uid 2 filtered
        assert "decode_block" in names                # shared, uid in uids
        assert "prefill_chunk" in names               # serving span rides
        assert "request_reap" in names
        assert "late_span" not in names
        marker = kept[-1]
        assert marker["name"] == "promoted"
        assert marker["args"] == {"uid": 1, "reason": "slo_breach",
                                  "events": 4}

    def test_export_writes_retained_ring_when_sampling(self, tmp_path):
        clk = ManualClock()
        tr = Tracer(enabled=True, sampling=True, clock=clk)
        clk.t = 1.0
        tr.event("request_submit", cat="request", uid=7)
        path = str(tmp_path / "t.json")
        tr.export(path)
        with open(path) as f:                 # only "M" metadata rows
            assert [ev for ev in json.load(f)["traceEvents"]
                    if ev["ph"] != "M"] == [] # nothing promoted
        tr.promote(7, 0.9, 1.1, reason="sample")
        tr.export(path)
        with open(path) as f:
            names = [ev["name"] for ev in json.load(f)["traceEvents"]]
        assert "request_submit" in names and "promoted" in names


# ---------------------------------------------------------------------------
# Flight-dump embedding & monitor bridge
# ---------------------------------------------------------------------------


class TestFlightMetricsEmbed:
    def test_dump_embeds_schema_valid_snapshot(self, registry, tmp_path):
        from deepspeed_tpu.telemetry import flight, read_flight_record

        registry.counter("dstpu_sdc_mismatches_total").inc(3)
        registry.histogram("dstpu_request_ttft_ms",
                           buckets=(1.0, 2.0)).observe(1.5)
        path = flight.dump_on_fault("unit_metrics", dir=str(tmp_path))
        header, _events = read_flight_record(path)
        snap = header["metrics"]
        assert snap["record"] == "metrics"
        assert validate_metrics_doc(snap) == []
        assert any(c["name"] == "dstpu_sdc_mismatches_total"
                   and c["value"] == 3.0 for c in snap["counters"])

    def test_reader_rejects_corrupt_embedded_snapshot(self, registry,
                                                      tmp_path):
        from deepspeed_tpu.telemetry import flight, read_flight_record

        registry.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
        path = flight.dump_on_fault("unit_corrupt", dir=str(tmp_path))
        with open(path) as f:
            lines = f.read().splitlines()
        header = json.loads(lines[0])
        header["metrics"]["histograms"][0]["counts"] = [1]
        lines[0] = json.dumps(header)
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="metrics"):
            read_flight_record(path)

    def test_disabled_registry_omits_snapshot(self, registry, tmp_path):
        from deepspeed_tpu.telemetry import flight, read_flight_record

        registry.configure(enabled=False)
        path = flight.dump_on_fault("unit_off", dir=str(tmp_path))
        header, _ = read_flight_record(path)
        assert "metrics" not in header


class TestMonitorBridge:
    def test_write_metrics_emits_series(self, registry, tmp_path):
        from deepspeed_tpu.config.config import CSVConfig
        from deepspeed_tpu.monitor.monitor import MonitorMaster

        registry.counter("dstpu_watchdog_timeouts_total").inc(2)
        registry.histogram("dstpu_request_ttft_ms",
                           buckets=(1.0, 2.0)).observe(1.5)
        clk = ManualClock()
        registry.slo = SLOSet(["ttft_ms_p99 <= 1"], clock=clk)
        registry.slo.record("ttft_ms", 5.0)   # burning
        off = types.SimpleNamespace(enabled=False)
        mc = types.SimpleNamespace(
            tensorboard=off, wandb=off, comet=off,
            csv_monitor=CSVConfig(enabled=True, output_path=str(tmp_path),
                                  job_name="j"))
        master = MonitorMaster(mc)
        master.write_metrics(registry, step=4)
        master.close()
        names = {p.name for p in (tmp_path / "j").iterdir()}
        assert "Metrics_dstpu_watchdog_timeouts_total.csv" in names
        assert "Metrics_dstpu_request_ttft_ms_p50.csv" in names
        assert "Metrics_slo_ttft_ms_p99_burn_rate.csv" in names


# ---------------------------------------------------------------------------
# Engine integration: breach promotes a timeline; no recompiles
# ---------------------------------------------------------------------------

CFG = None


def _cfg():
    global CFG
    if CFG is None:
        import jax.numpy as jnp

        from deepspeed_tpu.models.llama import get_config

        CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                         intermediate_size=64, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         max_position_embeddings=128, dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=True,
                         remat=False, use_flash_attention=False)
    return CFG


@pytest.fixture(scope="module")
def engine_params():
    import jax

    from deepspeed_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(_cfg())
    return jax.jit(model.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))


@pytest.fixture
def armed_tracer():
    """Singleton tracer armed for tail sampling, fully restored after."""
    tr = tracer_mod.trace
    prev = (tr.enabled, tr.sampling, tr.sample_n)
    tr.clear()
    tr.configure(enabled=True, sampling=True, sample_n=0)
    yield tr
    tr.configure(enabled=prev[0], sampling=prev[1], sample_n=prev[2])
    tr.clear()


def _run_engine(engine_params, **kw):
    import jax

    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    eng = RaggedInferenceEngineV2(
        LlamaForCausalLM(_cfg()), params=engine_params, max_seqs=2,
        max_seq_len=64, prefill_chunk=8, decode_block_size=4,
        rng=jax.random.PRNGKey(11), **kw)
    r = np.random.default_rng(3)
    prompts = [r.integers(1, 64, size=(s,), dtype=np.int32)
               for s in (5, 9)]
    outs = eng.generate_all(prompts, max_new_tokens=6)
    return outs, eng


class TestEngineIntegration:
    @pytest.mark.faults
    def test_slo_breach_promotes_full_timeline(self, registry,
                                               armed_tracer,
                                               engine_params):
        """Every request breaches a sub-microsecond TTFT objective, so
        every reap must promote: the retained ring carries each uid's
        submit→reap lifecycle plus ``promoted`` markers with the breach
        reason, and the SLO window reports the burn."""
        _outs, eng = _run_engine(engine_params,
                                 slo=["ttft_ms_p99 <= 0.0001"],
                                 trace_sample=0)
        kept = armed_tracer.retained_snapshot()
        by_name = {}
        for ev in kept:
            by_name.setdefault(ev["name"], []).append(ev)
        markers = by_name.get("promoted", [])
        assert len(markers) == 2
        # reason carries the breach verdict plus the objective names
        assert all(m["args"]["reason"] == "slo_breach:ttft_ms_p99"
                   for m in markers)
        submit_uids = {ev["args"]["uid"]
                       for ev in by_name.get("request_submit", [])}
        reap_uids = {ev["args"]["uid"]
                     for ev in by_name.get("request_reap", [])}
        all_uids = {m["args"]["uid"] for m in markers}
        assert submit_uids == reap_uids == all_uids
        assert len(all_uids) == 2
        st = eng.serving_stages()
        assert st["slo"]["ttft_ms_p99_breaches"] == 2
        assert st["slo"]["ttft_ms_p99_ok"] == 0
        assert st["trace_sampling"]["promoted_breach"] == 2
        assert st["trace_sampling"]["dropped"] == 0
        # the registry rode along: request histograms saw both reaps
        assert registry.get("dstpu_request_ttft_ms").labels(
            replica="").merged()[2] == 2

    def test_zero_new_compilations_with_metrics_and_sampling(
            self, registry, armed_tracer, engine_params):
        """Acceptance: the registry and the tail sampler evaluate at
        reap time on host — arming both must add zero XLA compilations
        to a warmed steady-state run."""
        import jax

        from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
        from deepspeed_tpu.models.llama import LlamaForCausalLM

        from deepspeed_tpu.telemetry.compiles import count_compiles
        eng = RaggedInferenceEngineV2(
            LlamaForCausalLM(_cfg()), params=engine_params, max_seqs=2,
            max_seq_len=64, prefill_chunk=8, decode_block_size=4,
            rng=jax.random.PRNGKey(11), slo=["ttft_ms_p99 <= 0.0001"],
            trace_sample=0)
        r = np.random.default_rng(3)
        prompts = [r.integers(1, 64, size=(s,), dtype=np.int32)
                   for s in (5, 9)]
        eng.generate_all(prompts, max_new_tokens=6)  # warm every program
        with count_compiles() as compiles:
            eng.generate_all(prompts, max_new_tokens=6)
        assert compiles.count == 0, (
            f"{compiles.count} recompilations with metrics + tail sampling "
            "armed — observability must stay out of traced dispatch")
