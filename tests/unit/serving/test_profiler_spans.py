"""The serving loop's stage brackets reach the profiler's own trace.

One bracket, three sinks: a ``jax.profiler.TraceAnnotation`` always (so
the spans show in any profiler session, with no environment variable and
no ``configure`` call), the tracer's ring under ``DSTPU_TRACE``, and
``HostStageStats``.  A tiny engine behind a ``ReplicaSet`` and a
``Router`` serves a few requests inside ``jax.profiler.start_trace``; the
``/host:CPU`` plane of the ``.xplane.pb`` is read back with nothing but
JAX.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from deepspeed_tpu.inference.common import HostStageStats
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.serving import ReplicaSet, Router
from deepspeed_tpu.telemetry import tracer as tracer_mod

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=True, remat=False,
                 use_flash_attention=False)
SIZES = (21, 5, 13)             # prompts cross the 8-token prefill chunk
MAX_NEW = 9                     # one fused token, then two decode blocks
K = 4                           # decode block
# (no loop of the engine opens ``engine.sample``: its ticks sample inside
# their programs)
LEAVES = ("engine.plan", "engine.upload", "engine.rng_split",
          "engine.dispatch", "engine.fetch", "engine.harvest",
          "engine.reap")
# pieces of ``engine.plan``, bracketed inside it (test_plan_leaves.py)
PLAN_LEAVES = ("engine.plan.admit", "engine.plan.grow", "engine.plan.rows",
               "engine.plan.pack", "engine.plan.counts", "engine.plan.attrs")
COUNTERS = ("prefill_tokens", "prefill_key_tokens",
            "decode_key_token_steps", "dispatches", "ticks", "meta_uploads")


def _serve(params):
    """The same traffic every time: three greedy requests through a
    router and one replica; returns what ``serving_stages()`` and the
    completed-request window say afterwards."""
    rs = ReplicaSet(lambda i: RaggedInferenceEngineV2(
        LlamaForCausalLM(CFG), params=params, pipeline=True,
        rng=jax.random.PRNGKey(11), max_seqs=3, max_seq_len=128,
        prefill_chunk=8, decode_block_size=K, harvest_interval=3), 1)
    try:
        router = Router(rs)
        r = np.random.default_rng(3)
        for n in SIZES:
            router.submit(r.integers(1, 64, size=(n,), dtype=np.int32),
                          max_new_tokens=MAX_NEW)
        outs = router.drain()
        assert len(outs) == len(SIZES)
        eng = rs.handles[0].engine
        return eng.serving_stages(), eng.request_latency.completed()
    finally:
        rs.close()


def _host_threads(trace_dir):
    """``{line index: [(name, start_ns, end_ns, stats)]}`` of the program's
    spans in the ``/host:CPU`` plane (one line a thread)."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for k, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith(("engine.", "replica.", "router."))]
            if evs:
                out[k] = evs
    return out


@pytest.fixture(scope="module")
def params():
    return jax.jit(LlamaForCausalLM(CFG).init)(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32))


@pytest.fixture(scope="module")
def profiled(params, tmp_path_factory):
    """One profiled run for the whole module: no environment variable, no
    ``configure`` call — the profiler's session is the only switch."""
    assert not tracer_mod.trace.enabled
    d = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(d)
    try:
        stages, completed = _serve(params)
    finally:
        jax.profiler.stop_trace()
    threads = _host_threads(d)
    worker = [evs for evs in threads.values()
              if any(e[0] == "replica.op" for e in evs)]
    pump = [evs for evs in threads.values()
            if any(e[0] == "router.pump" for e in evs)]
    assert len(worker) == 1 and len(pump) == 1 and worker[0] is not pump[0]
    intake = [e for e in pump[0]
              if e[0] in ("router.submit", "router.prefix_hash")]
    return {"stages": stages, "completed": completed, "worker": worker[0],
            "pump": [e for e in pump[0] if e not in intake],
            "intake": intake}


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_host_plane_holds_every_span_of_the_table(profiled):
    worker = {e[0] for e in profiled["worker"]}
    assert worker == {"replica.op", "engine.step", *LEAVES, *PLAN_LEAVES}
    pump = {e[0] for e in profiled["pump"]}
    assert pump == {"router.pump", "router.fold", "router.dispatch"}
    kinds = {e[3]["kind"] for e in profiled["worker"]
             if e[0] == "replica.op"}
    assert {"put", "step"} <= kinds
    # a count rides on a span only where something reads it: the op's
    # kind, the dispatched program's work (the two tests below), and what
    # explains a plan leaf's cost (``fused_tick_leaves``, the log line of
    # ``benchmark/layer_metrics/fused_tick_host_ms.py``)
    assert all(e[3] == {} for e in profiled["worker"] + profiled["pump"]
               if e[0] not in ("replica.op", "engine.dispatch",
                               "router.dispatch", *PLAN_LEAVES)
               ), "an unread count"


def test_request_intake_is_a_span_of_the_thread_that_submits(profiled):
    """``Router.submit`` runs on the caller's thread (the front door's
    pump thread; here the test's own), which steps no replica meanwhile:
    ``router.submit`` with the sticky router's token-by-token
    ``router.prefix_hash`` nested in it."""
    evs = profiled["intake"]
    submits = [e for e in evs if e[0] == "router.submit"]
    hashes = [e for e in evs if e[0] == "router.prefix_hash"]
    assert len(submits) == len(hashes) == len(SIZES)
    assert all(any(_inside(h, s) for s in submits) for h in hashes)


def test_leaves_nest_in_engine_step_nested_in_replica_op(profiled):
    evs = profiled["worker"]
    ops = [e for e in evs if e[0] == "replica.op"]
    steps = [e for e in evs if e[0] == "engine.step"]
    assert steps and all(any(_inside(s, o) and o[3]["kind"] == "step"
                             for o in ops) for s in steps)
    for leaf in (e for e in evs if e[0] in LEAVES):
        assert any(_inside(leaf, s) for s in steps), leaf
    # one thread's spans never straddle
    steps.sort(key=lambda s: s[1])
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))


def test_prefill_counts_on_dispatch_spans_equal_the_prompts_and_the_counter(
        profiled):
    fused = [e[3] for e in profiled["worker"] if e[0] == "engine.dispatch"
             and e[3]["program"] == "fused"]
    assert sum(f["prefill_tokens"] for f in fused) == sum(SIZES)
    # a token at position p attends to p + 1 keys: n (n + 1) / 2 a prompt
    keys = sum(n * (n + 1) // 2 for n in SIZES)
    assert sum(f["prefill_key_tokens"] for f in fused) == keys
    st = profiled["stages"]
    assert st["prefill_tokens"] == sum(SIZES)
    assert st["prefill_key_tokens"] == keys


def test_decode_counts_on_dispatch_spans_equal_the_counter(profiled):
    spans = [e[3] for e in profiled["worker"] if e[0] == "engine.dispatch"]
    blocks = [s for s in spans if s["program"] == "decode_block"]
    assert blocks and all(b["ticks"] == K and 1 <= b["rows"] <= 3
                          for b in blocks)
    steps = sum(b["ticks"] * b["key_tokens"] +
                b["rows"] * b["ticks"] * (b["ticks"] - 1) // 2
                for b in blocks)
    steps += sum(s["decode_key_tokens"] for s in spans
                 if s["program"] == "fused")
    assert steps == profiled["stages"]["decode_key_token_steps"] > 0
    # the first block reads every prompt and what each request generated
    # in the fused ticks: at least the token its last chunk sampled
    assert blocks[0]["key_tokens"] >= sum(SIZES) + len(SIZES)


def test_without_a_session_nothing_is_written_and_the_counters_are_the_same(
        profiled, params, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert not TraceAnnotation.is_enabled()
    stages, completed = _serve(params)
    assert os.listdir(tmp_path) == []
    for key in COUNTERS:
        assert stages[key] == profiled["stages"][key], key
    for key in ("rng_split_s", "reap_s"):
        assert stages[key] > 0.0
    assert stages["sample_s"] == 0.0    # nothing samples on the host here
    # the keys serving_stages() always had are still there
    for s in HostStageStats.STAGES:
        assert f"{s}_ms" in stages
    assert sorted(r["prompt_tokens"] for r in completed) == sorted(SIZES)
    assert sorted(r["prompt_tokens"] for r in profiled["completed"]) == \
        sorted(SIZES)


def test_the_ring_still_receives_the_stages_when_the_tracer_is_on(params):
    tr = tracer_mod.trace
    prev = (tr.enabled, tr.buffer_size, tr.clock)
    tr.clear()
    tr.configure(enabled=True)
    try:
        stages, _ = _serve(params)
        evs = [e for e in tr.snapshot() if e["ph"] == "X"]
    finally:
        tr.configure(enabled=prev[0], buffer_size=prev[1], clock=prev[2])
        tr.clear()
    names = {e["name"] for e in evs}
    assert {"replica.op", "engine.step", *LEAVES, "router.pump",
            "router.fold", "router.dispatch", "router.submit",
            "router.prefix_hash"} <= names
    assert all(e["cat"] == "serving" for e in evs
               if e["name"].startswith(("engine.", "replica.", "router.")))
    fused = [e["args"] for e in evs if e["name"] == "engine.dispatch"
             and e["args"]["program"] == "fused"]
    assert sum(a["prefill_tokens"] for a in fused) == sum(SIZES) == \
        stages["prefill_tokens"]
    # one bracket, one duration: the ring's spans are what the stage
    # seconds were summed from
    plan_us = sum(e["dur"] for e in evs if e["name"] == "engine.plan")
    d = stages["dispatches"]
    assert plan_us / 1e3 / d == pytest.approx(stages["plan_ms"], abs=1e-3)


def test_a_bracket_is_live_with_the_tracer_off_and_a_span_is_not():
    tr = tracer_mod.Tracer(enabled=False)
    assert tr.span("a") is tracer_mod._NULL_SPAN
    with tr.bracket("replica.op", cat="serving", kind="step") as sp:
        pass
    assert isinstance(sp, tracer_mod.Span) and sp.dur >= 0.0
    assert sp.attrs == {"kind": "step"} and tr.snapshot() == []
    tr.configure(enabled=True)
    with tr.bracket("engine.plan", cat="serving"):
        pass
    (ev,) = tr.snapshot()
    assert ev["name"] == "engine.plan" and ev["cat"] == "serving"
    # the knob is gone: the profiler's session is the only switch
    assert not hasattr(tr, "annotate")
    with pytest.raises(TypeError):
        tr.configure(annotate=True)


def test_a_stage_bracket_feeds_seconds_histogram_and_span_from_one_clock():
    clk = iter([0.0, 10.0, 10.25, 20.0, 20.5])   # configure reads the epoch
    tr = tracer_mod.trace
    prev = (tr.enabled, tr.buffer_size, tr.clock)
    tr.clear()
    tr.configure(enabled=True, clock=lambda: next(clk))
    try:
        st = HostStageStats()
        with st.stage("device"):
            pass
        with st.stage("dispatch", program="fused", prefill_tokens=2):
            pass
        evs = tr.snapshot()
    finally:
        tr.configure(enabled=prev[0], buffer_size=prev[1], clock=prev[2])
        tr.clear()
    assert st.seconds["device"] == 0.25 and st.seconds["dispatch"] == 0.5
    assert [(e["name"], e["dur"], e["args"]) for e in evs] == [
        ("engine.fetch", 0.25e6, {}),
        ("engine.dispatch", 0.5e6, {"program": "fused",
                                    "prefill_tokens": 2})]
    out = st.serving_stages()
    assert out["host_s"] == 0.5 and out["device_wait_s"] == 0.25
    assert out["reap_s"] == 0.0         # bracketed, no part of the host sum
