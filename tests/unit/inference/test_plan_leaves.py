"""The leaves inside ``engine.plan`` and the step's booking by program.

A tiny engine serves three prompts with the tracer's ring on and an
injected clock (every read a second later than the last), so a span's
duration is the number of clock reads inside it and every sum below is
exact.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.common import HostStageStats
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.telemetry import tracer as tracer_mod

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=True, remat=False,
                 use_flash_attention=False)
SIZES = (21, 5, 13)             # prompts cross the 8-token prefill chunk
MAX_NEW = 9                     # one fused token, then two decode blocks
FUSED_LEAVES = {"engine.plan.admit": {"waiting", "admitted"},
                "engine.plan.grow": {"rows"},
                "engine.plan.rows": {"decode_rows", "prefill_seqs",
                                     "pages_granted", "page_limited",
                                     "prompt_rows", "prompt_tiles",
                                     "tiles_aligned"},
                "engine.plan.pack": {"rows", "seqs", "page_cells"},
                "engine.plan.counts": {"rows"},
                "engine.plan.attrs": set()}
BLOCK_LEAVES = ("engine.plan.counts", "engine.plan.attrs")
# what ``serving_stages()`` said before the leaves
KEYS = {f"{s}_ms" for s in HostStageStats.STAGES} | {
    "host_s", "device_wait_s", "host_bound_fraction", "ticks", "dispatches",
    "meta_uploads", "blocking_gets", "harvests", "prefill_tokens",
    "prefill_key_tokens", "decode_key_token_steps", "fused_ticks",
    "fused_ticks_ahead", "decode_blocks", "decode_blocks_ahead",
    "admissions", "admissions_ahead", "drains", "rng_split_s", "sample_s",
    "reap_s", "kernels", "fused_head", "pool", "requests"}


@pytest.fixture(scope="module")
def served():
    """``(spans, engine)``: the ring's complete events in order of start,
    as ``(name, start, end, args)`` in clock reads, and the engine after
    the last step."""
    params = jax.jit(LlamaForCausalLM(CFG).init)(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32))
    eng = RaggedInferenceEngineV2(
        LlamaForCausalLM(CFG), params=params, pipeline=True,
        rng=jax.random.PRNGKey(11), max_seqs=3, max_seq_len=128,
        prefill_chunk=8, decode_block_size=4, harvest_interval=3)
    tr = tracer_mod.trace
    prev = (tr.enabled, tr.buffer_size, tr.clock)
    tr.clear()
    reads = itertools.count()
    tr.configure(enabled=True, clock=lambda: float(next(reads)))
    try:
        r = np.random.default_rng(3)
        for n in SIZES:
            eng.put_request(r.integers(1, 64, size=(n,), dtype=np.int32),
                            max_new_tokens=MAX_NEW)
        while eng.has_work():
            eng.step()
        evs = [e for e in tr.snapshot() if e["ph"] == "X"]
    finally:
        tr.configure(enabled=prev[0], buffer_size=prev[1], clock=prev[2])
        tr.clear()
        eng.close()
    spans = sorted(((e["name"], round(e["ts"] / 1e6),
                     round((e["ts"] + e["dur"]) / 1e6), e["args"])
                    for e in evs), key=lambda s: (s[1], -s[2]))
    assert len(eng.get_outputs()) == len(SIZES)
    return spans, eng


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _steps(spans, program):
    """Each step that dispatched ``program`` (and no fused tick besides, if
    it is not ``fused``) as the spans inside it."""
    out = []
    for step in (s for s in spans if s[0] == "engine.step"):
        inner = [s for s in spans if s is not step and _inside(s, step)]
        programs = [s[3]["program"] for s in inner
                    if s[0] == "engine.dispatch"]
        if program in programs and ("fused" not in programs
                                    or program == "fused"):
            out.append(inner)
    return out


def test_a_fused_tick_emits_the_six_leaves_each_inside_a_plan_bracket(
        served):
    spans, eng = served
    ticks = _steps(spans, "fused")
    assert len(ticks) >= 3          # 21 tokens alone are three chunks
    for inner in ticks:
        plans = [s for s in inner if s[0] == "engine.plan"]
        leaves = [s for s in inner if s[0].startswith("engine.plan.")]
        assert {s[0] for s in leaves} == set(FUSED_LEAVES)
        for leaf in leaves:
            assert set(leaf[3]) == FUSED_LEAVES[leaf[0]], leaf
            assert sum(_inside(leaf, p) for p in plans) == 1, leaf
        # a stage holds its leaves' time: no second count of it
        assert sum(s[2] - s[1] for s in leaves) <= \
            sum(s[2] - s[1] for s in plans)
        # the attributes of the dispatch span are built in a plan bracket
        # of their own, the last thing before the dispatch bracket opens
        (dispatch,) = [s for s in inner if s[0] == "engine.dispatch"]
        k = inner.index(dispatch)
        assert [s[0] for s in inner[k - 2:k]] == ["engine.plan",
                                                  "engine.plan.attrs"]
        assert inner[k - 2][2] == dispatch[1] - 1   # one clock read apart
        rows = [s for s in inner if s[0] == "engine.plan.rows"][-1][3]
        pack = next(s[3] for s in inner if s[0] == "engine.plan.pack")
        assert pack["rows"] == dispatch[3]["prefill_tokens"] + \
            rows["decode_rows"]
        assert pack["seqs"] == rows["prefill_seqs"] + rows["decode_rows"]
        assert pack["page_cells"] == pack["seqs"] * eng.pages_per_seq


def test_what_the_leaves_say_adds_up_to_the_run(served):
    spans, eng = served
    ticks = _steps(spans, "fused")
    by = lambda name, key: sum(s[3][key] for inner in ticks  # noqa: E731
                               for s in inner if s[0] == name)
    assert by("engine.plan.admit", "admitted") == len(SIZES)
    assert by("engine.plan.rows", "page_limited") == 0
    # every prompt row went through a pack, and a row more a sampled token
    assert by("engine.plan.pack", "rows") >= sum(SIZES)
    assert by("engine.plan.pack", "rows") == by("engine.plan.counts", "rows")
    # (a page of 64 tokens a prompt, attached at admission)
    assert by("engine.plan.rows", "pages_granted") == 0


def test_a_decode_block_emits_counts_and_attrs_in_one_plan_bracket(served):
    spans, _ = served
    blocks = _steps(spans, "decode_block")
    assert blocks
    for inner in blocks:
        (dispatch,) = [s for s in inner if s[0] == "engine.dispatch"]
        k = inner.index(dispatch)
        assert [s[0] for s in inner[k - 3:k]] == ["engine.plan",
                                                  *BLOCK_LEAVES]
        assert all(_inside(s, inner[k - 3]) for s in inner[k - 2:k])
        # no tick is planned or packed for a block
        assert not {s[0] for s in inner} & {"engine.plan.rows",
                                            "engine.plan.pack"}
    # the first block leaves ``_step`` past its admission and growth; a
    # block of the pipeline's own step brackets these two alone
    assert [sorted(s[0] for s in inner if s[0].startswith("engine.plan."))
            for inner in blocks][-1] == sorted(BLOCK_LEAVES)


def test_a_leaf_adds_nothing_to_the_stage_seconds(served):
    spans, eng = served
    st = eng.host_stats
    for stage, name in HostStageStats.SPAN_NAMES.items():
        assert st.seconds[stage] == sum(
            s[2] - s[1] for s in spans if s[0] == name), stage
    assert st.seconds["plan"] > 0
    stages = eng.serving_stages()
    assert KEYS <= set(stages)
    assert stages["plan_ms"] == pytest.approx(
        st.seconds["plan"] * 1e3 / st.dispatches, abs=1e-3)


def test_a_step_is_booked_under_the_program_it_dispatched(served):
    spans, eng = served
    st = eng.host_stats
    got = eng.serving_stages()["fused_tick_host"]
    ticks, blocks = _steps(spans, "fused"), _steps(spans, "decode_block")
    steps = [s for s in spans if s[0] == "engine.step"]
    assert got["ticks"] == got["fused_steps"] == len(ticks) == st.fused_ticks
    assert got["decode_block_steps"] == len(blocks) == st.decode_blocks
    assert got["none_steps"] == len(steps) - len(ticks) - len(blocks)
    for program, inners in (("fused", ticks), ("decode_block", blocks)):
        mine = [s for s in steps
                if any(_inside(i[0], s) for i in inners)]
        assert got[f"{program}_wall_s"] == sum(s[2] - s[1] for s in mine)
        assert got[f"{program}_wait_s"] == sum(
            s[2] - s[1] for inner in inners for s in inner
            if s[0] == "engine.fetch")
    n = len(ticks)
    assert got["host_ms"] == pytest.approx(
        (got["fused_wall_s"] - got["fused_wait_s"]) * 1e3 / n, abs=1e-3)
    assert got["wait_ms"] == pytest.approx(
        got["fused_wait_s"] * 1e3 / n, abs=1e-3)
    # by span: every bracket inside a tick's step, a tick
    for name in ("engine.plan", "engine.upload", "engine.dispatch",
                 *FUSED_LEAVES):
        assert got[f"{name}_ms"] == pytest.approx(
            sum(s[2] - s[1] for inner in ticks for s in inner
                if s[0] == name) * 1e3 / n, abs=1e-3), name
    # one level of scalars: ``MonitorMaster.write_serving_health`` writes
    # each as ``Serving/fused_tick_host/<name>``
    assert all(isinstance(v, (int, float)) for v in got.values())


def test_a_stat_known_afterwards_joins_the_span():
    tr = tracer_mod.Tracer(enabled=True)
    with tr.bracket("engine.plan.admit", cat="serving", waiting=2) as sp:
        sp.add(admitted=1)
    with tr.bracket("engine.plan.rows", cat="serving") as bare:
        bare.add(decode_rows=3)
    assert [e["args"] for e in tr.snapshot()] == [
        {"waiting": 2, "admitted": 1}, {"decode_rows": 3}]
