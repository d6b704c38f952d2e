"""Per-slot state beside the paged pool (the short convolutions of
``models/lfm2_moe.py``: a few ROWS a layer; the linear attention of
``models/minicpm_sala.py``: a MATRIX a head), owned by ``KVState`` with the
pages' life: counted
apart from a page's bytes, zeroed when a request attaches, carried by spill
and restore and by a handoff's export and adopt, dead at release and
cancel; one-ahead dispatch reads the state its predecessor wrote; what
cannot carry the state is refused by name; and the spans and
``serving_stages()`` say what ran."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import lfm2_moe as ref  # noqa: E402
from benchmark.reference import minicpm_sala as sala_ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2 import programs  # noqa: E402
from deepspeed_tpu.inference.v2.ragged_engine import \
    RaggedInferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import lfm2_moe, minicpm_sala  # noqa: E402
from deepspeed_tpu.telemetry import trace  # noqa: E402
from tests.unit.models.test_lfm2_moe import CFG as REF_CFG  # noqa: E402
from tests.unit.models.test_lfm2_moe import TOL  # noqa: E402
from tests.unit.models.test_minicpm_sala import CFG as SALA_CFG  # noqa: E402
from tests.unit.models.test_minicpm_sala import MODEL as SALA  # noqa: E402
from tests.unit.models.test_minicpm_sala import \
    REF_CFG as SALA_REF_CFG  # noqa: E402

PAGE = 8
CFG = lfm2_moe.get_config("tinylfm2", dtype=jnp.float32,
                          param_dtype=jnp.float32, remat=False,
                          use_flash_attention=False)
MODEL = lfm2_moe.Lfm2MoeForCausalLM(CFG)
STATE_LAYERS, STATE_BYTES = 5, 5 * 2 * 128 * 4
PROMPT = np.arange(1, 20, dtype=np.int32)           # 19 tokens: 3 pages


@pytest.fixture(scope="module")
def params():
    return jax.jit(MODEL.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))["params"]


@pytest.fixture(scope="module")
def sala_params():
    return jax.jit(SALA.init)(jax.random.PRNGKey(7),
                              np.zeros((1, 8), np.int32))["params"]


class Family:
    """One shape of per-slot state, with the engine that serves it."""

    def __init__(self, model, params, reference, ref_cfg, tol, state_layers,
                 state_bytes, **engine):
        self.model, self.params, self.reference = model, params, reference
        self.ref_cfg, self.tol, self.engine = ref_cfg, tol, engine
        self.state_layers, self.state_bytes = state_layers, state_bytes

    def make(self, **kw):
        return RaggedInferenceEngineV2(
            self.model, params=self.params, **{**dict(
                pipeline=False, max_seqs=2, max_seq_len=64, prefill_chunk=8,
                decode_block_size=2), **self.engine, **kw})

    def is_the_references(self, prompt, out):
        gaps = self.reference.generated_gaps(
            self.params, self.ref_cfg, list(prompt), out[len(prompt):])
        return gaps.max() < self.tol


@pytest.fixture(params=["rows", "matrix"])
def fam(request, params, sala_params):
    """The state life cycle holds for both shapes: a few rows a layer
    (``[max_seqs, 2, 128]``), a matrix a head (``[max_seqs, 2, 128,
    128]``)."""
    if request.param == "rows":
        return Family(MODEL, params, ref, REF_CFG, TOL, STATE_LAYERS,
                      STATE_BYTES, page_size=PAGE, num_pages=14)
    return Family(SALA, sala_params, sala_ref, SALA_REF_CFG, 1e-3, 2,
                  2 * 2 * 128 * 128 * 4, page_size=16, num_pages=10)


def make_engine(params, **kw):
    return RaggedInferenceEngineV2(
        MODEL, params=params, **{**dict(
            pipeline=False, max_seqs=2, max_seq_len=64, prefill_chunk=8,
            page_size=PAGE, num_pages=14, decode_block_size=2), **kw})


def _run(eng):
    while eng.has_work():
        eng.step()
    return {uid: list(toks) for uid, toks in eng.get_outputs()}


def _states(eng, slot):
    return [np.asarray(l)[slot] for l in paged.split_cache(eng.kv.cache)[1]]


def _is_the_references(params, prompt, out):
    gaps = ref.generated_gaps(params, REF_CFG, list(prompt),
                              out[len(prompt):])
    return gaps.max() < TOL


def test_the_state_is_counted_apart_from_a_pages_bytes(params):
    """One of six layers holds K/V: a page costs that layer's rows (heads
    of 64 stored at 128), and the per-slot state is no page's cost."""
    assert (programs.state_layers(CFG), programs.kv_layers(CFG)) == (5, 1)
    page_bytes = programs.paged_kv_page_bytes(MODEL, CFG, PAGE, "none")
    assert page_bytes == PAGE * 2 * 1 * 128 * 4
    eng = make_engine(params, num_pages=None,
                      kv_pool_bytes=10 * page_bytes + page_bytes // 2)
    assert eng.num_pages == 10
    pools, states = paged.split_cache(eng.kv.cache)
    assert [l.shape for l in pools] == [(10, PAGE, 2, 128)]
    assert [l.shape for l in states] == [(2, 2, 128)] * STATE_LAYERS
    assert eng.kv.state_bytes_per_seq == STATE_BYTES
    assert eng.cache_bytes() == 10 * page_bytes + 2 * STATE_BYTES
    # the published stage, by shapes alone: 2 K/V layers of 8 KV heads
    # padded to 128 = 8 KiB a token; 8 conv layers x 2 rows x 2048 x bf16
    big = lfm2_moe.get_config(
        "lfm2-24b-a2b", num_hidden_layers=10, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        layer_types=lfm2_moe.PRESETS["lfm2-24b-a2b"]["layer_types"][:10])
    assert programs.paged_kv_page_bytes(
        lfm2_moe.Lfm2MoeForCausalLM(big), big, 64, "none") == 64 * 8192


def test_a_reused_slot_starts_from_zeros(fam):
    """Two requests through the ONE slot of an engine: the second finds
    the slot's state zeroed at its attach and gives the reference's
    tokens, whatever the first left there."""
    eng = fam.make(max_seqs=1)
    first = eng.put_request(PROMPT, max_new_tokens=6)
    out = _run(eng)
    assert fam.is_the_references(PROMPT, out[first])
    assert all(np.abs(s).max() > 0 for s in _states(eng, 0))
    other = np.arange(40, 53, dtype=np.int32)
    second = eng.put_request(other, max_new_tokens=6)
    eng._admit()
    assert eng.slots[0].uid == second
    assert all(np.abs(s).max() == 0 for s in _states(eng, 0))
    out = _run(eng)
    assert fam.is_the_references(other, out[second])


def test_a_spilled_and_restored_sequence_carries_its_state(params):
    """Spill a sequence mid-decode, scribble over the pool AND the state,
    restore: the tokens of a run that never spilled."""
    want = make_engine(params)
    want.put_request(PROMPT, max_new_tokens=12)
    want = _run(want)[0]
    assert _is_the_references(params, PROMPT, want)

    trace.configure(enabled=True)
    try:
        trace.clear()
        eng = make_engine(params, kv_tiering={"host_pages": 32})
        eng.put_request(PROMPT, max_new_tokens=12)
        for _ in range(4):
            eng.step()
        (r,) = [s for s in eng.slots if s is not None]
        assert 0 < len(r.generated) < 12
        held = _states(eng, r.slot)
        assert eng._spill(r) and eng.spills == 1
        assert all((a == b).all() for a, b in zip(held, r.spilled["state"]))
        eng.kv.cache = jax.tree_util.tree_map(
            lambda l: jnp.full_like(l, 3.0), eng.kv.cache)
        got = _run(eng)[0]
        spans = {e["name"]: e["args"] for e in trace.snapshot()
                 if e["name"] in ("engine.spill", "engine.restore")}
    finally:
        trace.configure(enabled=False)
        trace.clear()
    assert eng.restores == 1 and got == want
    assert spans["engine.spill"]["state_bytes"] == STATE_BYTES
    assert spans["engine.restore"]["state_bytes"] == STATE_BYTES


def test_a_handoff_carries_the_state_to_another_engine(params):
    """A spilled session exported from one engine and adopted by another
    continues as if it had never moved."""
    want = make_engine(params)
    want.put_request(PROMPT, max_new_tokens=10)
    want = _run(want)[0]
    a = make_engine(params, kv_tiering={"host_pages": 32})
    a.put_request(PROMPT, max_new_tokens=10)
    for _ in range(4):
        a.step()
    (r,) = [s for s in a.slots if s is not None]
    assert a._spill(r)
    (blob,) = a.export_parked()
    assert len(blob["spill"]["state"]) == STATE_LAYERS
    b = make_engine(params, kv_tiering={"host_pages": 32})
    (uid,) = b.import_parked([blob])
    assert _run(b)[uid] == want


def test_cancel_and_release_give_back_the_slot_with_its_pages(fam):
    eng = fam.make(max_seqs=1)
    free = eng.allocator.free_pages
    uid = eng.put_request(PROMPT, max_new_tokens=20)
    for _ in range(3):
        eng.step()
    assert eng.allocator.free_pages < free
    assert eng.cancel(uid) in ("prefill", "decode")
    assert eng.slots == [None] and eng.allocator.free_pages == free
    eng.audit_kv_sharing()
    # the next tenant of the slot is served as if nothing had been there
    nxt = eng.put_request(PROMPT[:11], max_new_tokens=5)
    assert fam.is_the_references(PROMPT[:11], _run(eng)[nxt])
    assert eng.allocator.free_pages == free


def test_one_ahead_dispatch_reads_the_state_its_predecessor_wrote(fam):
    """Four requests of different lengths through two slots, with fused
    ticks dispatched one ahead (``pipeline=True``) and not: the same
    tokens, the reference's."""
    prompts = [np.arange(1 + 7 * i, 1 + 7 * i + n, dtype=np.int32)
               for i, n in enumerate((19, 5, 26, 11))]

    def serve(pipeline):
        eng = fam.make(pipeline=pipeline, decode_block_size=4, num_pages=20)
        uids = [eng.put_request(p, max_new_tokens=9) for p in prompts]
        out = _run(eng)
        return [out[u] for u in uids], eng.serving_stages()

    ahead, stages = serve(True)
    plain, _ = serve(False)
    assert ahead == plain
    assert stages["fused_ticks_ahead"] > 0
    assert all(fam.is_the_references(p, o) for p, o in zip(prompts, ahead))


@pytest.mark.parametrize("kw,named", [
    (dict(prefix_cache={"enabled": True}), "prefix_cache"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype")],
    ids=["prefix-cache", "quantized-pool"])
def test_what_cannot_carry_the_state_is_refused_by_name(fam, kw, named):
    with pytest.raises(NotImplementedError, match=named):
        fam.make(**kw)


def test_a_matrix_state_is_no_entry_of_the_spill_tiers(sala_params):
    """18 MiB a sequence at the published widths: the engine refuses the
    tiers by name for such a model (``KVState``'s own verbs carry any
    state leaf: ``test_kv_state.py``)."""
    with pytest.raises(NotImplementedError, match="kv_tiering"):
        RaggedInferenceEngineV2(
            SALA, params=sala_params, max_seqs=2, max_seq_len=64,
            prefill_chunk=8, page_size=16, num_pages=10,
            kv_tiering={"host_pages": 32})


def test_tensor_parallel_serving_is_refused_by_name(params):
    import deepspeed_tpu.comm as dist

    topo = dist.initialize_mesh(tp=2)
    try:
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            make_engine(params, topology=topo)
    finally:
        dist.set_topology(None)


def test_the_spans_and_the_stages_say_what_ran(params):
    """``engine.dispatch`` carries ``state_rows`` and ``state_layers``,
    ``engine.moe`` the ``bias_swaps`` beside the counts every expert layer
    sows, and ``serving_stages()`` the layers of each kind, a sequence's
    state bytes, the routing form and the attention routes."""
    trace.configure(enabled=True)
    try:
        trace.clear()
        eng = make_engine(params)
        eng.put_request(PROMPT, max_new_tokens=8)
        _run(eng)
        events = trace.snapshot()
    finally:
        trace.configure(enabled=False)
        trace.clear()
    dispatches = [e["args"] for e in events if e["name"] == "engine.dispatch"]
    assert {d["program"] for d in dispatches} == {"fused", "decode_block"}
    assert all(d["state_layers"] == STATE_LAYERS and d["state_rows"] == 1
               for d in dispatches)
    moe = [e["args"] for e in events if e["name"] == "engine.moe"]
    # 4 expert layers x 2 experts a token x (19 prompt + 7 fed-back tokens)
    assert sum(m["moe_rows"] for m in moe) == 4 * 2 * (19 + 7)
    assert all(0 <= m["bias_swaps"] <= m["moe_rows"] // 2 for m in moe)
    st = eng.serving_stages()
    assert st["hybrid"] == {
        "kv_layers": 1, "state_layers": STATE_LAYERS,
        "state_bytes_per_seq": STATE_BYTES, "kv_head_dim_stored": 128,
        "routing": "sigmoid+bias", "bias_swaps": st["hybrid"]["bias_swaps"],
        "attention_routes": st["kernels"]}
    assert st["hybrid"]["bias_swaps"] == sum(m["bias_swaps"] for m in moe)
    assert st["kernels"]["paged_attention"] == "xla-dense-pad128"


def test_a_matrix_states_spans_and_stages_say_what_ran(sala_params):
    """``engine.dispatch`` carries ``state_rows``, ``state_layers`` and
    ``state_bytes`` and the selection's counts; ``serving_stages()`` holds
    the same under ``linear_state`` and ``sparse``, and the four routes."""
    trace.configure(enabled=True)
    try:
        trace.clear()
        eng = RaggedInferenceEngineV2(
            SALA, params=sala_params, pipeline=False, max_seqs=2,
            max_seq_len=128, prefill_chunk=32, page_size=16, num_pages=20,
            decode_block_size=2)
        eng.put_request(np.arange(1, 91, dtype=np.int32), max_new_tokens=6)
        _run(eng)
        events = trace.snapshot()
    finally:
        trace.configure(enabled=False)
        trace.clear()
    dispatches = [e["args"] for e in events if e["name"] == "engine.dispatch"]
    assert {d["program"] for d in dispatches} == {"fused", "decode_block"}
    assert all(d["state_layers"] == 2 and d["state_rows"] == 1
               and d["state_bytes"] == 2 * 2 * 128 * 128 * 4
               for d in dispatches)
    # positions 64..89 of the prompt and every decode step choose blocks
    # (five of the six to seven visible ones); a block counts all its
    # ticks, the one past the last token too
    far = sum(d["sparse_rows"] for d in dispatches)
    assert (90 - 64) + 5 <= far <= (90 - 64) + 6
    keys = sum(d["sparse_key_tokens"] for d in dispatches)
    seen = sum(d["sparse_visible_tokens"] for d in dispatches)
    assert 0.5 < keys / seen < 1.0
    fused = [d for d in dispatches if d["program"] == "fused"]
    assert sum(d["attend_prefill_tokens"] for d in fused) < 90 * 91 // 2
    st = eng.serving_stages()
    assert st["linear_state"] == {
        "state_layers": 2, "state_bytes": 2 * 2 * 128 * 128 * 4,
        "state_rows": len(dispatches)}
    assert st["sparse"]["sparse_key_tokens"] == keys
    assert st["sparse"]["sparse_visible_tokens"] == seen
    assert st["sparse"]["compressed_bytes"] == 2 * 20 * 4 * 128 * 4
    assert set(st["kernels"]) == {
        "linear_attention_scan", "linear_attention_decode",
        "sparse_attention_prefill", "sparse_attention_decode"}
