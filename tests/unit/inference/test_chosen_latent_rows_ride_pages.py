"""The two pool leaves of a chosen-latent model (``models/glm_moe_dsa.py``:
a latent row and an index key a token) lead with the page dim, and both are
functions of the token and its position alone: so they ride their page
wherever ``KVState`` takes it.  A spilled and restored sequence, a session
handed to another engine, and a request that starts behind a SHARED prefix
(whose last page is copied before the write) each give the tokens of the
plain reference, with prompts long enough that every decode step chooses
``index_topk`` 24 of its rows."""
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

from benchmark.reference import glm_moe_dsa as ref  # noqa: E402
from deepspeed_tpu.inference import paged  # noqa: E402
from deepspeed_tpu.inference.v2.ragged_engine import \
    RaggedInferenceEngineV2  # noqa: E402
from tests.unit.models.test_glm_moe_dsa import (CFG, MODEL,  # noqa: E402
                                                REF_CFG)

PROMPT = np.random.default_rng(3).integers(1, 96, size=45).astype(np.int32)
TOL = 1e-3


@pytest.fixture(scope="module")
def params():
    return jax.jit(MODEL.init)(jax.random.PRNGKey(7),
                               np.zeros((1, 8), np.int32))["params"]


def make_engine(params, **kw):
    return RaggedInferenceEngineV2(
        MODEL, params=params, **{**dict(
            pipeline=False, max_seqs=2, max_seq_len=96, prefill_chunk=16,
            page_size=8, num_pages=30, decode_block_size=2), **kw})


def _run(eng):
    while eng.has_work():
        eng.step()
    return {uid: list(toks) for uid, toks in eng.get_outputs()}


def _is_the_references(params, prompt, out):
    gaps = ref.generated_gaps(params, REF_CFG, list(prompt),
                              out[len(prompt):])
    return gaps.max() < TOL


def test_the_pool_holds_a_latent_row_and_an_index_key_a_layer(params):
    eng = make_engine(params)
    pools, states = paged.split_cache(eng.kv.cache)
    # a row of 32 + 8 stored as 128; a page of 8 index keys of 16 is ONE
    # row of 128 lanes
    assert sorted(l.shape for l in pools) == sorted(
        [(30, 8, 128), (30, 1, 128)] * CFG.num_hidden_layers)
    assert states == [] and eng.state_layers == 0
    names = {getattr(k, "key", None) for path, _ in
             jax.tree_util.tree_flatten_with_path(eng.kv.cache)[0]
             for k in path}
    assert {paged.LATENT_PAGES, paged.INDEX_KEY_PAGES} <= names
    assert paged.TOKENSEL_KV_PAGES not in names
    eng.close()


def test_a_spilled_and_restored_sequence_carries_both_leaves(params):
    """Spill a sequence mid-decode, scribble over both pools, restore: the
    tokens of a run that never spilled, which are the reference's."""
    want = make_engine(params)
    want.put_request(PROMPT, max_new_tokens=12)
    want = _run(want)[0]
    assert _is_the_references(params, PROMPT, want)
    eng = make_engine(params, kv_tiering={"host_pages": 32})
    eng.put_request(PROMPT, max_new_tokens=12)
    for _ in range(5):
        eng.step()
    (r,) = [s for s in eng.slots if s is not None]
    assert 0 < len(r.generated) < 12
    assert eng._spill(r) and eng.spills == 1
    eng.kv.cache = jax.tree_util.tree_map(
        lambda l: jnp.full_like(l, 3.0), eng.kv.cache)
    got = _run(eng)[0]
    assert eng.restores == 1 and got == want


def test_a_handoff_carries_both_leaves_to_another_engine(params):
    want = make_engine(params)
    want.put_request(PROMPT, max_new_tokens=10)
    want = _run(want)[0]
    a = make_engine(params, kv_tiering={"host_pages": 32})
    a.put_request(PROMPT, max_new_tokens=10)
    for _ in range(5):
        a.step()
    (r,) = [s for s in a.slots if s is not None]
    assert a._spill(r)
    (blob,) = a.export_parked()
    b = make_engine(params, kv_tiering={"host_pages": 32})
    (uid,) = b.import_parked([blob])
    assert _run(b)[uid] == want


@pytest.mark.parametrize("tail", [0, 7], ids=["whole-prompt", "longer"])
def test_a_request_behind_a_shared_prefix_gives_the_references_tokens(
        params, tail):
    """The second request finds the first's pages in the prefix index (its
    latent rows AND index keys), prefills only what is new (the matched last
    page copied before the write where the whole prompt matched) and chooses
    among rows it never computed."""
    eng = make_engine(params, prefix_cache={"enabled": True})
    first = eng.put_request(PROMPT[:40], max_new_tokens=8)
    out = _run(eng)
    assert _is_the_references(params, PROMPT[:40], out[first])
    again = np.concatenate([PROMPT[:40], PROMPT[40:40 + tail]])
    second = eng.put_request(again, max_new_tokens=8)
    out = _run(eng)
    st = eng.serving_stages()["prefix_cache"]
    assert st["hit_requests"] == 1 and st["hit_tokens"] >= 32
    assert (st["cow_copies"] == 1) == (tail == 0)
    assert _is_the_references(params, again, out[second])
    eng.close()
