"""What ``ops/paged_decode.py:tiling`` and ``ops/paged_prefill.py:tiling``
answer for every serving configuration of the benchmark that keeps per-head
K/V pages, a case each: the answers the configurations had before four KV
heads were given a tile of their own (PR 64) are unchanged, and each agrees
with the route its file expects.  ``keye-vl2-30b-a3b-serve`` (4 KV heads) and
``minicpm-sala-serve`` (2) attend through selection paths with pools of their
own layout and never ask these two rules."""
import json
import os

import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference import paged
from deepspeed_tpu.ops import paged_decode, paged_prefill

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark", "configs")
# configuration: (decode tiles, prefill tiles) at the parent of PR 64, by its
# own rules at the file's heads AS STORED, page size and pages a sequence
BEFORE = {
    "mistral-7b-serve": ((4, 4), (128, 8)),
    "olmoe-1b-7b-serve": ((2, 2), (256, 8)),
    "ouro-2.6b-serve": ((2, 2), (256, 8)),
    "lfm2-24b-a2b-serve": ((4, 4), (128, 8)),
    "olmo-hybrid-7b-serve": ((1, 1), (256, 8)),
    "solar-open2-250b-serve": ((4, 4), (128, 8)),
    "trinity-large-serve": ((4, 4), (128, 8)),
    # and the one this rule was written for
    "smallthinker-21b-a3b-serve": ((8, 8), (128, 8)),
}


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_tiling_answers_by_configuration(name):
    c = _config(name)
    h, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    stored = paged.kv_heads_stored(kv)
    heads = h + (stored - kv) * (h // kv)       # zero heads ride along
    page = int(c["engine"]["page_size"])
    pps = -(-int(c["engine"]["max_seq_len"]) // page)
    decode = paged_decode.tiling(stored, page, pps, 128, jnp.bfloat16)
    chunk = paged_prefill.tiling(heads, stored, page, 128, jnp.bfloat16)
    assert (decode, chunk) == BEFORE[name]
    want = c.get("expected_routes", {})
    if paged_decode.KERNEL in want:
        assert paged_decode.route_name(decode, stored) == want[
            paged_decode.KERNEL]
    if paged_prefill.KERNEL in want:
        assert paged_prefill.route_name(chunk, heads, stored, page) == want[
            paged_prefill.KERNEL]


@pytest.mark.parametrize("name,kv", [("keye-vl2-30b-a3b-serve", 4),
                                     ("minicpm-sala-serve", 2)])
def test_the_selection_paths_keep_their_pools_and_routes(name, kv):
    """Neither file expects a route of these two kernels, and the heads
    their pools store are the model's own."""
    c = _config(name)
    assert int(c["num_key_value_heads"]) == kv == paged.kv_heads_stored(kv)
    assert not {paged_decode.KERNEL, paged_prefill.KERNEL} & set(
        c["expected_routes"])
    assert paged_decode.tokens_a_tile(2) is None
