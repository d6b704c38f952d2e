"""The two engine programs of the three serving configurations the
benchmark had before LFM2 (``mistral-7b-serve``, ``olmoe-1b-7b-serve``,
``ouro-2.6b-serve``) lower to the text they lowered to at the parent of
PR 35, at a tiny size: what state beside pages, sigmoid routing, per-head
QK-norm and padded heads of 64 added is reached by no model that does not
ask for it.  PR 43 added the two programs of ``minicpm-sala-serve``
(tile layout, chunked scan and one-token step over a matrix state; block
selection over a two-KV-head pool), taken on its own tree: what a later PR
adds must leave them as they are too (PR 44 rebuilt the fused tick's
block-selecting chunk kernel and re-took ``sala.fused`` alone).  PR 47 added
the two programs of ``keye-vl2-30b-a3b-serve`` (an indexer's keys a pool
leaf, the choice by token, 4-KV-head pages a token a row), taken on its own
tree; the eight older hashes are as they were.  PR 48 re-took ``olmoe.*``
and ``keye.*`` and no other: a dropless expert layer's counts gained a
fourth entry (``moe_visits``, the grouped matmul's row-tile visits), which
is an output of both programs of every model with expert layers.  PR 50
added the two programs of ``olmo-hybrid-7b-serve``, taken on its own tree,
and moved none of the ten.  PR 51 re-took ``olmo_hybrid.fused`` and no
other: it MEANT to move it (``conv_state.py:ragged_short_conv`` gathers its
carried rows a sequence and no longer a token where a call has more rows
than sequences; a decode block, a row a sequence, is the parent's to the
letter).  It also added the two programs of ``lfm2-24b-a2b-serve``, the
function's other caller: ``lfm2.decode_block`` taken on the parent, which
this tree reads too, ``lfm2.fused`` on its own tree.  PR 54 re-took
``olmo_hybrid.fused`` and ``sala.fused`` and no other: it MEANT to move them
(the chunked scans take a tick's rows ``[rows, H * d]`` as the projections
leave them, gathered to tiles and back as 2-D rows, where every operand was
transposed to heads first and ``o`` back); both decode blocks, whose rows
take the one-token step, are the parent's to the letter (PR 53, the same
change refused on a run of a cell it cannot reach, had read the same two
values).  PR 56 added the two programs of ``solar-open2-250b-serve``, taken
on its own tree, and moved none of the fourteen: an expert layer told
``expert_shards=1`` (every older family) reaches ``grouped_moe``'s own body
and sows no ``held`` leaf, ``ragged_gated_delta`` takes its rule as an
argument whose default is the module it named, and ``moe_counts`` picks the
same leaves in the same order.  PR 57 re-took ``olmo_hybrid.fused`` and
``solar.fused`` and no other: it MEANT to move these two (the doubling of
both chunked scans is one function, ``ops/gated_delta_rule.py:solve``, and a
level with ``s >= 8`` of it and of KDA's ``P`` / ``R`` products multiplies
the ``C / 2`` rows the level couples where it multiplied the whole tile;
this file lowers on the CPU, where the tick takes the twins, which unroll
the same levels); both decode blocks, whose rows take the one-token step,
and the twelve programs of the six other models are the parent's to the
letter.  PR 59 added the two programs of ``trinity-large-serve``, taken on its
own tree (window layers and full layers in one model, each kind with a pool
and a page table of its own: ``page_indices [2, S, pp]`` and ``new_kv_dest
[2, T]`` for this model, and the same ARGUMENTS as every other model's
programs), and moved none of the sixteen: no other model's program gains an
argument, ``paged_update_and_attend`` reads the one table and the one window
where it is told no layer kind, and the decode block's destination rows are
the same operations under a local name.  PR 61 added the two programs of
``glm-4.7-flash-serve``, taken on its PARENT, and then deleted speculative
decoding and the partial-residency driver from the engine, the program
builders and the cache state: the twenty read on its tree what they read
before it.  PR 64 added the two programs of
``smallthinker-21b-a3b-serve``, taken on its own tree (the router's matmul
and top-k traced BEFORE the layer's attention from the layer's unnormed
input, ReGLU experts, four KV heads under both tables), and moved none of
the twenty: ``moe/layer.py`` routes from the rows it dispatches where a
caller passes neither ``route_only`` nor ``routing``, ``swiglu`` is the same
``silu``, and the paged kernels' paired tile is the chip's alone (this file
lowers on the CPU, where both programs take the twins).  PR 69 added the two
programs of ``glm-5-serve``, taken on its own tree, and moved none of the
twenty-five: ``models/glm4_moe_lite.py``'s attention makes its projections
in a function the new family shares (``latent_parts``, every row's queries
where they were traced: between ``q_a``'s norm and ``kv_a``), its block takes
its routed half from ``routed_experts`` with ``expert_shards=1``, and
``paged.describe`` takes its new branch for a config with BOTH
``kv_lora_rank`` and an indexer, which no older family has.  PR 66 re-took
``sala.fused``, ``olmo_hybrid.fused`` and ``solar.fused`` and no other: it
MEANT to move them (the three matrix-state kinds scan a tick through
``ops/linear_attention.py:scan_tick``, which reads the tick's own rows as the
scan's tiles where ``tile_layout`` says they are; at this file's tick of 20
rows, under one tile, the three keep the gathers and nothing else, in
another order, and KDA's ``g`` is gathered by ``rows_to_tiles`` as q is), and
added ``<model>.fused_tiles`` for the three: the same tick at 128 + 4 rows,
which holds the conditional and both layouts.  The three decode blocks and
the sixteen programs of the eight other models are the parent's to the
letter: ``tile_layout`` gained a key, ``aligned``, and a program that does
not read it does not hold it (``keye.fused``, whose token selection reads
the same layout, is as it was).  PR 67 re-took NONE: a delta-rule mixer's
short convolution, SiLU and unit norms are one pass of a kernel in a fused tick
ON THE CHIP (``ops/short_conv.py``, chosen by ``short_conv.route``), and this
file lowers on the CPU, where ``models/olmo_hybrid.py:ShortConv`` keeps the
lines it had (now its own for both families: the concatenation and the norms
moved into it from the two model files, in the order they were traced) and
``ragged_short_conv`` of an array is the parent's body with its state update
in a function: ``olmo_hybrid.*``, ``solar.*`` and ``lfm2.*``, ticks and decode
blocks, read what they read (the kernel is held to those lines in
``tests/unit/inference/test_conv_state.py`` and compiled for the chip in
``tests/unit/ops/test_tpu_compile.py``).  ``lfm2``'s tick keeps the twin on
the chip too (Step 0 of PR 67: its ``[544, 2048]`` rows read slower through
the kernel).

The hashes were taken on the parent commit by this file's own
:func:`lowered_hashes` (``python tests/unit/inference/
test_accepted_programs_unchanged.py`` prints them).  A change that MEANS to
move one of these programs re-takes them and says so; JAX's version is part
of the text, so an upgrade re-takes them too.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ENGINE = dict(max_seqs=4, max_seq_len=64, page_size=16, prefill_chunk=16,
              decode_block_size=4, num_pages=12)
# a tick of one row tile and four rows: where a matrix-state layer's scan
# holds its conditional (``<model>.fused_tiles``)
TILES = dict(ENGINE, max_seq_len=256, prefill_chunk=128, num_pages=24)
MATRIX_STATE = ("sala", "olmo_hybrid", "solar")

# at the parent of PR 35 (commit 92a6aee), JAX 0.9.0.  The three ``fused``
# hashes were re-taken by PR 41, which MEANT to move them: the tick's final
# norm and head see the ``max_seqs`` rows it samples (gathered from the
# hidden states) and no longer all ``T``; the decode blocks, where every
# row samples, are the parent's to the letter
EXPECTED = {
    "mistral.fused": "1ddf10a92080f32a",
    "mistral.decode_block": "1eb55c041ba3f8f4",
    # re-taken by PR 48, which MEANT to move them (and ``keye.*`` below):
    # the expert layers' counts are four, the grouped matmul's visits the
    # fourth
    "olmoe.fused": "92c6b2106939dc21",
    "olmoe.decode_block": "d39fb8c7bb91ce0e",
    "ouro.fused": "8443cc00e342ba49",
    "ouro.decode_block": "f87646743932c66c",
    # taken by PR 43, which added the model.  ``sala.fused`` was re-taken by
    # PR 44, which MEANT to move it: the tick's prompt-chunk attention takes
    # the chosen blocks a block at a time (``[Hkv, NT, blocks, 128]``) where
    # it took a mask a key (``[Hkv, rows, keys]``); the decode block, whose
    # rows take the chosen-page kernel, is PR 43's to the letter
    # ``sala.fused`` re-taken by PR 54, which MEANT to move it
    # (2e90302b3dc12e5f before it): ``ragged_linear_attention`` keeps a
    # tick's rows ``[T, H * d]`` from the tiles' gather to the gather back
    # and transposes nothing to heads first
    # Re-taken by PR 66, which MEANT to move it (613d88dcd69cc94d before it):
    # the scan goes through ``linear_attention.scan_tick`` (header)
    "sala.fused": "75e3b78e6247a7db",
    "sala.decode_block": "86bf79c9930ecd7c",
    # taken by PR 47, which added the model; re-taken by PR 48 (above).
    # PR 49 made the chip's ``index_scores`` and ``index_select`` ONE kernel
    # and MEANT to move ``keye.fused``; it re-took it here and read the same
    # hash, because this file lowers on the CPU, where the tick takes the
    # three twins in the order it took them (the kernel is held to them in
    # ``tests/unit/ops/test_token_select_attention.py``, and compiled for
    # the chip in ``tests/unit/ops/test_tpu_compile.py``)
    "keye.fused": "354cbe5ddd30061e",
    "keye.decode_block": "1c188bb320a89427",
    # taken by PR 50, which added the model (the tile layout at the delta
    # scan's chunk, the scan and the one-token step over a state held heads
    # along the lanes, a convolution's rows beside it, fifteen attention
    # heads stored as sixteen); the ten older hashes are as they were.
    # ``olmo_hybrid.fused`` re-taken by PR 51, which MEANT to move it
    # (930b42b334a6e816 before it): the short convolution reads its carried
    # rows ``[max_seqs, K - 1, C]`` a sequence and adds them to a chunk's
    # first ``K - 1`` rows where it copied them a token; the decode block,
    # a row a sequence, is PR 50's to the letter.  Re-taken again by PR 54,
    # which MEANT to move it (d613a233d64e7c0f before it):
    # ``ragged_gated_delta`` keeps a tick's rows ``[T, H * d]`` as
    # ``ragged_linear_attention`` does (above).  And by PR 57, which MEANT
    # to move it (b0d11e56bd01f5d8 before it): the scan's solve is
    # ``gated_delta_rule.solve``, whose levels from ``s = 8`` multiply the
    # half of the tile they couple.  And by PR 66, which MEANT to move it
    # (53a639c575758088 before it): ``scan_tick`` (header)
    "olmo_hybrid.fused": "ac425874177e8ba6",
    "olmo_hybrid.decode_block": "45abd5d3a9dd0bed",
    # added by PR 51 (ROADMAP D14), the same function's other caller: the
    # decode block taken on PR 51's parent, the tick (da4cded4c6195441
    # there, moved by the same edit) on its own tree
    "lfm2.fused": "6965d992f8bd0789",
    "lfm2.decode_block": "05e1a91e1d19ab60",
    # taken by PR 56, which added the model (the tile layout at the KDA
    # scan's chunk, the scan and the one-token step with a decay a key
    # channel, gated GQA without rotary, an expert layer that HOLDS 3 of its
    # router's 12 experts: ``held_moe`` and its ``cond``); the fourteen older
    # hashes are as they were.  ``solar.fused`` re-taken by PR 57, which
    # MEANT to move it (022473bd94042151 before it): the same solve, and the
    # level products of ``P`` and ``R`` on the rows that have the level's bit
    # Re-taken by PR 66, which MEANT to move it (3b7548ccb6e0b14e before it):
    # ``scan_tick`` (header), and ``g``, a key channel wide, gathered to the
    # tiles as q is (the scan zeroes it past a tile's ``len`` itself)
    "solar.fused": "ca0485438f5ebfe5",
    "solar.decode_block": "dd04a8cc32f4b803",
    # taken by PR 59, which added the model (one dense layer and one period
    # [window, full, window, window] after it: four window layers in a pool
    # of their own behind the second table, the rotary embedding on them
    # alone, a norm of q and k a head, the gate, four norms a layer, 3 of 12
    # experts held); the sixteen older hashes are as they were
    "trinity.fused": "d10c4ad62da86dbe",
    "trinity.decode_block": "f5ca964f43bde1af",
    # added by PR 61 (ROADMAP D14), taken on its PARENT before anything was
    # deleted: a PR that edits the engine, the program builders and the cache
    # state can show that the latent pool's two programs stood (a pool of one
    # row a token a layer, the absorbed decode, sigmoid routing with a bias
    # beside a shared expert); the eighteen older hashes are as they were
    "glm.fused": "2d330374a021b606",
    "glm.decode_block": "1e96c6f55cbd033a",
    # taken by PR 64, which added the model (two periods [full, window,
    # window, window]: six window layers in a pool of their own behind the
    # second table, the rotary embedding on them alone, the router issued
    # before attention, ReGLU banks); the twenty older hashes are as they were
    "smallthinker.fused": "9243c30541c804a3",
    "smallthinker.decode_block": "20f5f50bd6b890a2",
    # added by PR 66, taken on its own tree: the fused tick of the three
    # matrix-state models at ``TILES``, 132 rows, where ``scan_tick`` holds
    # ``lax.cond`` on ``tile_layout``'s ``aligned`` over the scan on the
    # tick's own rows and the scan on gathered tiles
    "sala.fused_tiles": "705f67376f7e206d",
    "olmo_hybrid.fused_tiles": "b5a3ea3915d44f72",
    "solar.fused_tiles": "e607b2685e40c2a0",
    # taken by PR 69, which added the model (a latent row and an index key a
    # token, two pool leaves; the indexer fed by the query latent, the choice
    # by token, the absorbed queries made from gathered rows of ``c_q``; 4 of
    # 8 experts held); the twenty-five older hashes are as they were
    "glm5.fused": "871c959472f8a6ba",
    "glm5.decode_block": "2343f9c53f22d4b7",
}


def _models():
    from deepspeed_tpu.models import (glm4_moe_lite, glm_moe_dsa, keye_vl2,
                                      lfm2_moe, minicpm_sala, mistral,
                                      olmo_hybrid, olmoe, ouro, smallthinker,
                                      solar_open2, trinity)

    bf16 = dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                scan_layers=False, remat=False, use_flash_attention=False)
    return {
        # heads of 128, as the configuration's own
        "mistral": mistral.MistralForCausalLM(mistral.get_config(
            "tinymistral", hidden_size=256, num_attention_heads=2,
            num_key_value_heads=1, **bf16)),
        "olmoe": olmoe.OlmoeForCausalLM(olmoe.get_config(
            "tinyolmoe", expert_parallel=False, **bf16)),
        "ouro": ouro.OuroForCausalLM(ouro.get_config("tinyouro", **bf16)),
        "sala": minicpm_sala.MiniCPMSALAForCausalLM(minicpm_sala.get_config(
            "tinysala", **bf16)),
        "keye": keye_vl2.KeyeVL2ForCausalLM(keye_vl2.get_config(
            "tinykeye", expert_parallel=False, **bf16)),
        "olmo_hybrid": olmo_hybrid.OlmoHybridForCausalLM(
            olmo_hybrid.get_config("tinyolmohybrid", **bf16)),
        "lfm2": lfm2_moe.Lfm2MoeForCausalLM(lfm2_moe.get_config(
            "tinylfm2", expert_parallel=False, **bf16)),
        "solar": solar_open2.SolarOpen2ForCausalLM(solar_open2.get_config(
            "tinysolar", **bf16)),
        "trinity": trinity.TrinityForCausalLM(trinity.get_config(
            "tinytrinity", **bf16)),
        "glm": glm4_moe_lite.Glm4MoeLiteForCausalLM(glm4_moe_lite.get_config(
            "tinyglm", **bf16)),
        "smallthinker": smallthinker.SmallThinkerForCausalLM(
            smallthinker.get_config("tinysmallthinker", **bf16)),
        "glm5": glm_moe_dsa.GlmMoeDsaForCausalLM(glm_moe_dsa.get_config(
            "tinyglm5", **bf16)),
    }


def _strip(text: str) -> str:
    text = re.sub(r"loc\(.*?\)\)?", "", text)
    return "\n".join(l.rstrip() for l in text.splitlines()
                     if not l.lstrip().startswith("#loc"))


def lowered_hashes():
    from deepspeed_tpu.inference import paged
    from deepspeed_tpu.inference.v2.ragged_engine import \
        RaggedInferenceEngineV2

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    boo = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)
    digest = lambda low: hashlib.sha256(
        _strip(low.as_text()).encode()).hexdigest()[:16]

    def fused_tick(eng, two):
        S, T, pp = eng.max_seqs, eng.T, eng.pages_per_seq
        return eng.fused_sample_step(False).lower(
            eng.params, eng.kv.cache, i32(S), i32(1, T), i32(1, T),
            i32(1, T), i32(S), i32(*two, S, pp), i32(S + 1), i32(1),
            i32(*two, T), i32(S), i32(S),
            # a model with per-slot state: each packed sequence's slot
            *([i32(S)] if eng.state_layers else []))

    out = {}
    for name, model in _models().items():
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
        # a model whose window layers keep a pool of their own sizes it
        own = ({"window_pool_bytes": 12 * 16384}
               if paged.window_pool_layers(model.config) else {})
        eng = RaggedInferenceEngineV2(model, params=params, **ENGINE, **own)
        S, pp = eng.max_seqs, eng.pages_per_seq
        two = (2,) if own else ()       # ... and brings both tables
        out[f"{name}.fused"] = digest(fused_tick(eng, two))
        out[f"{name}.decode_block"] = digest(eng.decode_block(False).lower(
            eng.params, eng.kv.cache, i32(S), i32(S), boo(S), i32(S),
            i32(*two, S, pp), i32(S), boo(S), f32(S), i32(S), f32(S), i32(S),
            jax.random.PRNGKey(0)))
        eng.close()
        if name in MATRIX_STATE:
            eng = RaggedInferenceEngineV2(model, params=params, **TILES)
            out[f"{name}.fused_tiles"] = digest(fused_tick(eng, ()))
            eng.close()
    return out


@pytest.fixture(scope="module")
def hashes():
    return lowered_hashes()


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_program_lowers_to_the_parents_text(hashes, program):
    assert hashes[program] == EXPECTED[program], (
        f"{program} no longer lowers to the text it had when its hash was "
        "taken: a model that asked for no new mechanism got one")


if __name__ == "__main__":
    import json

    print(json.dumps(lowered_hashes(), indent=4))
