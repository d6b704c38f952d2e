"""Ask the chip's compiler, without the chip.

Every Pallas kernel the main path reaches is compiled here for a
*described* v5e device at published widths, so a kernel that only ever ran
under the interpreter cannot reach a chip run before Mosaic has seen it.
Nothing executes: a pass says the compiler accepts the kernel, not that
its results are right (the interpret-mode parity tests say that).

The topology is described inside a fixture of THIS file only — never at
import, in a ``skipif`` or in a ``parametrize`` argument: one process at a
time may load the TPU library, and under xdist every worker imports every
test file.  The code under test asks ``jax.default_backend()`` and sees
the CPU here, so each case compiles the kernel function itself.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference import paged
from deepspeed_tpu.ops import fused_adam
from deepspeed_tpu.ops.flash_attention import (_flash_bwd_pallas,
                                               _flash_fwd_pallas)
from deepspeed_tpu.ops.ragged_paged_quant import ragged_paged_attention_quant


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip):
    """``chip_compile(fn, *specs)`` -> compiled text; each spec is a
    ``(shape, dtype)`` placed on the described chip.  The persistent
    compile cache is off around it: an entry written for a chip that is
    not attached cannot be read back and only warns."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Pallas kernel in the program"
        return text

    yield compile_
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# (B, H, Hkv, S, D): GPT-2 medium's attention as chip_smoke trains it, a
# Llama-3-8B / Mistral-7B GQA layer at 2k, and a chip's share of
# `pretrain-zero3`'s step (two packed rows of 4,096 tokens); and a length
# that is one block whose half is no whole lane tiles (no row groups there)
FLASH_SHAPES = [
    pytest.param((2, 16, 16, 1024, 64), id="d64-s1024"),
    pytest.param((1, 32, 8, 2048, 128), id="d128-s2048-gqa"),
    pytest.param((2, 32, 8, 4096, 128), id="d128-s4096-gqa"),
    pytest.param((1, 4, 2, 1000, 128), id="d128-s1000-one-block"),
]


@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_forward(chip_compile, shape):
    B, H, Hkv, S, D = shape
    chip_compile(
        functools.partial(_flash_fwd_pallas, sm_scale=D ** -0.5,
                          causal=True, block_q=1024, block_k=1024),
        ((B, H, S, D), jnp.bfloat16), ((B, Hkv, S, D), jnp.bfloat16),
        ((B, Hkv, S, D), jnp.bfloat16))


@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_backward(chip_compile, shape):
    B, H, Hkv, S, D = shape
    q, kv = ((B, H, S, D), jnp.bfloat16), ((B, Hkv, S, D), jnp.bfloat16)
    chip_compile(
        functools.partial(_flash_bwd_pallas, sm_scale=D ** -0.5,
                          causal=True, block_q=1024, block_k=1024),
        q, kv, kv, q, ((B, H, S), jnp.float32), q)


def test_fused_adam_largest_leaf(chip_compile):
    """GPT-2's tied embedding, the largest leaf the optimizer walks."""
    def update(g, p, m, v):
        kern = functools.partial(fused_adam._adam_kernel, b1=0.9, b2=0.999,
                                 eps=1e-8, wd=0.01, adam_w=True)
        return fused_adam._run_elementwise(
            kern, jnp.ones((2,), jnp.float32),
            [fused_adam._tile(x) for x in (g, p, m, v)], 3, False)

    leaf = ((50257, 1024), jnp.float32)
    chip_compile(update, leaf, leaf, leaf, leaf)


# one fused serving tick at Mistral-7B attention widths, as chip_smoke
# serves it: T = 8 decode rows + a 256-token prefill chunk, 64-token
# pages, 33 pages a sequence, the published 4096 window
T, H, HKV, D = 264, 32, 8, 128
PAGE, SEQS, PAGES_PER_SEQ, WINDOW = 64, 8, 33, 4096
POOL = 1 + SEQS * PAGES_PER_SEQ
META = [((SEQS,), jnp.int32), ((SEQS, PAGES_PER_SEQ), jnp.int32),
        ((SEQS + 1,), jnp.int32), ((1,), jnp.int32)]


# (query heads, KV heads): Mistral-7B's GQA and OLMoE-1B-7B's MHA, whose
# pages are twice as wide
RPA_HEADS = [pytest.param((32, 8), id="mistral-8kv"),
             pytest.param((16, 16), id="olmoe-16kv")]


@pytest.mark.parametrize("heads", RPA_HEADS)
def test_upstream_ragged_paged_kernel_bf16_pages(chip_compile, heads):
    """With the block sizes ``inference/paged/kv_pages.py`` hands it: its own
    tuned-table lookup asks the ATTACHED device for its generation, and
    what it answers for this shape does not fit VMEM.  The KV block
    follows from the KV heads and the page size."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import \
        kernel as rpa

    h, hkv = heads
    kv_pages = paged.rpa_kv_pages_per_block(hkv, PAGE, PAGES_PER_SEQ)
    assert kv_pages * PAGE * hkv == 512 * 8       # Mistral's: 512 x 32
    chip_compile(
        functools.partial(
            rpa.ragged_paged_attention, sm_scale=D ** -0.5,
            sliding_window=WINDOW, num_kv_pages_per_block=kv_pages,
            num_queries_per_block=paged._RPA_QUERY_BLOCK),
        ((T, h, D), jnp.bfloat16),
        ((POOL, PAGE, 2 * hkv, D), jnp.bfloat16), *META)


# the engine sizes of both serving configurations: 32 slots, 64 pages of 64
# tokens a sequence, a pool of 1,280 pages
ENGINE_SLOTS, ENGINE_PAGES_PER_SEQ, ENGINE_POOL = 32, 64, 1280
# the attention calls a compiled program may hold, by their names in its
# text: the decode kernel, the chunk kernel, upstream's ragged kernel
ATTENTION_KERNELS = ("paged_decode_attention", "paged_chunk_attention",
                     "ragged_paged_attention_kernel")


@pytest.mark.parametrize("heads,window,tiles", [
    pytest.param((32, 8), WINDOW, (4, 4), id="mistral-8kv"),
    pytest.param((16, 16), None, (2, 2), id="olmoe-16kv")])
def test_decode_kernel_at_the_serving_configurations(chip_compile, heads,
                                                     window, tiles):
    """``ops/paged_decode.py`` at the tiling its rule gives, under the
    default scoped VMEM, and the name a device trace will show for it."""
    from deepspeed_tpu.ops import paged_decode as pd

    h, hkv = heads
    assert pd.tiling(hkv, PAGE, ENGINE_PAGES_PER_SEQ, D,
                     jnp.bfloat16) == tiles
    text = chip_compile(
        functools.partial(pd._decode, sm_scale=D ** -0.5,
                          sliding_window=window, tiles=tiles,
                          interpret=False),
        ((ENGINE_SLOTS, h, D), jnp.bfloat16),
        ((ENGINE_POOL, PAGE, 2 * hkv, D), jnp.bfloat16),
        ((ENGINE_SLOTS,), jnp.int32),
        ((ENGINE_SLOTS, ENGINE_PAGES_PER_SEQ), jnp.int32),
        ((ENGINE_SLOTS,), jnp.bool_))
    assert pd.NAME == "paged_decode_attention" and f"%{pd.NAME}." in text


@pytest.mark.parametrize("heads,window,slots,pages_per_seq,pool,tiles", [
    pytest.param((32, 8), WINDOW, ENGINE_SLOTS, ENGINE_PAGES_PER_SEQ,
                 ENGINE_POOL, (128, 8), id="mistral-8kv"),
    pytest.param((16, 16), None, ENGINE_SLOTS, ENGINE_PAGES_PER_SEQ,
                 ENGINE_POOL, (256, 8), id="olmoe-16kv"),
    pytest.param((16, 16), None, 16, 16, 85 * 4, (256, 8),
                 id="ouro-16kv-looped-pool"),
    pytest.param((32, 8), None, ENGINE_SLOTS, ENGINE_PAGES_PER_SEQ,
                 ENGINE_POOL, (128, 8), id="lfm2-8kv-heads-padded-to-128")])
def test_chunk_kernel_at_the_serving_configurations(
        chip_compile, heads, window, slots, pages_per_seq, pool, tiles):
    """``ops/paged_prefill.py`` at the tiling its rule gives for a fused
    tick of ``slots`` + 512 rows, under the VMEM it reckons for itself,
    and the name a device trace will show for it."""
    from deepspeed_tpu.ops import paged_prefill as pp

    h, hkv = heads
    width = slots + 512
    assert pp.tiling(h, hkv, PAGE, D, jnp.bfloat16) == tiles
    assert pp.vmem_bytes(width, h, hkv, PAGE, D, tiles) < pp._VMEM_CEILING
    text = chip_compile(
        functools.partial(pp._chunk, sm_scale=D ** -0.5,
                          sliding_window=window, tiles=tiles,
                          interpret=False),
        ((h, width, D), jnp.bfloat16),
        ((pool, PAGE, 2 * hkv, D), jnp.bfloat16), ((slots,), jnp.int32),
        ((slots, pages_per_seq), jnp.int32), ((slots + 1,), jnp.int32),
        ((1,), jnp.int32))
    assert pp.NAME == "paged_chunk_attention" and f"%{pp.NAME}." in text


def test_each_program_holds_its_own_attention_kernel(one_chip, chip_compile,
                                                     monkeypatch):
    """The engine's two programs at ``mistral-7b-serve``'s widths and engine
    sizes, two layers deep, compiled for the described chip: the decode
    block holds one ``paged_decode_attention`` call a layer, the fused tick
    one ``paged_chunk_attention`` call a layer for its chunks and one
    ``paged_decode_attention`` for its one-query rows, and neither holds
    upstream's ``ragged_paged_attention_kernel``.  The switches ask for
    the backend and see the CPU here, so the test answers for them."""
    import re

    from deepspeed_tpu.inference.v2 import programs
    from deepspeed_tpu.models import mistral
    from deepspeed_tpu.ops import routes

    monkeypatch.setattr(routes, "on_tpu", lambda: True)
    monkeypatch.setattr(routes, "_taken", {})
    layers, chunk, max_len = 2, 512, PAGE * ENGINE_PAGES_PER_SEQ
    slots, width = ENGINE_SLOTS, ENGINE_SLOTS + 512
    model = mistral.MistralForCausalLM(programs.serving_config(
        mistral.get_config(
            "mistral-7b", num_hidden_layers=layers, dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16, scan_layers=False, remat=False,
            use_flash_attention=False),
        max_seq_len=max_len, page_size=PAGE, num_pages=ENGINE_POOL,
        tensor_parallel=False, kv_cache_dtype="none"))
    i32 = jnp.int32
    meta = {"kv_lens": jnp.zeros((slots,), i32),
            "page_indices": jnp.zeros((slots, ENGINE_PAGES_PER_SEQ), i32),
            "cu_q_lens": jnp.zeros((slots + 1,), i32),
            "num_seqs": jnp.zeros((1,), i32),
            "new_kv_dest": jnp.zeros((width,), i32)}
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, width), i32),
        positions=jnp.zeros((1, width), i32), ragged_meta=meta))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, cache = (jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype), shapes[k])
        for k in ("params", "cache"))
    per_slot = [spec((slots,), d) for d in (
        i32, i32, jnp.bool_, i32)]                # last_tok .. remaining
    table = spec((slots, ENGINE_PAGES_PER_SEQ), i32)
    sampling = [spec((slots,), d) for d in (
        i32, jnp.bool_, jnp.float32, i32, jnp.float32, jnp.uint32)]
    decode = programs.build_decode_block(
        model, sampled=False, max_seqs=slots, block=8, page_size=PAGE,
        max_seq_len=max_len, sample_base=jax.random.PRNGKey(0))
    fused = programs.build_fused_step(model)
    texts = {
        "decode": decode.lower(params, cache, *per_slot, table, *sampling,
                               spec((2,), jnp.uint32)).compile().as_text(),
        "fused": fused.lower(
            params, cache, spec((1, width), i32), spec((1, width), i32),
            spec((slots,), i32), table, spec((slots + 1,), i32),
            spec((1,), i32), spec((width,), i32),
            spec((slots,), i32)).compile().as_text()}
    calls = {name: tuple(len(re.findall(rf"%{kernel}[.\d]* = ", t))
                         for kernel in ATTENTION_KERNELS)
             for name, t in texts.items()}
    assert calls == {"decode": (layers, 0, 0), "fused": (layers, layers, 0)}
    assert routes.taken() == {"paged_attention": "pallas-ragged",
                              "paged_attention_prefill":
                                  "pallas-chunk-512x512",
                              "paged_attention_decode": "pallas-decode-4x8"}


def test_a_hybrid_models_programs_serve_heads_of_64_from_both_kernels(
        one_chip, monkeypatch):
    """The engine's two programs of ``lfm2-24b-a2b-serve`` at its widths
    and engine sizes, three layers deep (a convolution, attention and a
    convolution; a dense MLP and two expert layers), compiled for the
    described chip: heads of 64 lie in the pool zero-padded to 128, so the
    fused tick holds the chunk kernel (and the decode kernel for its
    one-query rows) and the decode block the decode kernel, one call a K/V
    LAYER, and neither falls to upstream's kernel or the dense XLA route;
    the convolution state is a leaf of its own beside the pool."""
    import re

    from deepspeed_tpu.inference import paged
    from deepspeed_tpu.inference.v2 import programs
    from deepspeed_tpu.models import lfm2_moe
    from deepspeed_tpu.ops import routes

    monkeypatch.setattr(routes, "on_tpu", lambda: True)
    monkeypatch.setattr(routes, "_taken", {})
    max_len = PAGE * ENGINE_PAGES_PER_SEQ
    slots, width = ENGINE_SLOTS, ENGINE_SLOTS + 512
    model = lfm2_moe.Lfm2MoeForCausalLM(programs.serving_config(
        lfm2_moe.get_config(
            "lfm2-24b-a2b", num_hidden_layers=3, num_dense_layers=1,
            layer_types=("conv", "full_attention", "conv"),
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False,
            use_flash_attention=False),
        max_seq_len=max_len, page_size=PAGE, num_pages=ENGINE_POOL,
        tensor_parallel=False, kv_cache_dtype="none"))
    i32 = jnp.int32
    meta = {"kv_lens": jnp.zeros((slots,), i32),
            "page_indices": jnp.zeros((slots, ENGINE_PAGES_PER_SEQ), i32),
            "cu_q_lens": jnp.zeros((slots + 1,), i32),
            "num_seqs": jnp.zeros((1,), i32),
            "new_kv_dest": jnp.zeros((width,), i32)}
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, width), i32),
        positions=jnp.zeros((1, width), i32), ragged_meta=meta))
    pools, states = paged.split_cache(shapes["cache"])
    assert [l.shape for l in pools] == [(ENGINE_POOL, PAGE, 16, 128)]
    assert [l.shape for l in states] == [(slots, 2, 2048)] * 2

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, cache = (jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype), shapes[k])
        for k in ("params", "cache"))
    per_slot = [spec((slots,), d) for d in (i32, i32, jnp.bool_, i32)]
    table = spec((slots, ENGINE_PAGES_PER_SEQ), i32)
    sampling = [spec((slots,), d) for d in (
        i32, jnp.bool_, jnp.float32, i32, jnp.float32, jnp.uint32)]
    decode = programs.build_decode_block(
        model, sampled=False, max_seqs=slots, block=8, page_size=PAGE,
        max_seq_len=max_len, sample_base=jax.random.PRNGKey(0))
    fused = programs.build_fused_step(model)
    texts = {
        "decode": decode.lower(params, cache, *per_slot, table, *sampling,
                               spec((2,), jnp.uint32)).compile().as_text(),
        "fused": fused.lower(
            params, cache, spec((1, width), i32), spec((1, width), i32),
            spec((slots,), i32), table, spec((slots + 1,), i32),
            spec((1,), i32), spec((width,), i32), spec((slots,), i32),
            spec((slots,), i32)).compile().as_text()}
    calls = {name: tuple(len(re.findall(rf"%{kernel}[.\d]* = ", t))
                         for kernel in ATTENTION_KERNELS + ("ragged-dot-gmm",))
             for name, t in texts.items()}
    assert calls == {"decode": (1, 0, 0, 6), "fused": (1, 1, 0, 6)}
    taken = routes.taken()
    assert taken["paged_attention"] == "pallas-ragged-pad128"
    assert taken["paged_attention_prefill"] == "pallas-chunk-512x512"
    assert taken["paged_attention_decode"] == "pallas-decode-4x8"


# ouro-2.6b-serve: 16 slots, 16 pages of 64 tokens a sequence, 85 pages of
# 4 K/V slots each, which both kernels read as a pool of 340
LOOP_SLOTS, LOOP_PAGES_PER_SEQ, LOOP_STEPS, LOOP_POOL = 16, 16, 4, 85


def test_both_kernels_on_a_looped_models_pool(chip_compile):
    """16 KV heads of 128 on ``P * U`` = 340 pages: the decode kernel at
    the tiling its rule gives for 16 pages a sequence, and the upstream
    kernel for a fused tick of 16 + 512 rows, no window."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import \
        kernel as rpa

    from deepspeed_tpu.ops import paged_decode as pd

    h = hkv = 16
    pool = ((LOOP_POOL * LOOP_STEPS, PAGE, 2 * hkv, D), jnp.bfloat16)
    tiles = pd.tiling(hkv, PAGE, LOOP_PAGES_PER_SEQ, D, jnp.bfloat16)
    assert tiles == (2, 2) and pd.route_name(tiles, hkv) == \
        "pallas-decode-2x16"
    text = chip_compile(
        functools.partial(pd._decode, sm_scale=D ** -0.5,
                          sliding_window=None, tiles=tiles, interpret=False),
        ((LOOP_SLOTS, h, D), jnp.bfloat16), pool, ((LOOP_SLOTS,), jnp.int32),
        ((LOOP_SLOTS, LOOP_PAGES_PER_SEQ), jnp.int32),
        ((LOOP_SLOTS,), jnp.bool_))
    assert f"%{pd.NAME}." in text
    chip_compile(
        functools.partial(
            rpa.ragged_paged_attention, sm_scale=D ** -0.5,
            sliding_window=None,
            num_kv_pages_per_block=paged.rpa_kv_pages_per_block(
                hkv, PAGE, LOOP_PAGES_PER_SEQ),
            num_queries_per_block=paged._RPA_QUERY_BLOCK),
        ((LOOP_SLOTS + 512, h, D), jnp.bfloat16), pool,
        ((LOOP_SLOTS,), jnp.int32),
        ((LOOP_SLOTS, LOOP_PAGES_PER_SEQ), jnp.int32),
        ((LOOP_SLOTS + 1,), jnp.int32), ((1,), jnp.int32))


def test_a_looped_models_programs_hold_each_layer_once(one_chip,
                                                       chip_compile,
                                                       monkeypatch):
    """The engine's two programs at ``ouro-2.6b-serve``'s widths and engine
    sizes, two layers deep and four passes a token, compiled for the
    described chip: one attention call of a kind a LAYER (the pass loop is
    a ``while`` of the program), each on its own route, and the pool one
    buffer of ``[P, U, page, 2 * Hkv, D]`` a layer."""
    import re

    from deepspeed_tpu.inference.v2 import programs
    from deepspeed_tpu.models import ouro
    from deepspeed_tpu.ops import routes

    monkeypatch.setattr(routes, "on_tpu", lambda: True)
    monkeypatch.setattr(routes, "_taken", {})
    layers, slots, width = 2, LOOP_SLOTS, LOOP_SLOTS + 512
    model = ouro.OuroForCausalLM(programs.serving_config(
        ouro.get_config(
            "ouro-2.6b", num_hidden_layers=layers, dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16, scan_layers=False, remat=False,
            use_flash_attention=False),
        max_seq_len=PAGE * LOOP_PAGES_PER_SEQ, page_size=PAGE,
        num_pages=LOOP_POOL, tensor_parallel=False, kv_cache_dtype="none"))
    i32 = jnp.int32
    meta = {"kv_lens": jnp.zeros((slots,), i32),
            "page_indices": jnp.zeros((slots, LOOP_PAGES_PER_SEQ), i32),
            "cu_q_lens": jnp.zeros((slots + 1,), i32),
            "num_seqs": jnp.zeros((1,), i32),
            "new_kv_dest": jnp.zeros((width,), i32)}
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, width), i32),
        positions=jnp.zeros((1, width), i32), ragged_meta=meta))
    assert [leaf.shape for leaf in jax.tree_util.tree_leaves(
        shapes["cache"])] == [(LOOP_POOL, LOOP_STEPS, PAGE, 32, D)] * layers

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, cache = (jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype), shapes[k])
        for k in ("params", "cache"))
    per_slot = [spec((slots,), d) for d in (i32, i32, jnp.bool_, i32)]
    table = spec((slots, LOOP_PAGES_PER_SEQ), i32)
    sampling = [spec((slots,), d) for d in (
        i32, jnp.bool_, jnp.float32, i32, jnp.float32, jnp.uint32)]
    decode = programs.build_decode_block(
        model, sampled=False, max_seqs=slots, block=8, page_size=PAGE,
        max_seq_len=PAGE * LOOP_PAGES_PER_SEQ,
        sample_base=jax.random.PRNGKey(0))
    fused = programs.build_fused_step(model)
    texts = {
        "decode": decode.lower(params, cache, *per_slot, table, *sampling,
                               spec((2,), jnp.uint32)).compile().as_text(),
        "fused": fused.lower(
            params, cache, spec((1, width), i32), spec((1, width), i32),
            spec((slots,), i32), table, spec((slots + 1,), i32),
            spec((1,), i32), spec((width,), i32),
            spec((slots,), i32)).compile().as_text()}
    calls = {name: tuple(len(re.findall(rf"%{kernel}[.\d]* = ", t))
                         for kernel in ATTENTION_KERNELS)
             for name, t in texts.items()}
    assert calls == {"decode": (layers, 0, 0), "fused": (layers, layers, 0)}
    assert routes.taken() == {"paged_attention": "pallas-ragged",
                              "paged_attention_prefill":
                                  "pallas-chunk-256x512",
                              "paged_attention_decode": "pallas-decode-2x16"}


# glm-4.7-flash-serve: 20 heads over rows of 512 + 64 stored as 640, pages
# of 64, 8 slots of 258 pages, a pool of 2,457 pages
LATENT = dict(S=8, pp=258, pool=2457, page=64, H=20, W=640, rank=512)


def test_latent_decode_kernel_at_the_serving_configuration(chip_compile):
    """``ops/latent_attention.py``'s decode kernel at the group its rule
    gives for ``glm-4.7-flash-serve``."""
    from deepspeed_tpu.ops import latent_attention as la

    c, i32, bf = LATENT, jnp.int32, jnp.bfloat16
    group, _, _ = la.tiling(c["page"], c["pp"], c["W"], c["rank"], bf)
    assert group == 16
    text = chip_compile(
        functools.partial(la._decode, rank=c["rank"], sm_scale=1 / 16.0,
                          group=group, interpret=False),
        ((c["S"], c["H"], c["W"]), bf),
        ((c["pool"], c["page"], c["W"]), bf), ((c["S"],), i32),
        ((c["S"], c["pp"]), i32), ((c["S"],), jnp.bool_))
    assert f"%{la.NAME_DECODE}" in text


@pytest.mark.parametrize("chunk", [512, 1024, 2048])
def test_latent_chunk_kernel_at_the_serving_configuration(chip_compile,
                                                          chunk):
    """The chunk kernel at the tiling its rule gives, at the published
    widths (20 heads, rows of 640, rank 512, pages of 64), heads first as
    the module hands it q: the fused tick of ``glm-4.7-flash-serve`` (a
    chunk of 1,024 beside 8 slots: 1,032 rows, grown to whole sublane
    tiles), the chunk of 512 it was first served at, and a chunk of 2,048,
    which fits VMEM since q no longer lies whole in it (ROADMAP R3(d)).
    ``chunk_vmem_bytes`` is what a call keeps there: the reckoning stands
    over what the chip's compiler asks (45.75 and 65.9 MiB at 1,024 and
    2,048: compile rehearsal, PR 45) and under the ceiling the call hands
    it."""
    from deepspeed_tpu.ops import latent_attention as la

    c, i32, bf = LATENT, jnp.int32, jnp.bfloat16
    _, rt, kb = la.tiling(c["page"], c["pp"], c["W"], c["rank"], bf)
    T = la.tick_rows(c["S"] + chunk)
    assert T == -(-(c["S"] + chunk) // 16) * 16
    need = la.chunk_vmem_bytes(T, c["H"], c["W"], c["rank"], rt,
                               kb * c["page"])
    assert need < la._VMEM_CEILING
    assert need >> 20 == {512: 40, 1024: 50, 2048: 70}[chunk]
    text = chip_compile(
        functools.partial(la._chunk, rank=c["rank"], sm_scale=1 / 16.0,
                          rt=rt, kb=kb, interpret=False),
        ((c["H"], T, c["W"]), bf), ((c["pool"], c["page"], c["W"]), bf),
        ((c["S"],), i32), ((c["S"], c["pp"]), i32), ((c["S"] + 1,), i32),
        ((1,), i32))
    assert f"%{la.NAME_PREFILL}" in text


def test_a_latent_pool_of_576_columns_is_laid_out_in_640(chip_compile):
    """Why the pool stores the row padded: the chip's compiler refuses a
    page copy that is no whole number of lane tiles, the memref of an
    unpadded pool being 640 wide whatever the program declares."""
    from deepspeed_tpu.ops import latent_attention as la

    c, i32, bf = LATENT, jnp.int32, jnp.bfloat16
    with pytest.raises(Exception, match="aligned to tiling"):
        chip_compile(
            functools.partial(la._decode, rank=c["rank"], sm_scale=1 / 16.0,
                              group=16, interpret=False),
            ((c["S"], c["H"], 576), bf), ((c["pool"], c["page"], 576), bf),
            ((c["S"],), i32), ((c["S"], c["pp"]), i32),
            ((c["S"],), jnp.bool_))


# minicpm-sala-serve: 32 linear heads of 128 over a 128 x 128 float32 state;
# 32 query heads over 2 KV heads, a pool of 2,457 pages laid out heads
# first, 8 slots of 258 pages, a fused tick of 8 + 1,024 rows
SALA = dict(S=8, pp=258, pool=2457, page=64, H=32, Hkv=2, D=128, T=1032)


def test_linear_attention_kernels_at_the_serving_configuration(chip_compile):
    """``ops/linear_attention.py``: the chunked scan over the tiles a
    fused tick of ``minicpm-sala-serve`` can hold, and the one-token step
    over its eight slots."""
    from deepspeed_tpu.ops import linear_attention as la

    c, i32, bf, f32 = SALA, jnp.int32, jnp.bfloat16, jnp.float32
    NT = -(-c["T"] // la.CHUNK) + c["S"]
    # rows as the projections leave them: a head a lane block of 128, four
    # heads a grid step
    rows = ((NT * la.CHUNK, c["H"] * c["D"]), bf)
    assert la.scan_heads(c["H"]) == 4
    state = ((c["S"], c["H"], c["D"], c["D"]), f32)
    lam = ((c["H"], 1, 128), f32)
    text = chip_compile(
        functools.partial(la._scan.__wrapped__, interpret=False),
        rows, rows, rows, lam, state, ((NT,), i32), ((NT,), i32),
        ((NT,), i32))
    assert f"%{la.SCAN_NAME}" in text
    row = ((c["S"], c["H"], c["D"]), bf)
    text = chip_compile(
        functools.partial(la._step.__wrapped__, interpret=False),
        row, row, row, lam, state, ((c["S"],), i32), ((c["S"],), jnp.bool_),
        ((1,), i32))
    assert f"%{la.STEP_NAME}" in text


def test_block_select_kernels_are_tiled_for_two_kv_heads(chip_compile):
    """``ops/block_select_attention.py`` over a pool of two KV heads laid
    out heads first (``[P, 4, page, D]``: a slab a head is whole tiles):
    the chunk kernel over a tick's tiles, its choice a block a bit and its
    VMEM reckoned from the shapes, and the chosen-page kernel over lists of
    128 pages."""
    from deepspeed_tpu.ops import block_select_attention as bsa
    from deepspeed_tpu.ops import linear_attention as la

    c, i32, bf = SALA, jnp.int32, jnp.bfloat16
    G = c["H"] // c["Hkv"]
    NT = -(-c["T"] // bsa.TILE) + c["S"]
    assert bsa.TILE == la.CHUNK         # one tile layout for both mixers
    assert NT == 17
    pool = ((c["pool"], 2 * c["Hkv"], c["page"], c["D"]), bf)
    blocks = bsa.chunk_blocks(c["pp"])
    assert blocks == 33 * 8
    # the limit the call asks for is its own reckoning, and the compiler
    # (which refuses a kernel that needs more than its limit) takes it
    asked = bsa.chunk_vmem_bytes(c["Hkv"], G, c["D"], c["page"], blocks)
    assert asked < 64 * 1024 * 1024     # half of the v5e's VMEM
    text = chip_compile(
        functools.partial(bsa._chunk.__wrapped__, dense_len=8192,
                          sm_scale=0.0884, interpret=False),
        ((c["Hkv"], G, NT * bsa.TILE, c["D"]), bf), pool,
        ((c["Hkv"], NT, blocks, bsa.TILE), jnp.float32), ((NT,), i32),
        ((NT,), i32), ((NT,), i32), ((c["S"], c["pp"]), i32))
    assert f"%{bsa.CHUNK_NAME}" in text
    assert f'"size":"{asked}"' in text      # the call's scoped memory
    L = bsa.Selection().list_width()
    assert L == 128
    text = chip_compile(
        functools.partial(bsa._decode.__wrapped__, sm_scale=0.0884,
                          interpret=False),
        ((c["S"], c["Hkv"], G, c["D"]), bf), pool,
        ((c["S"], c["Hkv"], L), i32), ((c["S"],), i32), ((c["S"],), i32))
    assert f"%{bsa.DECODE_NAME}" in text


# keye-vl2-30b-a3b-serve: 32 query heads over 4 KV heads of 128, an indexer
# of 16 heads of 64 over one index key a token, a pool of 4,160 pages (K/V a
# token a row, index keys beside them), 8 slots of 513 pages, a fused tick
# of 8 + 1,024 rows
KEYE = dict(S=8, pp=513, pool=4160, page=64, H=32, Hkv=4, D=128, HI=16,
            dI=64, T=1032, topk=2048)


def test_token_select_kernels_at_the_serving_configuration(chip_compile):
    """``ops/token_select_attention.py`` at the published widths: the one
    kernel that scores a tile's index queries through the page table (eight
    pages a key step), finds the exact threshold with the tile's 33,280
    scores resident in VMEM and never elsewhere, and copies the bias out a
    key step at a time; the dense pass under that per-(row, key) bias over
    a pool of four KV heads a token a row; and a decode row's scores over
    the pages it sees; the limits the calls ask for are their own
    reckoning."""
    from deepspeed_tpu.ops import linear_attention as la
    from deepspeed_tpu.ops import token_select_attention as tsa

    c, i32, bf = KEYE, jnp.int32, jnp.bfloat16
    G = c["H"] // c["Hkv"]
    NT = -(-c["T"] // tsa.TILE) + c["S"]
    assert tsa.TILE == la.CHUNK and NT == 17
    K = tsa.padded_keys(c["pp"], c["page"])
    assert K == 65 * 512 and tsa.key_step(c["page"]) == 512
    tiles = (((NT,), i32),) * 3
    table = ((c["S"], c["pp"]), i32)
    index_pool = tsa.index_leaf_shape(c["pool"], c["page"], c["dI"])
    assert index_pool == (c["pool"], 32, 128)     # two keys a row of lanes
    asked = tsa.select_vmem_bytes(K, c["HI"] * tsa.TILE, 512, 2)
    assert asked < 32 * 1024 * 1024         # a quarter of the v5e's VMEM
    text = chip_compile(
        functools.partial(tsa._select.__wrapped__, topk=c["topk"],
                          interpret=False),
        ((NT, c["HI"] * tsa.TILE, c["dI"]), bf),
        ((NT, 1, c["HI"] * tsa.TILE), jnp.float32),
        (index_pool, bf), table, *tiles)
    assert f"%{tsa.SELECT_NAME}" in text and f'"size":"{asked}"' in text
    # the scores and the choice are one kernel: no second call, and no
    # [tiles, keys, 128] int32 array between two
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"s32[{NT},{K},{tsa.TILE}]" not in text
    # its bias comes a key step at a time: the limit no longer grows with K
    asked = tsa.chunk_vmem_bytes(c["Hkv"], G, c["D"], c["page"])
    assert asked < 32 * 1024 * 1024
    text = chip_compile(
        functools.partial(tsa._chunk.__wrapped__, sm_scale=0.0884,
                          interpret=False),
        ((c["Hkv"], G, NT * tsa.TILE, c["D"]), bf),
        ((c["pool"], c["page"], 2 * c["Hkv"] * c["D"]), bf),
        ((NT, K, tsa.TILE), bf), *tiles, table)
    assert f"%{tsa.CHUNK_NAME}" in text and f'"size":"{asked}"' in text
    text = chip_compile(
        functools.partial(tsa._row_index.__wrapped__, interpret=False),
        ((c["S"], c["HI"], c["dI"]), bf), ((c["S"], c["HI"]), jnp.float32),
        (index_pool, bf), table, ((c["S"],), i32),
        ((c["S"],), i32))
    assert f"%{tsa.ROW_INDEX_NAME}" in text


# glm-5-serve: 64 heads over rows of 512 + 64 stored as 640, 32 index heads
# of 128 over ONE index key a row of lanes, pages of 64, 8 slots of 258
# pages, a pool of 2,080 pages, a fused tick of 8 + 1,024 rows
GLM5 = dict(S=8, pp=258, pool=2080, page=64, H=64, W=640, rank=512, HI=32,
            dI=128, T=1032, topk=2048)


def test_chosen_latent_kernels_at_the_serving_configuration(chip_compile):
    """ISSUE 69's step 0, asked of the chip's compiler before any chip call:
    ``index_select`` and ``row_index_scores`` at 32 index heads of 128 (a key
    a row: ``index_pack`` 1), and ``ops/latent_select_attention.py``'s chunk
    kernel at 64 heads, sixteen a grid step.  The DENSE latent chunk kernel
    cannot stand at 64 heads: its tile is every head of 128 tokens and it
    keeps the tick's whole output, 158 MiB against its 100 MiB ceiling, so
    ``fused_tick_attention`` would take its XLA twin there."""
    from deepspeed_tpu.ops import latent_attention as dense
    from deepspeed_tpu.ops import latent_select_attention as lsa
    from deepspeed_tpu.ops import linear_attention as la
    from deepspeed_tpu.ops import token_select_attention as tsa

    c, i32, bf = GLM5, jnp.int32, jnp.bfloat16
    _, rt, kb = dense.tiling(c["page"], c["pp"], c["W"], c["rank"], bf)
    need = dense.chunk_vmem_bytes(dense.tick_rows(c["T"]), c["H"], c["W"],
                                  c["rank"], rt, kb * c["page"])
    assert need > dense._VMEM_CEILING and need >> 20 == 158
    NT = -(-c["T"] // tsa.TILE) + c["S"]
    assert tsa.TILE == la.CHUNK == lsa.TILE and NT == 17
    K = tsa.padded_keys(c["pp"], c["page"])
    assert K == 33 * 512 and tsa.key_step(c["page"]) == 512
    tiles = (((NT,), i32),) * 3
    table = ((c["S"], c["pp"]), i32)
    assert tsa.index_pack(c["page"], c["dI"]) == 1
    index_pool = tsa.index_leaf_shape(c["pool"], c["page"], c["dI"])
    assert index_pool == (c["pool"], 64, 128)       # a key a row of lanes
    assert tsa._index_eligible(
        jax.ShapeDtypeStruct((1, 1, c["dI"]), bf),
        jax.ShapeDtypeStruct(index_pool, bf))
    asked = tsa.select_vmem_bytes(K, c["HI"] * tsa.TILE, 512, 1)
    assert asked >> 20 == 44                # a third of the v5e's VMEM
    text = chip_compile(
        functools.partial(tsa._select.__wrapped__, topk=c["topk"],
                          interpret=False),
        ((NT, c["HI"] * tsa.TILE, c["dI"]), bf),
        ((NT, 1, c["HI"] * tsa.TILE), jnp.float32),
        (index_pool, bf), table, *tiles)
    assert f"%{tsa.SELECT_NAME}" in text and f'"size":"{asked}"' in text
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    text = chip_compile(
        functools.partial(tsa._row_index.__wrapped__, interpret=False),
        ((c["S"], c["HI"], c["dI"]), bf), ((c["S"], c["HI"]), jnp.float32),
        (index_pool, bf), table, ((c["S"],), i32), ((c["S"],), i32))
    assert f"%{tsa.ROW_INDEX_NAME}" in text
    hg = lsa.heads_a_step(c["H"])
    assert hg == 16 and lsa.eligible(c["W"], c["rank"], c["page"], bf)
    asked = lsa.chunk_vmem_bytes(hg, c["W"], c["rank"], c["page"])
    assert asked >> 20 == 32
    text = chip_compile(
        functools.partial(lsa._chunk.__wrapped__, rank=c["rank"],
                          sm_scale=1 / 16.0, hg=hg, interpret=False),
        ((c["H"], NT * lsa.TILE, c["W"]), bf),
        ((c["pool"], c["page"], c["W"]), bf), ((NT, K, lsa.TILE), bf),
        *tiles, table)
    assert f"%{lsa.CHUNK_NAME}" in text and f'"size":"{asked}"' in text


# olmo-hybrid-7b-serve: 30 delta-rule heads of 96 / 192 over a 96 x 192
# float32 state held heads along the lanes; 30 attention heads of 128 stored
# as 32 in a pool of 2,065 pages, 8 slots of 258 pages, a fused tick of
# 8 + 1,024 rows
OLMO = dict(S=8, pp=258, pool=2065, page=64, H=30, dk=96, dv=192, D=128,
            T=1032)


@pytest.mark.parametrize("chunk", [64, 128])
def test_gated_delta_kernels_at_the_serving_configuration(chip_compile,
                                                          chunk):
    """``ops/gated_delta_rule.py`` at the published 96 / 192: the chunked
    scan (the solve, the unaligned lane slice a head of the state block and
    of a tile's rows ``[NT * C, H * d]``: two heads a step, q's and k's
    block the four heads' lanes that are whole lane tiles, the last block
    half past the row's end) over the tiles a fused tick of
    ``olmo-hybrid-7b-serve`` can hold, and the one-token step over its
    eight slots."""
    from deepspeed_tpu.ops import gated_delta_rule as gd

    c, i32, bf, f32 = OLMO, jnp.int32, jnp.bfloat16, jnp.float32
    NT = -(-c["T"] // chunk) + c["S"]
    rows = lambda d: ((NT * chunk, c["H"] * d), bf)
    state = ((c["S"], c["dk"], c["H"] * c["dv"]), f32)
    assert (c["H"] * c["dv"]) % 128 == 0
    assert gd.scan_eligible(c["H"], c["dk"], c["dv"], bf)
    assert gd._scan_blocks(c["H"], c["dk"]) == (2, 2)
    text = chip_compile(
        functools.partial(gd._scan.__wrapped__, chunk=chunk,
                          interpret=False),
        rows(c["dk"]), rows(c["dk"]), rows(c["dv"]),
        ((c["H"], NT, 2, chunk), f32), state, ((NT,), i32), ((NT,), i32),
        ((NT,), i32))
    assert f"%{gd.SCAN_NAME}" in text
    if chunk != gd.CHUNK:
        return
    row = lambda d: ((c["S"], c["H"], d), bf)
    gate = ((c["S"], c["H"]), f32)
    text = chip_compile(
        functools.partial(gd._step.__wrapped__, interpret=False),
        row(c["dk"]), row(c["dk"]), row(c["dv"]), gate, gate, state,
        ((c["S"],), i32), ((c["S"],), jnp.bool_), ((1,), i32))
    assert f"%{gd.STEP_NAME}" in text


def test_thirty_heads_are_served_from_both_kernels_as_thirty_two(
        chip_compile):
    """30 KV heads are 60 combined heads a token, no whole number of
    sublane tiles: both kernels refuse them (``tiling`` answers None).
    Stored as 32 (``kv_pages.py:kv_heads_stored``) both take the pool."""
    from deepspeed_tpu.ops import paged_decode, paged_prefill

    c, i32, bf = OLMO, jnp.int32, jnp.bfloat16
    assert paged.kv_heads_stored(c["H"]) == 32
    assert paged_prefill.tiling(30, 30, c["page"], c["D"], bf) is None
    assert paged_decode.tiling(30, c["page"], c["pp"], c["D"], bf) is None
    tiles = paged_prefill.tiling(32, 32, c["page"], c["D"], bf)
    decode_tiles = paged_decode.tiling(32, c["page"], c["pp"], c["D"], bf)
    assert tiles is not None and decode_tiles is not None
    assert paged_prefill.vmem_bytes(c["T"] + 8, 32, 32, c["page"], c["D"],
                                    tiles) < paged_prefill._VMEM_CEILING
    pool = ((c["pool"], c["page"], 64, c["D"]), bf)
    meta = [((c["S"],), i32), ((c["S"], c["pp"]), i32),
            ((c["S"] + 1,), i32), ((1,), i32)]
    text = chip_compile(
        functools.partial(paged_prefill._chunk.__wrapped__, sm_scale=0.0884,
                          sliding_window=None, tiles=tiles, interpret=False),
        ((32, c["T"] + 8, c["D"]), bf), pool, *meta)
    assert f"%{paged_prefill.NAME}" in text
    text = chip_compile(
        functools.partial(paged_decode._decode.__wrapped__, sm_scale=0.0884,
                          sliding_window=None, tiles=decode_tiles,
                          interpret=False),
        ((c["S"], 32, c["D"]), bf), pool, meta[0], meta[1],
        ((c["S"],), jnp.bool_))
    assert f"%{paged_decode.NAME}" in text


# solar-open2-250b-serve: 64 KDA heads of 128 / 128 over a 128 x 128 float32
# state held heads along the lanes (both dimensions whole lane tiles), g a
# vector a key channel; 64 query heads over 8 KV heads of 128 in a pool of
# 2,065 pages, 8 slots of 258 pages, a fused tick of 8 + 1,024 rows
SOLAR = dict(S=8, pp=258, pool=2065, page=64, H=64, dk=128, dv=128, Hq=64,
             Hkv=8, D=128, T=1032)


def test_kda_kernels_at_the_serving_configuration(chip_compile):
    """``ops/kda.py`` at the published 128 / 128: the chunked scan (the
    running sum, the seven level products with their sublane rolls, the
    solve) over the tiles a fused tick of ``solar-open2-250b-serve`` can
    hold, and the one-token step over its eight slots."""
    from deepspeed_tpu.ops import kda

    c, i32, bf, f32 = SOLAR, jnp.int32, jnp.bfloat16, jnp.float32
    chunk = kda.CHUNK
    NT = -(-c["T"] // chunk) + c["S"]
    rows = lambda d, t=bf: ((NT * chunk, c["H"] * d), t)
    state = ((c["S"], c["dk"], c["H"] * c["dv"]), f32)
    assert kda.scan_eligible(c["H"], c["dk"], c["dv"], bf)
    text = chip_compile(
        functools.partial(kda._scan.__wrapped__, chunk=chunk,
                          interpret=False),
        rows(c["dk"]), rows(c["dk"]), rows(c["dv"]), rows(c["dk"], f32),
        ((NT * chunk, c["H"]), f32), state, ((NT,), i32), ((NT,), i32),
        ((NT,), i32))
    assert f"%{kda.SCAN_NAME}" in text
    row = lambda d: ((c["S"], c["H"], d), bf)
    text = chip_compile(
        functools.partial(kda._step.__wrapped__, interpret=False),
        row(c["dk"]), row(c["dk"]), row(c["dv"]),
        ((c["S"], c["H"], c["dk"]), f32), ((c["S"], c["H"]), f32), state,
        ((c["S"],), i32), ((c["S"],), jnp.bool_), ((1,), i32))
    assert f"%{kda.STEP_NAME}" in text


# (width, the head size the pass norms, or None): Solar's q~ (64 KDA heads of
# 128, a head a lane tile) and v~; Olmo-Hybrid's q~ (30 heads of 96: no whole
# lane tiles, so the block is the part's width, a tile of the pass four heads
# in three lane tiles and the last one two heads in one and a half) and v~
# (45 lane tiles: blocks of five)
SHORT_CONV = [
    pytest.param(8192, 128, id="solar-q-normed"),
    pytest.param(8192, None, id="solar-v"),
    pytest.param(2880, 96, id="olmo-hybrid-q-normed"),
    pytest.param(5760, None, id="olmo-hybrid-v")]


@pytest.mark.parametrize("width,head_dim", SHORT_CONV)
def test_short_conv_rows_at_the_serving_configurations(chip_compile, width,
                                                       head_dim):
    """``ops/short_conv.py`` over a part of a fused tick of 8 + 1,024 rows
    (the last block of rows holds 8 of them) with the 24 rows that start a
    chunk to set, under the default scoped VMEM by its own reckoning."""
    from deepspeed_tpu.ops import short_conv

    assert short_conv.eligible(SOLAR["T"], 4, jnp.bfloat16, head_dim)
    assert short_conv.vmem_bytes(width, 4, head_dim) < 16 * 1024 * 1024
    text = chip_compile(
        functools.partial(short_conv._rows.__wrapped__, silu=True,
                          head_dim=head_dim, eps=1e-6, interpret=False),
        ((SOLAR["T"], width), jnp.bfloat16), ((4, width), jnp.float32),
        ((24, width), jnp.float32), ((24,), jnp.int32), ((1,), jnp.float32))
    assert f"%{short_conv.NAME}" in text


def test_a_kda_layers_pre_scan_function_writes_no_float32_rows(chip_compile):
    """q~, k~ and v~ of a Solar KDA layer through the one-pass body of
    ``ragged_short_conv`` (the rows that start a chunk computed whole, three
    calls of the kernel, the state's update): no value of the
    compiled program is the tick's rows in float32, which the twin's
    scatter-add wrote and SiLU read back (PR 67)."""
    import flax.linen as nn

    from deepspeed_tpu.inference.paged.tree import SLOT_STATE
    from deepspeed_tpu.ops import short_conv

    c, i32, bf = SOLAR, jnp.int32, jnp.bfloat16
    T, S, W, K = c["T"], c["S"], c["H"] * c["dk"], 4

    class PreScan(nn.Module):
        @nn.compact
        def __call__(self, parts, taps, pos, meta):
            return paged.ragged_short_conv(
                self, parts, taps, pos, meta, silu=True,
                unit=(c["dk"], 1e-6, (c["dk"] ** -0.5, 1.0, None)))

    def run(state, q, k, v, taps, pos, cu, num_seqs, seq_slot):
        meta = {"cu_q_lens": cu, "num_seqs": num_seqs, "seq_slot": seq_slot}
        return PreScan().apply({"cache": {SLOT_STATE: state}}, (q, k, v),
                               taps, pos, meta, mutable=["cache"])

    text = chip_compile(
        run, ((S, K - 1, 3 * W), bf), ((T, W), bf), ((T, W), bf),
        ((T, W), bf), ((3 * W, K), bf), ((T,), i32), ((S + 1,), i32),
        ((1,), i32), ((S,), i32))
    assert text.count(f"%{short_conv.NAME}") >= 3
    assert f"f32[{T},{3 * W}]" not in text and f"f32[{T},{W}]" not in text


# trinity-large-serve: 48 query heads over 8 KV heads of 128, a query group
# of SIX, which no older configuration has (Mistral 4, Solar 8, Olmo-Hybrid
# 1); 8 slots of 513 pages, a fused tick of 8 + 1,024 rows; the window
# layers' pool (657 pages, the published window of 4,096) and the full
# layers' (4,105 pages, no window) behind a page table each
TRINITY = dict(S=8, pp=513, page=64, Hq=48, Hkv=8, D=128, T=1032)


@pytest.mark.parametrize("pool,window", [
    pytest.param(657, 4096, id="window-layers-pool"),
    pytest.param(4105, None, id="full-layers-pool")])
def test_both_kernels_at_a_query_group_of_six(chip_compile, pool, window):
    """Both attention kernels at the tiles their rules give a group of six:
    row tiles of 128 tokens are 768 MXU rows a product against key blocks
    of 512, under the chunk kernel's own VMEM ceiling at 1,024-row chunks
    (and over it at 2,048); the decode kernel's groups are Mistral's.  The
    route names are the ones ``trinity-large-serve`` expects."""
    from deepspeed_tpu.ops import paged_decode, paged_prefill

    c, i32, bf = TRINITY, jnp.int32, jnp.bfloat16
    tiles = paged_prefill.tiling(c["Hq"], c["Hkv"], c["page"], c["D"], bf)
    decode_tiles = paged_decode.tiling(c["Hkv"], c["page"], c["pp"],
                                       c["D"], bf)
    assert (tiles, decode_tiles) == ((128, 8), (4, 4))
    assert paged_prefill.route_name(
        tiles, c["Hq"], c["Hkv"], c["page"]) == "pallas-chunk-768x512"
    assert paged_decode.route_name(decode_tiles,
                                   c["Hkv"]) == "pallas-decode-4x8"
    assert paged.rpa_kv_pages_per_block(c["Hkv"], c["page"], c["pp"]) == 8
    width = -(-c["T"] // 16) * 16
    vmem = lambda rows: paged_prefill.vmem_bytes(   # noqa: E731
        rows, c["Hq"], c["Hkv"], c["page"], c["D"], tiles)
    assert vmem(width) < paged_prefill._VMEM_CEILING < vmem(2048 + 16)
    pages = ((pool, c["page"], 2 * c["Hkv"], c["D"]), bf)
    meta = [((c["S"],), i32), ((c["S"], c["pp"]), i32),
            ((c["S"] + 1,), i32), ((1,), i32)]
    text = chip_compile(
        functools.partial(paged_prefill._chunk.__wrapped__,
                          sm_scale=c["D"] ** -0.5, sliding_window=window,
                          tiles=tiles, interpret=False),
        ((c["Hq"], width, c["D"]), bf), pages, *meta)
    assert f"%{paged_prefill.NAME}" in text
    text = chip_compile(
        functools.partial(paged_decode._decode.__wrapped__,
                          sm_scale=c["D"] ** -0.5, sliding_window=window,
                          tiles=decode_tiles, interpret=False),
        ((c["S"], c["Hq"], c["D"]), bf), pages, meta[0], meta[1],
        ((c["S"],), jnp.bool_))
    assert f"%{paged_decode.NAME}" in text


# smallthinker-21b-a3b-serve: 28 query heads over FOUR KV heads of 128, a
# query group of SEVEN; a token's eight combined heads are HALF a bf16
# sublane tile, so both kernels fetch and buffer the pool a PAIR of tokens a
# tile (``paged_decode.tokens_a_tile``); 32 slots of 129 pages, a fused tick
# of 32 + 1,024 rows (ISSUE 64's Step 0; the sweep on the chip then chose
# chunks of 512, the same kernel at fewer rows); the window layers' pool at
# that tick (2,625 pages, the published window of 4,096) and the full layers'
# (4,129 pages, no window)
SMALLTHINKER = dict(S=32, pp=129, page=64, Hq=28, Hkv=4, D=128, T=1056)


@pytest.mark.parametrize("pool,window", [
    pytest.param(2625, 4096, id="window-layers-pool"),
    pytest.param(4129, None, id="full-layers-pool")])
def test_both_kernels_at_four_kv_heads_and_a_query_group_of_seven(
        chip_compile, pool, window):
    """Step 0 of ISSUE 64: Mosaic takes both attention kernels with a token
    PAIR a sublane tile, at a tick of 1,056 rows and at 32 one-row
    sequences, with the pool stored as FOUR heads.  Row tiles of 128 tokens
    are 896 MXU rows a product against key blocks of 512; the decode
    kernel's groups are 8 pages of a step.  The route names are the ones
    ``smallthinker-21b-a3b-serve`` expects, and the pool is read where it
    lies: no copy of it is in either program."""
    from deepspeed_tpu.ops import paged_decode, paged_prefill

    c, i32, bf = SMALLTHINKER, jnp.int32, jnp.bfloat16
    assert paged_decode.tokens_a_tile(c["Hkv"]) == 2
    assert paged.kv_heads_stored(c["Hkv"]) == 4
    tiles = paged_prefill.tiling(c["Hq"], c["Hkv"], c["page"], c["D"], bf)
    decode_tiles = paged_decode.tiling(c["Hkv"], c["page"], c["pp"],
                                       c["D"], bf)
    assert (tiles, decode_tiles) == ((128, 8), (8, 8))
    assert paged_prefill.route_name(
        tiles, c["Hq"], c["Hkv"], c["page"]) == "pallas-chunk-896x512"
    assert paged_decode.route_name(decode_tiles,
                                   c["Hkv"]) == "pallas-decode-8x4"
    vmem = lambda rows: paged_prefill.vmem_bytes(   # noqa: E731
        rows, c["Hq"], c["Hkv"], c["page"], c["D"], tiles)
    assert vmem(c["T"]) == 49_479_680 < paged_prefill._VMEM_CEILING
    pool_shape = (pool, c["page"], 2 * c["Hkv"], c["D"])
    pages = (pool_shape, bf)
    meta = [((c["S"],), i32), ((c["S"], c["pp"]), i32),
            ((c["S"] + 1,), i32), ((1,), i32)]

    def reads_the_pool_where_it_lies(text):
        # the paired view is a bitcast of the parameter, never a copy
        assert f"bf16[{pool},32,16,128]" in text
        copies = [line for line in text.splitlines()
                  if f"bf16[{pool}," in line and " copy(" in line]
        assert not copies, copies

    text = chip_compile(
        functools.partial(paged_prefill._chunk.__wrapped__,
                          sm_scale=c["D"] ** -0.5, sliding_window=window,
                          tiles=tiles, interpret=False),
        ((c["Hq"], c["T"], c["D"]), bf), pages, *meta)
    assert f"%{paged_prefill.NAME}" in text
    reads_the_pool_where_it_lies(text)
    text = chip_compile(
        functools.partial(paged_decode._decode.__wrapped__,
                          sm_scale=c["D"] ** -0.5, sliding_window=window,
                          tiles=decode_tiles, interpret=False),
        ((c["S"], c["Hq"], c["D"]), bf), pages, meta[0], meta[1],
        ((c["S"],), jnp.bool_))
    assert f"%{paged_decode.NAME}" in text
    reads_the_pool_where_it_lies(text)


def test_kv_block_never_under_a_page_nor_over_512_tokens():
    assert paged.rpa_kv_pages_per_block(8, 64, 64) == 8
    assert paged.rpa_kv_pages_per_block(16, 64, 64) == 4
    assert paged.rpa_kv_pages_per_block(2, 64, 64) == 8
    assert paged.rpa_kv_pages_per_block(128, 64, 64) == 1
    assert paged.rpa_kv_pages_per_block(8, 16, 4) == 4


@pytest.mark.parametrize("rows", [32 * 8, (32 + 512) * 8],
                         ids=["decode-tick", "fused-tick"])
def test_grouped_expert_matmul_is_a_kernel_not_a_dense_expansion(
        chip_compile, rows):
    """OLMoE's expert bank (64 x 2048 x 1024) against the routed copies
    of a decode tick and of a fused tick: the v5e compiler lowers
    ``jax.lax.ragged_dot`` to a Mosaic kernel, with no operand of
    experts x rows x width size."""
    E, M, I = 64, 2048, 1024
    text = chip_compile(
        jax.lax.ragged_dot, ((rows, M), jnp.bfloat16),
        ((E, M, I), jnp.bfloat16), ((E,), jnp.int32))
    assert "ragged-dot" in text
    assert f"[{E},{rows}," not in text and f"[{rows},{E}," not in text


# (experts, K, N) of a bank: OLMoE-1B-7B's gate/up and down, Mixtral-8x7B's
# (K = 14336 has to be split) and Qwen1.5-MoE-A2.7B's (the widest tiles the
# rule gives: N = 1408 has no divisor between 128 and itself)
GMM_BANKS = [
    pytest.param((64, 2048, 1024), (128, 2048, 1024), id="olmoe-gate-up"),
    pytest.param((64, 1024, 2048), (128, 1024, 2048), id="olmoe-down"),
    pytest.param((8, 4096, 14336), (128, 4096, 512), id="mixtral-gate-up"),
    pytest.param((8, 14336, 4096), (128, 7168, 256), id="mixtral-down"),
    pytest.param((60, 2048, 1408), (128, 2048, 1408), id="qwen-gate-up"),
    pytest.param((60, 1408, 2048), (128, 1408, 2048), id="qwen-down"),
    pytest.param((64, 2048, 1536), (128, 2048, 768), id="lfm2-gate-up"),
    pytest.param((64, 1536, 2048), (128, 1536, 1024), id="lfm2-down"),
    # a third tiling regime: 768 divides by 128 six ways, not eight
    pytest.param((128, 2048, 768), (128, 2048, 768), id="keye-gate-up"),
    pytest.param((128, 768, 2048), (128, 768, 2048), id="keye-down"),
]


def _gmm_compiles(chip_compile, bank, tiling, rows):
    from deepspeed_tpu.ops import grouped_matmul as gm

    E, K, N = bank
    assert gm.tiles(K, N, 2) == tiling
    text = chip_compile(
        functools.partial(gm._gmm, tiling=tiling, interpret=False),
        ((rows, K), jnp.bfloat16), (bank, jnp.bfloat16), ((E,), jnp.int32))
    assert gm.NAME == "ragged-dot-gmm" and f"%{gm.NAME}." in text
    assert f"[{E},{rows}," not in text and f"[{rows},{E}," not in text
    # three bank tiles where the tile rule counted two: asked for, and
    # under half of the v5e's VMEM at the widest tile the rule gives
    asked = gm.vmem_limit_bytes(*tiling, 2)
    assert asked == gm._vmem_bytes(*tiling, 2, slots=3) + 2 * 1024 * 1024
    assert asked < 64 * 1024 * 1024 and f'"size":"{asked}"' in text


@pytest.mark.parametrize("rows", [32 * 8, (32 + 512) * 8],
                         ids=["decode-tick", "fused-tick"])
@pytest.mark.parametrize("bank,tiling", GMM_BANKS)
def test_grouped_matmul_kernel_at_the_tiles_the_rule_gives(
        chip_compile, bank, tiling, rows):
    """``ops/grouped_matmul.py`` with the VMEM it reckons for itself, and
    the name a device trace will show for it (the benchmark's expert-layer
    readers find the grouped matmul by ``ragged-dot`` in that name)."""
    _gmm_compiles(chip_compile, bank, tiling, rows)


# a prefill tick of 1,024 + 8 rows, its routed copies padded to whole row
# tiles: GLM-4.7-Flash's (top 4 of 64) and Keye-VL-2.0's (top 8 of 128)
GMM_PREFILL = [
    pytest.param((64, 2048, 1536), (128, 2048, 768), 4224, id="glm-gate-up"),
    pytest.param((64, 1536, 2048), (128, 1536, 1024), 4224, id="glm-down"),
    pytest.param((128, 2048, 768), (128, 2048, 768), 8320,
                 id="keye-gate-up"),
    pytest.param((128, 768, 2048), (128, 768, 2048), 8320, id="keye-down"),
]


@pytest.mark.parametrize("bank,tiling,rows", GMM_PREFILL)
def test_grouped_matmul_kernel_at_the_prefill_ticks_of_the_cells(
        chip_compile, bank, tiling, rows):
    _gmm_compiles(chip_compile, bank, tiling, rows)


# trinity-large-serve: 32 of 256 experts of 3,072 x 3,072 held, the widest
# bank a cell gives the kernel (56.6 MB an expert); the routed copies of a
# tick of 1,032 rows (top 4) as ``held_moe`` gathers them, a quarter where
# the live ones fit and all of them where not, and a decode tick's
GMM_WIDE = [
    pytest.param(1032, id="prefill-tick-quarter"),
    pytest.param(4128, id="prefill-tick-all"),
    pytest.param(32, id="decode-tick")]


@pytest.mark.parametrize("rows", GMM_WIDE)
def test_grouped_matmul_kernel_at_a_bank_3072_wide(chip_compile, rows):
    _gmm_compiles(chip_compile, (32, 3072, 3072), (128, 3072, 768), rows)


@pytest.mark.parametrize("pool_dtype", [jnp.int8, jnp.float8_e4m3fn],
                         ids=["int8", "fp8"])
def test_quantized_pages_kernel(chip_compile, pool_dtype):
    chip_compile(
        functools.partial(ragged_paged_attention_quant, sm_scale=D ** -0.5,
                          sliding_window=WINDOW),
        ((T, H, D), jnp.bfloat16), ((POOL, PAGE, 2 * HKV, D), pool_dtype),
        ((POOL, PAGE, 2 * HKV), jnp.float32), *META)
