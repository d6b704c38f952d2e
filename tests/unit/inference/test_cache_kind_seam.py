"""The seam between the paged cache (``inference/paged/``) and the engine:
the engine reads ONE description of what a model's layers keep
(``paged.describe``) and knows no kind by name.

- On the six tiny serving models the description's numbers, what every
  ``engine.dispatch`` span says of the cache, what each dispatch's rows ask of
  it and the cache kind's blocks of ``serving_stages()`` are what the engine
  reported at the parent of PR 46, where it branched on five predicates.  The
  expected values were taken on that commit (as
  ``test_accepted_programs_unchanged.py`` takes its hashes) from one request
  of 90 tokens that generates 6, with a clean record of routes.
- Every refusal is the description's data, and its message the parent's to
  the letter (taken there too).
- ``inference/paged/`` imports nothing of ``models``, ``inference/v2`` or
  ``inference/config.py``; ``ragged_engine.py`` names no kind outside its
  comments and docstrings, and refuses from one site.
"""
import ast
import importlib
import io
import pathlib
import re
import tokenize

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import paged
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.ops import routes
from deepspeed_tpu.ops.block_select_attention import Selection
from deepspeed_tpu.telemetry import trace

PACKAGE = pathlib.Path(paged.__file__).parent
ENGINE_FILE = PACKAGE.parent / "v2" / "ragged_engine.py"

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
           scan_layers=False, use_flash_attention=False)
# preset -> (module, class, keywords beside F32)
MODELS = {
    "tinymistral": ("mistral", "MistralForCausalLM", {}),
    "tinyolmoe": ("olmoe", "OlmoeForCausalLM", dict(expert_parallel=False)),
    "tinyouro": ("ouro", "OuroForCausalLM", {}),
    "tinylfm2": ("lfm2_moe", "Lfm2MoeForCausalLM", {}),
    "tinyglm": ("glm4_moe_lite", "Glm4MoeLiteForCausalLM", {}),
    "tinysala": ("minicpm_sala", "MiniCPMSALAForCausalLM", {}),
    "tinykeye": ("keye_vl2", "KeyeVL2ForCausalLM",
                 dict(expert_parallel=False)),
    "tinyolmohybrid": ("olmo_hybrid", "OlmoHybridForCausalLM", {}),
    "tinysolar": ("solar_open2", "SolarOpen2ForCausalLM", {}),
}
ENGINE = dict(pipeline=False, max_seqs=2, max_seq_len=128, prefill_chunk=32,
              page_size=16, num_pages=20, decode_block_size=2)
# what a dispatch span says that is no business of the cache kind's
COMMON = {"program", "ahead", "rows", "ticks", "key_tokens", "pages_in_use",
          "pages_total", "prefill_tokens", "prefill_key_tokens",
          "decode_key_tokens", "head_rows"}
KIND_BLOCKS = ("kernels", "latent", "loop", "linear_state", "sparse",
               "hybrid", "token_select", "delta_state", "kda_state")
PLAIN = {"paged_attention": "xla-dense",
         "paged_attention_decode": "xla-dense",
         "paged_attention_prefill": "xla-dense"}
PAD128 = {k: "xla-dense-pad128" for k in PLAIN}
GROUPED = {"moe_grouped_matmul": "ragged-dot-xla",
           "moe_grouped_matmul_kernel": "ragged-dot"}
# the rule's two kernels, and since PR 67 the route of the short convolution
# before them (``ops/short_conv.py``: the twin on the CPU)
DELTA = {"gated_delta_scan": "xla", "gated_delta_decode": "xla",
         "short_conv": "xla"}
KDA = {"kda_scan": "xla", "kda_decode": "xla", "short_conv": "xla"}
ANY = type("Any", (), {"__eq__": lambda self, other: True})()
KEY_STEPS = dict(index_key_steps=3, index_live_key_steps=1)

# ``numbers``: (kv_slots, state_layers, kv_layers, latent_width, selection);
# ``attrs``: on every dispatch span; ``asks``: of each of the six dispatches
# (three fused ticks, three decode blocks); ``stages``: the kind's blocks
EXPECTED = {
    "tinymistral": dict(
        numbers=(1, 0, 2, 0, None), attrs={}, asks=[{}] * 6,
        stages={"kernels": PLAIN}),
    "tinyolmoe": dict(
        numbers=(1, 0, 2, 0, None), attrs={}, asks=[{}] * 6,
        stages={"kernels": {**PLAIN, **GROUPED}}),
    "tinyouro": dict(
        numbers=(3, 0, 2, 0, None), attrs={"ut_steps": 3, "kv_slots": 6},
        asks=[{}] * 6,
        stages={"kernels": PLAIN,
                "loop": {"ut_steps": 3, "kv_slots": 6, "rows": 93,
                         "exit_cdf": pytest.approx(
                             [48.70681083202362, 68.04203832149506],
                             rel=1e-3)}}),
    "tinylfm2": dict(
        numbers=(1, 5, 1, 0, None),
        attrs={"state_layers": 5, "state_bytes": 5120},
        asks=[{"state_rows": 1}] * 6,
        stages={"kernels": {**PAD128, **GROUPED},
                "hybrid": {"kv_layers": 1, "state_layers": 5,
                           "state_bytes_per_seq": 5120,
                           "kv_head_dim_stored": 128,
                           "routing": "sigmoid+bias",
                           # by the weights' bits: 58 here
                           "bias_swaps": ANY,
                           "attention_routes": {**PAD128, **GROUPED}}}),
    "tinyglm": dict(
        numbers=(1, 0, 3, 128, None), attrs={"latent_bytes_per_token": 1536},
        asks=[{}] * 6,
        stages={"kernels": {"latent_attention_decode": "xla-dense-latent",
                            "latent_attention_prefill": "xla-dense-latent",
                            **GROUPED},
                "latent": {"width": 128, "layers": 3,
                           "bytes_per_token": 1536,
                           "bytes_per_token_layer": 512, "pages_peak": 6}}),
    "tinysala": dict(
        numbers=(1, 2, 2, 0, Selection(
            kernel_size=8, kernel_stride=4, block_size=16, init_blocks=1,
            window_size=32, topk=5, dense_len=64)),
        attrs={"state_layers": 2, "state_bytes": 262144},
        asks=[dict(state_rows=1, one_row_seqs=0, attend_decode_tokens=0,
                   attend_prefill_tokens=595, sparse_rows=0,
                   sparse_key_tokens=0, sparse_visible_tokens=0),
              dict(state_rows=1, one_row_seqs=0, attend_decode_tokens=0,
                   attend_prefill_tokens=1751, sparse_rows=4,
                   sparse_key_tokens=266, sparse_visible_tokens=266),
              dict(state_rows=1, one_row_seqs=0, attend_decode_tokens=0,
                   attend_prefill_tokens=1589, sparse_rows=22,
                   sparse_key_tokens=1589, sparse_visible_tokens=1749),
              dict(state_rows=1, attend_decode_tokens=151,
                   attend_prefill_tokens=0, sparse_rows=2,
                   sparse_key_tokens=151, sparse_visible_tokens=183),
              dict(state_rows=1, attend_decode_tokens=155,
                   attend_prefill_tokens=0, sparse_rows=2,
                   sparse_key_tokens=155, sparse_visible_tokens=187),
              dict(state_rows=1, attend_decode_tokens=159,
                   attend_prefill_tokens=0, sparse_rows=2,
                   sparse_key_tokens=159, sparse_visible_tokens=191)],
        stages={"kernels": {"linear_attention_decode": "xla",
                            "linear_attention_scan": "xla",
                            "sparse_attention_decode": "xla",
                            "sparse_attention_prefill": "xla"},
                "linear_state": {"state_layers": 2, "state_bytes": 262144,
                                 "state_rows": 6},
                "sparse": {"kv_layers": 2, "compressed_bytes": 81920,
                           "sparse_rows": 32, "sparse_key_tokens": 2320,
                           "sparse_visible_tokens": 2576, "kernel_size": 8,
                           "kernel_stride": 4, "block_size": 16,
                           "init_blocks": 1, "window_size": 32, "topk": 5,
                           "dense_len": 64}}),
    # topk 24: the first tick's last ten rows choose, every later row does
    # (PR 49: a fused tick also says how many key steps of its program's
    # tiles the index kernel works on: 34 rows are 1 + 2 tiles of one step
    # of 128 keys each, and the one chunk's tile is live in its one step)
    "tinykeye": dict(
        numbers=(1, 0, 2, 0, None),
        attrs={"index_bytes_per_token": 128,
               "tokensel_kv_bytes_per_token": 1024},
        asks=[dict(index_rows=34, index_visible_tokens=595,
                   index_prefill_tokens=595, index_decode_tokens=0,
                   sel_rows=10, sel_key_tokens=240, sel_visible_tokens=295,
                   attend_prefill_tokens=540, attend_decode_tokens=0,
                   one_row_seqs=0, **KEY_STEPS),
              dict(index_rows=34, index_visible_tokens=1751,
                   index_prefill_tokens=1751, index_decode_tokens=0,
                   sel_rows=34, sel_key_tokens=816, sel_visible_tokens=1751,
                   attend_prefill_tokens=816, attend_decode_tokens=0,
                   one_row_seqs=0, **KEY_STEPS),
              dict(index_rows=22, index_visible_tokens=1749,
                   index_prefill_tokens=1749, index_decode_tokens=0,
                   sel_rows=22, sel_key_tokens=528, sel_visible_tokens=1749,
                   attend_prefill_tokens=528, attend_decode_tokens=0,
                   one_row_seqs=0, **KEY_STEPS)]
        + [dict(index_rows=2, index_visible_tokens=seen,
                index_prefill_tokens=0, index_decode_tokens=seen, sel_rows=2,
                sel_key_tokens=48, sel_visible_tokens=seen,
                attend_prefill_tokens=0, attend_decode_tokens=48)
           for seen in (183, 187, 191)],
        stages={"kernels": {"token_select_decode": "xla-token-gather-24",
                            "token_select_index": "xla",
                            "token_select_threshold": "xla",
                            "token_select_prefill": "xla",
                            "token_select_row_index": "xla", **GROUPED},
                "token_select": {
                    "kv_layers": 2, "index_pool_bytes": 40960,
                    "index_bytes_per_token": 128,
                    "tokensel_kv_bytes_per_token": 1024, "index_rows": 96,
                    "index_visible_tokens": 4656, "sel_rows": 72,
                    "sel_key_tokens": 1728, "sel_visible_tokens": 4356,
                    "pages_peak": 6, "indexer_num_heads": 2,
                    "indexer_head_dim": 16, "indexer_num_kv_heads": 1,
                    "topk": 24, "q_chunk_size": 512,
                    "kv_chunk_size": 512}}),
    # nine of twelve as three of four: three state layers of TWO leaves each
    # (a 8 x 2*16 float32 matrix and 3 rows of 64 convolution inputs: 5,376
    # bytes a sequence, stored as counted), one K/V layer of fifteen heads
    # stored as sixteen
    "tinyolmohybrid": dict(
        numbers=(1, 3, 1, 0, None),
        attrs={"state_layers": 3, "state_bytes": 5376, "delta_layers": 3},
        asks=[dict(state_rows=1, one_row_seqs=0)] * 3
        + [dict(state_rows=1)] * 3,
        stages={"kernels": {**PLAIN, **DELTA},
                "delta_state": {"state_layers": 3, "kv_layers": 1,
                                "state_bytes_per_seq": 5376,
                                "state_bytes_per_seq_counted": 5376,
                                "state_rows": 6, "kv_heads_stored": 16,
                                "kv_bytes_per_token": 1024,
                                # of the doubling's levels at a tile of 128
                                # (PR 57): those that multiply half of it
                                "solve_levels_compacted": 4,
                                "solve_levels": 6,
                                "routes": {**PLAIN, **DELTA}}}),
    # one period: three KDA layers of two leaves each (a 16 x 2*16 float32
    # matrix and 3 rows of 96 convolution inputs: 3,200 bytes a layer a
    # sequence), one K/V layer of two heads; 3 of 12 experts held
    "tinysolar": dict(
        numbers=(1, 3, 1, 0, None),
        attrs={"state_layers": 3, "state_bytes": 9600, "kda_layers": 3},
        asks=[dict(state_rows=1, one_row_seqs=0)] * 3
        + [dict(state_rows=1)] * 3,
        stages={"kernels": {**PLAIN, **KDA, **GROUPED},
                "kda_state": {"state_layers": 3, "kv_layers": 1,
                              "state_bytes_per_seq": 9600,
                              "state_bytes_per_seq_counted": 9600,
                              "state_rows": 6, "kv_heads_stored": 2,
                              "kv_bytes_per_token": 256,
                              "solve_levels_compacted": 4, "solve_levels": 6,
                              "pair_levels_compacted": 4, "pair_levels": 7,
                              "routes": {**PLAIN, **KDA, **GROUPED}}}),
}


def _model(preset, **kw):
    module, cls, more = MODELS[preset]
    mod = importlib.import_module(f"deepspeed_tpu.models.{module}")
    return getattr(mod, cls)(mod.get_config(preset, **kw, **more))


@pytest.mark.parametrize("preset", sorted(MODELS))
def test_the_engine_says_of_the_cache_what_it_said_at_the_parent(
        preset, monkeypatch):
    want = EXPECTED[preset]
    model = _model(preset, **F32)
    kind = paged.describe(model.config)
    assert (kind.kv_slots, kind.state_layers, kind.kv_layers,
            kind.latent_width, kind.selection) == want["numbers"]
    assert (paged.kv_slots_per_layer(model.config),
            paged.state_layers(model.config), paged.kv_layers(model.config),
            paged.latent_width(model.config),
            paged.sparse_selection(model.config)) == want["numbers"]
    params = jax.jit(model.init)(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32))["params"]
    monkeypatch.setattr(routes, "_taken", {})
    trace.configure(enabled=True)
    try:
        trace.clear()
        eng = RaggedInferenceEngineV2(model, params=params, **ENGINE)
        eng.put_request(np.arange(1, 91, dtype=np.int32), max_new_tokens=6)
        while eng.has_work():
            eng.step()
        events = trace.snapshot()
    finally:
        trace.configure(enabled=False)
        trace.clear()
    said = [{k: v for k, v in e["args"].items() if k not in COMMON}
            for e in events if e["name"] == "engine.dispatch"]
    assert said == [{**want["attrs"], **asks} for asks in want["asks"]]
    kind_blocks = lambda: {k: v for k, v in eng.serving_stages().items()
                           if k in KIND_BLOCKS}
    assert kind_blocks() == want["stages"]
    # another thread asks while a dispatch holds the donated pool: what the
    # kinds report comes from shapes, never from a buffer
    for leaf in jax.tree_util.tree_leaves(eng.kv.cache):
        leaf.delete()
    assert kind_blocks() == want["stages"]
    eng.close()


# -- the refusals: the description's data, the parent's messages -------------

# the four features a cache kind can refuse: what the engine has that reads
# a leaf as per-head K/V pages of one pool
FEATURES = {"prefix_cache", "tiering", "quantized", "tensor_parallel"}
ASK = {"prefix_cache": dict(prefix_cache={"enabled": True}),
       "tiering": dict(kv_tiering={"host_pages": 32}),
       "quantized": dict(kv_cache_dtype="int8"),
       "tensor_parallel": dict(tensor_parallel=2)}
_OURO = "OuroForCausalLM runs its layers 3 times a token"
_LFM2 = "Lfm2MoeForCausalLM keeps per-slot state in 5 of its 6 layers"
_SALA = "MiniCPMSALAForCausalLM keeps per-slot state in 2 of its 4 layers"
_GLM = ("Glm4MoeLiteForCausalLM caches one latent row of 128 values a token "
        "a layer")
_OLMO = "OlmoHybridForCausalLM keeps per-slot state in 3 of its 4 layers"
_SOLAR = "SolarOpen2ForCausalLM keeps per-slot state in 3 of its 4 layers"
_KEYE = ("KeyeVL2ForCausalLM attends the 24 keys an indexer of 2 heads "
         "chooses, token by token, in 2 of its 2 layers")
_STATE = {
    "prefix_cache": "prefix_cache: {}, and a shared prefix (or its copy-on-"
    "write page) brings keys and values only — a sequence that starts behind "
    "one needs the state AT the prefix's end, which the index does not keep",
    "quantized": "kv_cache_dtype='int8': {}; its pool is full-width (heads "
    "of 64 are stored padded to the kernels' 128 lanes, which the quantized "
    "pool's kernel does not know)",
    "tensor_parallel": "tensor-parallel serving: {}, and the cache's sharding "
    "rule knows page pools only"}
MESSAGES = {
    ("tinyouro", "quantized"): f"kv_cache_dtype='int8': {_OURO}; its pool is "
    "full-width (the quantized pool's scale rows have one slot a layer)",
    ("tinyouro", "tensor_parallel"): f"tensor-parallel serving: {_OURO}, and "
    "the pool's sharding rule knows pages of one slot",
    **{("tinylfm2", f): m.format(_LFM2) for f, m in _STATE.items()},
    **{("tinysala", f): m.format(_SALA) for f, m in _STATE.items()},
    ("tinysala", "tiering"): "kv_tiering: MiniCPMSALAForCausalLM keeps a "
    "matrix state a head in 2 of its 4 layers and chooses the pages its other "
    "layers attend, and a spilled request's record would carry megabytes of "
    "state a sequence beside its pages (ROADMAP R5(d): a tier for matrix "
    "state)",
    **{("tinyolmohybrid", f): m.format(_OLMO) for f, m in _STATE.items()},
    ("tinyolmohybrid", "tiering"): f"kv_tiering: {_OLMO}, and a spilled "
    "request's record would carry megabytes of state a sequence beside its "
    "pages (ROADMAP R5(d): a tier for matrix state)",
    **{("tinysolar", f): m.format(_SOLAR) for f, m in _STATE.items()},
    ("tinysolar", "tiering"): f"kv_tiering: {_SOLAR}, and a spilled "
    "request's record would carry megabytes of state a sequence beside its "
    "pages (ROADMAP R5(d): a tier for matrix state)",
    ("tinyglm", "quantized"): f"kv_cache_dtype='int8': {_GLM}; the quantized "
    "pool's scale rows and kernel are per head",
    ("tinyglm", "tensor_parallel"): f"tensor-parallel serving: {_GLM}, which "
    "has no head dim for the pool's sharding rule to split",
    ("tinykeye", "quantized"): f"kv_cache_dtype='int8': {_KEYE}; the "
    "quantized pool's scale rows and kernel are per head of a pool laid out "
    "heads first, and an index key has no scale",
    ("tinykeye", "tensor_parallel"): f"tensor-parallel serving: {_KEYE}, and "
    "the cache's sharding rule splits a pool's head dim, which a token's row "
    "of every head and the one index key a token do not have",
}


def test_the_descriptions_refusals_are_the_ones_listed():
    """Twenty-one refusals in five kinds' files (two of a looped pool, a
    latent pool and an indexer's keys each; per-slot state's three serve four
    models, and the matrix state's tiering three), none for a model of plain
    K/V pages, and every one of a feature the engine HAS: a dictionary that
    grows a key for another fails here."""
    listed = set()
    for preset in MODELS:
        model = _model(preset)
        for model_is, refuses in paged.describe(model.config).refusals(
                type(model).__name__):
            assert model_is.startswith(type(model).__name__)
            assert set(refuses) <= FEATURES, set(refuses) - FEATURES
            listed |= {(preset, feature) for feature in refuses}
    assert listed == set(MESSAGES) and len(MESSAGES) == 2 * 3 + 3 * 4 + 3
    assert set(ASK) == FEATURES


def test_what_the_engine_no_longer_has_is_an_unknown_name(caplog):
    """Speculative decoding and the partial-residency driver are gone with no
    shim: a config that names either warns of the key by name, as of any
    unknown key, and the constructor refuses their keywords."""
    import logging

    from deepspeed_tpu.inference.config import load_inference_config
    from deepspeed_tpu.utils.logging import logger

    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.WARNING, logger=logger.name):
            v2 = load_inference_config({"v2": {
                "speculation": {"mode": "ngram"},
                "kv_tiering": {"host_pages": 32, "long_context": True}}}).v2
    finally:
        logger.removeHandler(caplog.handler)
    said = caplog.text
    assert "speculation" in said and "long_context" in said
    assert "speculation" not in type(v2).model_fields
    assert "long_context" not in type(v2.kv_tiering).model_fields
    for gone in ("speculation", "draft_model"):
        with pytest.raises(TypeError, match=gone):
            RaggedInferenceEngineV2(_model("tinymistral"), **{gone: None})


@pytest.mark.parametrize("preset,feature", sorted(MESSAGES))
def test_a_refusal_is_the_parents_to_the_letter(preset, feature):
    import deepspeed_tpu.comm as dist

    kw = dict(ASK[feature])
    if kw.pop("tensor_parallel", None):
        kw["topology"] = dist.initialize_mesh(tp=2)
    try:
        with pytest.raises(NotImplementedError) as refused:
            RaggedInferenceEngineV2(
                _model(preset), max_seqs=2, max_seq_len=64, prefill_chunk=8,
                page_size=16, num_pages=10, **kw)
    finally:
        dist.set_topology(None)
    assert str(refused.value) == MESSAGES[preset, feature]


# -- the direction, and the engine's ignorance, held as text ------------------

def _imports(path):
    """Every module a file imports, at any depth of its code."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield from ("." * node.level + f"{node.module or ''}.{a.name}"
                        for a in node.names)


def _code(path):
    """``(line, token)`` of a file's code: no comment, no docstring."""
    src = path.read_text()
    docs = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Expr) and isinstance(
                getattr(node.value, "value", None), str):
            docs.update(range(node.lineno, node.end_lineno + 1))
    return [(tok.start[0], tok.string)
            for tok in tokenize.generate_tokens(io.StringIO(src).readline)
            if tok.type != tokenize.COMMENT
            and not (tok.type == tokenize.STRING and tok.start[0] in docs)]


@pytest.mark.parametrize("path", sorted(PACKAGE.glob("*.py")),
                         ids=lambda p: p.name)
def test_the_paged_cache_imports_nothing_that_stands_on_it(path):
    """``ops`` and ``utils``, its own files, and (``kv_pages.py``'s
    tensor-parallel branch, inside the function) ``comm`` and ``sequence``:
    never ``models``, ``inference/v2``, ``inference/config.py``."""
    allowed = ("deepspeed_tpu.ops", "deepspeed_tpu.utils",
               "deepspeed_tpu.inference.paged")
    if path.name == "kv_pages.py":
        allowed += ("deepspeed_tpu.comm", "deepspeed_tpu.sequence")
    ours = [m for m in _imports(path)
            if m.startswith(("deepspeed_tpu", "."))]
    assert all(m.startswith(allowed) for m in ours), ours
    if path.name == "allocator.py":
        assert not any(m.split(".")[0] == "jax" for m in _imports(path))
    assert len(path.read_text().splitlines()) <= 500


@pytest.mark.parametrize("first_pos,q_lens,live", [
    # one chunk of 1,024 rows that ends at 12,287: four tiles end in key
    # step 22, four in 23
    ([11264], [1024], 4 * 23 + 4 * 24),
    # a chunk that straddles two sequences among three decoding ones: five
    # tiles near 30k (59 + 4 x 60 steps), four in key step 0
    ([5000, 70, 30000, 0, 9], [1, 1, 600, 424, 1], 299 + 4),
    # decoding sequences alone: no tile holds a row
    ([5000, 70, 9], [1, 1, 1], 0)],
    ids=["one-chunk-at-12k", "straddles-two-sequences", "no-chunk"])
def test_index_key_steps_are_the_live_tiles_up_to_their_last_row(
        first_pos, q_lens, live):
    """``keye-longdoc32k-closed``'s fused program: 1,032 rows are 9 + 8
    tiles of 65 key steps of 512 keys; a tile is live up to the step of its
    last row's own key."""
    tick = paged.TickShape(rows=1032, max_seqs=8, page_size=64,
                           pages_per_seq=513)
    got = paged.index_key_steps(np.asarray(first_pos), np.asarray(q_lens),
                                tick)
    assert got == {"index_key_steps": 17 * 65, "index_live_key_steps": live}


def test_the_engine_names_no_cache_kind():
    words = re.compile(r"latent|sparse|conv(?!ert|enien)|lightning|"
                       r"linear_state|hybrid|ut_steps|CKEY|tokensel|"
                       r"token_select|index_key|indexer|delta_|gated_delta|kda",
                       re.IGNORECASE)
    code = _code(ENGINE_FILE)
    assert [(n, t) for n, t in code if words.search(t)] == []
    kernels = ("latent_attention", "block_select_attention",
               "linear_attention", "token_select_attention",
               "gated_delta_rule", "kda")
    assert not any(k in m for m in _imports(ENGINE_FILE) for k in kernels)
    calls = [n for (n, t), (_, nxt) in zip(code, code[1:])
             if t == "_refuse_features" and nxt == "("]
    assert len(calls) == 2          # its definition and ONE call


def test_a_gated_delta_model_is_nine_state_layers_of_two_leaves_each():
    """``olmo-hybrid-7b-serve``'s twelve layers: nine keep per-slot state
    (two leaves a layer, each in a module of its own), three keep K/V pages
    of thirty heads stored as thirty-two."""
    from deepspeed_tpu.inference.v2 import programs
    from deepspeed_tpu.models import olmo_hybrid as oh

    cfg = oh.get_config("olmo-hybrid-7b", num_hidden_layers=12,
                        layer_types=oh._PERIOD * 3, **F32)
    kind = paged.describe(cfg)
    assert (kind.state_layers, kind.kv_layers, kind.kv_slots) == (9, 3, 1)
    assert [type(p).__name__ for p in kind.parts] == ["KVPages",
                                                      "DeltaState"]
    tiny = _model("tinyolmohybrid", **F32)
    model = type(tiny)(programs.serving_config(
        tiny.config, max_seq_len=64, page_size=16, num_pages=5,
        tensor_parallel=False, kv_cache_dtype="none"))
    cache = jax.eval_shape(lambda: programs.init_cache(
        model, max_seqs=2, width=10))
    pools, states = paged.split_cache(cache)
    assert len(states) == 2 * 3 and len(pools) == 1
    assert sorted({s.shape for s in states}) == [(2, 3, 64), (2, 8, 32)]
    assert pools[0].shape == (5, 16, 2 * 16, 8)     # fifteen heads as sixteen
    assert paged.state_bytes_per_seq(cache) == 3 * (8 * 32 + 3 * 64) * 4
    assert paged.kv_heads_stored(30) == 32 and paged.kv_heads_stored(15) == 16
    assert [paged.kv_heads_stored(h) for h in (1, 2, 4, 8, 16)] == [
        1, 2, 4, 8, 16]
