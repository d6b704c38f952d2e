"""A fused tick's head multiplies the rows it samples and no other.

``programs._fused_forward`` hands the tick's ``sample_rows`` to the model in
its metadata; a serving-form model gathers those rows of its body's output
before the final norm and the head (``inference/common.py:sampled_rows``;
Ouro after its pass loop) and returns ``[1, max_seqs, V]``.  For every family that can serve a fused
tick, at a tiny size in bfloat16:

- ``build_fused_step``'s ``sel`` is, row for row, ``jnp.take`` of the same
  model's logits of ALL rows (the model called without the key), within
  bfloat16's rounding of a matmul at another row count;
- the lowered tick holds no ``[T, V]`` array (the all-row program, lowered
  beside it, does: the search finds what it looks for);
- a model whose head ignores the key is refused by name when the tick is
  traced, and is not served the old way;
- Ouro's exit gate sums over every live row inside the pass loop, so its
  ``exit_cdf`` is the all-row program's to the bit.

And the engine says what ran: ``head_rows`` on a fused tick's
``engine.dispatch`` span, ``fused_head`` in ``serving_stages()``.
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import common
from deepspeed_tpu.inference.common import logits_of
from deepspeed_tpu.inference.paged import state_layers
from deepspeed_tpu.inference.v2 import programs

S, CHUNK, PAGE, MAX_LEN = 4, 16, 8, 32
T, PP = S + CHUNK, MAX_LEN // PAGE
V = 168          # no width of any tiny preset, nor the pool's 136 tokens
BF16 = dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False,
            scan_layers=False, use_flash_attention=False, vocab_size=V)

# family -> (module, class, preset, keywords beside BF16)
FAMILIES = {
    "llama": ("llama", "LlamaForCausalLM", "tinyllama", {}),
    "mistral": ("mistral", "MistralForCausalLM", "tinymistral", {}),
    "qwen2": ("qwen2", "Qwen2ForCausalLM", "tinyqwen2", {}),
    "phi3": ("phi3", "Phi3ForCausalLM", "tinyphi3", {}),
    "phi": ("phi", "PhiForCausalLM", "tinyphi", {}),
    "gptj": ("gptj", "GPTJForCausalLM", "tinygptj", {}),
    "gptneox": ("gptneox", "GPTNeoXForCausalLM", "tinyneox", {}),
    "falcon": ("falcon", "FalconForCausalLM", "tinyfalcon", {}),
    "mixtral": ("mixtral", "MixtralForCausalLM", "tinymixtral",
                dict(expert_parallel=False)),
    "qwen2_moe": ("qwen2_moe", "Qwen2MoeForCausalLM", "tinyqwen2moe",
                  dict(expert_parallel=False)),
    "olmoe": ("olmoe", "OlmoeForCausalLM", "tinyolmoe",
              dict(expert_parallel=False)),
    "lfm2": ("lfm2_moe", "Lfm2MoeForCausalLM", "tinylfm2", {}),
    "glm": ("glm4_moe_lite", "Glm4MoeLiteForCausalLM", "tinyglm", {}),
    "ouro": ("ouro", "OuroForCausalLM", "tinyouro", {}),
}


class Tick:
    """One family's serving-form model, its weights, a zeroed pool and the
    arguments of ONE fused tick: three prompt chunks of 7, 1 and 5 tokens
    (the rows sampled are 6, 7 and 12), a fourth sequence that is not
    there, seven rows of padding."""

    def __init__(self, family):
        module, cls, preset, kw = FAMILIES[family]
        mod = importlib.import_module(f"deepspeed_tpu.models.{module}")
        plain = getattr(mod, cls)(mod.get_config(preset, **BF16, **kw))
        self.params = jax.jit(plain.init)(
            jax.random.PRNGKey(3), np.zeros((1, 8), np.int32))["params"]
        self.model = type(plain)(programs.serving_config(
            plain.config, max_seq_len=MAX_LEN, page_size=PAGE,
            num_pages=1 + S * PP, tensor_parallel=False,
            kv_cache_dtype="none"))
        self.stateful = state_layers(self.model.config) > 0
        rng = np.random.default_rng(7)
        lens = (7, 1, 5)
        table = np.arange(1, 1 + S * PP, dtype=np.int32).reshape(S, PP)
        token_ids = np.zeros((T,), np.int32)
        positions = np.zeros((T,), np.int32)
        dest = np.zeros((T,), np.int32)
        kv_lens = np.zeros((S,), np.int32)
        pages = np.full((S, PP), -1, np.int32)
        cu = np.zeros((S + 1,), np.int32)
        self.sample_rows = np.zeros((S,), np.int32)
        t = 0
        for j, n in enumerate(lens):
            pos = np.arange(n)
            token_ids[t:t + n] = rng.integers(1, V, size=n)
            positions[t:t + n] = pos
            dest[t:t + n] = table[j, pos // PAGE] * PAGE + pos % PAGE
            kv_lens[j], pages[j], cu[j + 1] = n, table[j], cu[j] + n
            self.sample_rows[j] = t + n - 1
            t += n
        cu[len(lens) + 1:] = cu[len(lens)]
        self.n = len(lens)
        # the fused tick's arguments after (params, cache), in its order
        self.args = (token_ids[None], positions[None], kv_lens, pages, cu,
                     np.asarray([self.n], np.int32), dest, self.sample_rows)
        if self.stateful:
            self.args += (np.arange(S, dtype=np.int32),)

    def cache(self):
        return programs.init_cache(self.model, max_seqs=S, width=T)

    @functools.cached_property
    def fused(self):
        """The tick, lowered once for the tests that read or run it."""
        return programs.build_fused_step(self.model).lower(
            self.params, self.cache(), *self.args)

    @functools.cached_property
    def every_row(self):
        """:meth:`all_rows`, lowered once as well."""
        return self.all_rows().lower(self.params, self.cache(), *self.args)

    def all_rows(self):
        """The program the tick was before: the model called WITHOUT
        ``sample_rows``, every row's logits ``[T, V]``, the take after."""
        def run(params, cache, token_ids, positions, kv_lens, pages, cu,
                num_seqs, dest, sample_rows, *seq_slot):
            meta = {"kv_lens": kv_lens, "page_indices": pages,
                    "cu_q_lens": cu, "num_seqs": num_seqs,
                    "new_kv_dest": dest, "fused_tick": True,
                    "live": jnp.arange(T) < cu[num_seqs[0]]}
            if seq_slot:
                meta["seq_slot"], = seq_slot
            out, vars_ = self.model.apply(
                {"params": params, "cache": cache}, token_ids,
                positions=positions, mutable=programs.MUTABLE,
                ragged_meta=meta)
            logits = logits_of(out)[0]
            assert logits.shape == (T, V)
            return (jnp.take(logits, sample_rows, axis=0),
                    programs.loop_counts(vars_))
        return jax.jit(run)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def tick(request):
    return Tick(request.param)


def _holds(lowered, rows):
    text = lowered.as_text()
    return any(f"tensor<{lead}{rows}x{V}x" in text for lead in ("", "1x"))


def test_the_sampled_rows_logits_are_the_all_row_programs(tick):
    sel, _, _, cdf = tick.fused.compile()(
        tick.params, tick.cache(), *tick.args)
    want, want_cdf = tick.every_row.compile()(
        tick.params, tick.cache(), *tick.args)
    assert sel.shape == (S, V) and sel.dtype == jnp.bfloat16
    got, want = (np.asarray(a, np.float32)[:tick.n] for a in (sel, want))
    assert np.abs(want).max() > 0.05        # logits, not zeros
    # two bfloat16 roundings of the largest logit: what a matmul's row
    # count may move
    assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
    if want_cdf is None:
        assert cdf is None
    else:                       # Ouro: the gate saw every live row
        assert float(np.asarray(cdf).sum()) > 0
        np.testing.assert_array_equal(np.asarray(cdf), np.asarray(want_cdf))


def test_the_lowered_tick_holds_no_array_of_all_rows_logits(tick):
    assert not _holds(tick.fused, T)
    assert _holds(tick.fused, S)
    # the search finds the array where it exists
    assert _holds(tick.every_row, T)


def test_a_model_that_ignores_the_rows_is_refused_by_name(tick, monkeypatch):
    monkeypatch.setattr(common, "sampled_rows", lambda x, ragged_meta: x)
    with pytest.raises(NotImplementedError,
                       match=type(tick.model).__name__ +
                       rf" ran its head on {T} rows .* samples {S}"):
        programs.build_fused_step(tick.model).lower(
            tick.params, tick.cache(), *tick.args)


def test_the_engine_says_how_many_rows_the_head_multiplied():
    from deepspeed_tpu.inference.v2.ragged_engine import \
        RaggedInferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
    from deepspeed_tpu.telemetry import trace

    model = LlamaForCausalLM(get_config(
        "tinyllama", dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=False, remat=False, use_flash_attention=False))
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 np.zeros((1, 8), np.int32))
    prev = (trace.enabled, trace.buffer_size, trace.clock)
    trace.clear()
    trace.configure(enabled=True)
    try:
        eng = RaggedInferenceEngineV2(model, params=params, max_seqs=3,
                                      max_seq_len=64, prefill_chunk=8,
                                      page_size=8, decode_block_size=4)
        eng.generate_all([np.arange(1, 20, dtype=np.int32)],
                         max_new_tokens=6)
        dispatches = [e["args"] for e in trace.snapshot()
                      if e["name"] == "engine.dispatch"]
    finally:
        trace.configure(enabled=prev[0], buffer_size=prev[1], clock=prev[2])
        trace.clear()
    fused = [d for d in dispatches if d["program"] == "fused"]
    # 19 prompt tokens, at most 11 a tick: max_seqs rows of each tick's 11
    assert len(fused) == eng.host_stats.fused_ticks >= 2
    assert {d["head_rows"] for d in fused} == {3}
    # a decode block samples every row it runs: nothing to say
    blocks = [d for d in dispatches if d["program"] == "decode_block"]
    assert blocks and not any("head_rows" in d for d in blocks)
    assert eng.serving_stages()["fused_head"] == {
        "form": "sampled-rows", "rows": 3, "tick_rows": 11}
    eng.close()
