"""HF checkpoint conversion tests (reference ``tests/unit/inference``
checkpoint-loading strategy, upgraded: logits parity against real
``transformers`` modules on shared weights)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.module_inject import (convert_hf_state_dict,
                                         load_hf_checkpoint)

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")
# models of 32 columns: one thread does them, and a pool a core wide beside
# five other workers' does not (227 s in a run of six workers, 12 s alone)
torch.set_num_threads(1)


def _gpt2_pair():
    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=2, n_head=2,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    # seeded: a greedy continuation is compared token for token below, and
    # an unseeded draw leaves a near-tie now and then (it depended on which
    # test files the worker had run before)
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=96, n_positions=32, n_embd=32, n_layer=2,
                     n_head=2, dropout=0.0, dtype=jnp.float32,
                     param_dtype=jnp.float32, scan_layers=True,
                     remat=False, use_flash_attention=False)
    return hf, GPT2Model(cfg)


class TestGPT2Conversion:
    def test_logits_parity_with_transformers(self):
        hf, ours = _gpt2_pair()
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(0).integers(0, 96, size=(2, 16),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_unscanned_layout(self):
        hf, _ = _gpt2_pair()
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

        cfg = GPT2Config(vocab_size=96, n_positions=32, n_embd=32,
                         n_layer=2, n_head=2, dropout=0.0,
                         dtype=jnp.float32, param_dtype=jnp.float32,
                         scan_layers=False, remat=False,
                         use_flash_attention=False)
        ours = GPT2Model(cfg)
        params = convert_hf_state_dict(ours, hf)
        ids = np.ones((1, 8), np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


class TestLlamaConversion:
    def test_logits_parity_with_transformers(self):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, attention_dropout=0.0,
            rms_norm_eps=1e-5)
        hf = transformers.LlamaForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(vocab_size=96, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=64, rope_theta=10000.0,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          scan_layers=True, remat=False,
                          use_flash_attention=False)
        ours = LlamaForCausalLM(cfg)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(1).integers(0, 96, size=(2, 12),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


class TestMixtralConversion:
    def test_weight_placement_and_finite_logits(self):
        hf_cfg = transformers.MixtralConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, num_local_experts=4,
            num_experts_per_tok=2, max_position_embeddings=64)
        hf = transformers.MixtralForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.mixtral import (MixtralConfig,
                                                  MixtralForCausalLM)

        cfg = MixtralConfig(vocab_size=96, hidden_size=32,
                            intermediate_size=64, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=2,
                            num_local_experts=4, num_experts_per_tok=2,
                            max_position_embeddings=64, dtype=jnp.float32,
                            param_dtype=jnp.float32, scan_layers=True,
                            remat=False, use_flash_attention=False,
                            expert_parallel=False)
        ours = MixtralForCausalLM(cfg)
        params = convert_hf_state_dict(ours, hf)
        # placement: expert w1 of layer 0, expert 2 matches transposed HF
        sd = hf.state_dict()
        np.testing.assert_allclose(
            np.asarray(params["params"]["model"]["layers"]["block"]
                       ["block_sparse_moe"]["w1"][0, 2]),
            sd["model.layers.0.block_sparse_moe.experts.2.w1.weight"]
            .numpy().T, rtol=1e-6)
        ids = np.ones((1, 8), np.int64)
        out = ours.apply(params, jnp.asarray(ids, jnp.int32))
        logits = out[0] if isinstance(out, tuple) else out
        assert np.isfinite(np.asarray(logits)).all()


class TestSourceFormats:
    def test_torch_file_roundtrip(self, tmp_path):
        hf, ours = _gpt2_pair()
        path = str(tmp_path / "pytorch_model.bin")
        torch.save(hf.state_dict(), path)
        params = load_hf_checkpoint(ours, path)
        ids = np.ones((1, 8), np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_numpy_dict_source(self):
        hf, ours = _gpt2_pair()
        sd = {k: v.numpy() for k, v in hf.state_dict().items()}
        params = convert_hf_state_dict(ours, sd)
        assert "params" in params

    def test_unknown_family_raises(self):
        class Weird:
            config = object()

        with pytest.raises(TypeError):
            convert_hf_state_dict(Weird(), {})


class TestInitInferenceCheckpoint:
    def test_generate_from_hf_checkpoint(self):
        import deepspeed_tpu

        hf, _ = _gpt2_pair()
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

        cfg = GPT2Config(vocab_size=96, n_positions=32, n_embd=32,
                         n_layer=2, n_head=2, dropout=0.0,
                         dtype=jnp.float32, param_dtype=jnp.float32,
                         scan_layers=True, remat=False,
                         use_flash_attention=False, decode=True)
        eng = deepspeed_tpu.init_inference(
            model=GPT2Model(cfg), checkpoint=hf, max_out_tokens=32)
        out = eng.generate(np.ones((1, 4), np.int32), max_new_tokens=4)
        assert out.shape == (1, 8)
        # greedy continuation matches HF generate
        with torch.no_grad():
            ref = hf.generate(torch.ones((1, 4), dtype=torch.long),
                              max_new_tokens=4, do_sample=False).numpy()
        np.testing.assert_array_equal(out, ref)


class TestPhi3Conversion:
    """Reference phi3/containers.py: fused qkv_proj + gate_up_proj split
    onto the Llama layout."""

    def _pair(self, scan_layers=True):
        hf_cfg = transformers.Phi3Config(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-5, attention_dropout=0.0,
            resid_pdrop=0.0, embd_pdrop=0.0, pad_token_id=0)
        hf = transformers.Phi3ForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.phi3 import Phi3ForCausalLM, get_config

        cfg = get_config("tinyphi3", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers,
                         remat=False, use_flash_attention=False)
        return hf, Phi3ForCausalLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(1).integers(0, 96, size=(2, 12),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


class TestQwen2MoeConversion:
    """Reference qwen_v2_moe/container.py: routed experts + shared expert
    with sigmoid gate, non-normalized top-k."""

    def _pair(self, scan_layers=True):
        hf_cfg = transformers.Qwen2MoeConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=48, shared_expert_intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, num_experts=4, num_experts_per_tok=2,
            norm_topk_prob=False, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6, attention_dropout=0.0,
            decoder_sparse_step=1, mlp_only_layers=[])
        hf = transformers.Qwen2MoeForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                                    get_config)

        # eval-mode capacity (deterministic apply) is eval_capacity_factor
        # = 2.0 -> C = ceil(k*2*G/E) >= G: no drops, HF (dropless) parity
        # is exact
        cfg = get_config("tinyqwen2moe", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers,
                         remat=False, use_flash_attention=False,
                         capacity_factor=4.0)
        return hf, Qwen2MoeForCausalLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(2).integers(0, 96, size=(2, 12),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got, _aux = ours.apply(params, jnp.asarray(ids, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), ref, rtol=3e-4,
                                   atol=3e-4)


class TestFalconConversion:
    """Reference falcon/container.py: fused query_key_value split, MQA,
    parallel attention+MLP residual, LayerNorms with bias."""

    def _pair(self, scan_layers=True):
        hf_cfg = transformers.FalconConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_kv_heads=1, alibi=False,
            parallel_attn=True, new_decoder_architecture=False, bias=False,
            max_position_embeddings=64, rope_theta=10000.0,
            layer_norm_epsilon=1e-5, hidden_dropout=0.0,
            attention_dropout=0.0)
        hf = transformers.FalconForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.falcon import (FalconForCausalLM,
                                                 get_config)

        cfg = get_config("tinyfalcon", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers,
                         remat=False, use_flash_attention=False)
        return hf, FalconForCausalLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(4).integers(0, 96, size=(2, 12),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


class TestOPTConversion:
    """Reference opt/container.py: learned positions (+2 offset), biased
    q/k/v/out, pre-LN, ReLU MLP; serves through the v1 engine."""

    def _pair(self, scan_layers=True):
        hf_cfg = transformers.OPTConfig(
            vocab_size=96, hidden_size=32, ffn_dim=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, do_layer_norm_before=True,
            dropout=0.0, attention_dropout=0.0, activation_function="relu",
            word_embed_proj_dim=32)
        hf = transformers.OPTForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.opt import OPTForCausalLM, get_config

        cfg = get_config("tinyopt", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers,
                         remat=False, use_flash_attention=False)
        return hf, OPTForCausalLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(5).integers(0, 96, size=(2, 12),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_v1_generate_matches_hf(self):
        import deepspeed_tpu

        hf, ours = self._pair(scan_layers=True)
        from deepspeed_tpu.models.opt import get_config

        params = convert_hf_state_dict(ours, hf)
        eng = deepspeed_tpu.init_inference(model=ours, params=params,
                                           max_out_tokens=32,
                                           dtype="float32")
        prompt = np.arange(3, 9, dtype=np.int32)[None]
        out = eng.generate(prompt, max_new_tokens=5, do_sample=False)
        with torch.no_grad():
            ref = hf.generate(torch.from_numpy(prompt.astype(np.int64)),
                              max_new_tokens=5, do_sample=False).numpy()
        np.testing.assert_array_equal(out, ref)

    def test_falcon_40b_layout_parity(self):
        """new_decoder_architecture: per-kv-group qkv interleave + the
        ln_attn/ln_mlp pair (reference falcon 40B containers)."""
        hf_cfg = transformers.FalconConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_kv_heads=2, alibi=False,
            parallel_attn=True, new_decoder_architecture=True, bias=False,
            max_position_embeddings=64, rope_theta=10000.0,
            layer_norm_epsilon=1e-5, hidden_dropout=0.0,
            attention_dropout=0.0)
        hf = transformers.FalconForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.falcon import (FalconForCausalLM,
                                                 get_config)

        cfg = get_config("tinyfalcon", num_key_value_heads=2,
                         new_decoder_architecture=True,
                         dtype=jnp.float32, param_dtype=jnp.float32,
                         scan_layers=True, remat=False,
                         use_flash_attention=False)
        ours = FalconForCausalLM(cfg)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(6).integers(0, 96, size=(2, 10),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_unsupported_falcon_layouts_fail_fast(self):
        from deepspeed_tpu.models.falcon import get_config
        from deepspeed_tpu.module_inject import convert_hf_state_dict

        class M:
            config = get_config("tinyfalcon", num_key_value_heads=4,
                                dtype=jnp.float32)

        with pytest.raises(AssertionError, match="num_kv_heads"):
            convert_hf_state_dict(M(), {})


class TestPhiConversion:
    """Reference phi/containers.py: biased projections, parallel
    residual, PARTIAL rotary (0.5 of head dims at test scale)."""

    def _pair(self, scan_layers=True):
        hf_cfg = transformers.PhiConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, partial_rotary_factor=0.5,
            max_position_embeddings=64, rope_theta=10000.0,
            layer_norm_eps=1e-5, resid_pdrop=0.0, embd_pdrop=0.0,
            attention_dropout=0.0, qk_layernorm=False)
        hf = transformers.PhiForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.phi import PhiForCausalLM, get_config

        cfg = get_config("tinyphi", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers,
                         remat=False, use_flash_attention=False)
        return hf, PhiForCausalLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(7).integers(0, 96, size=(2, 12),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


class TestGPTJConversion:
    """Reference gptj/containers: parallel residual, interleaved partial
    rotary (rows permuted to the half layout on load), biased GELU MLP
    and lm_head."""

    def _pair(self, scan_layers=True):
        hf_cfg = transformers.GPTJConfig(
            vocab_size=96, n_embd=32, n_layer=2, n_head=4, rotary_dim=4,
            n_inner=128, n_positions=64, activation_function="gelu_new",
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
        hf = transformers.GPTJForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.gptj import GPTJForCausalLM, get_config

        cfg = get_config("tinygptj", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers,
                         remat=False, use_flash_attention=False)
        return hf, GPTJForCausalLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(8).integers(0, 96, size=(2, 12),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_v1_generate_matches_hf(self):
        import deepspeed_tpu

        hf, ours = self._pair(scan_layers=True)
        params = convert_hf_state_dict(ours, hf)
        eng = deepspeed_tpu.init_inference(model=ours, params=params,
                                           max_out_tokens=32,
                                           dtype="float32")
        prompt = np.arange(3, 9, dtype=np.int32)[None]
        out = eng.generate(prompt, max_new_tokens=5, do_sample=False)
        with torch.no_grad():
            ref = hf.generate(torch.from_numpy(prompt.astype(np.int64)),
                              max_new_tokens=5, do_sample=False).numpy()
        np.testing.assert_array_equal(out, ref)


class TestGPTNeoXConversion:
    """Reference gptneox.py GPTNEOXLayerPolicy: fused per-head qkv split,
    parallel residual, half-layout partial rotary, untied embed_out."""

    def _pair(self, scan_layers=True, parallel_residual=True):
        hf_cfg = transformers.GPTNeoXConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=64, rotary_pct=0.25,
            use_parallel_residual=parallel_residual, hidden_act="gelu",
            hidden_dropout=0.0, attention_dropout=0.0)
        hf = transformers.GPTNeoXForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.gptneox import (GPTNeoXForCausalLM,
                                                  get_config)

        cfg = get_config("tinyneox", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers,
                         remat=False, use_flash_attention=False,
                         use_parallel_residual=parallel_residual)
        return hf, GPTNeoXForCausalLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(11).integers(0, 96, size=(2, 12),
                                                 dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_sequential_residual_parity(self):
        """Pythia-v0 style use_parallel_residual=False checkpoints."""
        hf, ours = self._pair(scan_layers=True, parallel_residual=False)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(12).integers(0, 96, size=(1, 10),
                                                 dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_v1_generate_matches_hf(self):
        import deepspeed_tpu

        hf, ours = self._pair(scan_layers=True)
        params = convert_hf_state_dict(ours, hf)
        eng = deepspeed_tpu.init_inference(model=ours, params=params,
                                           max_out_tokens=32,
                                           dtype="float32")
        prompt = np.arange(3, 9, dtype=np.int32)[None]
        out = eng.generate(prompt, max_new_tokens=5, do_sample=False)
        with torch.no_grad():
            ref = hf.generate(torch.from_numpy(prompt.astype(np.int64)),
                              max_new_tokens=5, do_sample=False).numpy()
        np.testing.assert_array_equal(out, ref)


class TestBertConversion:
    """Reference bert.py HFBertLayerPolicy: the encoder class — post-LN
    blocks, learned positions + token types, tied MLM decoder."""

    def _pair(self, scan_layers=True):
        hf_cfg = transformers.BertConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, hidden_act="gelu",
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
        hf = transformers.BertForMaskedLM(hf_cfg).eval()

        from deepspeed_tpu.models.bert import BertForMaskedLM, get_config

        cfg = get_config("tinybert", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers)
        return hf, BertForMaskedLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(13).integers(0, 96, size=(2, 12),
                                                 dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_padding_mask_parity(self):
        """Bidirectional attention under an HF-style attention_mask."""
        hf, ours = self._pair(scan_layers=True)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(14).integers(0, 96, size=(2, 10),
                                                 dtype=np.int64)
        mask = np.ones((2, 10), np.int64)
        mask[0, 7:] = 0
        mask[1, 4:] = 0
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids),
                     attention_mask=torch.from_numpy(mask)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32),
                                    attention_mask=jnp.asarray(mask)))
        # only non-pad rows are meaningful (HF also computes pads, with
        # identical masking, so full comparison holds too)
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_v1_forward_serves(self):
        """init_inference forward() — the encoder serving path."""
        import deepspeed_tpu

        hf, ours = self._pair(scan_layers=True)
        params = convert_hf_state_dict(ours, hf)
        eng = deepspeed_tpu.init_inference(model=ours, params=params,
                                           dtype="float32")
        ids = np.random.default_rng(15).integers(0, 96, size=(1, 9),
                                                 dtype=np.int64)
        got = np.asarray(eng.forward(ids.astype(np.int32)))
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_v1_forward_padded_batch(self):
        """The standard encoder workload: mixed-length sequences padded
        to one width, served with attention_mask through forward() —
        non-pad logits must match HF under the same mask."""
        import deepspeed_tpu

        hf, ours = self._pair(scan_layers=True)
        params = convert_hf_state_dict(ours, hf)
        eng = deepspeed_tpu.init_inference(model=ours, params=params,
                                           dtype="float32")
        ids = np.random.default_rng(16).integers(0, 96, size=(2, 12),
                                                 dtype=np.int64)
        mask = np.ones((2, 12), np.int64)
        mask[0, 8:] = 0
        mask[1, 5:] = 0
        got = np.asarray(eng.forward(ids.astype(np.int32),
                                     attention_mask=mask))
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids),
                     attention_mask=torch.from_numpy(mask)).logits.numpy()
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


class TestGPTNeoConversion:
    """Reference gptneo.py HFGPTNEOLayerPolicy: UNscaled attention,
    alternating global/local(window) layers, tied head."""

    def _pair(self):
        hf_cfg = transformers.GPTNeoConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=64, window_size=8,
            attention_types=[[["global", "local"], 1]],
            activation_function="gelu_new", resid_dropout=0.0,
            embed_dropout=0.0, attention_dropout=0.0)
        hf = transformers.GPTNeoForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.gptneo import GPTNeoForCausalLM, get_config

        cfg = get_config("tinyneo", dtype=jnp.float32,
                         param_dtype=jnp.float32)
        return hf, GPTNeoForCausalLM(cfg)

    @pytest.mark.parametrize("flash", [False, True])
    def test_logits_parity_with_transformers(self, flash):
        """flash=True runs the kernel (sm_scale=1.0, unscaled scores) on
        the GLOBAL layers; local-window layers keep the dense mask."""
        hf, ours = self._pair()
        params = convert_hf_state_dict(ours, hf)
        if flash:
            import dataclasses

            from deepspeed_tpu.models.gptneo import GPTNeoForCausalLM

            ours = GPTNeoForCausalLM(dataclasses.replace(
                ours.config, use_flash_attention=True))
        # long enough that the local layer's window=8 actually clips
        ids = np.random.default_rng(17).integers(0, 96, size=(2, 16),
                                                 dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_v1_generate_matches_hf(self):
        """Greedy decode through the KV cache — the local window masks
        cached keys beyond 8 positions behind each query."""
        import deepspeed_tpu

        hf, ours = self._pair()
        params = convert_hf_state_dict(ours, hf)
        eng = deepspeed_tpu.init_inference(model=ours, params=params,
                                           max_out_tokens=32,
                                           dtype="float32")
        prompt = np.arange(3, 15, dtype=np.int32)[None]   # 12 > window 8
        out = eng.generate(prompt, max_new_tokens=6, do_sample=False)
        with torch.no_grad():
            ref = hf.generate(torch.from_numpy(prompt.astype(np.int64)),
                              max_new_tokens=6, do_sample=False).numpy()
        np.testing.assert_array_equal(out, ref)


class TestDistilBertConversion:
    """Reference distil_bert.py HFDistilBertLayerPolicy: BERT-shaped
    minus token types, vocab_* MLM head, served by the BERT modules."""

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf_cfg = transformers.DistilBertConfig(
            vocab_size=96, dim=32, n_layers=2, n_heads=4, hidden_dim=64,
            max_position_embeddings=64, activation="gelu", dropout=0.0,
            attention_dropout=0.0)
        hf = transformers.DistilBertForMaskedLM(hf_cfg).eval()

        from deepspeed_tpu.models.bert import BertForMaskedLM, get_config

        cfg = get_config("tinydistil", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers)
        ours = BertForMaskedLM(cfg)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(18).integers(0, 96, size=(2, 12),
                                                 dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


class TestBloomConversion:
    """Reference bloom.py BLOOMLayerPolicy: fused per-head qkv split,
    ALiBi scores, embedding LayerNorm, tied lm_head."""

    def _pair(self, scan_layers=True):
        hf_cfg = transformers.BloomConfig(
            vocab_size=96, hidden_size=32, n_layer=2, n_head=4,
            layer_norm_epsilon=1e-5, hidden_dropout=0.0,
            attention_dropout=0.0, slow_but_exact=False)
        hf = transformers.BloomForCausalLM(hf_cfg).eval()

        from deepspeed_tpu.models.bloom import BloomForCausalLM, get_config

        cfg = get_config("tinybloom", dtype=jnp.float32,
                         param_dtype=jnp.float32, scan_layers=scan_layers,
                         remat=False, use_flash_attention=False)
        return hf, BloomForCausalLM(cfg)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_logits_parity_with_transformers(self, scan_layers):
        hf, ours = self._pair(scan_layers)
        params = convert_hf_state_dict(ours, hf)
        ids = np.random.default_rng(9).integers(0, 96, size=(2, 12),
                                                dtype=np.int64)
        with torch.no_grad():
            ref = hf(torch.from_numpy(ids)).logits.numpy()
        got = np.asarray(ours.apply(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)

    def test_v1_generate_matches_hf(self):
        """ALiBi through the KV-cache decode path (k_bias reduction)."""
        import deepspeed_tpu

        hf, ours = self._pair(scan_layers=True)
        params = convert_hf_state_dict(ours, hf)
        eng = deepspeed_tpu.init_inference(model=ours, params=params,
                                           max_out_tokens=32,
                                           dtype="float32")
        prompt = np.arange(3, 9, dtype=np.int32)[None]
        out = eng.generate(prompt, max_new_tokens=5, do_sample=False)
        with torch.no_grad():
            ref = hf.generate(torch.from_numpy(prompt.astype(np.int64)),
                              max_new_tokens=5, do_sample=False).numpy()
        np.testing.assert_array_equal(out, ref)
