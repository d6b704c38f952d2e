"""Inference engine (v1-equivalent).

TPU-native re-design of the reference ``InferenceEngine``
(``deepspeed/inference/engine.py:40``, entry ``deepspeed.init_inference``,
``deepspeed/__init__.py:291``).  The reference wraps an HF torch module,
injects fused CUDA kernel containers or AutoTP-shards it, optionally
captures a CUDA graph, and defers generation to HF ``generate``.  Here:

- kernel injection collapses: the flax models already run the fused
  Pallas/XLA ops (``replace_with_kernel_inject`` warns and no-ops);
- TP sharding is the same GSPMD story as training: param PartitionSpecs
  from flax metadata or AutoTP name rules, over the ``tensor`` mesh axis;
- the CUDA graph is the jit: prefill, decode step, and the whole generate
  loop (a ``lax.scan`` over decode steps with the KV cache as carry)
  compile into single XLA programs per shape;
- generation is native: greedy/temperature/top-k/top-p sampling fused into
  the loop (``inference/sampling.py``), KV cache per layer
  (``inference/kv_cache.py``);
- the token harvest is deferrable (the serving host-path pipeline,
  ``config.v2``): :meth:`InferenceEngine.generate_async` dispatches the
  fused prefill+decode program and returns a :class:`PendingGeneration`
  handle WITHOUT blocking on ``device_get`` — back-to-back calls overlap
  the next dispatch's host work with the previous call's device work, and
  the caller harvests when it actually needs tokens.  ``generate()`` is
  ``generate_async(...).result()``.  ``host_stats`` breaks the host path
  into plan/upload/dispatch/device/harvest per dispatch.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.common import HostStageStats
from deepspeed_tpu.inference.config import (DeepSpeedInferenceConfig,
                                            load_inference_config)
from deepspeed_tpu.inference.kv_cache import init_cache
from deepspeed_tpu.inference.sampling import sample_logits
from deepspeed_tpu.utils.logging import log_dist, logger

_DTYPES = {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
           "float16": jnp.float16, "fp16": jnp.float16,
           "float32": jnp.float32, "fp32": jnp.float32}


def init_inference(model: Any, config: Any = None, params: Any = None,
                   topology=None, rng: Optional[jax.Array] = None,
                   checkpoint: Any = None,
                   **kwargs) -> "InferenceEngine":
    """Create an :class:`InferenceEngine` (reference
    ``deepspeed.init_inference``, ``deepspeed/__init__.py:291``).

    ``model``: a flax causal-LM module returning ``[B, S, V]`` logits (or a
    ``(logits, aux)`` tuple, e.g. Mixtral).  If its ``config`` dataclass has
    a ``decode`` field, a decode-mode twin is constructed automatically.
    ``params``: trained parameters; randomly initialized when omitted
    (benchmarking).
    """
    cfg = load_inference_config(config, **kwargs)
    if checkpoint is not None:
        assert params is None, "pass checkpoint= or params=, not both"
        from deepspeed_tpu.module_inject import load_hf_checkpoint

        params = load_hf_checkpoint(model, checkpoint)
    return InferenceEngine(model, cfg, params=params, topology=topology,
                           rng=rng)


class PendingGeneration:
    """Deferred-harvest handle from :meth:`InferenceEngine.generate_async`.

    The fused decode program is already dispatched (the device runs
    asynchronously); :meth:`result` blocks on the ONE ``device_get`` and
    caches the numpy tokens.  :meth:`device_array` exposes the device
    buffer for callers chaining further device work (the bench harness's
    overlap loop) without ever paying the host copy."""

    def __init__(self, engine: "InferenceEngine", arr):
        self._engine = engine
        self._arr = arr
        self._result: Optional[np.ndarray] = None

    def device_array(self):
        return self._arr

    def ready(self) -> bool:
        """True when the tokens can be read without blocking (already
        harvested, or the device reports the buffer ready)."""
        if self._result is not None:
            return True
        return bool(self._arr.is_ready())

    def result(self) -> np.ndarray:
        if self._result is None:
            st = self._engine.host_stats
            with st.stage("device"):
                st.blocking_gets += 1
                out = jax.device_get(self._arr)
            st.harvests += 1
            with st.stage("harvest"):
                self._result = np.asarray(out)
        return self._result


class InferenceEngine:
    def __init__(self, model, config: DeepSpeedInferenceConfig, params=None,
                 topology=None, rng: Optional[jax.Array] = None,
                 param_source=None):
        """``param_source``: zero-copy live parameter callable (the hybrid
        engine's RLHF path) — when set, params are NOT staged here; every
        forward/generate reads ``param_source()`` and any dtype cast
        happens in-graph (flax computes in the serving dtype)."""
        self.config = config
        self.dtype = _DTYPES[config.dtype]
        self.module = model                      # API parity with reference
        self._param_source = param_source
        self.host_stats = HostStageStats()
        self.v2 = config.v2      # serving host-path knobs (pipeline, ...)

        tp_size = config.tensor_parallel.tp_size if config.tensor_parallel.enabled else 1
        dist.init_distributed()
        if topology is None:
            topology = (dist.initialize_mesh(tp=tp_size) if tp_size > 1
                        else dist.get_topology())
        else:
            dist.set_topology(topology)
        self.topology = topology
        self.mesh = topology.mesh

        # decode-mode twin of the model (KV cache threaded through attention)
        mcfg = getattr(model, "config", None)
        if (dataclasses.is_dataclass(mcfg) and
                any(f.name == "decode" for f in dataclasses.fields(mcfg))):
            # decode twins unroll the layer scan: flax scan restacks the
            # mutable cache per step (full-cache copies); unrolled layers
            # alias each cache independently — 3.8x decode on v5e.
            # Scan-stacked params convert in-jit (common.unroll_scan_params)
            self._unroll_params = bool(getattr(mcfg, "scan_layers", False))
            self._plain_model = (model if mcfg.dtype == self.dtype
                                 else type(model)(
                                     dataclasses.replace(mcfg,
                                                         dtype=self.dtype)))
            is_encoder = bool(getattr(mcfg, "is_encoder", False))
            has_cache = any(f.name == "max_cache_len"
                            for f in dataclasses.fields(mcfg))
            if not is_encoder and not has_cache:
                raise TypeError(
                    f"{type(mcfg).__name__} has a 'decode' field but no "
                    "'max_cache_len' and is not marked is_encoder=True — "
                    "decoder configs need max_cache_len for the KV "
                    "cache; encoder configs must set is_encoder")
            if has_cache and not is_encoder:
                # learned/rotary position tables bound usable positions;
                # clamp the cache so generate() can't run past them into
                # silently clamped embedding gathers
                pos_bound = (getattr(mcfg, "n_positions", None) or
                             getattr(mcfg, "max_position_embeddings", None))
                cache_len = (getattr(mcfg, "max_cache_len", 0) or
                             config.max_out_tokens)
                if pos_bound is not None and cache_len > pos_bound:
                    logger.warning(
                        f"max_out_tokens={cache_len} exceeds the model's "
                        f"position bound {pos_bound}; clamping the KV cache")
                    cache_len = pos_bound
                dcfg = dataclasses.replace(
                    mcfg, decode=True, dtype=self.dtype,
                    max_cache_len=cache_len, scan_layers=False)
                self._decode_model = type(model)(dcfg)
                self.max_cache_len = dcfg.max_cache_len
            else:
                # encoder families (BERT): forward()-only serving, the
                # reference's BertLayer injection scope — no KV cache,
                # generate() refuses below
                self._decode_model = None
                self.max_cache_len = 0
        else:
            raise TypeError(
                "init_inference needs a model whose config dataclass has a "
                "'decode' field (models/gpt2.py, models/llama.py, "
                "models/mixtral.py do)")

        # -- params: init if absent, cast to serving dtype, TP-shard -------
        from deepspeed_tpu.parallel import tensor_parallel as tp_lib

        if param_source is not None:
            self.params = None                   # live view, never staged
            self._generate_cache: Dict[Tuple, Any] = {}
            self._forward_fn = None
            self._cache_shapes: Dict[int, Any] = {}
            log_dist(f"InferenceEngine: dtype={config.dtype} tp={tp_size} "
                     f"max_cache_len={self.max_cache_len} "
                     "(live shared params)", ranks=[0])
            return
        from deepspeed_tpu.inference.common import normalize_params

        if params is None:
            log_dist("init_inference: params randomly initialized "
                     "(none provided)", ranks=[0])
        params = normalize_params(model, params, rng=rng,
                                  plain_model=self._plain_model)

        specs = None
        if tp_lib.has_partitioning(params):
            specs = tp_lib.extract_partition_specs({"params": params},
                                                   self.mesh.axis_names)
            specs = specs["params"]
            params = tp_lib.unbox_params(params)
        elif topology.tensor_parallel_size > 1:
            specs = tp_lib.auto_tp_specs(params,
                                         topology.tensor_parallel_size)
            log_dist("init_inference AutoTP: inferred tensor-parallel "
                     "sharding from parameter names", ranks=[0])

        def cast(x):
            x = jnp.asarray(x)
            return x.astype(self.dtype) if jnp.issubdtype(
                x.dtype, jnp.floating) else x

        params = jax.tree_util.tree_map(cast, params)
        self._wq = None
        if config.quant.enabled:
            # weight-only serving quantization (reference QuantizationConfig
            # -> replace_with_quantized_linear / FP6-LLM cuda_linear):
            # weights persist quantized, dequantize in-jit at use
            assert tp_size <= 1, (
                "quant.enabled does not compose with tensor-parallel "
                "serving yet")
            from deepspeed_tpu.inference.quantization import \
                quantize_param_tree

            self._wq = config.quant.qtype
            params, b0, b1 = quantize_param_tree(
                params, self._wq, group_size=config.quant.group_size)
            # quantized leaves are QuantizedWeight subtrees — the per-leaf
            # spec tree no longer lines up, and tp<=1 means replication
            # was the only placement anyway
            specs = None
            log_dist(f"init_inference weights -> {self._wq}: "
                     f"{b0 / 2**20:.1f} MiB -> {b1 / 2**20:.1f} MiB",
                     ranks=[0])
        from jax.sharding import NamedSharding, PartitionSpec as P

        if specs is not None:
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            params = jax.tree_util.tree_map(jax.device_put, params,
                                            shardings)
        else:
            params = jax.device_put(params,
                                    NamedSharding(self.mesh, P()))
        self.params = params

        self._generate_cache: Dict[Tuple, Any] = {}
        self._forward_fn = None
        self._cache_shapes: Dict[int, Any] = {}
        log_dist(f"InferenceEngine: dtype={config.dtype} tp={tp_size} "
                 f"max_cache_len={self.max_cache_len}", ranks=[0])

    # ------------------------------------------------------------------

    def _logits(self, out):
        from deepspeed_tpu.inference.common import logits_of

        return logits_of(out)

    def _zero_cache_shapes(self, B: int, S: int):
        if B not in self._cache_shapes:
            self._cache_shapes[B] = jax.tree_util.tree_map(
                lambda l: (l.shape, l.dtype),
                init_cache(self._decode_model, np.zeros((B, S), np.int32)))
        return self._cache_shapes[B]

    def forward(self, input_ids, attention_mask=None) -> jax.Array:
        """Full-sequence logits (reference ``InferenceEngine.forward``,
        ``engine.py:554``) — no KV cache, one fused program.

        ``attention_mask`` ([B, S], 1 = real token): padding mask for
        encoder families serving mixed-length padded batches (BERT —
        without it every query attends to pad keys); decoder models are
        causal and ignore it."""
        if self._forward_fn is None:
            model = self._plain_model
            wq = getattr(self, "_wq", None)
            takes_mask = "attention_mask" in inspect.signature(
                model.__call__).parameters

            def fwd(params, ids, mask):
                if wq:
                    from deepspeed_tpu.inference.quantization import \
                        dequantize_param_tree

                    params = dequantize_param_tree(params)
                kw = {"attention_mask": mask} if (takes_mask and
                                                  mask is not None) else {}
                return self._logits(model.apply({"params": params}, ids,
                                                **kw))

            self._forward_fn = jax.jit(fwd, static_argnames=())
            self._forward_takes_mask = takes_mask
        if attention_mask is not None and not self._forward_takes_mask:
            if not getattr(self, "_mask_warned", False):
                self._mask_warned = True
                logger.warning("forward(): this model takes no "
                               "attention_mask; ignoring it "
                               "(warning once)")
            attention_mask = None
        mask = (None if attention_mask is None
                else jnp.asarray(attention_mask))
        return self._forward_fn(self._live_params(),
                                jnp.asarray(input_ids), mask)

    def _live_params(self):
        if self._param_source is not None:
            p = self._param_source()
            return p["params"] if isinstance(p, dict) and "params" in p \
                else p
        return self.params

    __call__ = forward

    # ------------------------------------------------------------------

    def _build_generate(self, B: int, P: int, max_new: int, do_sample: bool,
                        temperature: float, top_k: int, top_p: float,
                        eos_id: Optional[int]):
        model = self._decode_model
        logits_of = self._logits
        cache_shapes = self._zero_cache_shapes(B, P)

        def sample(lg, rng):
            return sample_logits(lg, rng, do_sample=do_sample,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)

        unroll = self._unroll_params
        wq = getattr(self, "_wq", None)

        def gen(params, prompt, rng):
            if wq:
                from deepspeed_tpu.inference.quantization import \
                    dequantize_param_tree

                params = dequantize_param_tree(params)
            if unroll:
                from deepspeed_tpu.inference.common import \
                    unroll_scan_params

                params = unroll_scan_params(params)
            cache = jax.tree_util.tree_map(
                lambda sd: jnp.zeros(*sd), cache_shapes,
                is_leaf=lambda x: isinstance(x, tuple))
            out, vars_ = model.apply(
                {"params": params, "cache": cache}, prompt,
                positions=jnp.arange(P), mutable=["cache"])
            cache = vars_["cache"]
            rng, sub = jax.random.split(rng)
            tok = sample(logits_of(out)[:, -1], sub)
            done = (jnp.zeros((B,), bool) if eos_id is None
                    else tok == eos_id)

            def step(carry, _):
                cache, tok, pos, rng, done = carry
                out, vars_ = model.apply(
                    {"params": params, "cache": cache}, tok[:, None],
                    positions=pos[None, None], mutable=["cache"])
                rng, sub = jax.random.split(rng)
                nxt = sample(logits_of(out)[:, -1], sub)
                if eos_id is not None:
                    nxt = jnp.where(done, jnp.int32(eos_id), nxt)
                    done = done | (nxt == eos_id)
                return (vars_["cache"], nxt, pos + 1, rng, done), nxt

            (_, _, _, _, done), toks = jax.lax.scan(
                step, (cache, tok, jnp.int32(P), rng, done),
                length=max_new - 1)
            new = jnp.concatenate([tok[:, None], toks.T], axis=1)
            return jnp.concatenate([prompt, new.astype(prompt.dtype)],
                                   axis=1)

        return jax.jit(gen)

    def generate_async(self, input_ids, max_new_tokens: int = 128,
                       do_sample: bool = False, temperature: float = 1.0,
                       top_k: int = 0, top_p: float = 1.0,
                       eos_token_id: Optional[int] = None,
                       rng: Optional[jax.Array] = None
                       ) -> PendingGeneration:
        """Dispatch the fused prefill+decode program and return WITHOUT
        waiting for the device — the deferred-harvest half of
        :meth:`generate`.  The returned :class:`PendingGeneration`
        blocks only when ``result()`` is called, so a serving loop can
        keep dispatching (the host path of call k+1 overlaps the device
        work of call k) and harvest tokens in bulk."""
        if self._decode_model is None:
            raise TypeError(
                "generate() needs a decoder model; encoder families "
                "(BERT) serve through forward() only")
        st = self.host_stats
        with st.stage("upload"):
            st.meta_uploads += 1
            prompt = jnp.asarray(np.asarray(input_ids), jnp.int32)
        assert prompt.ndim == 2, "input_ids must be [batch, prompt_len]"
        B, P = prompt.shape
        if self.config.max_batch_size and B > self.config.max_batch_size:
            raise ValueError(f"batch {B} exceeds max_batch_size "
                             f"{self.config.max_batch_size}")
        assert max_new_tokens >= self.config.min_out_tokens, (
            f"max_new_tokens {max_new_tokens} < min_out_tokens "
            f"{self.config.min_out_tokens}")
        assert P + max_new_tokens <= self.max_cache_len, (
            f"prompt {P} + max_new_tokens {max_new_tokens} exceeds "
            f"max_cache_len {self.max_cache_len} (raise max_out_tokens)")
        with st.stage("plan"):
            key = (B, P, max_new_tokens, do_sample, temperature, top_k,
                   top_p, eos_token_id)
            if key not in self._generate_cache:
                self._generate_cache[key] = self._build_generate(
                    B, P, max_new_tokens, do_sample, temperature, top_k,
                    top_p, eos_token_id)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        with st.stage("dispatch"):
            st.dispatches += 1
            arr = self._generate_cache[key](self._live_params(), prompt,
                                            rng)
        st.ticks += max_new_tokens
        return PendingGeneration(self, arr)

    def generate(self, input_ids, max_new_tokens: int = 128,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 rng: Optional[jax.Array] = None) -> np.ndarray:
        """Autoregressive generation: prefill + ``max_new_tokens`` fused
        decode steps in one compiled program per (batch, prompt-len,
        max-new) shape.  Returns ``[B, P + max_new_tokens]`` token ids.
        (``generate_async`` is the non-blocking variant.)"""
        return self.generate_async(
            input_ids, max_new_tokens=max_new_tokens, do_sample=do_sample,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_token_id=eos_token_id, rng=rng).result()

    def serving_stages(self) -> Dict[str, Any]:
        """Per-dispatch host-path breakdown + ``host_bound_fraction``
        (see :class:`~deepspeed_tpu.inference.common.HostStageStats`)."""
        return self.host_stats.serving_stages()
