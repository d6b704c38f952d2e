"""FastGen-equivalent ragged / continuous-batching inference engine.

TPU-native re-design of the reference InferenceEngineV2 stack
(``inference/v2/engine_v2.py:30``, ragged batching ``inference/v2/ragged/``,
Dynamic SplitFuse scheduling from the FastGen blog): requests of different
lengths share one running decode batch — sequences join the moment a slot
frees, never waiting for the batch to drain.

Round-3 architecture (replacing the slot-row cache + split prefill/decode
dispatches of round 2):

- **Blocked KV** (reference ``ragged/blocked_allocator.py:1``,
  ``ragged/kv_cache.py``): KV lives in fixed-size pages addressed by a
  per-sequence page table; device memory scales with pages, not
  ``max_seqs x max_seq_len``.  Allocation is host-side
  (:class:`deepspeed_tpu.inference.paged.PageAllocator`), worst-case
  reserved at admission.
- **One fused compiled program per tick** (Dynamic SplitFuse,
  ``engine_v2.py:107``): a single static ``[1, T]`` token batch carries one
  decode token for EVERY ready sequence AND this tick's prefill chunk(s) —
  multiple prefilling requests share the chunk budget.  Shapes never vary,
  so exactly one XLA program is compiled; raggedness lives in int32
  metadata (``cu_q_lens`` et al.).
- **Attention** is the vLLM-TPU ragged paged Pallas kernel on TPU and an
  XLA-compilable reference on CPU (``inference/paged.py``).

Round-4 additions:

- **Tensor-parallel serving** (reference v2 TP sharding,
  ``inference/v2/model_implementations/sharding/attn.py`` + engine TP
  groups ``inference/engine.py:247``): pass a ``topology`` with a >1
  ``tensor`` axis — weights shard by AutoTP name rules, the paged KV pool
  shards over its head dim, and the fused tick runs under GSPMD with the
  paged attention shard_map-manual over ``tensor``.
- **On-device multi-tick decode**: when every active sequence is past
  prefill, ``step()`` dispatches ONE compiled program that runs
  ``decode_block_size`` decode ticks in a ``lax.scan`` with on-device
  per-sequence sampling (``sampling.sample_logits_batched``) — amortizing
  the host round trip the reference's FastGen scheduler pays per tick to
  1/K.  Finished sequences park on the trash page mid-block; the host
  reconstructs outputs from the per-tick produced mask.

Round-5/6 addition — the **pipelined serving host path** (this repo's
software-pipeline treatment, same shape as the NVMe moment stream): the
round-5 verdict measured the ragged engine at 23.3k device tok/s but 295
WALL tok/s — ~99% of serving wall time was host planning, per-tick
``jnp.asarray`` metadata uploads, and a blocking ``device_get`` per
dispatch.  With ``pipeline=True`` (default) the decode steady state runs
as a software pipeline:

- the decode-block carry (``last_tok``/``pos``/``active``/``remaining``)
  and the per-tick metadata (``page_indices``/``kv_lens`` derivation
  inputs, sampler configs, eos ids) stay RESIDENT on device across
  dispatches — uploaded once at loop entry, re-uploaded only when the
  page table actually grows;
- while block *k* executes on device, the host plans block *k+1* from an
  exact projection of each sequence's length/remaining budget (JAX
  dispatch is async — the host never synchronizes per block, bounded by
  ``async_depth`` blocks in flight);
- sampled tokens accumulate on device and are harvested (one
  ``device_get``) every ``harvest_interval`` blocks.  EOS/finish
  detection stays device-side (the decode block's ``active`` carry).

Harvests are FORCED at every point where the unpipelined engine could
have reaped, admitted, or evicted (a possible finish, a newly admittable
request, page-growth failure), so the dispatch sequence — programs,
metadata values, and rng splits — is identical to ``pipeline=False`` and
outputs are bit-identical, greedy or seeded-sampling.  ``host_stats``
(:class:`~deepspeed_tpu.inference.common.HostStageStats`) breaks the
host path into plan/upload/dispatch/device/harvest per dispatch.

Round-6 addition — **speculative decoding on the pipelined decode
path**: decode is memory-bound (every dispatch re-reads the weights from
HBM for ONE token per sequence), so with ``speculation.mode != off`` each
decode-block tick drafts ``k`` tokens per slot and the target model
scores all ``k+1`` positions in ONE ragged dispatch (the drafted tokens
enter as a short prefill-like chunk against the paged KV — the same
SplitFuse machinery that mixes prefill chunks into decode ticks).  A
device-resident accept/rollback step then compares draft vs target:

- **greedy** slots accept the longest exact-match prefix and emit the
  target's argmax everywhere, so speculative greedy output is
  bit-identical to non-speculative decode regardless of draft quality;
- **sampled** slots use standard rejection sampling with
  residual-distribution resampling
  (:func:`~deepspeed_tpu.inference.sampling.speculative_verify`), so
  the output distribution provably equals the non-speculative one.

Two draft modes share the interface: ``ngram`` (prompt-lookup over a
device-resident token-history buffer — no second model) and ``draft``
(a small same-vocab family member runs its own decode carry against the
SAME page table; its KV pool is separate, its page cursors are shared).
Rollback is pure position rollback: KV rows written for rejected draft
positions are provably overwritten by the next block before any query
can attend to them, and the pages stay owned (the next block writes the
same span).  Accepted length, rolled-back cursors, and the corrected
bonus token all live in the decode carry, so speculation composes with
the pipelined host path — the host projects per-slot advance as
``[1, k+1]``-per-tick BOUNDS instead of exact counts, grows pages to
the worst case, and forces a harvest whenever a finish is possible
under the fast bound.

Host-side scheduling (admission, chunk budgeting, finish detection) is
plain Python — the reference's scheduler tier is host-side too.  Models:
anything llama-shaped in the zoo (Llama, Mistral, Qwen2, Mixtral, ... —
per-token positions thread through attention, which the ragged path
requires).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.common import HostStageStats
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.telemetry import RequestLatencyTracker, trace
from deepspeed_tpu.utils.async_stage import BoundedAsyncStage, StageTimers
from deepspeed_tpu.inference.paged import (PageAllocator,
                                           pages_for)
from deepspeed_tpu.inference.prefix_cache import (ROOT_HASH,
                                                  PrefixCacheIndex)
from deepspeed_tpu.inference.sampling import (filter_logits_batched,
                                              position_keys,
                                              sample_logits,
                                              sample_logits_batched,
                                              speculative_verify)
from deepspeed_tpu.utils.logging import log_dist


def _fold_moe_counts(counts: jax.Array) -> jax.Array:
    """``[n, 3]`` per-layer (or per-tick) expert-layer counts -> ``[3]``:
    token copies routed and experts hit add up, the fullest expert's rows
    take the largest."""
    return jnp.concatenate([jnp.sum(counts[:, :2], axis=0),
                            jnp.max(counts[:, 2:], axis=0)])


def _moe_counts(variables) -> Optional[jax.Array]:
    """One apply's ``moe_stats`` collection (each dropless expert layer
    sows ``[rows, experts_hit, load_max]``) folded over its layers; None
    for a model without expert layers, so its programs gain no output."""
    leaves = jax.tree_util.tree_leaves(variables.get("moe_stats", {}))
    return _fold_moe_counts(jnp.stack(leaves)) if leaves else None


def _paged_kv_page_bytes(model, mcfg, page_size: int,
                         kv_cache_dtype: str) -> int:
    """Exact device bytes ONE KV page costs across every cache leaf —
    for a quantized pool that is the 1-byte payload page plus its fp32
    scale rows.  Measured by an eval_shape probe of a 2-page pool
    rather than guessed from the layer count, so any model-zoo cache
    layout (extra leaves, fused layers) is accounted automatically."""
    probe_cfg = dataclasses.replace(
        mcfg, decode=True, ragged_decode=False, paged_decode=True,
        max_cache_len=2 * page_size, scan_layers=False,
        kv_page_size=page_size, kv_num_pages=2,
        tensor_parallel=False, kv_cache_dtype=kv_cache_dtype)
    probe = type(model)(probe_cfg)
    meta = {"kv_lens": jnp.zeros((1,), jnp.int32),
            "page_indices": jnp.full((1, 2), -1, jnp.int32),
            "cu_q_lens": jnp.zeros((2,), jnp.int32),
            "num_seqs": jnp.zeros((1,), jnp.int32),
            "new_kv_dest": jnp.zeros((4,), jnp.int32)}
    ids = jnp.zeros((1, 4), jnp.int32)
    pos = jnp.zeros((1, 4), jnp.int32)
    shapes = jax.eval_shape(lambda: probe.init(
        jax.random.PRNGKey(0), ids, positions=pos, ragged_meta=meta))
    total = sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                for leaf in jax.tree_util.tree_leaves(shapes["cache"]))
    assert total % 2 == 0, total
    return total // 2


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                    # [P] int32
    max_new_tokens: int = 64
    eos_token_id: Optional[int] = None
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    # runtime state
    slot: int = -1
    prefill_done: int = 0                 # context tokens already cached
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # prefill SOURCE: the prompt, or prompt + already-generated tokens
    # after an eviction (the continuation re-prefills its own output)
    ctx: Optional[np.ndarray] = None
    # tiered-KV spill payload metadata while the sequence's pages sit
    # in host RAM / NVMe (None <=> not spilled); the page bytes live in
    # the engine's TieredKVStore keyed by uid.  With a prefix cache,
    # "shared_pages" records shared-prefix pages held resident by
    # spill-holds instead of spilling (only private pages hit the tiers)
    spilled: Optional[Dict[str, Any]] = None
    # prefix-cache registration cursor: pc_pages full pages of ctx are
    # in the index, pc_parent is the chain hash at that point, and
    # pc_cached counts prefill tokens this admission skipped via attach
    pc_parent: int = ROOT_HASH
    pc_pages: int = 0
    pc_cached: int = 0
    # partial residency (kv_tiering.long_context): the sequence's KV
    # exceeds the HBM pool, so only sinks + the recent window stay
    # resident and LongContextDriver ticks it outside the fused batch.
    # lc_parked counts middle page GROUPS demoted to the tiers under
    # "mid-<uid>-<g>" keys (always a contiguous prefix of the middle)
    lc: bool = False
    lc_parked: int = 0
    # disaggregated serving: the router marked this request for a
    # prefill->decode handoff — the engine runs its prefill, lets the
    # first token land (it is sampled in the same fused tick the last
    # prompt chunk runs in), then parks the session for
    # ``export_handoff`` instead of decoding it locally
    handoff: bool = False

    @property
    def ctx_len(self) -> int:
        return int(self.ctx.size if self.ctx is not None
                   else self.prompt.size)

    @property
    def length(self) -> int:
        # tokens in the KV cache: prefilled context + tokens generated
        # AFTER that context (an evicted continuation's ctx already
        # contains its earlier output)
        return self.prefill_done + len(self.generated) - \
            (self.ctx_len - self.prompt.size)


class RaggedInferenceEngineV2:
    """``put_request`` -> repeated ``step()`` -> ``get_outputs``.

    One ``step()`` = (admit waiting requests into free slots, reserving
    KV pages) + EITHER one fused SplitFuse tick (any sequence still
    prefilling: a decode token for every ready sequence plus prompt
    chunks, in one ``T = max_seqs + prefill_chunk`` batch) OR one
    ``decode_block_size``-tick on-device decode block (everyone
    decoding).
    """

    def __init__(self, model, params: Any = None, max_seqs: int = 8,
                 max_seq_len: int = 512, prefill_chunk: int = 128,
                 rng: Optional[jax.Array] = None, page_size: int = 64,
                 num_pages: Optional[int] = None, topology=None,
                 decode_block_size: int = 8,
                 kv_cache_dtype: Optional[str] = None,
                 kv_pool_bytes: Optional[int] = None,
                 quantize_weights: Optional[str] = None,
                 kv_reserve: str = "on_demand",
                 pipeline: Optional[bool] = None,
                 async_depth: Optional[int] = None,
                 harvest_interval: Optional[int] = None,
                 speculation: Any = None,
                 draft_model=None, draft_params: Any = None,
                 draft_kv_cache_dtype: Optional[str] = None,
                 kv_tiering: Any = None,
                 prefix_cache: Any = None,
                 slo: Any = None,
                 trace_sample: Optional[int] = None,
                 replica: Optional[str] = None,
                 control: Any = None,
                 config: Any = None):
        """``kv_cache_dtype``: ``None`` (config subtree
        ``v2.kv_cache_dtype`` decides; "none" by default) | "none" |
        "fp8" | "int8" — paged KV pool storage format (reference
        fp_quantizer KV quantization).  Quantized pools are read
        dequant-free: the Pallas quantized-pages kernel on TPU at
        head_dim 128, the gathered-pages XLA reference elsewhere
        (:func:`~deepspeed_tpu.inference.paged.kv_dequant_path`).
        ``kv_pool_bytes``: size the pool by a device byte budget instead
        of page count — ``num_pages`` becomes the exact number of pages
        (payload + scale rows) that fit, so the same HBM budget holds
        ~2x the pages when quantized.  Ignored when ``num_pages`` is
        given explicitly.
        ``draft_kv_cache_dtype``: storage format for the draft model's
        pool under ``speculation.mode='draft'``; default ``None``
        follows the target pool's resolved ``kv_cache_dtype``.
        ``quantize_weights``: None | "int8" | "fp8" | "fp6" | "w8a8" —
        weights persist quantized in HBM and dequantize in-jit at use
        (reference FP6-LLM cuda_linear / int8 quantized inference);
        "w8a8" additionally quantizes activations per row and dots
        int8 x int8 on the MXU (reference W8A8 GEMMs,
        ``csrc/quantization``) — Llama-family models only, and the
        faster choice whenever decode is weight-bandwidth-bound.
        ``kv_reserve``: "on_demand" (reference blocked-allocator model —
        admit on prompt-size pages, grow per decode block, evict +
        requeue as a continuation when the pool runs dry) or
        "worst_case" (reserve prompt + max_new_tokens at admission; no
        mid-flight out-of-pages state, lower concurrency per byte).
        ``pipeline``/``async_depth``/``harvest_interval``: the serving
        host-path pipeline knobs (module docstring).  Defaults come from
        ``config`` (a ``DeepSpeedInferenceConfig``/dict with a ``v2``
        subtree: ``inference.v2.pipeline`` default-on, ``async_depth``
        2, ``harvest_interval`` 4); explicit kwargs win.
        ``pipeline=False`` preserves the unpipelined host loop exactly
        — one blocking harvest and a fresh metadata upload per
        dispatch.
        ``speculation``: ``None`` (config subtree decides; off by
        default), a mode string (``"ngram"``/``"draft"``/``"off"``), a
        dict, or a :class:`~deepspeed_tpu.inference.config.SpeculationConfig`
        — speculative decoding on the decode-block path (module
        docstring).  ``mode="draft"`` additionally needs ``draft_model``
        (a small same-vocab llama-family zoo module) and its
        ``draft_params``.
        ``kv_tiering``: ``None`` (config subtree decides; off by
        default), a dict (implies ``enabled=True`` unless it says
        otherwise), or a
        :class:`~deepspeed_tpu.inference.config.KVTieringConfig` —
        host-RAM + NVMe spill tiers for the paged-KV pool
        (:mod:`deepspeed_tpu.inference.kv_tiering`).  With tiering
        disabled the engine is byte-for-byte the untiered engine.
        ``prefix_cache``: ``None`` (config subtree decides; off by
        default), a bool, a dict (implies ``enabled=True``), or a
        :class:`~deepspeed_tpu.inference.config.PrefixCacheConfig` —
        cross-request KV sharing over the paged pool
        (:mod:`deepspeed_tpu.inference.prefix_cache`): admission
        attaches fully-matched resident prefix pages read-only and
        prefills only the non-cached suffix; the first divergent write
        copy-on-writes.  Greedy outputs are bit-identical to
        cache-off, and seeded sampling too, because sampling keys are
        position-keyed (:func:`~deepspeed_tpu.inference.sampling.position_keys`)
        rather than drawn from a dispatch-ordered stream.
        ``slo``: ``None`` (config subtree ``v2.slo`` decides; off by
        default), a list of objective strings like
        ``"ttft_ms_p99 <= 150"``, or a prebuilt
        :class:`~deepspeed_tpu.telemetry.slo.SLOSet` — every reaped
        request feeds its summary record; ``serving_stages()["slo"]``
        carries the rolling error-budget burn per objective.
        ``trace_sample``: tail-based trace sampling N (kwarg > config
        ``v2.trace_sample`` > env ``DSTPU_TRACE_SAMPLE``).  When the
        tracer's sampling mode is armed, a reaped request's spans are
        promoted to the retained ring only on SLO breach, error, or a
        deterministic 1-in-N draw.
        ``replica``: metric-label identity for scale-out serving — each
        replica engine's registry children (``dstpu_request_*``,
        ``dstpu_serving_stage_seconds``) carry ``replica="<value>"`` so
        ``export_text()`` distinguishes replicas; solo engines keep the
        empty label value."""
        mcfg = getattr(model, "config", None)
        assert dataclasses.is_dataclass(mcfg) and hasattr(mcfg, "decode"), \
            "ragged engine needs a model-zoo module with a decode config"
        assert hasattr(mcfg, "rope_theta"), (
            "ragged batching requires per-token positions through "
            "attention — supported by the Llama family models")
        assert hasattr(mcfg, "paged_decode"), (
            "model config predates paged ragged decode support")

        import deepspeed_tpu.comm as dist

        # a ONE-device topology is a replica slice: this engine's params,
        # KV pool and per-tick metadata are committed to that device, and
        # the process-global topology is left alone — N such engines share
        # the process, each ticking on its own thread
        # (serving/replica_set.py), so a global one would be whichever
        # replica was built last
        if topology is not None and topology.mesh.size > 1:
            dist.set_topology(topology)
        elif topology is None:
            topology = dist.peek_topology()
        self.topology = topology
        self.mesh = topology.mesh if topology is not None else None
        self.tp = (topology.tensor_parallel_size
                   if topology is not None else 1)
        self._device = (self.mesh.devices.flat[0]
                        if self.mesh is not None and self.mesh.size == 1
                        else None)

        # config-sourced knobs resolve BEFORE pool sizing: the resolved
        # kv_cache_dtype decides the per-page byte cost a kv_pool_bytes
        # budget divides by (kwarg > config > default, as for every
        # other v2 knob)
        if config is not None:
            from deepspeed_tpu.inference.config import \
                load_inference_config

            v2cfg = load_inference_config(config).v2
            pipeline = v2cfg.pipeline if pipeline is None else pipeline
            async_depth = (v2cfg.async_depth if async_depth is None
                           else async_depth)
            harvest_interval = (v2cfg.harvest_interval
                                if harvest_interval is None
                                else harvest_interval)
            speculation = (v2cfg.speculation if speculation is None
                           else speculation)
            kv_tiering = (v2cfg.kv_tiering if kv_tiering is None
                          else kv_tiering)
            prefix_cache = (v2cfg.prefix_cache if prefix_cache is None
                            else prefix_cache)
            kv_cache_dtype = (v2cfg.kv_cache_dtype
                              if kv_cache_dtype is None
                              else kv_cache_dtype)
            slo = (v2cfg.slo if slo is None else slo)
            trace_sample = (v2cfg.trace_sample if trace_sample is None
                            else trace_sample)
            control = v2cfg.control if control is None else control
        kv_cache_dtype = ("none" if kv_cache_dtype is None
                          else str(kv_cache_dtype))
        assert kv_cache_dtype in ("none", "int8", "fp8", "fp8_e4m3"), (
            f"kv_cache_dtype must be none|int8|fp8|fp8_e4m3, got "
            f"{kv_cache_dtype!r}")
        self.kv_cache_dtype = kv_cache_dtype

        self.page_size = int(page_size)
        self.pages_per_seq = pages_for(max_seq_len, self.page_size)
        if num_pages is None and kv_pool_bytes is not None:
            # byte-accounted sizing: probe the exact per-page device
            # cost (quantized pools count the 1-byte payload AND the
            # fp32 scale rows) and fit as many pages as the budget holds
            # — page 0 is the trash page, so >= 2 keeps one usable
            page_bytes = _paged_kv_page_bytes(
                model, mcfg, self.page_size, kv_cache_dtype)
            num_pages = max(2, int(kv_pool_bytes) // page_bytes)
        if num_pages is None:
            # full provisioning: every slot can reach max_seq_len. Callers
            # serving long-max_len traffic shrink this — memory then
            # scales with tokens in flight (admission backpressure).
            num_pages = 1 + max_seqs * self.pages_per_seq
        self.num_pages = int(num_pages)

        self._unroll_params = bool(getattr(mcfg, "scan_layers", False))
        self.cfg = dataclasses.replace(
            mcfg, decode=True, ragged_decode=False, paged_decode=True,
            max_cache_len=max_seq_len, scan_layers=False,
            kv_page_size=self.page_size, kv_num_pages=self.num_pages,
            tensor_parallel=self.tp > 1, kv_cache_dtype=kv_cache_dtype)
        self.model = type(model)(self.cfg)
        self.max_seqs = max_seqs
        self.max_seq_len = max_seq_len
        self.prefill_chunk = prefill_chunk
        self.T = max_seqs + prefill_chunk          # fused batch width
        self.decode_block_size = max(int(decode_block_size), 1)
        assert kv_reserve in ("on_demand", "worst_case"), kv_reserve
        self.kv_reserve = kv_reserve
        self.evictions = 0
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        # base key for position-keyed sampling (sampling.position_keys):
        # derived from the pristine engine rng BEFORE any split, so a
        # drawn token's key depends only on (engine seed, uid, position)
        # — never on how dispatches happened to be scheduled
        self._sample_base = jax.random.fold_in(self.rng, 0x5EED)

        self.pipeline = True if pipeline is None else bool(pipeline)
        self.async_depth = max(
            int(async_depth) if async_depth is not None else 2, 1)
        self.harvest_interval = max(
            int(harvest_interval) if harvest_interval is not None else 4,
            1)
        self.replica = "" if replica is None else str(replica)
        self.host_stats = HostStageStats(replica=self.replica)
        # substrate timers for the pipelined decode window (submitted/
        # completed counters + submit_wait brackets; serving_stages()
        # exposes the snapshot as ``pipeline_window``)
        self._pipe_timers = StageTimers(cat="serving")
        # per-request lifecycle latency (TTFT/TPOT/queue-wait/spill-
        # stall percentiles) — always on; independent of the tracer
        self.request_latency = RequestLatencyTracker(replica=self.replica)

        # -- SLO objectives + tail-based trace sampling --
        # All evaluation happens at reap time on the host — the traced
        # dispatch path never sees the registry or the sampler, so the
        # zero-new-compilations guarantee is structural, not incidental.
        from deepspeed_tpu.telemetry.slo import SLOSet, TailSampler

        if slo is None or slo is False or (isinstance(slo, (list, tuple))
                                           and not slo):
            self.slo = None
        elif isinstance(slo, SLOSet):
            self.slo = slo
        else:
            self.slo = SLOSet(list(slo))
        n = (int(trace_sample) if trace_sample is not None
             else trace.sample_n)
        self._tail_sampler = (TailSampler(n=n)
                              if (trace.sampling or n > 0) else None)
        if n > 0 and not trace.sampling:
            # an explicit engine/config N arms the tracer's sampling
            # mode the same way DSTPU_TRACE_SAMPLE does
            trace.configure(enabled=True, sampling=True, sample_n=n)
        # device-resident decode-loop state while the pipeline runs
        # ahead of the host (None <=> host state is authoritative)
        self._dev: Optional[Dict[str, Any]] = None

        # -- speculative decoding config (module docstring) --
        from deepspeed_tpu.inference.config import SpeculationConfig

        if speculation is None:
            speculation = SpeculationConfig()
        elif isinstance(speculation, str):
            speculation = SpeculationConfig(mode=speculation)
        elif isinstance(speculation, dict):
            speculation = SpeculationConfig(**speculation)
        self.spec_mode = speculation.mode
        self.spec_k = int(speculation.k)
        self.spec_ngram = int(speculation.ngram)
        self._spec_block_cache: Dict[bool, Any] = {}
        self._draft = None
        self._draft_params: Any = {}
        self._draft_cache: Any = {}
        self._draft_unroll = False
        self._draft_prefill = None
        # host tracker: draft KV coverage per slot (positions < value
        # hold correct draft K/V; reset on admit/evict/reap)
        self._draft_len = np.zeros((max_seqs,), np.int64)

        from deepspeed_tpu.inference.common import normalize_params

        params = normalize_params(
            model, params,
            plain_model=type(model)(dataclasses.replace(mcfg,
                                                        decode=False)))
        self._wq = quantize_weights
        self._wq_native = False
        if quantize_weights is not None:
            assert self.tp <= 1, (
                "quantize_weights does not compose with tensor-parallel "
                "serving yet — quantized leaves carry their own "
                "group-scale layout")
            from deepspeed_tpu.inference.quantization import \
                quantize_param_tree
            from deepspeed_tpu.parallel import tensor_parallel as tp_lib

            # "w8a8" (explicit opt-in — it quantizes ACTIVATIONS too, so
            # numerics differ from weight-only "int8") runs the NATIVE
            # path on models whose Dense layers consume quantized
            # kernels: int8 stays on the per-tick read path (decode is
            # weight-bandwidth-bound — tree-level dequant reads 2x the
            # bytes), dotted on the MXU's int8 path with dynamic
            # per-row activation scales
            if quantize_weights == "w8a8":
                assert getattr(type(model), "w8a8_native", False), (
                    f"quantize_weights='w8a8' needs a model whose Dense "
                    f"layers consume w8a8 kernels natively (the Llama "
                    f"family: llama/mistral/qwen2); "
                    f"{type(model).__name__} does not — use weight-only "
                    f"'int8' instead")
                self._wq_native = True
                self.cfg = dataclasses.replace(self.cfg,
                                               weight_quant="w8a8")
                self.model = type(model)(self.cfg)
                if self._unroll_params:
                    # unroll scan-stacked [L, ...] kernels NOW: the
                    # per-channel w8a8 format is 2-D-kernel only, so a
                    # stacked tree would silently fall back to the
                    # dequant path for every block kernel
                    from deepspeed_tpu.inference.common import \
                        unroll_scan_params

                    params = (
                        {"params": unroll_scan_params(params["params"])}
                        if isinstance(params, dict) and "params" in params
                        else unroll_scan_params(params))
                    self._unroll_params = False
            # unbox flax Partitioned metadata FIRST: the quantizer's
            # leaf-name check reads path tails, which inside a metadata
            # box are the box's own keys — boxed trees would silently
            # pass through unquantized
            if tp_lib.has_partitioning(params):
                params = tp_lib.unbox_params(params)
            params, b0, b1 = quantize_param_tree(params, quantize_weights)
            params = jax.device_put(params)
            log_dist(f"ragged engine weights -> {quantize_weights}"
                     f"{' (native int8 dots)' if self._wq_native else ''}: "
                     f"{b0 / 2**20:.1f} MiB -> {b1 / 2**20:.1f} MiB "
                     f"({b0 / max(b1, 1):.2f}x)", ranks=[0])
        self.params = self._place_params(params)

        self.allocator = PageAllocator(self.num_pages, self.page_size)
        self.page_table = np.full((max_seqs, self.pages_per_seq), -1,
                                  np.int32)
        self.cache = self._init_cache()
        if self.spec_mode == "draft":
            if draft_model is None:
                raise ValueError(
                    "speculation.mode='draft' needs a draft model: pass "
                    "draft_model=<small same-vocab llama-family module> "
                    "and draft_params=... (the config's "
                    "speculation.draft_model preset name is for CLIs to "
                    "construct one)")
            assert self.tp <= 1, (
                "draft-model speculation does not compose with "
                "tensor-parallel serving yet")
            dmcfg = getattr(draft_model, "config", None)
            assert (dataclasses.is_dataclass(dmcfg) and
                    hasattr(dmcfg, "rope_theta") and
                    hasattr(dmcfg, "paged_decode")), (
                "draft model must be a llama-family model-zoo module "
                "(the ragged paged decode path's requirement)")
            assert dmcfg.vocab_size == mcfg.vocab_size, (
                f"draft vocab {dmcfg.vocab_size} != target vocab "
                f"{mcfg.vocab_size} — speculative verify compares token "
                "ids, the models must share a tokenizer")
            self._draft_unroll = bool(getattr(dmcfg, "scan_layers",
                                              False))
            # the draft pool defaults to the target pool's storage
            # format — self-draft speculation gets the same capacity
            # win unless the caller overrides draft_kv_cache_dtype
            draft_fmt = (self.kv_cache_dtype
                         if draft_kv_cache_dtype is None
                         else str(draft_kv_cache_dtype))
            assert draft_fmt in ("none", "int8", "fp8", "fp8_e4m3"), (
                f"draft_kv_cache_dtype must be none|int8|fp8|fp8_e4m3, "
                f"got {draft_fmt!r}")
            self.draft_kv_cache_dtype = draft_fmt
            self._draft_cfg = dataclasses.replace(
                dmcfg, decode=True, ragged_decode=False,
                paged_decode=True, max_cache_len=max_seq_len,
                scan_layers=False, kv_page_size=self.page_size,
                kv_num_pages=self.num_pages, tensor_parallel=False,
                kv_cache_dtype=draft_fmt)
            self._draft = type(draft_model)(self._draft_cfg)
            from deepspeed_tpu.parallel import tensor_parallel as tp_lib
            dparams = normalize_params(
                draft_model, draft_params,
                plain_model=type(draft_model)(dataclasses.replace(
                    dmcfg, decode=False)))
            if tp_lib.has_partitioning(dparams):
                dparams = tp_lib.unbox_params(dparams)
            self._draft_params = jax.device_put(dparams, self._device)
            self._draft_cache = self._init_cache(self._draft)
        self._uid = itertools.count()
        self.waiting: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_seqs
        self.finished: List[Request] = []
        self._unclaimed: Dict[int, np.ndarray] = {}
        self._step_fn = None
        self._decode_block_cache: Dict[bool, Any] = {}
        # (device counts, span attributes) of dispatched expert-layer
        # programs whose counts no fetch has brought to the host yet
        self._moe_pending: List[Tuple[Any, Dict[str, Any]]] = []
        self._last_tokens = np.zeros((max_seqs,), np.int32)
        # streaming cursor: generated-token count already reported per
        # uid (stream_deltas); cancels counts cancellations at any stage
        self._stream_cursor: Dict[int, int] = {}
        self.cancels = 0
        # disaggregated serving: sessions whose prefill (+ first token)
        # finished and which now wait for the router to pull them via
        # export_handoff — out of slots and out of the waiting queue
        self._handoff_ready: List[Request] = []
        self.handoffs = 0              # sessions exported to a decoder
        self.handoff_folds = 0         # handoffs degraded to re-prefill

        # -- tiered KV spill store (HBM -> host RAM -> NVMe) --
        from deepspeed_tpu.inference.config import KVTieringConfig

        if kv_tiering is None:
            kv_tiering = KVTieringConfig()
        elif isinstance(kv_tiering, dict):
            kv_tiering = KVTieringConfig(**{"enabled": True, **kv_tiering})
        self._tier_cfg = kv_tiering
        self.tiering = None
        self._tier_gather = None       # jitted fixed-shape page gather
        self._tier_scatter = None      # jitted fixed-shape page scatter
        self._lc = None                # lazy LongContextDriver
        # queued spilled sequences prefetched ahead of a possible reap
        # (config field, online-tunable via the kv.prefetch_lookahead
        # knob — was a hardcoded islice(waiting, 8))
        self.prefetch_lookahead = max(
            int(getattr(kv_tiering, "prefetch_lookahead", 8)), 1)
        self._sched_seq = 0            # step counter for victim coldness
        self._last_sched = np.zeros((max_seqs,), np.int64)
        self.spills = 0                # sessions spilled to the tiers
        self.restores = 0              # sessions restored bit-identically
        if kv_tiering.enabled:
            assert self.kv_reserve == "on_demand", (
                "kv_tiering requires kv_reserve='on_demand' — the spill "
                "tiers ARE the on-demand model's overflow story; a "
                "worst-case reservation could never admit what tiering "
                "holds")
            from deepspeed_tpu.inference.kv_tiering import TieredKVStore

            leaves, self._cache_treedef = jax.tree_util.tree_flatten(
                self.cache)
            assert all(leaf.shape[0] == self.num_pages
                       for leaf in leaves), (
                "every paged-KV cache leaf must lead with the page dim")
            self.tiering = TieredKVStore(
                page_shapes=[leaf.shape[1:] for leaf in leaves],
                page_dtypes=[np.dtype(leaf.dtype) for leaf in leaves],
                pages_per_seq=self.pages_per_seq,
                host_pages=kv_tiering.host_pages,
                nvme_pages=kv_tiering.nvme_pages,
                nvme_dir=kv_tiering.nvme_dir,
                use_odirect=kv_tiering.use_odirect,
                prefetch=kv_tiering.prefetch,
                verify=kv_tiering.verify,
                checksum=kv_tiering.checksum,
                max_reread=kv_tiering.max_reread,
                nvme_fail_threshold=kv_tiering.nvme_fail_threshold,
                probe_every=kv_tiering.probe_every)
        # -- cross-request prefix cache over the paged pool --
        from deepspeed_tpu.inference.config import PrefixCacheConfig

        if prefix_cache is None:
            prefix_cache = PrefixCacheConfig()
        elif isinstance(prefix_cache, bool):
            prefix_cache = PrefixCacheConfig(enabled=prefix_cache)
        elif isinstance(prefix_cache, dict):
            prefix_cache = PrefixCacheConfig(
                **{"enabled": True, **prefix_cache})
        self._pfx_cfg = prefix_cache
        self._pfx: Optional[PrefixCacheIndex] = None
        self._cow_jit = None           # jitted fixed-shape page copy
        if prefix_cache.enabled:
            self._pfx = PrefixCacheIndex(
                self.allocator, self.page_size,
                max_entries=prefix_cache.max_index_entries,
                min_match_pages=prefix_cache.min_match_pages)
            if self.tiering is not None:
                # under pool pressure, cold single-ref prefix pages
                # demote into the tier store keyed by prefix hash (one
                # restore serves every waiter) instead of being dropped
                self._pfx.demote = self._pfx_demote
                self._pfx.drop_spilled = self.tiering.drop
        tier_note = ""
        if self.tiering is not None:
            tier_note = (f" kv_tiering=host:{kv_tiering.host_pages}"
                         f"+nvme:{kv_tiering.nvme_pages}p")
        if self._pfx is not None:
            tier_note += (f" prefix_cache=max:"
                          f"{prefix_cache.max_index_entries}"
                          f"/min:{prefix_cache.min_match_pages}p")
        log_dist(
            f"RaggedInferenceEngineV2: max_seqs={max_seqs} "
            f"max_seq_len={max_seq_len} prefill_chunk={prefill_chunk} "
            f"pages={self.num_pages}x{self.page_size} tp={self.tp} "
            f"decode_block={self.decode_block_size} "
            f"pipeline={self.pipeline} depth={self.async_depth} "
            f"harvest={self.harvest_interval} "
            f"spec={self.spec_mode}"
            f"{f'/k={self.spec_k}' if self.spec_mode != 'off' else ''}"
            f"{tier_note} "
            f"(paged KV, fused SplitFuse step)", ranks=[0])

        # -- closed-loop control plane (deepspeed_tpu.control) --
        # Ticks on this host loop (step() counts engine steps); no
        # thread of its own.  DSTPU_CONTROL=0 disarms regardless of
        # config, leaving the structurally pre-control engine.
        from deepspeed_tpu.inference.config import ControlConfig

        if control is None:
            control = ControlConfig()
        elif isinstance(control, bool):
            control = ControlConfig(enabled=control)
        elif isinstance(control, dict):
            control = ControlConfig(**{"enabled": True, **control})
        self._control_cfg = control
        self._controller = None
        self._control_steps = 0
        if control.enabled:
            from deepspeed_tpu.control import (Controller, control_enabled,
                                               engine_signal_feed,
                                               load_profile, prefetch_rule)
            if control_enabled():
                knobs = self.knob_registry()
                prof = load_profile(control.profile)
                if prof is not None:
                    # profile seeding runs pre-warmup, so recompiling
                    # knobs (decode_block, spec k) are still fair game
                    applied = knobs.apply_profile(prof.knobs)
                    if applied:
                        log_dist(
                            f"control plane seeded from host profile "
                            f"{prof.key}: {applied}", ranks=[0])
                rules = []
                if self.tiering is not None:
                    rules.append(prefetch_rule())
                self._controller = Controller(
                    knobs, engine_signal_feed(self),
                    objective=control.objective,
                    settle=control.settle,
                    hysteresis=control.hysteresis,
                    cooldown=control.cooldown,
                    guard_window=control.guard_window,
                    guard_reverts=control.guard_reverts,
                    freeze=control.freeze, smooth=control.smooth,
                    rules=rules)

    # -- parameter / cache placement (TP) --------------------------------

    def _place_params(self, params):
        """TP-shard (AutoTP name rules / flax metadata) over the `tensor`
        mesh axis, mirroring the v1 engine; replicate otherwise."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu.parallel import tensor_parallel as tp_lib

        if self.tp <= 1:
            if tp_lib.has_partitioning(params):
                params = tp_lib.unbox_params(params)
            if self._device is not None:
                params = jax.device_put(params, self._device)
            return params
        if tp_lib.has_partitioning(params):
            specs = tp_lib.extract_partition_specs(
                {"params": params}, self.mesh.axis_names)["params"]
            params = tp_lib.unbox_params(params)
            # training-oriented metadata (e.g. a MoE bank's `expert` axis
            # with no `tensor` entries) doesn't shard a TP serving mesh —
            # fall back to AutoTP name rules
            if not any("tensor" in tuple(s)
                       for s in jax.tree_util.tree_leaves(
                           specs, is_leaf=lambda x: isinstance(x, P))):
                specs = None
        else:
            specs = None
        if specs is None:
            specs = tp_lib.auto_tp_specs(params, self.tp)
            log_dist("ragged engine AutoTP: inferred tensor-parallel "
                     "sharding from parameter names", ranks=[0])
        self._param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.tree_util.tree_map(
            lambda x, sh: jax.device_put(jnp.asarray(x), sh), params,
            self._param_shardings)

    def _cache_sharding(self, leaf_shape):
        """KV page pools shard their combined-head dim over `tensor`
        (reference v2 KV sharding: heads split over the TP group); the
        quantized pools' [P, page, 2Hkv] scale buffers shard the same
        head dim.  A one-device replica slice pins them to its device;
        ``None`` leaves the default placement."""
        from jax.sharding import (NamedSharding, PartitionSpec as P,
                                  SingleDeviceSharding)

        if self.tp <= 1:
            return (SingleDeviceSharding(self._device)
                    if self._device is not None else None)
        if len(leaf_shape) == 4:
            return NamedSharding(self.mesh, P(None, None, "tensor", None))
        if len(leaf_shape) == 3:
            return NamedSharding(self.mesh, P(None, None, "tensor"))
        return None

    # -- request API ----------------------------------------------------

    def set_replica(self, replica: str) -> None:
        """Assign the scale-out metric-label identity after
        construction (``ReplicaSet`` labels engines built without
        one); re-labels the stage/latency emitters in place."""
        self.replica = str(replica)
        self.host_stats.set_replica(self.replica)
        self.request_latency.set_replica(self.replica)

    def validate_request(self, prompt, max_new_tokens: int = 64) -> None:
        """The submit-time schedulability checks, without enqueuing —
        raises ``ValueError`` for a request that could never run on
        THIS engine.  The scale-out router calls this before accepting
        a request (its typed rejection wraps the message), so loud
        rejection happens at the front door rather than deep inside a
        replica's feed queue."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(
                "max_new_tokens must be >= 1 (prefill seeds the first "
                "token)")
        total = prompt.size + max_new
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) = "
                f"{total} exceeds the engine token budget "
                f"max_seq_len={self.max_seq_len} — the request can never "
                "be scheduled; shorten the prompt or raise max_seq_len")
        if self.tiering is None:
            if self.allocator.pages_for(total) > self.num_pages - 1:
                raise ValueError(
                    f"request needs {self.allocator.pages_for(total)} KV "
                    f"pages but the engine owns {self.num_pages - 1} "
                    "usable pages — even after evicting every other "
                    "sequence it could never be scheduled; raise "
                    "num_pages")
        else:
            # two separate bounds, named separately in the rejection:
            # (1) with long_context, the RESIDENT-WINDOW need (sinks +
            # recent window + staging slack) must fit HBM — without it
            # the working-set bound stays an admission-time check, so
            # tiering keeps accepting requests beyond HBM whose others
            # spill (max_new_tokens is a budget, not a promise); (2)
            # the COMBINED-TIER total must fit HBM + host + NVMe.
            total_pages = self.allocator.pages_for(total)
            usable = self.num_pages - 1
            lc = bool(self._tier_cfg.long_context)
            if lc:
                resident_need = min(total_pages,
                                    self._lc_resident_pages())
                if resident_need > usable:
                    raise ValueError(
                        f"request needs {resident_need} HBM-resident KV "
                        "pages (the partial-residency window: "
                        "sink_pages + window_pages + chunk_pages + 1) "
                        f"but the HBM tier owns {usable} usable pages — "
                        "raise num_pages or shrink the kv_tiering "
                        "sink_pages/window_pages/chunk_pages knobs")
            cap = usable + self.tiering.budget_pages
            if total_pages > cap:
                raise ValueError(
                    f"request needs {total_pages} KV pages in total but "
                    f"the combined tiers — HBM ({usable} usable) + host "
                    f"({self.tiering.host_budget}) + NVMe "
                    f"({self.tiering.nvme_budget}) — hold only {cap} "
                    "— it could never be scheduled; raise num_pages or "
                    "the kv_tiering host_pages/nvme_pages budgets")

    def put_request(self, prompt, **kw) -> int:
        """Queue a request; raises ``ValueError`` AT SUBMIT TIME for a
        request that could never be scheduled (a prompt + budget beyond
        ``max_seq_len``, or needing more KV pages than the whole pool
        holds even after evicting every other sequence) — admitting one
        would deadlock the FIFO queue behind an unschedulable head.
        (``ValueError``, not ``assert``: these guard USER input and must
        stay loud under ``python -O``.)"""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        max_new = int(kw.get("max_new_tokens", 64))
        self.validate_request(prompt, max_new)
        req = Request(uid=next(self._uid), prompt=prompt, **kw)
        if (self.tiering is not None and self._tier_cfg.long_context
                and self.allocator.pages_for(prompt.size + max_new)
                > self.num_pages - 1):
            # the full KV cannot be device-resident: decode under the
            # windowed partial-residency policy (LongContextDriver)
            req.lc = True
        self.waiting.append(req)
        self.request_latency.on_submit(req.uid, int(prompt.size))
        if trace.enabled:
            trace.event("request_submit", cat="request", uid=req.uid,
                        prompt_len=int(prompt.size), max_new=max_new)
        return req.uid

    def get_outputs(self) -> List[Tuple[int, np.ndarray]]:
        out = list(self._unclaimed.items())
        self._unclaimed = {}
        out += [(r.uid, np.concatenate([r.prompt,
                                        np.asarray(r.generated, np.int32)]))
                for r in self.finished]
        self.finished = []
        for uid, _ in out:
            self._stream_cursor.pop(uid, None)
        return out

    def stream_deltas(self) -> List[Tuple[int, List[int], int, bool]]:
        """Incremental token harvest for streaming front ends: one
        ``(uid, new_tokens, total_generated, done)`` tuple per request
        whose generated-token count grew since the last call, plus
        every newly finished request (even with no fresh tokens).
        Tokens appear here exactly when they fold into host request
        state — HARVEST granularity, the honest streaming grain under
        the deferred-harvest pipeline.  Cursors are engine-side;
        callers that re-route across replicas de-duplicate with the
        cumulative ``total_generated`` (a re-routed request replays
        its tokens from zero on the new engine).

        Call BEFORE :meth:`get_outputs` in the same tick — collecting
        an output clears its cursor."""
        out: List[Tuple[int, List[int], int, bool]] = []
        cur = self._stream_cursor
        live = [r for r in self.slots if r is not None]
        for r in itertools.chain(live, self.waiting, self._handoff_ready):
            n = len(r.generated)
            seen = cur.get(r.uid, 0)
            if n > seen:
                out.append((r.uid, [int(t) for t in r.generated[seen:]],
                            n, False))
                cur[r.uid] = n
        for r in self.finished:
            n = len(r.generated)
            seen = cur.pop(r.uid, 0)
            out.append((r.uid, [int(t) for t in r.generated[seen:]],
                        n, True))
        return out

    def cancel(self, uid: int) -> Optional[str]:
        """Cancel one request at ANY lifecycle stage, releasing every
        resource it holds: slot + pool pages (mid-prefill or
        mid-decode, including inside a pipelined decode carry), tiered
        spill payloads and their shared-prefix spill-holds (parked
        requests), LC middle-group parkings, and per-slot draft state.
        ``audit_kv_sharing()`` stays clean across any interleaving —
        the front door's client-disconnect path depends on it.

        Returns the stage the request was cancelled at (``"queued"`` /
        ``"spilled"`` / ``"prefill"`` / ``"decode"`` / ``"lc"`` /
        ``"finished"``) or ``None`` for an unknown uid (never
        submitted, or already collected)."""
        stage: Optional[str] = None
        # fold an active pipelined carry first: the target may be
        # mid-decode inside it, and teardown re-anchors host state so
        # the slot release below is authoritative (the target may
        # FINISH during this harvest — then it lands in ``finished``)
        dv = self._dev
        if dv is not None and any(r.uid == uid for r in dv["reqs"]):
            self._pipeline_harvest(teardown=True)
        # parked in the waiting queue: never admitted, an evicted
        # continuation, or spilled out to the tiers
        for r in list(self.waiting):
            if r.uid != uid:
                continue
            self.waiting.remove(r)
            if r.spilled is not None:
                # release the spill-holds pinning shared prefix pages
                # resident, then the tiered payload itself (the same
                # cleanup export_parked runs when folding a session)
                for p in r.spilled.get("shared_pages", ()):
                    self.allocator.decref(p)
                if self.tiering is not None:
                    self.tiering.drop(r.uid)
                stage = "spilled"
            else:
                stage = "queued"
            self._drop_lc_parked(r)
            break
        if stage is None:
            # parked for a prefill->decode handoff the router never
            # collected: release like a waiting spilled session
            for r in list(self._handoff_ready):
                if r.uid != uid:
                    continue
                self._handoff_ready.remove(r)
                if r.spilled is not None:
                    for p in r.spilled.get("shared_pages", ()):
                        self.allocator.decref(p)
                    if self.tiering is not None:
                        self.tiering.drop(r.uid)
                stage = "handoff"
                break
        if stage is None:
            # resident in a slot (prefill or decode phase; LC sequences
            # tick outside the fused batch but park in slots the same)
            for i, r in enumerate(self.slots):
                if r is None or r.uid != uid:
                    continue
                stage = ("lc" if r.lc
                         else "prefill" if r.prefill_done < r.ctx_len
                         else "decode")
                self._drop_lc_parked(r)
                self.allocator.free(i)
                self.page_table[i, :] = -1
                self.slots[i] = None
                self._draft_len[i] = 0
                break
        if stage is None:
            # reaped but not yet collected: drop the pending output
            for r in list(self.finished):
                if r.uid == uid:
                    self.finished.remove(r)
                    stage = "finished"
                    break
        if stage is None and uid in self._unclaimed:
            del self._unclaimed[uid]
            stage = "finished"
        if stage is None:
            return None
        self.cancels += 1
        self._stream_cursor.pop(uid, None)
        self.request_latency.on_cancel(uid)
        if trace.enabled:
            trace.event("request_cancel", cat="request", uid=int(uid),
                        stage=stage)
        return stage

    def _drop_lc_parked(self, r: Request) -> None:
        """Release a long-context request's parked middle page groups
        (the tier keys ``_reap`` would drop at finish)."""
        if self.tiering is not None:
            for g in range(r.lc_parked):
                self.tiering.drop(f"mid-{r.uid}-{g}")
        r.lc_parked = 0

    def has_work(self) -> bool:
        return bool(self.waiting) or any(s is not None for s in self.slots)

    def sync(self) -> int:
        """Fold any pipelined in-flight device work back into host
        request state (deferred-harvest flush); returns the tokens
        folded.  No-op when the pipeline is off or idle.  Callers that
        read ``slots[*].generated`` / ``finished`` between ``step()``
        calls (benchmark boundaries, draining shutdown) call this
        first."""
        if self._dev is None:
            return 0
        return self._pipeline_harvest()

    def drain(self) -> Dict[int, np.ndarray]:
        """Run until every queued/resident request finishes; returns
        ``{uid: tokens}`` for everything reaped along the way (the
        replica-shutdown half of the engine handle — ``close()``
        releases resources after)."""
        outs: Dict[int, np.ndarray] = {}
        # nobody is coming to collect a pending handoff during a drain:
        # finish those sessions locally through the normal spilled /
        # continuation re-admission path
        for r in self._handoff_ready:
            r.handoff = False
            self.waiting.append(r)
        self._handoff_ready = []
        while self.has_work():
            self.step()
            outs.update(self.get_outputs())
        self.sync()
        outs.update(self.get_outputs())
        return outs

    # -- elastic shrink: parked-session handoff --------------------------

    def export_parked(self) -> List[Dict[str, Any]]:
        """Pop every PARKED session (the waiting queue: not yet
        admitted, spilled out of the pool, or queued as a re-prefill
        continuation) and return portable session blobs for
        :meth:`import_parked` on another replica.  A spilled session's
        private pages travel in SPILL FORMAT (packed bytes + the
        spill-time digests via ``TieredKVStore.export_spilled``), so
        the receiver's restore verifies them end-to-end.  Shared-prefix
        pages are rows in THIS engine's HBM and cannot travel — a
        session holding any folds to a re-prefill continuation
        (``ctx = prompt + generated``), which is output-identical under
        greedy decode, just re-paying its prefill."""
        sessions: List[Dict[str, Any]] = []
        while self.waiting:
            r = self.waiting.popleft()
            blob = self._session_blob(r)
            if trace.enabled:
                trace.event("request_export", cat="request", uid=r.uid,
                            spilled=blob["spill"] is not None)
            sessions.append(blob)
        # pending prefill->decode handoffs the router never pulled ride
        # the same retirement: they are parked sessions like any other
        for r in self._handoff_ready:
            blob = self._session_blob(r)
            if trace.enabled:
                trace.event("request_export", cat="request", uid=r.uid,
                            spilled=blob["spill"] is not None)
            sessions.append(blob)
        self._handoff_ready = []
        return sessions

    def _session_blob(self, r: Request) -> Dict[str, Any]:
        """Portable session blob for ``import_parked`` on another
        replica (shared by :meth:`export_parked` and
        :meth:`export_handoff`).  A spilled session's private pages
        travel in spill format via ``TieredKVStore.export_spilled``;
        one pinning shared-prefix pages (rows in THIS engine's HBM —
        they cannot travel) folds to a re-prefill continuation."""
        blob: Dict[str, Any] = {
            "uid": int(r.uid),
            "prompt": np.asarray(r.prompt, np.int32),
            "max_new_tokens": int(r.max_new_tokens),
            "eos_token_id": r.eos_token_id,
            "do_sample": bool(r.do_sample),
            "temperature": float(r.temperature),
            "top_k": int(r.top_k),
            "top_p": float(r.top_p),
            "generated": [int(t) for t in r.generated],
            "ctx": (None if r.ctx is None
                    else np.asarray(r.ctx, np.int32)),
            "prefill_done": int(r.prefill_done),
            "spill": None}
        if r.spilled is not None:
            shared = [int(p) for p in r.spilled.get("shared_pages",
                                                    ())]
            n_priv = int(r.spilled.get("n_pages", 0))
            holds = (self.tiering is not None
                     and self.tiering.holds(r.uid))
            if shared or (n_priv > 0 and not holds):
                # fold to a re-prefill continuation; release the
                # spill-holds and the orphaned payload
                for p in shared:
                    self.allocator.decref(p)
                if self.tiering is not None:
                    self.tiering.drop(r.uid)
                blob["ctx"] = np.concatenate(
                    [r.prompt, np.asarray(r.generated, np.int32)])
                blob["prefill_done"] = 0
            else:
                blob["spill"] = {
                    "last_tok": int(r.spilled["last_tok"]),
                    "live_tokens": int(r.spilled["live_tokens"]),
                    "payload": (self.tiering.export_spilled(r.uid)
                                if n_priv > 0 else None)}
        return blob

    def import_parked(self, sessions: List[Dict[str, Any]]) -> List[int]:
        """Receiving half of the handoff: install each exported session
        as a local waiting :class:`Request` under a FRESH uid (uids are
        per-engine) and park its spill payload in the local tier store
        with the donor's digests.  Returns the new uids in input order
        — the router re-keys its ledger with them.  A payload the local
        tiers can't hold folds to a re-prefill continuation instead of
        being dropped."""
        new_uids: List[int] = []
        for s in sessions:
            req = Request(uid=next(self._uid),
                          prompt=np.asarray(s["prompt"], np.int32),
                          max_new_tokens=int(s.get("max_new_tokens", 64)),
                          eos_token_id=s.get("eos_token_id"),
                          do_sample=bool(s.get("do_sample", False)),
                          temperature=float(s.get("temperature", 1.0)),
                          top_k=int(s.get("top_k", 0)),
                          top_p=float(s.get("top_p", 1.0)))
            req.generated = [int(t) for t in s.get("generated", ())]
            ctx = s.get("ctx")
            req.ctx = None if ctx is None else np.asarray(ctx, np.int32)
            req.prefill_done = int(s.get("prefill_done", 0))
            sp = s.get("spill")
            if sp is not None:
                payload = sp.get("payload")
                installed = payload is None
                if payload is not None and self.tiering is not None:
                    try:
                        self.tiering.import_spilled(req.uid, payload)
                        installed = True
                    except (ValueError, RuntimeError):
                        installed = False
                if installed:
                    req.spilled = {
                        "last_tok": int(sp["last_tok"]),
                        "n_pages": (int(payload["n_pages"])
                                    if payload is not None else 0),
                        "live_tokens": int(sp["live_tokens"]),
                        "shared_pages": []}
                else:
                    req.ctx = np.concatenate(
                        [req.prompt,
                         np.asarray(req.generated, np.int32)])
                    req.prefill_done = 0
            self.waiting.append(req)
            self.request_latency.on_submit(req.uid, int(req.prompt.size))
            if trace.enabled:
                trace.event("request_import", cat="request", uid=req.uid,
                            donor_uid=int(s.get("uid", -1)),
                            spilled=req.spilled is not None)
            new_uids.append(req.uid)
        return new_uids

    # -- disaggregated serving: prefill -> decode handoff ----------------

    def export_handoff(self) -> List[Dict[str, Any]]:
        """Pop every session parked by :meth:`_handoff_sweep` (prefill
        + first token done, KV spilled to the tiers or folded to a
        re-prefill continuation) as portable blobs for
        :meth:`import_handoff` on a decode-role replica.  The wire
        format is exactly :meth:`export_parked`'s — the receiver admits
        the session through the normal spilled-request re-admission
        path, so greedy outputs stay bit-identical to a fused tick."""
        sessions: List[Dict[str, Any]] = []
        for r in self._handoff_ready:
            blob = self._session_blob(r)
            self.handoffs += 1
            self._stream_cursor.pop(r.uid, None)
            self.request_latency.on_handoff_out(r.uid)
            if trace.enabled:
                trace.event("request_handoff", cat="request", uid=r.uid,
                            spilled=blob["spill"] is not None,
                            generated=len(blob["generated"]))
            sessions.append(blob)
        self._handoff_ready = []
        return sessions

    def import_handoff(self, sessions: List[Dict[str, Any]],
                       export_t: Optional[float] = None) -> List[int]:
        """Decode-role half of the handoff: install the exported
        sessions via :meth:`import_parked` (fresh uids, payloads parked
        in the local tiers with the DONOR's digests — the restore
        verifies end-to-end) and stamp the export->import stall onto
        each request's latency record.  The ``handoff.import`` fault
        site fires per session before installation; a ``bitflip``
        directive corrupts the wire payload, which the digest-verified
        restore must catch (re-read, then quarantine + re-prefill)."""
        for s in sessions:
            sp = s.get("spill")
            payload = None if sp is None else sp.get("payload")
            d = faults.hook("handoff.import", uid=int(s.get("uid", -1)))
            if (d is not None and d[0] == "bitflip"
                    and payload is not None):
                buf = np.frombuffer(bytearray(payload["payload"]),
                                    np.uint8)
                faults.apply_bitflip(buf, d[1])
                payload["payload"] = buf.tobytes()
        new_uids = self.import_parked(sessions)
        if export_t is not None:
            stall = max(time.perf_counter() - float(export_t), 0.0)
            for uid in new_uids:
                self.request_latency.on_handoff_stall(uid, stall)
        return new_uids

    def knob_registry(self):
        """The engine's typed knob surface for the control plane
        (:class:`~deepspeed_tpu.control.knobs.KnobRegistry`).

        Online-safe knobs: ``harvest_interval`` is read fresh each
        pipelined step; ``async_depth`` re-sizes the live decode window
        in place (the substrate back-pressures on the next submit).
        ``decode_block_size`` and ``spec_k`` are baked into compiled
        block shapes, so they carry ``recompiles=True`` — reachable only
        by the offline sweep / profile seeding, never the online policy
        (the zero-new-compilations contract).  With tiering on, the
        tier store's prefetch toggle and IO-window depths ride along
        under ``kv.*``."""
        from deepspeed_tpu.control.knobs import Knob, KnobRegistry

        reg = KnobRegistry()

        def _set_harvest(v):
            self.harvest_interval = max(int(v), 1)

        def _set_depth(v):
            self.async_depth = max(int(v), 1)
            if self._dev is not None:
                self._dev["window"].depth = self.async_depth

        def _set_block(v):
            self.decode_block_size = max(int(v), 1)
            self._decode_block_cache.clear()

        reg.register(Knob(
            "engine.harvest_interval",
            lambda: self.harvest_interval, _set_harvest,
            lo=1, hi=16, step=1, kind="int",
            doc="pipelined decode blocks between token harvests"))
        reg.register(Knob(
            "engine.async_depth",
            lambda: self.async_depth, _set_depth,
            lo=1, hi=8, step=1, kind="int",
            doc="in-flight decode blocks in the pipeline window"))
        reg.register(Knob(
            "engine.decode_block_size",
            lambda: self.decode_block_size, _set_block,
            lo=1, hi=16, step=1, kind="int", recompiles=True,
            doc="device ticks per decode block (compiled shape)"))
        if self.spec_mode != "off":
            def _set_spec_k(v):
                self.spec_k = max(int(v), 1)
                self._spec_block_cache.clear()

            reg.register(Knob(
                "engine.spec_k", lambda: self.spec_k, _set_spec_k,
                lo=1, hi=8, step=1, kind="int", recompiles=True,
                doc="speculative draft length (compiled shape)"))
        if self.tiering is not None:
            t = self.tiering

            def _set_prefetch(v):
                t.prefetch_enabled = bool(v) and t.nvme_budget > 0

            def _set_wdepth(v):
                t._writes.depth = max(int(v), 1)

            def _set_rdepth(v):
                t._reads.depth = max(int(v), 1)

            reg.register(Knob(
                "kv.prefetch", lambda: t.prefetch_enabled,
                _set_prefetch, kind="bool",
                doc="NVMe read-ahead on tier restore"))
            reg.register(Knob(
                "kv.write_depth", lambda: t._writes.depth, _set_wdepth,
                lo=1, hi=8, step=1, kind="int",
                doc="bounded spill write-back window depth"))
            reg.register(Knob(
                "kv.read_depth", lambda: t._reads.depth, _set_rdepth,
                lo=1, hi=8, step=1, kind="int",
                doc="bounded restore read-ahead window depth"))

            def _set_lookahead(v):
                self.prefetch_lookahead = max(int(v), 1)

            def _set_window(v):
                self._tier_cfg.window_pages = max(int(v), 1)

            reg.register(Knob(
                "kv.prefetch_lookahead",
                lambda: self.prefetch_lookahead, _set_lookahead,
                lo=1, hi=64, step=1, kind="int",
                doc="queued spilled sequences prefetched ahead of reap"))
            reg.register(Knob(
                "kv.window_pages",
                lambda: int(self._tier_cfg.window_pages), _set_window,
                lo=1, hi=64, step=1, kind="int",
                doc="recent HBM-resident pages per partially-resident "
                    "sequence (long-context residency window)"))
        return reg

    def serving_stages(self) -> Dict[str, Any]:
        """Per-dispatch host-path breakdown + ``host_bound_fraction``
        (see :class:`~deepspeed_tpu.inference.common.HostStageStats`);
        with tiering on, the tier store's flat stats ride along as a
        ``kv_tiering`` sub-dict (``MonitorMaster`` flattens it to
        ``Serving/kv_tiering/<name>`` series)."""
        out = self.host_stats.serving_stages()
        if self.tiering is not None:
            out["kv_tiering"] = self.tiering.stats()
        if self._pfx is not None:
            st = self.host_stats
            pc = self._pfx.stats()
            pc.update(hit_requests=st.prefix_hits,
                      miss_requests=st.prefix_misses,
                      hit_tokens=st.prefix_hit_tokens,
                      cow_copies=st.prefix_cow_copies)
            out["prefix_cache"] = pc
        from deepspeed_tpu.inference.paged import (kv_dequant_path,
                                                   paged_attention_path)

        head_dim = int(getattr(self.cfg, "head_dim", 0))
        if self.kv_cache_dtype != "none":
            from deepspeed_tpu.inference.common import kv_quant_block

            route = kv_dequant_path(head_dim)
            out["kv_quant"] = kv_quant_block(
                self.cache, self.kv_cache_dtype, route, self.num_pages)
        else:
            route = paged_attention_path(head_dim)
        # the attention read route this backend gives this pool: a Pallas
        # kernel, or the XLA twin it stands in for (ops/routes.py)
        out["kernels"] = {"paged_attention": route}
        st = self.host_stats
        if st.moe_rows:
            from deepspeed_tpu.moe.sharded_moe import GROUPED_MATMUL
            from deepspeed_tpu.ops import routes
            # its two keys, as the trace of this process's programs left them
            out["kernels"].update(routes.taken(prefix=GROUPED_MATMUL))
            out["moe"] = {"rows": st.moe_rows,
                          "experts_hit": st.moe_experts_hit,
                          "load_max": st.moe_load_max}
        # pool pressure: the scale-out router's least-pressure policy
        # reads this (waiting queue + page occupancy, both plain host
        # ints — no device sync)
        usable = max(self.num_pages - 1, 1)
        in_use = usable - self.allocator.free_pages
        out["pool"] = {
            "num_pages": self.num_pages,
            "pages_in_use": int(in_use),
            "waiting_requests": len(self.waiting),
            "pressure": round(in_use / usable
                              + len(self.waiting), 4)}
        if self.handoffs or self.handoff_folds or self._handoff_ready:
            out["handoff"] = {"exported": int(self.handoffs),
                              "folds": int(self.handoff_folds),
                              "pending": len(self._handoff_ready)}
        if self._pipe_timers.seconds or self._pipe_timers.counters:
            # the pipelined decode window's substrate counters
            # (submitted/completed blocks, submit_wait back-pressure)
            out["pipeline_window"] = self._pipe_timers.snapshot()
        if self._controller is not None:
            out["control"] = self._controller.stats()
        out["requests"] = self.request_latency.summary()
        if self.slo is not None:
            out["slo"] = self.slo.flat_summary()
        from deepspeed_tpu.telemetry.metrics import metrics as _metrics
        if _metrics.enabled:
            # flat registry view (histogram p50/p99 + counters) — one
            # scalar level, the MonitorMaster flattening contract
            out["metrics"] = _metrics.scalar_summary()
            if self._tail_sampler is not None:
                out["trace_sampling"] = dict(self._tail_sampler.counters())
        return out

    def close(self) -> None:
        """Release tier-store resources (AIO handle, staging buffers,
        digest pool, spill files) and prefix-cache holds.  Idempotent;
        a no-op with tiering and the prefix cache off."""
        for r in itertools.chain(self.waiting, self._handoff_ready):
            if r.spilled is not None:
                for p in r.spilled.get("shared_pages", ()):
                    self.allocator.decref(int(p))
                r.spilled["shared_pages"] = []
        if self._pfx is not None:
            self._pfx.clear()
            self._pfx = None
        if self.tiering is not None:
            self.tiering.close()
            self.tiering = None

    # -- host<->device funnels (every transfer is counted/timed) ---------

    def _upload(self, x):
        """Host -> device metadata transfer.  The pipelined decode loop
        must NOT call this in steady state (metadata is device-resident;
        ``host_stats.meta_uploads`` asserts it in tests)."""
        return self._upload_all((x,))[0]

    def _upload_all(self, arrays) -> List[Any]:
        """One ``upload`` bracket around the transfers of one dispatch's
        metadata (each array still its own ``device_put``)."""
        with self.host_stats.stage("upload"):
            self.host_stats.meta_uploads += len(arrays)
            return [jax.device_put(x, self._device) for x in arrays]

    def _split_rng(self):
        """The eager per-block key split — a tiny program of its own,
        so it gets its own bracket."""
        with self.host_stats.stage("rng_split"):
            self.rng, sub = jax.random.split(self.rng)
        return sub

    def _fetch(self, tree):
        """Blocking device -> host fetch — the serving loop's only sync
        point (``host_stats.blocking_gets`` counts them)."""
        with self.host_stats.stage("device"):
            self.host_stats.blocking_gets += 1
            if not self._moe_pending:
                return jax.device_get(tree)
            # the expert layers' counts of the dispatches since the last
            # fetch ride on this one: no round trip of their own
            pending, self._moe_pending = self._moe_pending, []
            tree, counts = jax.device_get((tree, [c for c, _ in pending]))
        for c, (_, attrs) in zip(counts, pending):
            self._record_moe(c, attrs)
        return tree

    def _record_moe(self, counts, attrs: Dict[str, Any]) -> None:
        """One dispatch's expert-layer counts, as a span of their own in
        the profiler's host plane and in ``serving_stages()['moe']``."""
        rows, hit, load = (int(v) for v in counts)
        st = self.host_stats
        st.moe_rows += rows
        st.moe_experts_hit += hit
        st.moe_load_max = max(st.moe_load_max, load)
        with trace.bracket("engine.moe", cat="serving", moe_rows=rows,
                           moe_experts_hit=hit, moe_load_max=load, **attrs):
            pass

    def _block_ready(self, block):
        """The pipeline window's waiter: joining a decode block means
        waiting for its device tokens (run-ahead bound, NOT a fetch —
        harvest later folds all ready blocks in one blocking get)."""
        with self.host_stats.stage("device"):
            jax.block_until_ready(block[0])
        return block

    # -- compiled fused step ---------------------------------------------

    def _init_cache(self, model=None):
        """Zeroed page buffers for every layer (eval_shape, no params);
        ``model`` defaults to the target (the draft model gets its own,
        smaller, pool tree)."""
        model = model if model is not None else self.model
        dummy_meta = self._device_meta(
            np.zeros((self.max_seqs,), np.int32),
            np.full((self.max_seqs, self.pages_per_seq), -1, np.int32),
            np.zeros((self.max_seqs + 1,), np.int32),
            np.zeros((1,), np.int32),
            np.zeros((self.T,), np.int32))
        ids = jnp.zeros((1, self.T), jnp.int32)
        pos = jnp.zeros((1, self.T), jnp.int32)

        def _init():
            return model.init(jax.random.PRNGKey(0), ids,
                              positions=pos, ragged_meta=dummy_meta)

        shapes = jax.eval_shape(_init)
        assert "cache" in shapes

        def make(s):
            # allocated where it lives: a pool is most of a chip's memory
            return jnp.zeros(s.shape, s.dtype,
                             device=self._cache_sharding(s.shape))

        return jax.tree_util.tree_map(make, shapes["cache"])

    @staticmethod
    def _device_meta(kv_lens, page_indices, cu_q_lens, num_seqs,
                     new_kv_dest):
        return {"kv_lens": jnp.asarray(kv_lens),
                "page_indices": jnp.asarray(page_indices),
                "cu_q_lens": jnp.asarray(cu_q_lens),
                "num_seqs": jnp.asarray(num_seqs),
                "new_kv_dest": jnp.asarray(new_kv_dest)}

    def _fused_step_fn(self):
        """ONE jitted program for every tick: fused decode + prefill
        chunk(s) forward, paged-KV update, and logits row selection."""
        if self._step_fn is not None:
            return self._step_fn
        from deepspeed_tpu.inference.common import (logits_of,
                                                    unroll_scan_params)

        model = self.model
        unroll = self._unroll_params
        wq = self._wq
        native = self._wq_native

        def run(params, cache, token_ids, positions, kv_lens, page_indices,
                cu_q_lens, num_seqs, new_kv_dest, sample_rows):
            if wq:
                from deepspeed_tpu.inference.quantization import \
                    dequantize_param_tree

                params = dequantize_param_tree(params, native_w8a8=native)
            if unroll:
                params = unroll_scan_params(params)
            meta = {"kv_lens": kv_lens, "page_indices": page_indices,
                    "cu_q_lens": cu_q_lens, "num_seqs": num_seqs,
                    "new_kv_dest": new_kv_dest,
                    # rows past the last sequence's end are padding
                    "live": (jnp.arange(token_ids.shape[1]) <
                             cu_q_lens[num_seqs[0]])}
            out, vars_ = model.apply(
                {"params": params, "cache": cache}, token_ids,
                positions=positions, mutable=["cache", "moe_stats"],
                ragged_meta=meta)
            logits = logits_of(out)[0]                      # [T, V]
            sel = jnp.take(logits, sample_rows, axis=0)     # [max_seqs, V]
            return sel, vars_["cache"], _moe_counts(vars_)

        # distinguishable XLA program name ("jit_ragged_fused_step") so
        # the profiler bridge can attribute device time per program
        run.__name__ = run.__qualname__ = "ragged_fused_step"
        self._step_fn = jax.jit(run, donate_argnums=(1,))
        return self._step_fn

    # -- the on-device decode block --------------------------------------

    def _decode_block_fn(self, sampled: bool):
        """K decode ticks per dispatch: ``lax.scan`` over fused
        [1, max_seqs] decode forwards with on-device sampling.  The host
        round trip the reference pays per generated token
        (``engine_v2.py:107`` put -> schedule -> logits) amortizes to 1/K.
        Two variants compile: pure-greedy (no sort) and per-seq sampled."""
        if sampled in self._decode_block_cache:
            return self._decode_block_cache[sampled]
        from deepspeed_tpu.inference.common import (logits_of,
                                                    unroll_scan_params)

        model = self.model
        unroll = self._unroll_params
        S = self.max_seqs
        K = self.decode_block_size
        page = self.page_size
        max_len = self.max_seq_len

        wq = self._wq
        native = self._wq_native
        sample_base = self._sample_base

        def run(params, cache, last_tok, pos, active, remaining,
                page_table, eos_ids, do_sample, temperature, top_k, top_p,
                seeds, rng):
            if wq:
                from deepspeed_tpu.inference.quantization import \
                    dequantize_param_tree

                params = dequantize_param_tree(params, native_w8a8=native)
            if unroll:
                params = unroll_scan_params(params)

            def tick(carry, _):
                cache, last_tok, pos, active, remaining, rng = carry
                dest_page = jnp.take_along_axis(
                    jnp.maximum(page_table, 0),
                    (pos // page)[:, None], axis=1)[:, 0]
                dest = jnp.where(active, dest_page * page + pos % page, 0)
                kv_lens = jnp.where(active, pos + 1, 1)
                meta = {"kv_lens": kv_lens,
                        "page_indices": page_table,
                        "cu_q_lens": jnp.arange(S + 1, dtype=jnp.int32),
                        "num_seqs": jnp.asarray([S], jnp.int32),
                        "new_kv_dest": dest, "live": active}
                out, vars_ = model.apply(
                    {"params": params, "cache": cache}, last_tok[None],
                    positions=jnp.where(active, pos, 0)[None],
                    mutable=["cache", "moe_stats"], ragged_meta=meta)
                logits = logits_of(out)[0]              # [S, V]
                moe = _moe_counts(vars_)
                rng, _ = jax.random.split(rng)
                # position-keyed per-row keys: the draw at cache position
                # `pos` is the same bits the fused tick's host sampler
                # would use, no matter how this block was scheduled
                sub = (position_keys(sample_base, seeds, pos)
                       if sampled else None)
                nxt = sample_logits_batched(
                    logits, sub, do_sample,
                    temperature, top_k, top_p)
                produced = active
                nxt = jnp.where(active, nxt, last_tok)
                hit_eos = active & (nxt == eos_ids)
                remaining = remaining - produced.astype(jnp.int32)
                pos = jnp.where(active, pos + 1, pos)
                active = (active & ~hit_eos & (remaining > 0) &
                          (pos + 1 < max_len))
                return (vars_["cache"], nxt, pos, active, remaining,
                        rng), (nxt, produced, moe)

            carry, (toks, mask, moe) = jax.lax.scan(
                tick, (cache, last_tok, pos, active, remaining, rng),
                length=K)
            cache, last_tok, pos, active, remaining, rng = carry
            # the full carry returns so the pipelined host path can keep
            # it device-resident across dispatches (no re-upload)
            return (cache, last_tok, pos, active, remaining, toks, mask,
                    None if moe is None else _fold_moe_counts(moe))

        run.__name__ = run.__qualname__ = "ragged_decode_block"
        fn = jax.jit(run, donate_argnums=(1,))
        self._decode_block_cache[sampled] = fn
        return fn

    def _block_arrays(self, reqs: List[Request]):
        """Host numpy decode-block state for ``reqs`` (shared by the
        unpipelined per-block rebuild and the pipelined loop's one-time
        entry upload)."""
        S = self.max_seqs
        last_tok = np.asarray(self._last_tokens, np.int32)
        pos = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        remaining = np.zeros((S,), np.int32)
        eos_ids = np.full((S,), -1, np.int32)
        do_sample = np.zeros((S,), bool)
        temperature = np.ones((S,), np.float32)
        top_k = np.zeros((S,), np.int32)
        top_p = np.ones((S,), np.float32)
        seeds = np.zeros((S,), np.int32)   # per-row sampling seed (uid)
        for r in reqs:
            s = r.slot
            self._last_sched[s] = self._sched_seq
            pos[s] = min(r.length - 1, self.max_seq_len - 1)
            active[s] = True
            remaining[s] = r.max_new_tokens - len(r.generated)
            if r.eos_token_id is not None:
                eos_ids[s] = r.eos_token_id
            do_sample[s] = r.do_sample
            temperature[s] = r.temperature
            top_k[s] = r.top_k
            top_p[s] = r.top_p
            seeds[s] = r.uid
        return (last_tok, pos, active, remaining, eos_ids, do_sample,
                temperature, top_k, top_p, seeds)

    def _fold_block(self, reqs: List[Request], toks: np.ndarray,
                    mask: np.ndarray) -> int:
        """Fold one harvested [K, S] block into request state."""
        produced = 0
        for r in reqs:
            new = toks[mask[:, r.slot], r.slot]
            r.generated.extend(int(t) for t in new)
            produced += int(new.size)
            if new.size:
                # harvest-time token visibility: the honest host-side
                # TTFT/TPOT timestamp under the deferred-harvest pipeline
                self.request_latency.on_tokens(r.uid, len(r.generated))
        return produced

    # -- the speculative decode block (round-6 tentpole) ------------------

    def _spec_grow_want(self, plen: int, rem: int) -> int:
        """Token coverage one speculative block needs for a slot at
        cache length ``plen`` with ``rem`` budget left: each of the
        block's ticks WRITES k+1 KV rows ahead of the cursor regardless
        of how many tokens are accepted, so pages must cover the
        worst-case span (writes past ``max_seq_len`` route to the trash
        page and need no backing)."""
        K1 = self.spec_k + 1
        ticks = min(self.decode_block_size, max(int(rem), 1))
        return int(min(plen + ticks * K1, self.max_seq_len))

    def _hist_array(self, reqs: List[Request]) -> np.ndarray:
        """Host build of the device token-history buffer [S, max_len]:
        ``hist[s, i]`` = the sequence's token at cache position ``i``
        (prompt + generated — exact in the decode phase).  The n-gram
        drafter matches/continues against it; rebuilt from host state at
        every pipeline entry/harvest re-anchor."""
        hist = np.zeros((self.max_seqs, self.max_seq_len), np.int32)
        if self.spec_mode == "ngram":
            for r in reqs:
                seq = np.concatenate(
                    [r.prompt, np.asarray(r.generated, np.int32)])
                L = min(r.length, self.max_seq_len)
                hist[r.slot, :L] = seq[:L]
        return hist

    def _draft_prefill_fn(self):
        """ONE compiled chunked prefill program for the draft model's
        paged KV catch-up (fixed [1, prefill_chunk] shape, one sequence
        per call — entry-time cost, not steady-state)."""
        if self._draft_prefill is not None:
            return self._draft_prefill
        from deepspeed_tpu.inference.common import unroll_scan_params

        draft = self._draft
        dunroll = self._draft_unroll

        def run(dparams, dcache, ids, positions, kv_lens, page_row, cu,
                dest):
            if dunroll:
                dparams = unroll_scan_params(dparams)
            meta = {"kv_lens": kv_lens, "page_indices": page_row,
                    "cu_q_lens": cu,
                    "num_seqs": jnp.asarray([1], jnp.int32),
                    "new_kv_dest": dest}
            _, vars_ = draft.apply(
                {"params": dparams, "cache": dcache}, ids,
                positions=positions, mutable=["cache"], ragged_meta=meta)
            return vars_["cache"]

        run.__name__ = run.__qualname__ = "draft_prefill"
        self._draft_prefill = jax.jit(run, donate_argnums=(1,))
        return self._draft_prefill

    def _draft_catchup(self, reqs: List[Request]) -> None:
        """Bring the draft model's paged KV up to each slot's cursor
        (positions ``< length - 1``; the drafter itself processes the
        cursor token).  No-op for slots already covered — inside a
        decode phase the speculative block keeps draft KV in sync by
        construction, so this only runs at admission/re-admission."""
        if self.spec_mode != "draft":
            return
        st = self.host_stats
        C = self.prefill_chunk
        page = self.page_size
        with st.stage("draft"):
            fn = self._draft_prefill_fn()
            for r in reqs:
                target = r.length - 1
                lo = int(self._draft_len[r.slot])
                if lo >= target:
                    continue
                seq = np.concatenate(
                    [r.prompt, np.asarray(r.generated, np.int32)])
                while lo < target:
                    take = min(C, target - lo)
                    ids = np.zeros((C,), np.int32)
                    ids[:take] = seq[lo:lo + take]
                    pos = np.arange(lo, lo + C, dtype=np.int32)
                    posc = np.minimum(pos, self.max_seq_len - 1)
                    pg = self.page_table[
                        r.slot, np.minimum(pos // page,
                                           self.pages_per_seq - 1)]
                    dest = np.where(
                        np.arange(C) < take,
                        np.maximum(pg, 0) * page + pos % page,
                        0).astype(np.int32)
                    st.meta_uploads += 6
                    self._draft_cache = fn(
                        self._draft_params, self._draft_cache,
                        jnp.asarray(ids[None]), jnp.asarray(posc[None]),
                        jnp.asarray([lo + take], jnp.int32),
                        jnp.asarray(self.page_table[r.slot][None]),
                        jnp.asarray([0, take], jnp.int32),
                        jnp.asarray(dest))
                    lo += take
                self._draft_len[r.slot] = target

    def _spec_block_fn(self, sampled: bool):
        """The fused draft+verify+accept block: ``decode_block_size``
        speculative ticks per dispatch in a ``lax.scan``.  Each tick
        drafts k tokens (n-gram lookup over the history buffer, or a
        k-step draft-model sub-scan sharing the target's page table),
        scores all k+1 positions with the TARGET in one ragged chunk
        per slot, and accepts/rolls back device-side
        (:func:`~deepspeed_tpu.inference.sampling.speculative_verify`).
        Rollback is position rollback: rows written for rejected
        positions sit between the new cursor and the old write frontier,
        a span the NEXT tick's scatter fully overwrites before attention
        can reach it."""
        if sampled in self._spec_block_cache:
            return self._spec_block_cache[sampled]
        from deepspeed_tpu.inference.common import (logits_of,
                                                    unroll_scan_params)

        model = self.model
        unroll = self._unroll_params
        wq = self._wq
        native = self._wq_native
        mode = self.spec_mode
        draft = self._draft
        dunroll = self._draft_unroll
        S = self.max_seqs
        Tt = self.decode_block_size
        k = self.spec_k
        K1 = k + 1
        n = self.spec_ngram
        page = self.page_size
        pp = self.pages_per_seq
        max_len = self.max_seq_len

        def ngram_propose(hist, pos, last_tok):
            """Prompt-lookup drafting: most recent earlier occurrence of
            the trailing n-gram proposes the k tokens that followed it;
            no match proposes the last token repeated (any proposal is
            distribution-safe — bad drafts are simply rejected)."""
            L = max_len
            ar = jnp.arange(L, dtype=jnp.int32)
            histp = jnp.pad(hist, ((0, 0), (0, n + k)))
            tpos = jnp.clip(pos[:, None] - (n - 1) +
                            jnp.arange(n, dtype=jnp.int32)[None, :],
                            0, L - 1)
            tail = jnp.take_along_axis(hist, tpos, axis=1)      # [S, n]
            match = jnp.ones((S, L), bool)
            for t in range(n):
                match = match & (histp[:, t:t + L] == tail[:, t:t + 1])
            valid = ar[None, :] <= (pos[:, None] - n)
            score = jnp.where(match & valid, ar[None, :] + 1, 0)
            best = jnp.argmax(score, axis=1)       # most recent match
            found = jnp.any(match & valid, axis=1)
            cidx = best[:, None] + n + jnp.arange(k,
                                                  dtype=jnp.int32)[None, :]
            cand = jnp.take_along_axis(histp, cidx, axis=1)     # [S, k]
            fb = jnp.broadcast_to(last_tok[:, None], (S, k))
            return jnp.where(found[:, None], cand, fb).astype(jnp.int32)

        def run(params, dparams, cache, dcache, hist, last_tok, pos,
                active, remaining, page_table, eos_ids, do_sample,
                temperature, top_k, top_p, rng):
            if wq:
                from deepspeed_tpu.inference.quantization import \
                    dequantize_param_tree

                params = dequantize_param_tree(params, native_w8a8=native)
            if unroll:
                params = unroll_scan_params(params)
            if mode == "draft" and dunroll:
                dparams = unroll_scan_params(dparams)
            rows = jnp.arange(S)

            def draft_propose(dcache, last_tok, pos, active, key):
                def dstep(carry, key_j):
                    dcache, cur, dpos = carry
                    dvalid = active & (dpos < max_len)
                    dp = jnp.take_along_axis(
                        jnp.maximum(page_table, 0),
                        jnp.minimum(dpos // page, pp - 1)[:, None],
                        axis=1)[:, 0]
                    ddest = jnp.where(dvalid, dp * page + dpos % page, 0)
                    dmeta = {"kv_lens": jnp.where(active, dpos + 1, 1),
                             "page_indices": page_table,
                             "cu_q_lens": jnp.arange(S + 1,
                                                     dtype=jnp.int32),
                             "num_seqs": jnp.asarray([S], jnp.int32),
                             "new_kv_dest": ddest}
                    dout, dvars = draft.apply(
                        {"params": dparams, "cache": dcache}, cur[None],
                        positions=jnp.where(dvalid, dpos, 0)[None],
                        mutable=["cache"], ragged_meta=dmeta)
                    dlg = logits_of(dout)[0].astype(jnp.float32)
                    dgreedy = jnp.argmax(dlg, axis=-1).astype(jnp.int32)
                    if sampled:
                        flg = filter_logits_batched(dlg, temperature,
                                                    top_k, top_p)
                        qj = jax.nn.softmax(flg, axis=-1)
                        samp = jax.random.categorical(
                            key_j, flg, axis=-1).astype(jnp.int32)
                        nxt = jnp.where(do_sample, samp, dgreedy)
                        out = (nxt, qj)
                    else:
                        nxt = dgreedy
                        out = (nxt,)
                    return (dvars["cache"], nxt, dpos + 1), out

                keys = jax.random.split(key, k)
                (dcache, _, _), outs = jax.lax.scan(
                    dstep, (dcache, last_tok, pos), keys)
                d_toks = outs[0].T                              # [S, k]
                q_probs = (outs[1].transpose(1, 0, 2) if sampled
                           else None)
                return dcache, d_toks, q_probs

            def tick(carry, _):
                (cache, dcache, hist, last_tok, pos, active, remaining,
                 rng, prop, accd) = carry
                rng, key_d, key_v = jax.random.split(rng, 3)
                # ---- draft k proposals per slot ----
                if mode == "ngram":
                    # the cursor token joins the history before matching
                    hist = hist.at[rows, jnp.where(active, pos,
                                                   max_len)].set(
                        last_tok, mode="drop")
                    d_toks = ngram_propose(hist, pos, last_tok)
                    q_probs = None
                else:
                    dcache, d_toks, q_probs = draft_propose(
                        dcache, last_tok, pos, active, key_d)
                # ---- verify: one ragged chunk of k+1 rows per slot ----
                chunk = jnp.concatenate([last_tok[:, None], d_toks],
                                        axis=1)                 # [S, K1]
                cpos = (pos[:, None] +
                        jnp.arange(K1, dtype=jnp.int32)[None, :])
                valid = active[:, None] & (cpos < max_len)
                dest_page = jnp.take_along_axis(
                    jnp.maximum(page_table, 0),
                    jnp.minimum(cpos // page, pp - 1), axis=1)
                dest = jnp.where(valid,
                                 dest_page * page + cpos % page, 0)
                meta = {"kv_lens": jnp.where(active, pos + K1, 1),
                        "page_indices": page_table,
                        "cu_q_lens": jnp.arange(
                            S + 1, dtype=jnp.int32) * K1,
                        "num_seqs": jnp.asarray([S], jnp.int32),
                        "new_kv_dest": dest.reshape(-1)}
                out, vars_ = model.apply(
                    {"params": params, "cache": cache},
                    chunk.reshape(1, -1),
                    positions=jnp.where(valid, cpos, 0).reshape(1, -1),
                    mutable=["cache"], ragged_meta=meta)
                cache = vars_["cache"]
                logits = logits_of(out)[0].reshape(S, K1, -1)
                out_toks, acc = speculative_verify(
                    logits, d_toks, q_probs, key_v if sampled else None,
                    do_sample, temperature, top_k, top_p)
                # ---- emission clamp: budget, max_len, first eos ----
                emit = jnp.minimum(acc + 1, remaining)
                emit = jnp.minimum(emit, max_len - 1 - pos)
                eos_hit = out_toks == eos_ids[:, None]
                first_eos = jnp.argmax(eos_hit, axis=1)
                emit = jnp.where(jnp.any(eos_hit, axis=1),
                                 jnp.minimum(emit, first_eos + 1), emit)
                emit = jnp.where(active, jnp.clip(emit, 1, K1), 0)
                j = jnp.arange(K1, dtype=jnp.int32)[None, :]
                mask_out = active[:, None] & (j < emit[:, None])
                # ---- advance / roll back the carry ----
                new_last = jnp.take_along_axis(
                    out_toks, jnp.maximum(emit - 1, 0)[:, None],
                    axis=1)[:, 0]
                ended_eos = jnp.any(eos_hit & (j < emit[:, None]),
                                    axis=1)
                if mode == "ngram":
                    hidx = jnp.where(mask_out, pos[:, None] + 1 + j,
                                     max_len)
                    hist = hist.at[rows[:, None], hidx].set(
                        out_toks, mode="drop")
                last_tok = jnp.where(active, new_last, last_tok)
                pos = jnp.where(active, pos + emit, pos)
                remaining = remaining - emit
                prop = prop + jnp.sum(jnp.where(active, k, 0))
                accd = accd + jnp.sum(jnp.where(active, emit - 1, 0))
                active = (active & ~ended_eos & (remaining > 0) &
                          (pos + 1 < max_len))
                return (cache, dcache, hist, last_tok, pos, active,
                        remaining, rng, prop, accd), (out_toks, mask_out)

            carry0 = (cache, dcache, hist, last_tok, pos, active,
                      remaining, rng, jnp.int32(0), jnp.int32(0))
            carry, (toks, mask) = jax.lax.scan(tick, carry0, length=Tt)
            (cache, dcache, hist, last_tok, pos, active, remaining, _,
             prop, accd) = carry
            # tick-major emission order, [Tt*K1, S] — _fold_block's shape
            toks = toks.transpose(0, 2, 1).reshape(Tt * K1, S)
            mask = mask.transpose(0, 2, 1).reshape(Tt * K1, S)
            return (cache, dcache, hist, last_tok, pos, active,
                    remaining, toks, mask, prop, accd)

        run.__name__ = run.__qualname__ = "spec_verify_block"
        fn = jax.jit(run, donate_argnums=(2, 3, 4))
        self._spec_block_cache[sampled] = fn
        return fn

    def _step_spec_block(self, reqs: List[Request]) -> int:
        """One speculative block, unpipelined (fresh uploads + one
        blocking harvest per dispatch) — the ``pipeline=False`` spec
        reference path."""
        st = self.host_stats
        with st.stage("plan"):
            # the spec block keeps the global rng stream (its rejection-
            # sampling keys are not position-keyed), so seeds is unused
            (last_tok, pos, active, remaining, eos_ids, do_sample,
             temperature, top_k, top_p, _seeds) = self._block_arrays(reqs)
            sampled = bool(do_sample.any())
            hist = self._hist_array(reqs)
        self._draft_catchup(reqs)
        sub = self._split_rng()
        args = self._upload_all(
            (hist, last_tok, pos, active, remaining, self.page_table,
             eos_ids, do_sample, temperature, top_k, top_p))
        if trace.enabled:
            trace.event("decode_block", cat="request",
                        uids=[r.uid for r in reqs],
                        ticks=self.decode_block_size, spec=True)
        with st.stage("verify"):
            st.dispatches += 1
            st.spec_dispatches += 1
            (self.cache, self._draft_cache, _h, new_last, _p, _a, _r,
             toks, mask, prop, accd) = self._spec_block_fn(sampled)(
                self.params, self._draft_params, self.cache,
                self._draft_cache, *args, sub)
        st.ticks += self.decode_block_size
        toks, mask, new_last, prop, accd = self._fetch(
            (toks, mask, new_last, prop, accd))
        st.harvests += 1
        with st.stage("harvest"):
            self._last_tokens = np.array(new_last)
            produced = self._fold_block(reqs, np.asarray(toks),
                                        np.asarray(mask))
            st.spec_proposed += int(prop)
            st.spec_accepted += int(accd)
            st.spec_tokens += produced
            for r in reqs:
                self._maybe_finish(r)
                if not r.done:
                    self._draft_len[r.slot] = r.length - 1
            self._reap()
        return produced

    def _step_decode_block(self, reqs: List[Request]) -> int:
        """Run one on-device decode block and fold results back into the
        host request state (the ``pipeline=False`` path: fresh metadata
        upload + one blocking harvest per block)."""
        st = self.host_stats
        with st.stage("plan"):
            (last_tok, pos, active, remaining, eos_ids, do_sample,
             temperature, top_k, top_p, seeds) = self._block_arrays(reqs)
            sampled = bool(do_sample.any())
        sub = self._split_rng()
        args = self._upload_all(
            (last_tok, pos, active, remaining, self.page_table,
             eos_ids, do_sample, temperature, top_k, top_p, seeds))
        if trace.enabled:
            trace.event("decode_block", cat="request",
                        uids=[r.uid for r in reqs],
                        ticks=self.decode_block_size)
        with self._decode_dispatch(reqs, [r.length for r in reqs]):
            (cache, new_last, _pos, _active, _remaining, toks, mask,
             moe) = self._decode_block_fn(sampled)(
                self.params, self.cache, *args, sub)
        self.cache = cache
        self._moe_dispatched(moe, "decode_block", self.decode_block_size)
        st.ticks += self.decode_block_size
        toks, mask, new_last = self._fetch((toks, mask, new_last))
        st.harvests += 1
        with st.stage("harvest"):
            toks = np.asarray(toks)                     # [K, S]
            mask = np.asarray(mask)                     # [K, S]
            # np.array: device_get returns a READ-ONLY view; the
            # SplitFuse tick assigns into _last_tokens per sampled token
            self._last_tokens = np.array(new_last)
            produced = self._fold_block(reqs, toks, mask)
            for r in reqs:
                self._maybe_finish(r)
            self._reap()
        return produced

    def _moe_dispatched(self, counts, program: str, ticks: int) -> None:
        """Keep a dispatch's expert-layer counts (a device array, or None
        for a model without expert layers) until a fetch brings them."""
        if counts is not None:
            self._moe_pending.append(
                (counts, {"program": program, "ticks": ticks}))

    def _decode_dispatch(self, reqs: List[Request], contexts):
        """The ``dispatch`` bracket of one plain decode block, with the
        counts its roofline needs: ``rows`` live sequences run ``ticks``
        token steps, the first reading ``key_tokens`` context tokens in
        all (``contexts``: each row's length as the host projects it at
        dispatch) and every later one a token more per row."""
        st = self.host_stats
        K = self.decode_block_size
        rows, keys = len(reqs), int(sum(contexts))
        st.dispatches += 1
        st.decode_key_token_steps += K * keys + rows * K * (K - 1) // 2
        return st.stage("dispatch", program="decode_block", rows=rows,
                        ticks=K, key_tokens=keys)

    # -- the pipelined decode loop (serving host-path tentpole) ----------

    def _admittable(self) -> bool:
        """Would the unpipelined engine admit the queue head right now?
        Evaluated from EXACT global state (allocator + slots), so the
        pipelined loop reconciles at precisely the steps where
        ``pipeline=False`` would have admitted."""
        if not self.waiting or not any(s is None for s in self.slots):
            return False
        req = self.waiting[0]
        need = self._admit_need(req)
        fresh, entries = self._fresh_pages_needed(req, need, touch=False)
        avail = self.allocator.free_pages
        if self._pfx is not None:
            avail += self._pfx.reclaimable(
                exclude={e.key for e in entries})
        return fresh <= avail

    def _lc_resident_pages(self) -> int:
        """HBM pages a partially-resident sequence needs at steady
        state: sinks + the recent window + one not-yet-parked group in
        flight + the growth frontier."""
        t = self._tier_cfg
        return (int(t.sink_pages) + int(t.window_pages)
                + int(t.chunk_pages) + 1)

    def _admit_need(self, req: Request) -> int:
        """Token coverage ``_admit`` reserves for ``req`` — ONE formula
        shared with ``_admittable`` so the pipelined loop reconciles at
        precisely the steps where ``pipeline=False`` would admit."""
        ctx_len = req.ctx_len
        rem = max(req.max_new_tokens - len(req.generated), 1)
        if req.lc:
            # partial residency: reserve the resident WINDOW, not the
            # context — the parked middle lives in the spill tiers
            return min(ctx_len + rem,
                       self._lc_resident_pages() * self.page_size)
        if self.kv_reserve == "worst_case":
            # worst case INCLUDING re-prefilled output for evicted
            # continuations (their ctx carries earlier tokens)
            return ctx_len + req.max_new_tokens - len(req.generated)
        if req.spilled is not None and req.prefill_done >= ctx_len:
            # spilled decode-phase continuation: _admit allocates for
            # its full restored length, not just the prompt
            return req.length + min(self.decode_block_size, rem)
        # on-demand (reference can_schedule): context + the first
        # decode block; growth happens per block
        return ctx_len + min(self.decode_block_size, rem)

    def _fresh_pages_needed(self, req: Request, need: int,
                            touch: bool = False):
        """Free pages an admission of ``req`` would consume, after
        prefix-cache attaches (resident matched pages cost nothing; a
        FULL match costs one extra page for the COW re-prefill) and
        spill-hold re-attaches.  Returns ``(fresh, matched_entries)``.
        ``touch=False`` for probes — LRU order must not move until the
        admission actually happens."""
        total = self.allocator.pages_for(need)
        if req.lc:
            # long-context admissions skip the prefix cache (parked
            # columns would punch holes in a shared prefix run)
            return total, []
        if req.spilled is not None:
            return total - len(req.spilled.get("shared_pages", ())), []
        if self._pfx is None:
            return total, []
        ctx = req.ctx if req.ctx is not None else req.prompt
        entries = self._pfx.match(ctx, touch=touch)
        resident = sum(1 for e in entries if e.state == "resident")
        full = bool(entries) and (
            len(entries) * self.page_size == ctx.size)
        return total - resident + (1 if full else 0), entries

    def _pipeline_start(self, reqs: List[Request],
                        spec: bool = False) -> None:
        """Enter the pipelined decode loop: upload the decode-block
        carry and sampler metadata ONCE; subsequent blocks chain
        device-resident state (zero steady-state uploads).  With
        ``spec`` the speculative block runs instead of the plain one and
        the projection becomes per-slot BOUNDS (advance is 1..k+1 per
        tick, data-dependent): ``plen``/``rem`` hold the slow bound
        (1 token per tick — the guaranteed floor), ``plen_hi``/
        ``rem_lo`` the fast bound (k+1 per tick); growth covers the
        fast bound's write span and a harvest is forced as soon as the
        fast bound says a finish is POSSIBLE."""
        if spec:
            self._draft_catchup(reqs)
        with self.host_stats.stage("plan"):
            (last_tok, pos, active, remaining, eos_ids, do_sample,
             temperature, top_k, top_p, seeds) = self._block_arrays(reqs)
            S = self.max_seqs
            # exact host projection of per-slot cache length and token
            # budget — for eos-free sequences the device's active/
            # remaining carry is a deterministic function of these, so
            # the host can plan ahead without syncing; eos-bearing
            # sequences force a harvest every block (finish_possible)
            plen = np.zeros((S,), np.int64)
            rem = np.zeros((S,), np.int64)
            has_eos = np.zeros((S,), bool)
            for r in reqs:
                plen[r.slot] = r.length
                rem[r.slot] = remaining[r.slot]
                has_eos[r.slot] = r.eos_token_id is not None
        names = ("last_tok", "pos", "active", "remaining", "page_table",
                 "eos_ids", "do_sample", "temperature", "top_k", "top_p")
        self._dev = {
            "reqs": list(reqs),
            "sampled": bool(do_sample.any()),
            **dict(zip(names, self._upload_all(
                (last_tok, pos, active, remaining, self.page_table,
                 eos_ids, do_sample, temperature, top_k, top_p)))),
            "plen": plen, "rem": rem, "has_eos": has_eos,
            "spec": spec,
            # un-harvested decode blocks ride the shared bounded-window
            # substrate: the window bounds device run-ahead at
            # async_depth (joining = block_until_ready on the block's
            # tokens, bracketed as device wait), joined blocks park in
            # "ready" until the next harvest folds them.  Same substrate
            # instance shape as the NVMe moment stream and the router's
            # per-replica feed loop.
            "window": BoundedAsyncStage(
                waiter=self._block_ready, depth=self.async_depth,
                timers=self._pipe_timers, name="serving_pipeline"),
            "ready": [],                  # joined, un-harvested blocks
            "block_seq": 0,
        }
        if spec:
            self._dev["hist"] = self._upload(self._hist_array(reqs))
            self._dev["plen_hi"] = plen.copy()
            self._dev["rem_lo"] = rem.copy()
        else:
            self._dev["seeds"] = self._upload(seeds)

    def _pipeline_step(self) -> int:
        """One pipelined iteration: plan + dispatch block k+1 while the
        device still runs block k; harvest only when forced."""
        dv = self._dev
        st = self.host_stats
        K = self.decode_block_size
        spec = dv.get("spec", False)
        K1 = self.spec_k + 1
        # a queued request became admittable (put_request arrived, or a
        # reap freed capacity): reconcile so the normal path admits it
        # exactly when the unpipelined engine would
        if self._admittable():
            return self._pipeline_harvest(teardown=True)
        with st.stage("plan"):
            # grow pages to cover the next block — exact for the plain
            # block (the projection is exact for every sequence that can
            # reach this point un-harvested, see _pipeline_start); for a
            # speculative block the projection is the FAST bound, so
            # growth covers the worst-case k+1-wide write span
            live = [r for r in dv["reqs"] if dv["rem"][r.slot] > 0 and
                    dv["plen"][r.slot] < self.max_seq_len]
            slots_active = [r.slot for r in live]
            grow_ok = bool(slots_active)
            table_dirty = False
            for s in slots_active:
                self._last_sched[s] = self._sched_seq
                if spec:
                    want = self._spec_grow_want(int(dv["plen_hi"][s]),
                                                int(dv["rem"][s]))
                else:
                    want = int(min(dv["plen"][s] + min(K, dv["rem"][s]),
                                   self.max_seq_len))
                before = self.allocator.owned(s)
                if not self._ensure_pages(s, want):
                    grow_ok = False
                    break
                table_dirty |= self.allocator.owned(s) != before
        if not grow_ok:
            # out of pages (or nothing left to run): reconcile and hand
            # control back to the normal path (stall/evict semantics)
            return self._pipeline_harvest(teardown=True)
        if table_dirty:
            dv["page_table"] = self._upload(self.page_table)
        sub = self._split_rng()
        if trace.enabled:
            trace.event("decode_block", cat="request",
                        uids=[r.uid for r in dv["reqs"]],
                        ticks=self.decode_block_size, pipelined=True,
                        spec=bool(spec))
        if spec:
            with st.stage("verify"):
                st.dispatches += 1
                st.spec_dispatches += 1
                (self.cache, self._draft_cache, dv["hist"],
                 dv["last_tok"], dv["pos"], dv["active"],
                 dv["remaining"], toks, mask, prop,
                 accd) = self._spec_block_fn(dv["sampled"])(
                    self.params, self._draft_params, self.cache,
                    self._draft_cache, dv["hist"], dv["last_tok"],
                    dv["pos"], dv["active"], dv["remaining"],
                    dv["page_table"], dv["eos_ids"], dv["do_sample"],
                    dv["temperature"], dv["top_k"], dv["top_p"], sub)
            block = (toks, mask, prop, accd)
        else:
            with self._decode_dispatch(
                    live, [dv["plen"][r.slot] for r in live]):
                (self.cache, dv["last_tok"], dv["pos"], dv["active"],
                 dv["remaining"], toks, mask,
                 moe) = self._decode_block_fn(dv["sampled"])(
                    self.params, self.cache, dv["last_tok"], dv["pos"],
                    dv["active"], dv["remaining"], dv["page_table"],
                    dv["eos_ids"], dv["do_sample"], dv["temperature"],
                    dv["top_k"], dv["top_p"], dv["seeds"], sub)
            self._moe_dispatched(moe, "decode_block", K)
            block = (toks, mask)
        # track the block in the bounded window: past async_depth the
        # submit first joins the oldest un-joined block (waiting for
        # its tokens under the "device" bracket), bounding device
        # run-ahead exactly as the hand-rolled carry did
        dv["window"].submit(dv["block_seq"], block,
                            on_done=dv["ready"].append)
        dv["block_seq"] += 1
        st.ticks += K
        with st.stage("plan"):
            # advance the projection past this block and decide whether
            # the unpipelined engine could have reaped after it
            finish_possible = False
            for s in slots_active:
                if spec:
                    # bounds: per tick a slot advances 1..k+1 tokens
                    slow = int(max(0, min(K, dv["rem_lo"][s])))
                    fast = int(min(K * K1, max(dv["rem"][s], 0)))
                    dv["plen"][s] = min(dv["plen"][s] + slow,
                                        self.max_seq_len)
                    dv["plen_hi"][s] = min(dv["plen_hi"][s] + fast,
                                           self.max_seq_len)
                    dv["rem"][s] -= slow
                    dv["rem_lo"][s] -= K * K1
                    if (dv["has_eos"][s] or dv["rem_lo"][s] <= 0 or
                            dv["plen_hi"][s] >= self.max_seq_len):
                        finish_possible = True
                else:
                    prod = int(min(K, dv["rem"][s],
                                   self.max_seq_len - dv["plen"][s]))
                    dv["rem"][s] -= prod
                    dv["plen"][s] += prod
                    if (dv["has_eos"][s] or dv["rem"][s] <= 0 or
                            dv["plen"][s] >= self.max_seq_len):
                        finish_possible = True
            if self.tiering is not None and finish_possible:
                # the projection says a slot may free at the next
                # harvest: start NVMe->host reads for the spilled
                # sequences the FIFO queue would re-admit first, under
                # the decode block the device is still running
                self.tiering.prefetch(
                    [q.uid for q in
                     itertools.islice(self.waiting,
                                      self.prefetch_lookahead)
                     if q.spilled is not None])
        pending = dv["window"].in_flight + len(dv["ready"])
        if finish_possible or pending >= self.harvest_interval:
            return self._pipeline_harvest()
        return 0

    def _pipeline_harvest(self, teardown: bool = False) -> int:
        """Fold every pending block back into host request state (ONE
        blocking fetch), reap finishes, and either keep the
        device-resident carry (nothing changed) or tear down so the
        normal path re-plans."""
        dv = self._dev
        st = self.host_stats
        st.harvests += 1
        spec = dv.get("spec", False)
        # join every block still tracked by the bounded window (on_done
        # appends them to dv["ready"] in submit order), then fold the
        # whole run with ONE blocking fetch
        dv["window"].drain()
        blocks = dv["ready"]
        toks_l, mask_l, last_tok, extra = self._fetch((
            [p[0] for p in blocks],
            [p[1] for p in blocks], dv["last_tok"],
            [p[2:] for p in blocks] if spec else []))
        with st.stage("harvest"):
            # np.array: device_get returns READ-ONLY views
            self._last_tokens = np.array(last_tok)
            produced = 0
            for toks, mask in zip(toks_l, mask_l):
                produced += self._fold_block(
                    dv["reqs"], np.asarray(toks), np.asarray(mask))
            if spec:
                st.spec_proposed += sum(int(p) for p, _ in extra)
                st.spec_accepted += sum(int(a) for _, a in extra)
                st.spec_tokens += produced
            for r in dv["reqs"]:
                self._maybe_finish(r)
                if spec and not r.done:
                    self._draft_len[r.slot] = max(r.length - 1, 0)
            changed = any(r.done for r in dv["reqs"])
            self._reap()
            dv["ready"] = []
            if teardown or changed:
                self._dev = None
            else:
                # device carry stays authoritative; re-anchor the host
                # projection on the now-exact lengths (and the
                # speculative fast/slow bounds collapse to exact)
                for r in dv["reqs"]:
                    dv["plen"][r.slot] = r.length
                    dv["rem"][r.slot] = (r.max_new_tokens -
                                         len(r.generated))
                if spec:
                    np.copyto(dv["plen_hi"], dv["plen"])
                    np.copyto(dv["rem_lo"], dv["rem"])
        return produced

    # -- the scheduler tick ----------------------------------------------

    def step(self) -> int:
        """One engine iteration; returns the number of tokens produced
        (0 for pipelined iterations whose harvest is still deferred —
        the tokens are counted at the harvest step).

        All-decoding batches take the multi-tick on-device block (K
        tokens per sequence per host dispatch) — pipelined across
        dispatches when ``pipeline=True``; any prefilling sequence
        falls back to the fused SplitFuse tick."""
        self._sched_seq += 1
        with trace.bracket("engine.step", cat="serving"):
            return self._step()

    def _step(self) -> int:
        if self._controller is not None:
            self._control_steps += 1
            if self._control_steps >= self._control_cfg.interval:
                self._control_steps = 0
                with self.host_stats.stage("plan"):
                    self._controller.tick()
        if self._dev is not None:
            return self._pipeline_step()
        st = self.host_stats
        with st.stage("plan"):
            # park finished handoff prefills BEFORE admission: a
            # handoff request never reaches the decode block / spec /
            # pipeline paths — it leaves its slot the step after its
            # first token lands
            self._handoff_sweep()
            self._admit()
            lc_live = [r for r in self.slots
                       if r is not None and not r.done and r.lc]
            live = [r for r in self.slots
                    if r is not None and not r.done and not r.lc]
            decoding_ready = (not lc_live and bool(live) and all(
                r.prefill_done >= r.ctx_len for r in live))
            # speculation first: its block writes a k+1-wide span per
            # tick, so it needs more page coverage than a plain block —
            # when the pool can't back it, degrade to the plain decode
            # block (greedy outputs are unchanged either way; the
            # decision is taken from EXACT state, so pipelined and
            # unpipelined runs degrade at the same steps)
            spec_block = (decoding_ready and self.spec_mode != "off" and
                          all(self._ensure_pages(
                              r.slot,
                              self._spec_grow_want(
                                  r.length, r.max_new_tokens -
                                  len(r.generated)))
                              for r in live))
            all_decoding = (
                not spec_block and decoding_ready and
                self.decode_block_size > 1 and
                all(self._ensure_pages(
                    r.slot,
                    r.length + min(self.decode_block_size,
                                   r.max_new_tokens - len(r.generated)))
                    for r in live))
        if spec_block:
            if self.pipeline:
                self._pipeline_start(live, spec=True)
                return self._pipeline_step()
            return self._step_spec_block(live)
        if all_decoding:
            if self.pipeline:
                self._pipeline_start(live)
                return self._pipeline_step()
            return self._step_decode_block(live)
        # partially-resident sequences tick through the chunked-scan
        # driver — never the fused batch, the decode block, or the
        # pipeline (decoding_ready is gated off while any is live, so
        # the pipeline cannot start and orphan them)
        lc_produced = 0
        if lc_live:
            if self._lc is None:
                from deepspeed_tpu.inference.v2.long_context import \
                    LongContextDriver
                self._lc = LongContextDriver(self)
            for r in lc_live:
                lc_produced += self._lc.tick(r)
        with st.stage("plan"):
            plan = self._plan_tick()
        if plan is None:
            self._reap()
            # every live sequence is page-stalled: evict the youngest as
            # a continuation so the rest (and the queue) can progress
            # (reference scheduler backpressure, engine_v2.py:184)
            stalled = getattr(self, "_stalled", [])
            if stalled and live:
                if len(live) == 1 and not self.waiting:
                    raise RuntimeError(
                        ("HBM KV tier" if self.tiering is not None
                         else "KV pool") +
                        " too small for the only live sequence "
                        f"(uid={live[0].uid}, needs "
                        f"{pages_for(live[0].length + 1, self.page_size)}"
                        f" pages of {self.allocator.num_pages - 1}) — "
                        "raise num_pages or lower max_new_tokens" +
                        (" (spill tiers hold parked sessions, not the "
                         "live working set)" if self.tiering is not None
                         else ""))
                if self.tiering is not None:
                    # park the coldest stalled sequence in the spill
                    # tiers (restore = page upload); destructive evict
                    # only when the tiers are full
                    victim = self._pick_victim(stalled)
                    if not self._spill(victim):
                        self._evict(victim)
                else:
                    self._evict(max(stalled, key=lambda r: r.uid))
            return lc_produced
        (token_ids, positions, kv_lens, page_indices, cu_q_lens, num_seqs,
         new_kv_dest, sample_rows, samplers, counts) = plan
        args = self._upload_all(
            (token_ids[None], positions[None], kv_lens, page_indices,
             cu_q_lens, num_seqs, new_kv_dest, sample_rows))
        st.prefill_tokens += counts["prefill_tokens"]
        st.prefill_key_tokens += counts["prefill_key_tokens"]
        st.decode_key_token_steps += counts["decode_key_tokens"]
        with st.stage("dispatch", program="fused", **counts):
            st.dispatches += 1
            sel_logits, self.cache, moe = self._fused_step_fn()(
                self.params, self.cache, *args)
        self._moe_dispatched(moe, "fused", 1)
        st.ticks += 1
        produced = self._sample(sel_logits, samplers)
        # a handoff prefill that just sampled its first token parks NOW
        # — same step — so the router's next export pulls it without an
        # extra tick of decode-side latency
        self._reap(handoff_sweep=True)
        return produced + lc_produced

    def _admit(self) -> None:
        for i in range(self.max_seqs):
            if not self.waiting:
                break
            if self.slots[i] is not None:
                continue
            req = self.waiting[0]
            if req.ctx is None:
                req.ctx = req.prompt
            need = self._admit_need(req)
            if self.allocator.pages_for(need) > self.num_pages - 1:
                # defense in depth behind put_request's submit-time
                # check: an unschedulable head would deadlock the FIFO
                # queue forever — drop it and fail loudly.  The HBM
                # bound stays hard with tiering on: a sequence's WORKING
                # SET must be device-resident to decode; the tiers only
                # hold whole parked sessions.
                self.waiting.popleft()
                if self.tiering is not None:
                    self.tiering.drop(req.uid)
                    raise ValueError(
                        f"request uid={req.uid} needs "
                        f"{self.allocator.pages_for(need)} KV pages to "
                        f"admit ({need} tokens) but the HBM tier owns "
                        f"{self.num_pages - 1} usable pages — a working "
                        "set can only decode device-resident; raise "
                        "num_pages (spill tiers hold parked sessions, "
                        "not live ones)")
                raise ValueError(
                    f"request uid={req.uid} needs "
                    f"{self.allocator.pages_for(need)} KV pages to admit "
                    f"({need} tokens) but the engine owns "
                    f"{self.num_pages - 1} usable pages — it can never "
                    "be scheduled, even after full eviction")
            fresh, probe = self._fresh_pages_needed(req, need,
                                                    touch=False)
            avail = self.allocator.free_pages
            if self._pfx is not None:
                avail += self._pfx.reclaimable(
                    exclude={e.key for e in probe})
            if fresh > avail:
                break                      # FIFO: wait for pages to free
            self.waiting.popleft()
            req.slot = i
            if req.spilled is None:
                req.prefill_done = 0       # spilled reqs keep their rows
                req.pc_parent, req.pc_pages, req.pc_cached = ROOT_HASH, \
                    0, 0
            self.slots[i] = req
            self._draft_len[i] = 0
            self.page_table[i, :] = -1
            if not self._attach_and_allocate(req, need):
                # a tombstone revival failed mid-attach and the shrunken
                # match needs more fresh pages than the pool holds —
                # undo and retry this head next step (rare: the probe
                # assumed the revivals would land)
                self.allocator.free(i)
                self.page_table[i, :] = -1
                self.slots[i] = None
                req.slot = -1
                self.waiting.appendleft(req)
                break
            self.request_latency.on_admit(req.uid)
            if trace.enabled:
                trace.event("request_admit", cat="request", uid=req.uid,
                            slot=i, pages=self.allocator.owned(i),
                            cached_pages=req.pc_pages
                            if req.spilled is None else 0,
                            spilled=req.spilled is not None)
            if req.spilled is not None:
                self._restore(req)

    def _attach_and_allocate(self, req: Request, need: int) -> bool:
        """Build slot ``req.slot``'s page run for an admission covering
        ``need`` tokens: prefix-cache attaches (and tombstone revivals)
        first, spill-hold re-attaches for a restoring request, COW of
        the last page on a FULL prefix match, then fresh pages for the
        remainder.  False when revival failures shrank the match below
        what the pool can cover (caller undoes the admission)."""
        i = req.slot
        st = self.host_stats
        attached = 0
        full = False
        if req.spilled is not None:
            shared = [int(p) for p in req.spilled.get("shared_pages", ())]
            if shared:
                self.allocator.attach(i, shared)
                self.page_table[i, :len(shared)] = shared
                attached = len(shared)
        elif self._pfx is not None and not req.lc:
            with st.stage("prefix"):
                entries = self._pfx.match(req.ctx, touch=True)
                pages_att: List[int] = []
                parent = ROOT_HASH
                for e in entries:
                    if e.state == "spilled" and not self._pfx_revive(e):
                        break
                    pages_att.append(int(e.page))
                    parent = e.key
                attached = len(pages_att)
                if attached:
                    self.allocator.attach(i, pages_att)
                    self.page_table[i, :attached] = pages_att
                    full = attached * self.page_size == req.ctx_len
                    # the matched pages are already prefilled: schedule
                    # only the suffix.  A FULL match still re-prefills
                    # its LAST token through the fused program (after a
                    # COW below) so the first sampled token's logits
                    # come from the same compiled program as a cache-off
                    # run — the bit-parity contract
                    req.prefill_done = attached * self.page_size - (
                        1 if full else 0)
                    req.pc_parent = parent
                    req.pc_pages = attached
                    req.pc_cached = req.prefill_done
                    st.prefix_hits += 1
                    st.prefix_hit_pages += attached
                    st.prefix_hit_tokens += req.prefill_done
                else:
                    st.prefix_misses += 1
        grow_n = self.allocator.pages_for(need) - attached
        want_free = grow_n + (1 if full else 0)
        self._reclaim_for(want_free)
        if want_free > self.allocator.free_pages:
            return False
        if full:
            # the write frontier (position ctx_len - 1) lands in the
            # last matched page: make it private before the re-prefill
            old, new = self.allocator.cow(i, attached - 1)
            if new != old:
                self._cow_copy(old, new)
                self.page_table[i, attached - 1] = new
        if grow_n > 0:
            pages = self.allocator.grow(i, grow_n)
            self.page_table[i, attached:attached + grow_n] = pages
        return True

    def _ensure_pages(self, slot: int, upto_tokens: int) -> bool:
        """Grow ``slot``'s page run to cover ``upto_tokens`` cache
        positions; False when the pool can't (scheduler backpressure —
        the sequence sits this tick out, or gets evicted)."""
        upto_tokens = min(upto_tokens, self.max_seq_len)
        need = pages_for(upto_tokens, self.page_size)
        have = self.allocator.owned(slot)
        if need <= have:
            return True
        self._reclaim_for(need - have)
        if need - have > self.allocator.free_pages:
            return False
        pages = self.allocator.grow(slot, need - have)
        self.page_table[slot, have:have + len(pages)] = pages
        return True

    def _reclaim_for(self, n_pages: int) -> None:
        """Ask the prefix index to give back cold single-reference pages
        when the free list can't cover ``n_pages``.  Safe against any
        page a live slot uses: those hold a slot reference on top of the
        index's, so the index never reclaims them."""
        if self._pfx is None:
            return
        short = n_pages - self.allocator.free_pages
        if short > 0:
            self._pfx.reclaim(short)

    def _handoff_sweep(self) -> None:
        """Park every handoff-marked sequence whose prefill AND first
        token are done: spill its KV to the tiers (the decode replica's
        restore is then bit-identical to never having left) or — when
        the pages can't travel (tiering off, tiers full) — fold it to a
        re-prefill continuation, the degraded leg.  Either way the
        sequence leaves its slot and waits in ``_handoff_ready`` for
        the router's ``export_handoff`` pull."""
        for r in list(self.slots):
            if (r is None or not r.handoff or r.done or r.lc
                    or r.prefill_done < r.ctx_len or not r.generated):
                continue
            if self._spill(r):
                self.waiting.remove(r)     # _spill parked it there
            else:
                # planned handoff, not pool exhaustion — fold inline
                # instead of via _evict (no eviction counter/log)
                self.allocator.free(r.slot)
                self.page_table[r.slot, :] = -1
                self.slots[r.slot] = None
                self._draft_len[r.slot] = 0
                r.ctx = np.concatenate(
                    [r.prompt, np.asarray(r.generated, np.int32)])
                r.prefill_done = 0
                r.pc_parent, r.pc_pages, r.pc_cached = ROOT_HASH, 0, 0
                r.slot = -1
                self.handoff_folds += 1
            self._handoff_ready.append(r)
            if trace.enabled:
                trace.event("request_handoff_ready", cat="request",
                            uid=r.uid, spilled=r.spilled is not None)

    def _evict(self, r) -> None:
        """Requeue ``r`` as a CONTINUATION: its pages return to the
        pool, and on re-admission it re-prefills prompt + its own
        generated tokens (greedy continuations are exact; sampled ones
        resume from the same sampled prefix)."""
        from deepspeed_tpu.utils.logging import logger

        self.allocator.free(r.slot)
        self.page_table[r.slot, :] = -1
        self.slots[r.slot] = None
        self._draft_len[r.slot] = 0
        r.ctx = np.concatenate(
            [r.prompt, np.asarray(r.generated, np.int32)])
        r.prefill_done = 0
        r.pc_parent, r.pc_pages, r.pc_cached = ROOT_HASH, 0, 0
        r.slot = -1
        self.waiting.append(r)             # back of the queue: the freed
        self.evictions += 1                # pages go to older work first
        if trace.enabled:
            trace.event("request_evict", cat="request", uid=r.uid,
                        ctx_tokens=int(r.ctx.size))
        logger.info(f"ragged engine: evicted uid={r.uid} "
                    f"({r.ctx.size} ctx tokens) — KV pool exhausted; "
                    "requeued as continuation")

    # -- tiered KV spill/restore (HBM <-> host RAM <-> NVMe) -------------

    def _tier_jits(self):
        """The two fixed-shape page-movement programs (compiled once,
        first spill/restore — the zero-new-compilation guard covers the
        steady state after that):

        - gather: ``[pages_per_seq]`` page rows out of every cache leaf
          (indices padded with the trash page 0 — always allocated).
        - scatter: the same rows back in, donating the cache buffers;
          pad indices point one past the pool and ``mode='drop'``
          discards them, so a partial restore writes exactly its live
          rows."""
        if self._tier_gather is None:
            def gather(cache, idx):
                return jax.tree_util.tree_map(
                    lambda l: jnp.take(l, idx, axis=0), cache)

            def scatter(cache, idx, rows):
                return jax.tree_util.tree_map(
                    lambda l, r: l.at[idx].set(r, mode="drop"),
                    cache, rows)

            self._tier_gather = jax.jit(gather)
            self._tier_scatter = jax.jit(scatter, donate_argnums=(0,))
        return self._tier_gather, self._tier_scatter

    def _live_tokens(self, r) -> int:
        """Cache rows that hold real KV for ``r`` RIGHT NOW.  Decode
        phase: the last sampled token's row is written by the NEXT tick
        (at position length-1), so ``length - 1`` rows are live.
        Prefill phase: exactly the prefilled prefix."""
        if r.prefill_done >= r.ctx_len:
            return r.length - 1
        return r.prefill_done

    def _spill(self, r) -> bool:
        """Park ``r`` in the spill tiers instead of destroying its KV:
        gather its live page rows, hand them (device_get) to the tier
        store, and requeue it as a RESTORABLE continuation.  Returns
        False when the tiers can't take it (caller falls back to
        ``_evict``'s re-prefill).  Restore is bit-identical to never
        having spilled: the exact cache rows come back, ``prefill_done``
        and the pending last token are preserved."""
        live = self._live_tokens(r)
        n_live = pages_for(live, self.page_size) if live > 0 else 0
        if n_live == 0 or self.tiering is None:
            return False
        # shared-prefix pages (refcount > 1: the prefix index or another
        # sequence also holds them) are maximally hot — they never leave
        # HBM.  Take a spill-hold (+1 ref) on the maximal shared PREFIX
        # of the live run and spill only the private suffix; no owner
        # ever writes a shared page (COW precedes any write), so the
        # rows stay valid for re-attach at restore
        j = 0
        while j < n_live and self.allocator.refcount(
                int(self.page_table[r.slot, j])) > 1:
            j += 1
        n_priv = n_live - j
        if n_priv > 0 and not self.tiering.can_spill(n_priv):
            return False
        shared = [int(p) for p in self.page_table[r.slot, :j]]
        st = self.host_stats
        with st.stage("spill"):
            if n_priv > 0:
                gather, _ = self._tier_jits()
                idx = np.zeros((self.pages_per_seq,),
                               np.int32)               # pad: trash
                idx[:n_priv] = self.page_table[r.slot, j:n_live]
                rows = jax.device_get(gather(self.cache,
                                             jnp.asarray(idx)))
                try:
                    self.tiering.spill(
                        r.uid,
                        [np.asarray(leaf[:n_priv]) for leaf in
                         jax.tree_util.tree_leaves(rows)],
                        n_priv)
                except RuntimeError:
                    return False           # tiers full: caller evicts
            r.spilled = {"last_tok": int(self._last_tokens[r.slot]),
                         "n_pages": n_priv, "live_tokens": live,
                         "shared_pages": shared}
            for p in shared:
                self.allocator.incref(p)   # spill-hold survives free()
        from deepspeed_tpu.utils.logging import logger

        self.allocator.free(r.slot)
        self.page_table[r.slot, :] = -1
        self.slots[r.slot] = None
        self._draft_len[r.slot] = 0
        r.slot = -1
        self.waiting.append(r)             # back of the queue, like evict
        self.spills += 1
        self.request_latency.on_spill(r.uid)
        if trace.enabled:
            trace.event("request_spill", cat="request", uid=r.uid,
                        pages=int(n_live), live_tokens=int(live))
        logger.info(f"ragged engine: spilled uid={r.uid} ({n_live} pages,"
                    f" {live} live tokens) to the KV tiers — restore is "
                    "a page upload, not a re-prefill")
        return True

    def _restore(self, req) -> None:
        """Upload ``req``'s spilled page rows into its freshly allocated
        pages (slot already assigned by ``_admit``).  On unrecoverable
        corruption (:class:`KVRestoreError` — the store already
        quarantined the payload) the request falls back to a plain
        re-prefill continuation at the FRONT of the queue, loudly."""
        from deepspeed_tpu.inference.kv_tiering import KVRestoreError
        from deepspeed_tpu.utils.logging import logger

        st = self.host_stats
        info = req.spilled
        n = info["n_pages"]                 # private pages in the tiers
        shared = info.get("shared_pages", [])
        jn = len(shared)                    # shared prefix re-attached by
        t_restore0 = time.perf_counter()    # _attach_and_allocate
        try:
            if n > 0:
                with st.stage("restore"):
                    arrs = self.tiering.restore(req.uid)
                    _, scatter = self._tier_jits()
                    # pad indices past the pool: mode='drop' drops them
                    idx = np.full((self.pages_per_seq,), self.num_pages,
                                  np.int32)
                    idx[:n] = self.page_table[req.slot, jn:jn + n]
                    leaves = []
                    for a in arrs:
                        full = np.zeros(
                            (self.pages_per_seq,) + a.shape[1:], a.dtype)
                        full[:n] = a
                        leaves.append(jnp.asarray(full))
                    rows = jax.tree_util.tree_unflatten(
                        self._cache_treedef, leaves)
                    self.cache = scatter(self.cache, jnp.asarray(idx),
                                         rows)
            self._last_tokens[req.slot] = info["last_tok"]
            req.spilled = None
            # release the spill-holds: the slot attach owns its refs now
            for p in shared:
                self.allocator.decref(p)
            self.restores += 1
            self.request_latency.on_restore_stall(
                req.uid, time.perf_counter() - t_restore0)
            if trace.enabled:
                trace.event("request_restore", cat="request",
                            uid=req.uid, pages=int(n),
                            shared_pages=int(jn))
        except KVRestoreError as e:
            self.allocator.free(req.slot)
            self.page_table[req.slot, :] = -1
            self.slots[req.slot] = None
            self._draft_len[req.slot] = 0
            for p in shared:
                self.allocator.decref(p)
            req.ctx = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])
            req.prefill_done = 0
            req.pc_parent, req.pc_pages, req.pc_cached = ROOT_HASH, 0, 0
            req.spilled = None
            req.slot = -1
            self.waiting.appendleft(req)   # front: it already waited
            self.request_latency.on_restore_stall(
                req.uid, time.perf_counter() - t_restore0)
            self.request_latency.on_error(req.uid)
            if trace.enabled:
                trace.event("request_restore_failed", cat="request",
                            uid=req.uid, page=int(e.page))
            logger.error(
                f"ragged engine: restore of uid={req.uid} failed "
                f"verification (page {e.page}; payload quarantined) — "
                "re-prefilling the session from its own tokens")

    def _pfx_demote(self, e) -> bool:
        """Index-LRU hook: move a single-reference prefix page's KV into
        the tiered store under the entry's PREFIX-HASH key (not a uid —
        one restore serves every future waiter).  Returns False when the
        tiers can't take it, in which case the index drops the entry."""
        if self.tiering is None or not self.tiering.can_spill(1):
            return False
        st = self.host_stats
        with st.stage("spill"):
            gather, _ = self._tier_jits()
            idx = np.zeros((self.pages_per_seq,), np.int32)  # pad: trash
            idx[0] = e.page
            rows = jax.device_get(gather(self.cache, jnp.asarray(idx)))
            try:
                self.tiering.spill(
                    PrefixCacheIndex.tier_key(e.key),
                    [np.asarray(leaf[:1]) for leaf in
                     jax.tree_util.tree_leaves(rows)],
                    1)
            except RuntimeError:
                return False
        if trace.enabled:
            trace.event("prefix_demote", cat="request",
                        key=PrefixCacheIndex.tier_key(e.key))
        return True

    def _pfx_revive(self, e) -> bool:
        """Bring a demoted prefix page back into a fresh pool page so an
        admission can attach it.  On failure the tombstone is dropped —
        the requester falls back to computing that page itself."""
        from deepspeed_tpu.inference.kv_tiering import KVRestoreError

        if self.tiering is None:
            self._pfx._drop(e)
            return False
        if self.allocator.free_pages < 1:
            return False
        st = self.host_stats
        page = self.allocator.take_page()
        try:
            with st.stage("restore"):
                arrs = self.tiering.restore(
                    PrefixCacheIndex.tier_key(e.key))
                _, scatter = self._tier_jits()
                idx = np.full((self.pages_per_seq,), self.num_pages,
                              np.int32)
                idx[0] = page
                leaves = []
                for a in arrs:
                    full = np.zeros((self.pages_per_seq,) + a.shape[1:],
                                    a.dtype)
                    full[:1] = a
                    leaves.append(jnp.asarray(full))
                rows = jax.tree_util.tree_unflatten(self._cache_treedef,
                                                    leaves)
                self.cache = scatter(self.cache, jnp.asarray(idx), rows)
        except KVRestoreError:
            self.allocator.decref(page)
            self._pfx._drop(e)             # payload quarantined: forget
            return False
        self._pfx.revive(e, page)
        if trace.enabled:
            trace.event("prefix_revive", cat="request",
                        key=PrefixCacheIndex.tier_key(e.key),
                        page=int(page))
        return True

    def _cow_copy(self, src: int, dst: int) -> None:
        """Fixed-shape device copy of one page row across every cache
        leaf — the copy half of copy-on-write.  ``src``/``dst`` are
        traced int32 operands, so every COW reuses one compiled
        program."""
        st = self.host_stats
        if self._cow_jit is None:
            self._cow_jit = jax.jit(
                lambda cache, s, d: jax.tree_util.tree_map(
                    lambda l: l.at[d].set(l[s]), cache),
                donate_argnums=(0,))
        with st.stage("prefix"):
            self.cache = self._cow_jit(self.cache, jnp.int32(src),
                                       jnp.int32(dst))
        st.prefix_cow_copies += 1
        if trace.enabled:
            trace.event("prefix_cow", cat="request", src=int(src),
                        dst=int(dst))

    def audit_kv_sharing(self) -> Dict[str, int]:
        """Refcount-conservation audit: every physical page's refcount
        must equal the number of holders that can reach it — slot
        page-table rows, resident prefix-index entries, and spill-holds
        on parked requests' shared prefixes.  Spilled payloads (tiers)
        hold no pool pages by construction.  Delegates the per-page
        equality to :meth:`PageAllocator.audit`."""
        external: Dict[int, int] = {}
        if self._pfx is not None:
            for e in self._pfx._entries.values():
                if e.state == "resident":
                    external[e.page] = external.get(e.page, 0) + 1
        for r in itertools.chain(self.waiting, self._handoff_ready):
            if r.spilled is not None:
                for p in r.spilled.get("shared_pages", ()):
                    external[p] = external.get(p, 0) + 1
        for s, r in enumerate(self.slots):
            if r is None:
                continue
            row = [int(p) for p in self.page_table[s] if p >= 0]
            owned = self.allocator.owned_pages(s)
            assert row == owned, (
                f"slot {s}: page-table row {row} != allocator "
                f"ownership {owned}")
        return self.allocator.audit(external=external)

    def _pick_victim(self, stalled):
        """Coldest page-stalled sequence: least-recently scheduled
        (tie: youngest) — it has waited longest for pages and will wait
        longest for them, so parking it frees the most useful HBM."""
        return min(stalled,
                   key=lambda r: (self._last_sched[r.slot], -r.uid))

    def _flat_dest(self, slot: int, pos: int) -> int:
        page = self.page_table[slot, pos // self.page_size]
        assert page > 0, "write into unallocated page"
        return int(page) * self.page_size + pos % self.page_size

    def _plan_tick(self):
        """Host-side SplitFuse plan: one decode token per ready sequence
        plus prompt chunks for prefilling sequences, all in ONE batch."""
        self._stalled = []
        decode_rs = []
        for r in self.slots:
            if r is None or r.done or r.lc or r.prefill_done < r.ctx_len:
                continue
            # the tick writes the last generated token at position
            # length-1, so pages must cover `length` tokens
            if self._ensure_pages(r.slot, r.length):
                decode_rs.append(r)
            else:
                self._stalled.append(r)    # out of pages: sit this tick out
        prefill_rs = sorted(
            (r for r in self.slots
             if r is not None and not r.lc
             and r.prefill_done < r.ctx_len),
            key=lambda r: r.uid)
        if not decode_rs and not prefill_rs:
            return None

        token_ids = np.zeros((self.T,), np.int32)
        positions = np.zeros((self.T,), np.int32)
        new_kv_dest = np.full((self.T,), 0, np.int32)   # trash page row 0
        kv_lens = np.zeros((self.max_seqs,), np.int32)
        # metadata rows are indexed by PACKED sequence number j, not slot:
        # pack each active slot's page-table row as it is assigned a j
        page_indices = np.full((self.max_seqs, self.pages_per_seq), -1,
                               np.int32)
        cu_q_lens = np.zeros((self.max_seqs + 1,), np.int32)
        sample_rows = np.zeros((self.max_seqs,), np.int32)
        samplers: List[Tuple[Request, int, bool]] = []  # (req, seq_j, sample?)
        # what the tick asks of the device, for the dispatch span and
        # the counters: a token at position p attends to p + 1 keys
        counts = dict(prefill_tokens=0, prefill_key_tokens=0,
                      decode_key_tokens=0)

        budget = self.T - len(decode_rs)
        takes: Dict[int, int] = {}
        for r in prefill_rs:
            take = min(budget, r.ctx_len - r.prefill_done)
            if take <= 0:
                continue                   # batch-budget-limited, not stalled
            if not self._ensure_pages(r.slot, r.prefill_done + take):
                # partial growth: cover what the pool allows this tick
                # (cold prefix-index pages count — _ensure_pages
                # reclaims them on demand)
                coverable = (self.allocator.owned(r.slot) +
                             self.allocator.free_pages +
                             (self._pfx.reclaimable()
                              if self._pfx is not None else 0)
                             ) * self.page_size
                take = min(take, coverable - r.prefill_done)
                if take <= 0:
                    self._stalled.append(r)     # page-limited
                    continue
                self._ensure_pages(r.slot, r.prefill_done + take)
            takes[r.uid] = take
            budget -= take

        # pack sequences in slot order (any fixed order works; the kernel
        # sees sequences via cu_q_lens row j)
        stalled_uids = {r.uid for r in self._stalled}
        t = 0
        j = 0
        for r in [s for s in self.slots if s is not None]:
            if r.done or r.lc or r.uid in stalled_uids:
                continue
            self._last_sched[r.slot] = self._sched_seq
            if r.prefill_done >= r.ctx_len:                 # decode: 1 tok
                p = min(r.length - 1, self.max_seq_len - 1)
                token_ids[t] = self._last_tokens[r.slot]
                positions[t] = p
                new_kv_dest[t] = self._flat_dest(r.slot, p)
                page_indices[j] = self.page_table[r.slot]
                kv_lens[j] = p + 1
                counts["decode_key_tokens"] += p + 1
                cu_q_lens[j + 1] = cu_q_lens[j] + 1
                sample_rows[j] = t
                samplers.append((r, j, True))
                t += 1
                j += 1
            else:                                           # prefill chunk
                take = takes.get(r.uid, 0)
                if take <= 0:
                    continue
                lo = r.prefill_done
                token_ids[t:t + take] = r.ctx[lo:lo + take]
                pos = np.arange(lo, lo + take)
                positions[t:t + take] = pos
                pg = self.page_table[r.slot, pos // self.page_size]
                assert (pg > 0).all(), "write into unallocated page"
                new_kv_dest[t:t + take] = (pg * self.page_size +
                                           pos % self.page_size)
                r.prefill_done += take
                counts["prefill_tokens"] += int(take)
                counts["prefill_key_tokens"] += int(
                    take * lo + take * (take + 1) // 2)
                if self._pfx is not None:
                    # publish every freshly completed full page to the
                    # prefix index (chain-hash it onto the request's
                    # registered prefix); identical in both pipeline
                    # modes — registration keys off prefill progress,
                    # not dispatch timing
                    page = self.page_size
                    while (r.pc_pages + 1) * page <= r.prefill_done:
                        k = r.pc_pages
                        r.pc_parent = self._pfx.register(
                            r.pc_parent, r.ctx[k * page:(k + 1) * page],
                            int(self.page_table[r.slot, k]))
                        r.pc_pages += 1
                if trace.enabled:
                    trace.event("prefill_chunk", cat="request",
                                uid=r.uid, take=int(take),
                                prefill_done=int(r.prefill_done),
                                ctx_len=int(r.ctx_len))
                page_indices[j] = self.page_table[r.slot]
                kv_lens[j] = r.prefill_done
                cu_q_lens[j + 1] = cu_q_lens[j] + take
                finishes = r.prefill_done >= r.ctx_len
                if finishes:
                    self.request_latency.on_prefill_done(
                        r.uid, r.ctx_len - r.pc_cached, r.pc_cached)
                sample_rows[j] = t + take - 1
                samplers.append((r, j, finishes))
                t += take
                j += 1
        cu_q_lens[j + 1:] = cu_q_lens[j]
        if j == 0:
            return None
        return (token_ids, positions, kv_lens, page_indices, cu_q_lens,
                np.asarray([j], np.int32), new_kv_dest, sample_rows,
                samplers, counts)

    def _sample(self, sel_logits, samplers) -> int:
        """One host sync per tick; one sampling call per distinct config."""
        produced = 0
        groups: Dict[Tuple, List[Tuple[Request, int]]] = {}
        for r, seq_j, wants in samplers:
            if not wants:
                continue
            key = (r.do_sample, r.temperature, r.top_k, r.top_p)
            groups.setdefault(key, []).append((r, seq_j))
        st = self.host_stats
        for (do_sample, temp, top_k, top_p), pairs in groups.items():
            # eager device work (a gather and a handful of tiny sampling
            # programs per distinct row count): its own bracket
            with st.stage("sample"):
                rows = np.asarray([j for _, j in pairs])
                sub = None
                if do_sample:
                    # (uid, position)-keyed streams: the draw for token n
                    # of request u is the same whatever else is
                    # co-batched, so seeded sampling is reproducible
                    # under prefix-cache admission reordering (same
                    # convention as the decode block's per-tick keys)
                    sub = position_keys(
                        self._sample_base,
                        jnp.asarray([r.uid for r, _ in pairs], jnp.int32),
                        jnp.asarray([r.length - 1 for r, _ in pairs],
                                    jnp.int32))
                dev_toks = sample_logits(
                    sel_logits[rows], sub, do_sample=do_sample,
                    temperature=temp, top_k=top_k, top_p=top_p)
            toks = np.asarray(self._fetch(dev_toks))
            with st.stage("harvest"):
                for (r, _), tok in zip(pairs, toks):
                    r.generated.append(int(tok))
                    self._last_tokens[r.slot] = int(tok)
                    produced += 1
                    self.request_latency.on_tokens(r.uid,
                                                   len(r.generated))
                    self._maybe_finish(r)
        return produced

    def _maybe_finish(self, req: Request) -> None:
        if (len(req.generated) >= req.max_new_tokens or
                (req.eos_token_id is not None and req.generated and
                 req.generated[-1] == req.eos_token_id) or
                req.length >= self.max_seq_len):
            req.done = True

    def _reap(self, handoff_sweep: bool = False) -> None:
        """Free the slots of finished sequences (one ``reap`` bracket,
        with the fused tick's post-sample handoff sweep inside it)."""
        with self.host_stats.stage("reap"):
            self._reap_finished()
            if handoff_sweep:
                self._handoff_sweep()

    def _reap_finished(self) -> None:
        for i, r in enumerate(self.slots):
            if r is not None and r.done:
                if r.lc and self.tiering is not None:
                    # drop the parked middle groups with the session
                    for g in range(r.lc_parked):
                        self.tiering.drop(f"mid-{r.uid}-{g}")
                if (self._pfx is not None and not r.lc
                        and self._pfx_cfg.include_generated):
                    # opt-in: publish full pages of generated tokens
                    # before the refs drop.  Decode pages come from a
                    # different compiled program than fused prefill, so
                    # the bit-parity contract is waived for hits on them
                    # (documented on the config knob).
                    seq = np.concatenate(
                        [r.ctx, np.asarray(r.generated, np.int32)[
                            r.ctx_len - r.prompt.size:]])
                    written = r.length - 1   # last token never written
                    page = self.page_size
                    while (r.pc_pages + 1) * page <= written:
                        k = r.pc_pages
                        r.pc_parent = self._pfx.register(
                            r.pc_parent, seq[k * page:(k + 1) * page],
                            int(self.page_table[i, k]))
                        r.pc_pages += 1
                self.finished.append(r)
                self.slots[i] = None
                self.allocator.free(i)
                self.page_table[i, :] = -1
                self._draft_len[i] = 0
                rec = self.request_latency.on_finish(r.uid)
                if trace.enabled:
                    trace.event("request_reap", cat="request", uid=r.uid,
                                tokens=len(r.generated))
                if rec is not None:
                    breaches = (self.slo.record_request(rec)
                                if self.slo is not None else [])
                    if (trace.sampling and trace.enabled
                            and self._tail_sampler is not None):
                        keep, why = self._tail_sampler.should_promote(
                            breached=bool(breaches),
                            errored=rec["errors"] > 0)
                        if keep:
                            if breaches:
                                why = f"{why}:{','.join(breaches)}"
                            trace.promote(r.uid, rec["submit_t"],
                                          rec["finish_t"], reason=why)

    # -- introspection ----------------------------------------------------

    def cache_bytes(self) -> int:
        """Device bytes held by the paged KV cache (scales with
        ``num_pages``, the blocked-KV contract the reference's allocator
        provides — NOT with ``max_seqs * max_seq_len``)."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(self.cache))

    # -- convenience ------------------------------------------------------

    def generate_all(self, prompts: List[np.ndarray], **kw
                     ) -> Dict[int, np.ndarray]:
        """Submit everything, run until drained (batch convenience API —
        the serving loop calls ``step`` itself)."""
        uids = set(self.put_request(p, **kw) for p in prompts)
        outs: Dict[int, np.ndarray] = {}
        while self.has_work():
            self.step()
            for uid, toks in self.get_outputs():
                if uid in uids:
                    outs[uid] = toks
                else:
                    # foreign request (submitted outside this call): keep
                    # it claimable by the caller's own get_outputs()
                    self._unclaimed[uid] = toks
        return outs
