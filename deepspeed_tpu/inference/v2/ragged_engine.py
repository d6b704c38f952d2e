"""FastGen-equivalent ragged / continuous-batching inference engine.

TPU-native re-design of the reference InferenceEngineV2 stack
(``inference/v2/engine_v2.py:30``, ragged batching ``inference/v2/ragged/``,
Dynamic SplitFuse scheduling from the FastGen blog): requests of different
lengths share one running decode batch — sequences join the moment a slot
frees, never waiting for the batch to drain.

This module is the LOOP: admission, chunk budgeting, the choice of program
per step, sampling, finish detection, the request API — plain Python on the
host, as the reference's scheduler tier is.  Two neighbours hold the rest:

- :mod:`~deepspeed_tpu.inference.v2.kv_state` owns every form of a
  sequence's KV state (reference ``ragged/blocked_allocator.py:1``,
  ``ragged/kv_cache.py``): fixed-size pages addressed by a per-sequence
  page table, so device memory scales with pages, not
  ``max_seqs x max_seq_len``; the spill tiers; the prefix index.
- :mod:`~deepspeed_tpu.inference.v2.programs` builds what the device runs:
  ONE fused SplitFuse tick (``engine_v2.py:107`` — a static ``[1, T]``
  batch carries a decode token for EVERY ready sequence AND this tick's
  prefill chunks; raggedness lives in int32 metadata, so exactly one XLA
  program compiles) and the on-device multi-tick decode block (everyone
  past prefill: ``decode_block_size`` ticks in a ``lax.scan`` with
  on-device sampling, the host round trip amortized to 1/K).  Attention is
  the vLLM-TPU ragged paged Pallas kernel on TPU and an XLA-compilable
  reference on CPU (``inference/paged/``).

**Tensor-parallel serving**: pass a ``topology`` with a >1 ``tensor``
axis — weights shard by AutoTP name rules, the paged KV pool shards over
its head dim, and the fused tick runs under GSPMD with the paged attention
shard_map-manual over ``tensor``.

**The pipelined host path** (``pipeline=True``, the default): the host
plans from COUNTS and the device runs behind it, through ONE in-order
queue of dispatched, un-folded work.

- *What is queued.*  Every fused tick and every decode block the loop
  dispatches (``_Work``), in dispatch order.  Both sample on the device
  and hand every slot's newest token to the next program there (a tick's
  ``last_tok`` result, a block's ``last_tok`` carry: the same ``[max_seqs]``
  vector, ``_dev_last_tok`` between them), so a row of the next program
  reads its token where it was sampled (``tok_src`` in a tick, the carry in
  a block).  ``Request.pending`` counts a request's tokens that are
  dispatched and not folded (a tick adds one, a block up to
  ``decode_block_size``): lengths, budgets, page growth and every finish
  by count are exact at dispatch.
- *The decode blocks' carry* (``last_tok``/``pos``/``active``/
  ``remaining``) and per-tick metadata (page table, sampler configs, eos
  ids) stay RESIDENT on the device while block follows block (``_dev``):
  uploaded at entry, built from counts, re-uploaded only when the page
  table grows.  A row that ends by its count is retired by the device's
  own ``active`` carry (it writes page 0 from then on); the carry lives on,
  less that request.  An admission releases the carry and leaves the
  blocks in the queue; the request's ticks are dispatched behind them and
  the blocks that follow start from the device's tokens again.
- *Folding* takes entries from the queue's HEAD, in order, one blocking
  ``device_get`` for all it takes (``_fold``), and reaps who finished: the
  freed slot's pages go back to the allocator there.  After each dispatch
  (``_settle``) the loop folds every tick with tokens and every entry a
  sequence ends with ONCE SOMETHING IS DISPATCHED BEHIND IT (a stream sees
  a tick's token, a prompt's first token and a finish when their program
  ends, while the chip already has the next to run), and, when
  ``harvest_interval`` entries are un-folded, those the run-ahead window
  (``async_depth`` entries in flight; a submit past it joins the oldest)
  has joined already: a copy, no wait.  None of this waits for the
  queue's tail.
- *The loop waits for the tail* (``_drain``: the chip idles for the host's
  turn) only where the next plan depends on a token's VALUE or on what a
  reap gives back, which it reads off its own state, and says why in
  ``host_stats.drain_reasons``: a live row with an EOS id (``eos``: its
  finish is the token's to say); a handoff prefill (``handoff``: parked
  the step its token lands); a plan short of pages, an eviction, a spill,
  or a finish in flight while a request waits that what is free before it
  cannot seat (``pages``); ``cancel``, ``sync``, ``drain``,
  ``export_handoff`` / ``export_parked`` (``api``).  Where
  nothing is left to run, folding the tail is the end of the work and
  counts as no drain.  ``has_work()`` is true while anything is queued.

Every request's tokens are ``pipeline=False``'s, greedy and seeded (a
greedy row is the argmax of its own logits; a drawn token's key is
``position_keys(sample_base, uid, position)``, never the schedule's), under
mid-run arrivals, finishes, eviction and EOS.  What is NOT kept is the
schedule: the pipelined loop admits at the host step where
``pipeline=False`` would, but the request's first tick runs behind the
blocks in flight (``async_depth`` and the one just dispatched, at most)
instead of after a drain, so the batch a row shares a tick with can differ
from the unpipelined run's.  ``host_stats``
(:class:`~deepspeed_tpu.inference.common.HostStageStats`) breaks the
host path into plan/upload/dispatch/device/harvest per dispatch, and
counts ``drains``, ``fused_ticks`` / ``decode_blocks`` / ``admissions``
and, of each, those that went out with work still in flight (``*_ahead``).

Models: anything llama-shaped in the zoo (Llama, Mistral, Qwen2, Mixtral,
OLMoE, ... — per-token positions thread through attention, which the
ragged path requires).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.inference.common import HostStageStats
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.telemetry import (RequestLatencyTracker, device_scopes,
                                     trace)
from deepspeed_tpu.utils.async_stage import BoundedAsyncStage, StageTimers
from deepspeed_tpu.inference.paged import (TickShape, describe,
                                           kv_dequant_path,
                                           paged_attention_path, pages_for)
from deepspeed_tpu.inference.prefix_cache import ROOT_HASH
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.kv_state import KVState
from deepspeed_tpu.utils.logging import log_dist

# rows of one tile of every chunk kernel a fused tick runs (the scans'
# ``CHUNK``, the attentions' ``TILE`` / ``_TILE_TOKENS`` under ``ops/``;
# ``tests/unit/inference/test_plan_tiles.py`` holds the seven to it).  The
# plan of a tick hands prompt rows out in whole tiles of it and packs them
# from the tick's row 0, so no kernel is asked for a tile of a few rows.
# The engine's to know: no option, no config key
ROW_TILE = 128
# ... where the rows a chunk gives up for it are this few.  A tile part
# full costs the chunk kernels a whole tile and the rows in it cost every
# other kernel ``rows / ROW_TILE`` of one, so holding them back pays where
# the chunk kernels are a larger share of a tick than that: at 8 rows, 6 %,
# which the least of them is (``paged_chunk_attention`` in Mistral's ticks,
# 6.8 % of busy time; 26-43 % in the long-prompt models: PERF.md section 6,
# PR 63, where 32 rows held back of 544 read -3.5 %)
ROWS_GIVEN_UP = ROW_TILE // 16


def whole_tiles(rest: int, room: int, first: bool) -> int:
    """The prompt rows a sequence with ``rest`` left takes of ``room``: all
    it has left; else the whole row tiles the room holds, where the part
    tile given up is :data:`ROWS_GIVEN_UP` rows or fewer; else the room.
    ``first``: no chunk leads the tick yet, and the first is never nothing
    (a room under a tile is all a tiny tick or a short pool has)."""
    if rest <= room:
        return rest
    part = room % ROW_TILE
    if part > ROWS_GIVEN_UP or (first and part == room):
        return room
    return room - part


def tiles_aligned(takes, T: int) -> bool:
    """Whether a tick of ``T`` rows that packs the prompt chunks ``takes``
    (rows a chunk, in the tick's order from row 0, the one-row sequences
    behind them) lies in its own row tiles: every chunk of two rows or more
    starts where the whole tiles of those before it end, and together they
    fit the ``T // ROW_TILE`` whole tiles the tick holds.  The rule the
    device evaluates from ``cu_q_lens``
    (``ops/linear_attention.py:tile_layout``'s ``aligned``), where a
    matrix-state layer then reads the tick's rows as its scan's tiles and
    gathers nothing; here it only counts
    (``HostStageStats.aligned_ticks``)."""
    rows = tiles = 0
    for n in takes:
        if n >= 2:
            if rows != tiles * ROW_TILE:
                return False
            tiles += -(-n // ROW_TILE)
        rows += n
    return tiles <= T // ROW_TILE


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
    # tokens that dispatched work (fused ticks, decode blocks) samples on
    # the device and no fetch has folded into ``generated`` yet: a tick
    # adds one, a block up to ``decode_block_size`` (0 wherever host state
    # is exact)
    pending: int = 0
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
        # contains its earlier output); a token still on the device is
        # as much in the sequence as one that was fetched
        return self.prefill_done + len(self.generated) + self.pending - \
            (self.ctx_len - self.prompt.size)

    def fold(self) -> None:
        """Become a re-prefill CONTINUATION: whatever rows the sequence
        had are gone, so its context is its prompt plus its own output,
        prefilled from zero and registered with the prefix index anew
        (greedy continuations are exact; sampled ones resume from the
        same sampled prefix)."""
        self.ctx = np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])
        self.prefill_done = 0
        self.pc_parent, self.pc_pages, self.pc_cached = ROOT_HASH, 0, 0


@dataclasses.dataclass
class _Work:
    """A dispatched program (a fused tick or a decode block) whose tokens
    no fetch has folded into their requests yet: one entry of the engine's
    in-order queue."""
    seq: int                              # its key in the run-ahead window
    block: bool                           # a decode block; else a fused tick
    # a tick: device [max_seqs] tokens by packed sequence; a block: ([K, S]
    # tokens, [K, S] produced-mask)
    toks: Any
    # who gets tokens of it: (request, packed sequence number) of a tick's
    # sampling rows, (request, tokens it added to ``pending``) of a block's
    rows: List[Tuple[Request, int]]
    # its expert-layer and pass-loop counts, for the fetch that folds it
    counts: List[Tuple[Any, Any, Dict[str, Any]]]
    # a sequence ends with it (by its count: nothing else is known ahead)
    finishes: bool = False


class RaggedInferenceEngineV2:
    """``put_request`` -> repeated ``step()`` -> ``get_outputs``.

    One ``step()`` = (admit waiting requests into free slots, reserving
    KV pages) + EITHER one fused SplitFuse tick (any sequence still
    prefilling: prompt chunks of whole row tiles from row 0 of the batch,
    tails and part tiles behind them, then a decode token for every ready
    sequence, in one batch of ``T = max_seqs + prefill_chunk`` rows) OR
    one ``decode_block_size``-tick on-device decode block (everyone
    decoding).
    """

    def __init__(self, model, params: Any = None, max_seqs: int = 8,
                 max_seq_len: int = 512, prefill_chunk: int = 128,
                 rng: Optional[jax.Array] = None, page_size: int = 64,
                 num_pages: Optional[int] = None, topology=None,
                 decode_block_size: int = 8,
                 kv_cache_dtype: Optional[str] = None,
                 kv_pool_bytes: Optional[int] = None,
                 window_pool_bytes: Optional[int] = None,
                 quantize_weights: Optional[str] = None,
                 kv_reserve: str = "on_demand",
                 pipeline: Optional[bool] = None,
                 async_depth: Optional[int] = None,
                 harvest_interval: Optional[int] = None,
                 kv_tiering: Any = None,
                 prefix_cache: Any = None,
                 slo: Any = None,
                 trace_sample: Optional[int] = None,
                 replica: Optional[str] = None,
                 control: Any = None,
                 config: Any = None):
        """The nine options that ``config`` (a ``DeepSpeedInferenceConfig``
        or dict with a ``v2`` subtree) also carries — ``pipeline``,
        ``async_depth``, ``harvest_interval``, ``kv_cache_dtype``,
        ``kv_tiering``, ``prefix_cache``, ``control``, ``slo``,
        ``trace_sample`` — default to None: the config decides
        (:class:`~deepspeed_tpu.inference.config.InferenceV2Config`
        documents each) and an explicit keyword wins.  The three subtrees
        take what their class's ``coerce`` takes.

        ``kv_pool_bytes``: size the pool by a device byte budget —
        ``num_pages`` becomes the exact number of pages (payload + scale
        rows) that fit; ignored when ``num_pages`` is given.
        ``window_pool_bytes``: the device bytes of the pool that a model's
        window layers keep for themselves beside its full layers' (the
        description's ``window``): required for such a model, refused for
        any other — two sizes because they are two pools.
        ``quantize_weights``: see
        :func:`~deepspeed_tpu.inference.v2.programs.prepare_params`.
        ``kv_reserve``: "on_demand" (reference blocked-allocator model —
        admit on prompt-size pages, grow per decode block, evict +
        requeue as a continuation when the pool runs dry) or
        "worst_case" (reserve prompt + max_new_tokens at admission).
        ``slo``: objective strings (``"ttft_ms_p99 <= 150"``) or an
        :class:`~deepspeed_tpu.telemetry.slo.SLOSet`, fed at reap time.
        ``replica``: metric-label identity for scale-out serving."""
        mcfg = getattr(model, "config", None)
        assert dataclasses.is_dataclass(mcfg) and hasattr(mcfg, "decode"), \
            "ragged engine needs a model-zoo module with a decode config"
        assert hasattr(mcfg, "rope_theta"), (
            "ragged batching requires per-token positions through "
            "attention — supported by the Llama family models")
        assert hasattr(mcfg, "paged_decode"), (
            "model config predates paged ragged decode support")

        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.inference.config import (ControlConfig,
                                                    KVTieringConfig,
                                                    PrefixCacheConfig,
                                                    load_inference_config)

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
        placement = dict(mesh=self.mesh, tp=self.tp, device=self._device)

        # config-sourced knobs resolve BEFORE pool sizing: the resolved
        # kv_cache_dtype decides the per-page byte cost a kv_pool_bytes
        # budget divides by (kwarg > config > default)
        o = dict(pipeline=pipeline, async_depth=async_depth,
                 harvest_interval=harvest_interval,
                 kv_tiering=kv_tiering, prefix_cache=prefix_cache,
                 kv_cache_dtype=kv_cache_dtype, slo=slo,
                 trace_sample=trace_sample, control=control)
        if config is not None:
            v2cfg = load_inference_config(config).v2
            o = {k: getattr(v2cfg, k) if v is None else v
                 for k, v in o.items()}
        self.kv_cache_dtype = kv_cache_dtype = (
            "none" if o["kv_cache_dtype"] is None
            else str(o["kv_cache_dtype"]))

        self.page_size = int(page_size)
        self.pages_per_seq = pages_for(max_seq_len, self.page_size)

        # what the model's layers keep for a token (K/V pages, slots a pass,
        # per-slot state, a row of another shape): ONE description, built by
        # the files that own each kind (inference/paged/).  KVState moves
        # ``leaf[page]`` and ``leaf[slot]`` whatever a leaf is; each feature
        # that reads a leaf as something it is not is refused by name
        self.cache_kind = describe(mcfg, TickShape(
            max_seqs + prefill_chunk, max_seqs, self.page_size,
            self.pages_per_seq))
        self.kv_slots = self.cache_kind.kv_slots
        self.state_layers = self.cache_kind.state_layers
        self._refuse_features(
            o, kv_cache_dtype, self.cache_kind.refusals(type(model).__name__))

        if num_pages is None and kv_pool_bytes is not None:
            # byte-accounted sizing: probe the exact per-page device
            # cost (quantized pools count the 1-byte payload AND the
            # fp32 scale rows) and fit as many pages as the budget holds
            # — page 0 is the trash page, so >= 2 keeps one usable
            num_pages = max(2, int(kv_pool_bytes) //
                            programs.paged_kv_page_bytes(
                                model, mcfg, self.page_size, kv_cache_dtype))
        if num_pages is None:
            # full provisioning: every slot can reach max_seq_len. Callers
            # serving long-max_len traffic shrink this — memory then
            # scales with tokens in flight (admission backpressure).
            num_pages = 1 + max_seqs * self.pages_per_seq
        self.num_pages = int(num_pages)
        # a second pool, where the description has layers that keep one
        own = self.cache_kind.window
        if (own is None) != (window_pool_bytes is None):
            raise ValueError(
                f"window_pool_bytes={window_pool_bytes!r}: "
                f"{type(model).__name__} " + (
                    "keeps every layer's pages behind one table (size it "
                    "by num_pages or kv_pool_bytes)" if own is None else
                    f"keeps {own.layers} of its {own.of} layers' pages in a "
                    "pool of their own, which has no default size"))
        self.window_num_pages = 0 if own is None else max(
            2, int(window_pool_bytes) // programs.paged_kv_page_bytes(
                model, mcfg, self.page_size, kv_cache_dtype, window=True))

        # the model and its weights in serving form; ``param_form`` is
        # what every program builder needs to know of the stored params
        self.cfg, params, self.param_form = programs.prepare_params(
            model, params, programs.serving_config(
                mcfg, max_seq_len=max_seq_len, page_size=self.page_size,
                num_pages=self.num_pages, tensor_parallel=self.tp > 1,
                kv_cache_dtype=kv_cache_dtype,
                window_num_pages=self.window_num_pages),
            quantize_weights, tp=self.tp)
        self.model = type(model)(self.cfg)
        self.params = programs.place_params(params, **placement)
        self.max_seqs = max_seqs
        self.max_seq_len = max_seq_len
        self.prefill_chunk = prefill_chunk
        self.T = max_seqs + prefill_chunk          # fused batch width
        self.decode_block_size = max(int(decode_block_size), 1)
        self.evictions = 0
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        # base key for position-keyed sampling (sampling.position_keys):
        # derived from the pristine engine rng BEFORE any split, so a
        # drawn token's key depends only on (engine seed, uid, position)
        # — never on how dispatches happened to be scheduled
        self._sample_base = jax.random.fold_in(self.rng, 0x5EED)

        self.pipeline = True if o["pipeline"] is None else bool(o["pipeline"])
        self.async_depth, self.harvest_interval = (
            max(int(default if o[k] is None else o[k]), 1)
            for k, default in (("async_depth", 2), ("harvest_interval", 4)))
        self.replica = "" if replica is None else str(replica)
        self.host_stats = HostStageStats(replica=self.replica)
        # dispatched, un-folded work (fused ticks and decode blocks alike),
        # in dispatch order; folding takes it from the head
        self._queue: Deque[_Work] = deque()
        self._work_seq = itertools.count()
        # the shared bounded-window substrate bounds the device's run-ahead
        # at async_depth entries: a submit past it first joins the oldest
        # (block_until_ready on its tokens, bracketed as device wait).  Same
        # substrate instance shape as the NVMe moment stream and the
        # router's per-replica feed loop; its timers are
        # serving_stages()["pipeline_window"]
        self._pipe_timers = StageTimers(cat="serving")
        self._window = BoundedAsyncStage(
            waiter=self._work_ready, depth=self.async_depth,
            timers=self._pipe_timers, name="serving_pipeline")
        # per-request lifecycle latency — always on, tracer or not
        self.request_latency = RequestLatencyTracker(replica=self.replica)

        # -- SLO objectives + tail-based trace sampling: evaluated at
        # reap time on the host, never on the traced dispatch path --
        from deepspeed_tpu.telemetry.slo import SLOSet, TailSampler

        slo = o["slo"]
        self.slo = (slo if isinstance(slo, SLOSet)
                    else SLOSet(list(slo)) if slo else None)
        n = (int(o["trace_sample"]) if o["trace_sample"] is not None
             else trace.sample_n)
        self._tail_sampler = (TailSampler(n=n)
                              if (trace.sampling or n > 0) else None)
        if n > 0 and not trace.sampling:
            # an explicit engine/config N arms the tracer's sampling
            # mode the same way DSTPU_TRACE_SAMPLE does
            trace.configure(enabled=True, sampling=True, sample_n=n)
        # the decode blocks' device-resident carry and metadata while
        # block follows block (None: the next block builds them anew)
        self._dev: Optional[Dict[str, Any]] = None

        # the built programs, cached: the fused tick with its logits, and a
        # greedy and a sampled variant of the sampling tick and of the block
        self._step_fn = None
        self._sample_step_cache: Dict[bool, Any] = {}
        self._decode_block_cache: Dict[bool, Any] = {}
        self._scoped: set = set()       # programs device_scopes knows

        # -- every form of a sequence's KV state: pool, tiers, index --
        tier_cfg = KVTieringConfig.coerce(o["kv_tiering"])
        self.kv = KVState(
            programs.init_cache(self.model, max_seqs=max_seqs, width=self.T,
                                **placement),
            num_pages=self.num_pages, page_size=self.page_size,
            max_seqs=max_seqs, max_seq_len=max_seq_len,
            kv_reserve=kv_reserve, tier_cfg=tier_cfg,
            pfx_cfg=PrefixCacheConfig.coerce(o["prefix_cache"]),
            host_stats=self.host_stats,
            request_latency=self.request_latency,
            window=None if own is None else own.host_pool(
                self.window_num_pages, self.page_size, max_seqs,
                self.pages_per_seq, self.T, self.host_stats))
        # the most pages any dispatch found in use, and what the cache kind
        # says of its leaves as they are allocated: on every
        # ``engine.dispatch`` span and in ``serving_stages()``
        self._pages_peak = 0
        self._kind_attrs = self.cache_kind.span_attrs(self.kv.cache)

        self._uid = itertools.count()
        self.waiting: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_seqs
        self.finished: List[Request] = []
        self._unclaimed: Dict[int, np.ndarray] = {}
        # (device counts, recorder, span attributes) of dispatched
        # programs whose expert-layer or pass-loop counts no fetch has
        # brought to the host yet
        self._counts_pending: List[Tuple[Any, Any, Dict[str, Any]]] = []
        # each slot's pending token: sampled, not yet written to the pool
        self.last_tokens = np.zeros((max_seqs,), np.int32)
        # the same as dispatched work keeps it on the device, each program
        # handing it to the next (tick to tick, a block's carry to the tick
        # behind it, a tick to the blocks' new carry): right in the slots
        # whose newest token queued work sampled (``pending``), which is
        # all that is read of it (a put, not a program: nothing compiles to
        # make it)
        self._dev_last_tok = jax.device_put(
            np.zeros((max_seqs,), np.int32), self._device)
        # what the last plan of a tick found of the pool: sequences that
        # sit the tick out, and whether any growth fell short; and whether
        # its chunks lie in the tick's own row tiles (``tiles_aligned``)
        self._stalled: List[Request] = []
        self._page_limited = False
        self._plan_aligned = False
        # generated-token count already reported per uid (stream_deltas)
        self._stream_cursor: Dict[int, int] = {}
        self.cancels = 0
        # disaggregated serving: sessions whose prefill (+ first token)
        # finished and which now wait for the router to pull them via
        # export_handoff — out of slots and out of the waiting queue
        self._handoff_ready: List[Request] = []
        self.handoffs = 0              # sessions exported to a decoder
        self.handoff_folds = 0         # handoffs degraded to re-prefill
        # queued spilled sequences prefetched ahead of a possible reap
        # (online-tunable via the kv.prefetch_lookahead knob)
        self.prefetch_lookahead = max(int(tier_cfg.prefetch_lookahead), 1)
        # victim coldness: step counter, each slot's last scheduled step
        self._sched_seq = 0
        self._last_sched = np.zeros((max_seqs,), np.int64)
        log_dist(
            f"RaggedInferenceEngineV2: max_seqs={max_seqs} "
            f"max_seq_len={max_seq_len} prefill_chunk={prefill_chunk} "
            f"pages={self.num_pages}x{self.page_size} tp={self.tp} "
            f"decode_block={self.decode_block_size} "
            f"pipeline={self.pipeline} depth={self.async_depth} "
            f"harvest={self.harvest_interval}"
            f"{self.kv.describe()} "
            f"(paged KV, fused SplitFuse step)", ranks=[0])

        self._arm_control(ControlConfig.coerce(o["control"]))

    # what serving/, control/, the benchmark and tests read of the KV
    # state; everything else goes through ``self.kv``
    allocator = property(lambda self: self.kv.allocator)
    page_table = property(lambda self: self.kv.page_table)
    cache = property(lambda self: self.kv.cache)
    tiering = property(lambda self: self.kv.tiering)
    prefix_index = property(lambda self: self.kv.pfx)
    spills = property(lambda self: self.kv.spills)
    restores = property(lambda self: self.kv.restores)

    def _arm_control(self, control) -> None:
        """The closed-loop control plane (:mod:`deepspeed_tpu.control`):
        it ticks on this host loop (``step()`` counts engine steps), with
        no thread of its own.  ``DSTPU_CONTROL=0`` disarms regardless of
        config, leaving the structurally pre-control engine."""
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
                    # knobs (decode_block) are still fair game
                    applied = knobs.apply_profile(prof.knobs)
                    if applied:
                        log_dist(
                            f"control plane seeded from host profile "
                            f"{prof.key}: {applied}", ranks=[0])
                self._controller = Controller(
                    knobs, engine_signal_feed(self),
                    rules=([prefetch_rule()] if self.tiering is not None
                           else []),
                    **{k: getattr(control, k) for k in (
                        "objective", "settle", "hysteresis", "cooldown",
                        "guard_window", "guard_reverts", "freeze",
                        "smooth")})

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
        self.kv.check_capacity(total)

    def put_request(self, prompt, **kw) -> int:
        """Queue a request; raises ``ValueError`` AT SUBMIT TIME
        (:meth:`validate_request`) for one that could never be
        scheduled — admitting it would deadlock the FIFO queue behind an
        unschedulable head.  (``ValueError``, not ``assert``: these
        guard USER input and must stay loud under ``python -O``.)"""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        max_new = int(kw.get("max_new_tokens", 64))
        self.validate_request(prompt, max_new)
        req = Request(uid=next(self._uid), prompt=prompt, **kw)
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
        requests).  ``audit_kv_sharing()`` stays clean across any
        interleaving — the front door's client-disconnect path depends
        on it.

        Returns the stage the request was cancelled at (``"queued"`` /
        ``"spilled"`` / ``"handoff"`` / ``"prefill"`` / ``"decode"`` /
        ``"finished"``) or ``None`` for an unknown uid (never
        submitted, or already collected)."""
        stage: Optional[str] = None
        # fold whatever is in flight first: the target may be mid-decode
        # inside it, and host state must be exact for the slot release
        # below to be authoritative (the target may FINISH during this
        # fold — then it lands in ``finished``); a device carry that
        # still counts the target as live is built anew
        self._drain("api")
        dv = self._dev
        if dv is not None and any(r.uid == uid for r in dv["reqs"]):
            self._release_carry()
        # parked: in the waiting queue (never admitted, an evicted
        # continuation, or spilled out to the tiers), or for a
        # prefill->decode handoff the router never collected
        for parked in (self.waiting, self._handoff_ready):
            for r in [r for r in parked if r.uid == uid]:
                parked.remove(r)
                stage = ("handoff" if parked is self._handoff_ready
                         else "spilled" if r.spilled is not None
                         else "queued")
                self.kv.forget(r)
        if stage is None:
            # resident in a slot (prefill or decode phase)
            for r in self.slots:
                if r is None or r.uid != uid:
                    continue
                stage = ("prefill" if r.prefill_done < r.ctx_len
                         else "decode")
                self._unseat(r)
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

    def has_work(self) -> bool:
        # queued work holds its sequences in their slots until a step
        # folds it
        return (bool(self.waiting) or bool(self._queue) or
                any(s is not None for s in self.slots))

    def sync(self) -> int:
        """Fold every dispatched, un-folded tick and decode block back
        into host request state (the decode blocks' device carry lives
        on); returns the tokens folded.  No-op when the pipeline is off
        or idle.  Callers that read ``slots[*].generated`` / ``finished``
        between ``step()`` calls (benchmark boundaries, draining
        shutdown) call this first."""
        return self._drain("api")

    def drain(self) -> Dict[int, np.ndarray]:
        """Run until every queued/resident request finishes; returns
        ``{uid: tokens}`` for everything reaped along the way (the
        replica-shutdown half of the engine handle — ``close()``
        releases resources after)."""
        outs: Dict[int, np.ndarray] = {}
        self._drain("api")
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
        continuation) and return portable session blobs
        (:meth:`KVState.export`) for :meth:`import_parked` on another
        replica.  A session that folds to a re-prefill continuation on
        the way is output-identical under greedy decode, just re-paying
        its prefill."""
        sessions: List[Dict[str, Any]] = []
        self._drain("api")
        # pending prefill->decode handoffs the router never pulled ride
        # the same retirement: they are parked sessions like any other
        for r in itertools.chain(self.waiting, self._handoff_ready):
            blob = self.kv.export(r)
            if trace.enabled:
                trace.event("request_export", cat="request", uid=r.uid,
                            spilled=blob["spill"] is not None)
            sessions.append(blob)
        self.waiting.clear()
        self._handoff_ready = []
        return sessions

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
            if sp is not None and not self.kv.adopt(sp, req):
                req.fold()
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
        self._drain("api")
        for r in self._handoff_ready:
            blob = self.kv.export(r)
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

        Online-safe knobs: ``harvest_interval`` is read fresh after each
        dispatch; ``async_depth`` re-sizes the run-ahead window in place
        (the substrate back-pressures on the next submit).
        ``decode_block_size`` is baked into the compiled block's shape,
        so it carries ``recompiles=True`` — reachable only
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
            self._window.depth = self.async_depth

        def _set_block(v):
            self.decode_block_size = max(int(v), 1)
            self._scoped.difference_update(
                self._decode_block_cache.values())
            self._decode_block_cache.clear()

        reg.register(Knob(
            "engine.harvest_interval",
            lambda: self.harvest_interval, _set_harvest,
            lo=1, hi=16, step=1, kind="int",
            doc="dispatched, un-folded ticks and decode blocks at which "
                "the oldest completed ones are folded (no wait for any "
                "still in flight)"))
        reg.register(Knob(
            "engine.async_depth",
            lambda: self.async_depth, _set_depth,
            lo=1, hi=8, step=1, kind="int",
            doc="dispatched ticks and decode blocks the device may run "
                "ahead of the host"))
        reg.register(Knob(
            "engine.decode_block_size",
            lambda: self.decode_block_size, _set_block,
            lo=1, hi=16, step=1, kind="int", recompiles=True,
            doc="device ticks per decode block (compiled shape)"))
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

            reg.register(Knob(
                "kv.prefetch_lookahead",
                lambda: self.prefetch_lookahead, _set_lookahead,
                lo=1, hi=64, step=1, kind="int",
                doc="queued spilled sequences prefetched ahead of reap"))
        return reg

    def _refuse_features(self, o: Dict[str, Any], kv_cache_dtype: str,
                         refusals) -> None:
        """Refuse by name each asked-for feature that cannot serve a model
        whose cache is not plain per-head K/V pages.  ``refusals``: the
        description's ``(model_is, {feature: why})`` pairs (what the model
        keeps; why that feature cannot carry it); ``o``: the resolved
        options."""
        from deepspeed_tpu.inference.config import (KVTieringConfig,
                                                    PrefixCacheConfig)

        asked = {       # feature: (asked for, as the message names it)
            "prefix_cache": (PrefixCacheConfig.coerce(
                o["prefix_cache"]).enabled, "prefix_cache"),
            "tiering": (KVTieringConfig.coerce(o["kv_tiering"]).enabled,
                        "kv_tiering"),
            "quantized": (kv_cache_dtype != "none",
                          f"kv_cache_dtype={kv_cache_dtype!r}"),
            "tensor_parallel": (self.tp > 1, "tensor-parallel serving")}
        for model_is, refuses in refusals:
            for feature, (wanted, named) in asked.items():
                if wanted and feature in refuses:
                    raise NotImplementedError(
                        f"{named}: {model_is}{refuses[feature]}")

    def serving_stages(self) -> Dict[str, Any]:
        """Per-dispatch host-path breakdown + ``host_bound_fraction``
        (see :class:`~deepspeed_tpu.inference.common.HostStageStats`);
        with tiering on, the tier store's flat stats ride along as a
        ``kv_tiering`` sub-dict (``MonitorMaster`` flattens it to
        ``Serving/kv_tiering/<name>`` series)."""
        out = self.host_stats.serving_stages()
        if self.tiering is not None:
            out["kv_tiering"] = self.tiering.stats()
        if self.kv.pfx is not None:
            st = self.host_stats
            pc = self.kv.pfx.stats()
            pc.update(hit_requests=st.prefix_hits,
                      miss_requests=st.prefix_misses,
                      hit_tokens=st.prefix_hit_tokens,
                      cow_copies=st.prefix_cow_copies)
            out["prefix_cache"] = pc
        head_dim = int(getattr(self.cfg, "head_dim", 0))
        if self.kv_cache_dtype != "none":
            from deepspeed_tpu.inference.common import kv_quant_block

            route = kv_dequant_path(head_dim)
            out["kv_quant"] = kv_quant_block(
                self.kv.cache, self.kv_cache_dtype, route, self.num_pages)
        else:
            route = paged_attention_path(head_dim)
        # the attention read route this backend gives this pool (a Pallas
        # kernel, or the XLA twin it stands in for: ops/routes.py) and what
        # the programs traced so far took, as the cache kind names them
        out["kernels"] = self.cache_kind.kernels(route)
        st = self.host_stats
        if st.moe_rows:
            from deepspeed_tpu.moe.sharded_moe import GROUPED_MATMUL
            from deepspeed_tpu.ops import routes
            # its two keys, as the trace of this process's programs left them
            out["kernels"].update(routes.taken(prefix=GROUPED_MATMUL))
            out["moe"] = {"rows": st.moe_rows,
                          "experts_hit": st.moe_experts_hit,
                          "load_max": st.moe_load_max,
                          # less ``experts_hit``: the visits of the grouped
                          # matmul that needed no new bank
                          "visits": st.moe_visits}
            if st.moe_experts_held:
                # a layer that holds a share of its router's experts: the
                # counts above are the held banks'; all the router's copies
                out["moe"].update(rows_routed=st.moe_rows_routed,
                                  experts_held=st.moe_experts_held)
        out.update(self.cache_kind.stages(
            st, self.kv.cache, self.cfg, out["kernels"], self._pages_peak))
        # the head of a fused tick multiplies the rows it samples, gathered
        # from the hidden states: ``max_seqs`` of the tick's ``T``
        out["fused_head"] = {"form": "sampled-rows", "rows": self.max_seqs,
                             "tick_rows": self.T}
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
            # the run-ahead window's substrate counters (submitted /
            # joined ticks and blocks, submit_wait back-pressure)
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
        self.kv.close(itertools.chain(self.waiting, self._handoff_ready))

    # -- host<->device funnels (every transfer is counted/timed) ---------

    def upload(self, x):
        """Host -> device metadata transfer.  The pipelined decode loop
        must NOT call this in steady state (metadata is device-resident;
        ``host_stats.meta_uploads`` asserts it in tests)."""
        return self.upload_all((x,))[0]

    def upload_all(self, arrays) -> List[Any]:
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

    def fetch(self, tree):
        """Blocking device -> host fetch — the serving loop's only sync
        point (``host_stats.blocking_gets`` counts them)."""
        with self.host_stats.stage("device"):
            self.host_stats.blocking_gets += 1
            if not self._counts_pending:
                return jax.device_get(tree)
            # the expert layers' and the pass loop's counts of the
            # dispatches since the last fetch ride on this one: no round
            # trip of their own
            pending, self._counts_pending = self._counts_pending, []
            tree, counts = jax.device_get((tree, [p[0] for p in pending]))
        for c, (_, record, attrs) in zip(counts, pending):
            record(c, attrs)
        return tree

    def _record_moe(self, counts, attrs: Dict[str, Any]) -> None:
        """One dispatch's expert-layer counts, as a span of their own in
        the profiler's host plane and in ``serving_stages()['moe']``."""
        rows, hit, load, visits, *more = (int(v) for v in counts)
        st = self.host_stats
        # four, then ``bias_swaps`` where the router has a selection bias,
        # then the two of a layer that holds a share of its router's experts
        # (``programs.moe_counts``)
        swaps, held = more[:len(more) % 2], more[len(more) % 2:]
        if held:
            # the four above are what the HELD banks saw; every copy the
            # router made, and the experts held, beside them
            st.moe_rows_routed += held[0]
            st.moe_experts_held += held[1]
            attrs = {**attrs, "moe_rows_routed": held[0],
                     "moe_experts_held": held[1]}
        st.moe_rows += rows
        st.moe_experts_hit += hit
        st.moe_load_max = max(st.moe_load_max, load)
        st.moe_visits += visits
        if swaps:
            # a router with a selection bias: tokens whose chosen set is
            # not the top k of the scores alone
            st.moe_bias_swaps += swaps[0]
            attrs = {**attrs, "bias_swaps": swaps[0]}
        with trace.bracket("engine.moe", cat="serving", moe_rows=rows,
                           moe_experts_hit=hit, moe_load_max=load,
                           moe_visits=visits, **attrs):
            pass

    def _record_loop(self, exit_cdf, attrs: Dict[str, Any]) -> None:
        """One dispatch's exit-gate sums of a looped model, as a span of
        their own (``engine.loop``) and in ``serving_stages()['loop']``:
        ``exit_cdf_<u>`` is the probability of having stopped by the end
        of pass ``u`` (``u < U``), summed over the dispatch's live rows
        and ticks."""
        cdf = [float(v) for v in exit_cdf]
        st = self.host_stats
        st.loop_rows += int(attrs["rows"])
        st.loop_exit_cdf = [a + b for a, b in zip(
            st.loop_exit_cdf or [0.0] * len(cdf), cdf)]
        with trace.bracket("engine.loop", cat="serving", **attrs,
                           **{f"exit_cdf_{u + 1}": v
                              for u, v in enumerate(cdf)}):
            pass

    def _work_ready(self, work: _Work) -> _Work:
        """The run-ahead window's waiter: joining a tick or a decode
        block means waiting for its device tokens (run-ahead bound, NOT a
        fetch: a fold later brings the tokens of all it takes in one
        blocking get)."""
        with self.host_stats.stage("device"):
            jax.block_until_ready(work.toks)
        return work

    # -- the compiled programs (built by programs.py, cached here) --------

    def fused_step(self):
        """The SplitFuse tick with its logits as the result
        (:func:`~deepspeed_tpu.inference.v2.programs.build_fused_step`),
        for whoever checks them; the loop's own ticks are
        :meth:`fused_sample_step`'s."""
        if self._step_fn is None:
            self._step_fn = programs.build_fused_step(
                self.model, **self.param_form)
        return self._step_fn

    def fused_sample_step(self, sampled: bool):
        """The program of every SplitFuse tick of the loop, greedy or
        ``sampled``: the tick samples on the device and hands every slot's
        newest token to the next (:func:`~deepspeed_tpu.inference.v2.
        programs.build_fused_sample_step`)."""
        if sampled not in self._sample_step_cache:
            self._sample_step_cache[sampled] = \
                programs.build_fused_sample_step(
                    self.model, sampled=sampled,
                    sample_base=self._sample_base, **self.param_form)
        return self._sample_step_cache[sampled]

    def decode_block(self, sampled: bool):
        """The on-device decode block, greedy or ``sampled``
        (:func:`~deepspeed_tpu.inference.v2.programs.build_decode_block`)."""
        if sampled not in self._decode_block_cache:
            self._decode_block_cache[sampled] = programs.build_decode_block(
                self.model, sampled=sampled, max_seqs=self.max_seqs,
                block=self.decode_block_size, page_size=self.page_size,
                max_seq_len=self.max_seq_len,
                sample_base=self._sample_base, **self.param_form)
        return self._decode_block_cache[sampled]

    def _run(self, fn, *args):
        """Dispatch a compiled program.  The first dispatch of each notes
        it with ``telemetry.device_scopes`` (its name in a trace and the
        avals it is about to compile for), so device time can be joined to
        layer kinds afterwards; every later one pays a membership test."""
        if fn not in self._scoped:
            self._scoped.add(fn)
            device_scopes.register(getattr(fn, "__name__", "program"), fn,
                                   args)
        return fn(*args)

    def _block_arrays(self, reqs: List[Request]):
        """Host numpy decode-block state for ``reqs`` (shared by the
        unpipelined per-block rebuild and the pipelined loop's entry
        upload), from counts alone: a token still on the device is in
        ``length`` and off the budget."""
        S = self.max_seqs
        # a copy: folds write ``last_tokens`` in place under the upload
        last_tok = np.array(self.last_tokens, np.int32)
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
            remaining[s] = self._budget(r)
            if r.eos_token_id is not None:
                eos_ids[s] = r.eos_token_id
            do_sample[s] = r.do_sample
            temperature[s] = r.temperature
            top_k[s] = r.top_k
            top_p[s] = r.top_p
            seeds[s] = r.uid
        return (last_tok, pos, active, remaining, eos_ids, do_sample,
                temperature, top_k, top_p, seeds)

    def _budget(self, r: Request) -> int:
        """Tokens ``r`` may still be given, those on the device counted
        as given."""
        return r.max_new_tokens - len(r.generated) - r.pending

    def _fold_block(self, reqs: List[Request], toks: np.ndarray,
                    mask: np.ndarray) -> int:
        """Fold one harvested [K, S] block into request state."""
        produced = 0
        for r in reqs:
            new = toks[mask[:, r.slot], r.slot]
            r.generated.extend(int(t) for t in new)
            produced += int(new.size)
            if new.size:
                self.last_tokens[r.slot] = r.generated[-1]
                # harvest-time token visibility: the honest host-side
                # TTFT/TPOT timestamp under the deferred-harvest pipeline
                self.request_latency.on_tokens(r.uid, len(r.generated))
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
        args = self.upload_all(
            (last_tok, pos, active, remaining, self._tables(),
             eos_ids, do_sample, temperature, top_k, top_p, seeds))
        if trace.enabled:
            trace.event("decode_block", cat="request",
                        uids=[r.uid for r in reqs],
                        ticks=self.decode_block_size)
        with self._decode_dispatch(reqs):
            (cache, new_last, _pos, _active, _remaining, toks, mask,
             moe, loop) = self._run(
                self.decode_block(sampled),
                self.params, self.kv.cache, *args, sub)
        self.kv.cache = cache
        self._counts_pending += self._count_entries(
            moe, loop, "decode_block", self.decode_block_size, len(reqs))
        st.ticks += self.decode_block_size
        toks, mask, new_last = self.fetch((toks, mask, new_last))
        st.harvests += 1
        with st.stage("harvest"):
            toks = np.asarray(toks)                     # [K, S]
            mask = np.asarray(mask)                     # [K, S]
            # np.array: device_get returns a READ-ONLY view; the
            # SplitFuse tick assigns into last_tokens per sampled token
            self.last_tokens = np.array(new_last)
            produced = self._fold_block(reqs, toks, mask)
            for r in reqs:
                self._maybe_finish(r)
            self._reap()
        return produced

    def _count_entries(self, moe, loop, program: str, ticks: int, rows: int
                       ) -> List[Tuple[Any, Any, Dict[str, Any]]]:
        """A dispatch's expert-layer and pass-loop counts (device arrays;
        None for a model without expert layers, or whose layers run once)
        as entries of ``_counts_pending``, where they wait for a fetch.
        ``rows``: its token rows (a fused tick) or live slots (a decode
        block) at dispatch."""
        out = []
        if moe is not None:
            out.append((moe, self._record_moe, {"program": program,
                                                "ticks": ticks}))
        if loop is not None:
            out.append((loop, self._record_loop, {"program": program,
                                                  "ticks": ticks,
                                                  "rows": rows}))
        return out

    def _decode_dispatch(self, reqs: List[Request]):
        """The ``dispatch`` bracket of one plain decode block, with the
        counts its roofline needs: ``rows`` live sequences run ``ticks``
        token steps, the first reading ``key_tokens`` context tokens in
        all (each row's length as the host counts it at dispatch) and
        every later one a token more per row; ``ahead``: earlier work
        was still un-folded when it went out."""
        st = self.host_stats
        K = self.decode_block_size
        rows, keys = len(reqs), int(sum(r.length for r in reqs))
        ahead = int(bool(self._queue))
        st.dispatched("decode_block")
        st.decode_blocks += 1
        st.decode_blocks_ahead += ahead
        st.decode_key_token_steps += K * keys + rows * K * (K - 1) // 2
        with st.stage("plan"):
            with st.leaf("plan.counts", rows=rows):
                # tick k of the block asks at position length - 1 + k
                asks = self.cache_kind.counts(st, np.add.outer(
                    [r.length - 1 for r in reqs], np.arange(K)))
            with st.leaf("plan.attrs"):
                attrs = dict(program="decode_block", ahead=ahead, rows=rows,
                             ticks=K, key_tokens=keys, **asks,
                             **self._pool_counts())
        return st.stage("dispatch", **attrs)

    def _pool_counts(self) -> Dict[str, int]:
        """What every ``engine.dispatch`` span says of the pool as the
        allocator has it at dispatch (``pages_in_use`` of ``pages_total``
        usable pages), and what the cache kind says of its leaves."""
        total = self.num_pages - 1
        in_use = total - self.allocator.free_pages
        self._pages_peak = max(self._pages_peak, in_use)
        return {"pages_in_use": in_use, "pages_total": total,
                **self._kind_attrs}

    # -- the queue of dispatched work and the decode blocks' carry --------

    def _admittable(self) -> bool:
        """Could the queue head be seated right now?  Evaluated from
        EXACT global state (pool + slots; a finish gives its slot and
        pages back at the fold that reaps it).  The pipelined loop admits
        at the host step where it turns true, as ``pipeline=False`` does,
        but plans the request's first tick BEHIND the work in flight
        instead of after a drain: the batch a row shares a tick with can
        differ from the unpipelined run's, its own tokens cannot."""
        if not self.waiting or not any(s is None for s in self.slots):
            return False
        req = self.waiting[0]
        return self.kv.can_admit(
            req, self.kv.need(req, self.decode_block_size))

    def _enqueue(self, work: _Work) -> None:
        """Put dispatched work at the queue's tail.  Past ``async_depth``
        entries in flight the window first joins the oldest: a wait with
        work queued behind it, so the chip stays busy."""
        self._queue.append(work)
        self._window.submit(work.seq, work)

    def _settle(self) -> int:
        """After a dispatch, fold what is due WITHOUT waiting for the
        queue's tail: every tick with tokens and every entry a sequence
        ends with, once something is dispatched behind it (a stream sees
        a tick's token and a finish when their program ends, and the chip
        has the next to run meanwhile); and, at ``harvest_interval``
        entries, those the run-ahead window has joined already (complete:
        a copy, no wait)."""
        q = self._queue
        n = len(q)
        due = 0
        for i in range(n - 1):
            w = q[i]
            if w.rows and (w.finishes or not w.block):
                due = i + 1
        if n >= self.harvest_interval:
            due = max(due, min(n - 1, max(n - self.async_depth,
                                          n - self.harvest_interval + 1)))
        return self._fold(due) if due else 0

    def _drain(self, reason: Optional[str]) -> int:
        """Fold EVERYTHING dispatched, the queue's tail included: host
        state is exact after it, and the chip idles while the host takes
        its turn.  For whoever's next plan depends on a token's value or
        on what a reap gives back, each by its ``reason`` in
        ``host_stats.drain_reasons`` (None: the unpipelined loop, or
        nothing is left to run, which counts as no drain)."""
        if not self._queue:
            return 0
        if reason is not None:
            st = self.host_stats
            st.drains += 1
            st.drain_reasons[reason] = st.drain_reasons.get(reason, 0) + 1
        return self._fold(len(self._queue))

    def _fold(self, n: int) -> int:
        """Take the ``n`` oldest entries off the queue, bring their tokens
        in ONE blocking fetch, fold them into their requests in dispatch
        order, and reap who finished."""
        st = self.host_stats
        batch = [self._queue.popleft() for _ in range(n)]
        for w in batch:
            self._window.discard(w.seq)
            self._counts_pending += w.counts
        ticks = any(not w.block for w in batch)
        batch = [w for w in batch if w.rows]
        produced = 0
        if batch:
            st.harvests += 1
            fetched = self.fetch([w.toks for w in batch])
            with st.stage("harvest"):
                for w, toks in zip(batch, fetched):
                    produced += (self._fold_block_work(w, toks) if w.block
                                 else self._fold_tick(w, toks))
        # a handoff prefill that just sampled its first token parks NOW
        # (same step) so the router's next export pulls it without an
        # extra tick of decode-side latency
        self._reap(handoff_sweep=ticks)
        return produced

    def _fold_tick(self, w: _Work, toks) -> int:
        toks = np.asarray(toks)
        for r, seq_j in w.rows:
            r.generated.append(int(toks[seq_j]))
            r.pending -= 1
            self.last_tokens[r.slot] = r.generated[-1]
            self.request_latency.on_tokens(r.uid, len(r.generated))
            self._maybe_finish(r)
        return len(w.rows)

    def _fold_block_work(self, w: _Work, fetched) -> int:
        toks, mask = fetched
        produced = self._fold_block([r for r, _ in w.rows],
                                    np.asarray(toks), np.asarray(mask))
        for r, n in w.rows:
            r.pending -= n
            self._maybe_finish(r)
        return produced

    def _table_now(self) -> np.ndarray:
        """The page table as the blocks about to be dispatched must see
        it: a COPY.  The host's table changes in place (growth, a reap, an
        admission) under blocks still in flight, and a ``device_put`` may
        alias host memory (the CPU backend does when it is aligned) or
        read it after the call returns."""
        tables = self._tables()         # two tables stacked are a copy
        return tables.copy() if tables is self.page_table else tables

    def _owned(self, slot: int) -> Tuple[int, ...]:
        """What any change of ``slot``'s page-table rows shows in."""
        own = self.kv.window
        return (self.allocator.owned(slot),) + (
            () if own is None else own.span(slot))

    def _tables(self) -> np.ndarray:
        """The page table the programs take: ``[max_seqs, pages_per_seq]``,
        or the first pool's and the second's stacked ``[2, ...]`` for a
        model whose window layers keep a pool of their own."""
        own = self.kv.window
        return self.page_table if own is None else np.stack(
            [self.page_table, own.page_table])

    def _release_carry(self) -> None:
        """Let the decode blocks' device carry go: the next blocks build
        theirs anew, from counts.  Every slot's newest token stays on the
        device, for whatever is dispatched next."""
        dv, self._dev = self._dev, None
        self._dev_last_tok = dv["last_tok"]

    def _pipeline_start(self, reqs: List[Request]) -> None:
        """Enter the pipelined decode loop: upload the decode-block
        carry and sampler metadata ONCE, built from counts (a token
        still on the device is in ``length`` and off the budget);
        subsequent blocks chain device-resident state (zero steady-state
        uploads).  With work in flight every row's newest token is on the
        device (the caller saw to it) and ``last_tok`` is what that work
        hands on, not an upload."""
        with self.host_stats.stage("plan"):
            (last_tok, pos, active, remaining, eos_ids, do_sample,
             temperature, top_k, top_p, seeds) = self._block_arrays(reqs)
        host = dict(pos=pos, active=active, remaining=remaining,
                    page_table=self._table_now(), eos_ids=eos_ids,
                    do_sample=do_sample, temperature=temperature,
                    top_k=top_k, top_p=top_p)
        if not self._queue:
            # nothing in flight: the host's tokens are the newest
            host["last_tok"] = last_tok
        self._dev = {
            "reqs": list(reqs),
            "sampled": bool(do_sample.any()),
            "last_tok": self._dev_last_tok,
            **dict(zip(host, self.upload_all(list(host.values())))),
        }
        self._dev["seeds"] = self.upload(seeds)

    def _pipeline_step(self, admit: bool = True) -> Optional[int]:
        """One more decode block, planned from counts and dispatched
        behind whatever is in flight; then fold what is due
        (:meth:`_settle`), or everything where the next plan needs it
        (:meth:`_drain`: a live row with an EOS id, a pool short of
        pages).  Returns None where the step is
        not a block's to take (a request became admittable, or no row
        goes on): the carry is released with the blocks left in the
        queue, and ``_step`` goes on along the fused path."""
        dv = self._dev
        st = self.host_stats
        K = self.decode_block_size
        # (the device's ``active`` carry retired a finished row by
        # itself; it writes page 0 in the blocks that follow)
        live = [r for r in dv["reqs"] if not r.done]
        if not live or (admit and self._admittable()):
            self._release_carry()
            return None
        with st.stage("plan"):
            # grow pages to cover the next block: exact (every sequence
            # that reaches this point un-folded runs to a count)
            grow_ok, table_dirty = True, False
            for r in live:
                s = r.slot
                self._last_sched[s] = self._sched_seq
                want = min(r.length + min(K, self._budget(r)),
                           self.max_seq_len)
                before = self._owned(s)
                if not self.kv.ensure(s, want, r.length - 1):
                    grow_ok = False
                    break
                table_dirty |= self._owned(s) != before
        if not grow_ok:
            # out of pages: fold everything and hand control back to the
            # fused path (stall/evict semantics)
            produced = self._drain("pages")
            if self._dev is not None:
                self._release_carry()
            return produced
        if table_dirty:
            dv["page_table"] = self.upload(self._table_now())
        sub = self._split_rng()
        if trace.enabled:
            trace.event("decode_block", cat="request",
                        uids=[r.uid for r in live],
                        ticks=self.decode_block_size, pipelined=True)
        with self._decode_dispatch(live):
            (self.kv.cache, dv["last_tok"], dv["pos"], dv["active"],
             dv["remaining"], toks, mask, moe,
             loop) = self._run(
                self.decode_block(dv["sampled"]),
                self.params, self.kv.cache, dv["last_tok"], dv["pos"],
                dv["active"], dv["remaining"], dv["page_table"],
                dv["eos_ids"], dv["do_sample"], dv["temperature"],
                dv["top_k"], dv["top_p"], dv["seeds"], sub)
        work = _Work(next(self._work_seq), True, (toks, mask), [],
                     self._count_entries(moe, loop, "decode_block", K,
                                         len(live)))
        st.ticks += K
        with st.stage("plan"):
            # count this block's tokens in, and see who ends with it
            wait = False
            for r in live:
                # exact but for an EOS id, which may end the row on any
                # token: the fold takes back what it gave
                n = int(min(K, self._budget(r),
                            self.max_seq_len - r.length))
                r.pending += n
                self._maybe_finish(r)
                work.rows.append((r, n))
                work.finishes |= r.done
                wait |= r.eos_token_id is not None
            if self.tiering is not None and (wait or work.finishes):
                # a slot may free at the fold of this block: start
                # NVMe->host reads for the spilled sequences the FIFO
                # queue would re-admit first, under the decode block the
                # device is still running
                self.tiering.prefetch(
                    [q.uid for q in
                     itertools.islice(self.waiting,
                                      self.prefetch_lookahead)
                     if q.spilled is not None])
        self._enqueue(work)
        if wait:
            return self._drain("eos")
        return self._settle()

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
        with self.host_stats.step():
            return self._step()

    def _step(self) -> int:
        if self._controller is not None:
            self._control_steps += 1
            if self._control_steps >= self._control_cfg.interval:
                self._control_steps = 0
                with self.host_stats.stage("plan"):
                    self._controller.tick()
        st = self.host_stats
        produced = 0
        if (self.waiting and any(w.finishes for w in self._queue)
                and not self._admittable()):
            # a sequence ends with work in flight, a request waits and
            # what is free BEFORE that finish does not seat it: what the
            # reap frees decides the admission
            produced += self._drain("pages")
        if self._dev is not None:
            out = self._pipeline_step()
            if out is not None:
                return produced + out
            # an admission behind the blocks in flight, or no row goes on
        with st.stage("plan"):
            # park finished handoff prefills BEFORE admission: a
            # handoff request never reaches the decode block / pipeline
            # paths — it leaves its slot the step after its first token
            # lands
            with st.leaf("plan.admit", waiting=len(self.waiting)) as leaf:
                seated = st.admissions
                self._handoff_sweep()
                self._admit()
                live = [r for r in self.slots
                        if r is not None and not r.done]
                decoding_ready = bool(live) and all(
                    r.prefill_done >= r.ctx_len for r in live)
                leaf.add(admitted=st.admissions - seated)
        if self._queue and decoding_ready:
            # the blocks' carry takes every row's newest token from the
            # device: a row whose token only the host has (restored from
            # a spill) goes through one more tick first
            decoding_ready = all(r.pending for r in live)
        with st.stage("plan"), st.leaf("plan.grow", rows=len(live)):
            all_decoding = (
                decoding_ready and self.decode_block_size > 1 and
                all(self.kv.ensure(
                    r.slot,
                    r.length + min(self.decode_block_size,
                                   self._budget(r)), r.length - 1)
                    for r in live))
        if all_decoding:
            if self.pipeline:
                self._pipeline_start(live)
                return produced + self._pipeline_step(admit=False)
            return produced + self._step_decode_block(live)
        with st.stage("plan"):
            takes = self._plan_rows()
        if self._queue and (takes is None or self._page_limited):
            # the pool fell short: what the work in flight gives back
            # comes first, then the plan anew.  Or nothing is left to run
            # (the end of the work, not a drain)
            produced += self._drain("pages" if self._page_limited else None)
            with st.stage("plan"):
                takes = self._plan_rows()
        if takes is None:
            self._reap()
            # every live sequence is page-stalled: evict the youngest as
            # a continuation so the rest (and the queue) can progress
            # (reference scheduler backpressure, engine_v2.py:184)
            stalled = self._stalled
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
                    victim = self.kv.pick_victim(stalled, self._last_sched)
                    if not self._spill(victim):
                        self._evict(victim)
                else:
                    self._evict(max(stalled, key=lambda r: r.uid))
            return produced
        with st.stage("plan"):
            arrays, sampled, sampling, rows, counts = self._pack_tick(takes)
        args = self.upload_all(arrays)
        with st.stage("plan"), st.leaf("plan.attrs"):
            st.prefill_tokens += counts["prefill_tokens"]
            st.prefill_key_tokens += counts["prefill_key_tokens"]
            st.decode_key_token_steps += counts["decode_key_tokens"]
            ahead = int(bool(self._queue))
            attrs = dict(program="fused", ahead=ahead, **counts,
                         head_rows=self.max_seqs, **self._pool_counts())
        with st.stage("dispatch", **attrs):
            st.dispatched("fused")
            toks, self._dev_last_tok, self.kv.cache, moe, loop = \
                self._run(
                    self.fused_sample_step(sampled),
                    self.params, self.kv.cache, self._dev_last_tok, *args)
            # the tick's uploads are let go HERE: ten device buffers take
            # 70 us to free (and each free gives the interpreter lock up),
            # which the frame's end would leave in no bracket
            del args
        st.ticks += 1
        st.fused_ticks += 1
        st.aligned_ticks += self._plan_aligned
        st.fused_ticks_ahead += ahead
        for r, _ in sampling:
            # the token exists from here on, wherever it is: lengths and
            # budgets count it, and who ends by its count is known now
            r.pending += 1
            self._maybe_finish(r)
        self._enqueue(_Work(
            next(self._work_seq), False, toks, sampling,
            self._count_entries(moe, loop, "fused", 1, rows),
            any(r.done for r, _ in sampling)))
        if not self.pipeline:
            return produced + self._drain(None)
        # the next plan is exact without this tick's tokens where nothing
        # they could say changes it: no sequence short of pages, no handoff
        # parked the step its token lands, no end by an EOS id; else they
        # are waited for now, as ever
        why = ("pages" if self._page_limited else
               "handoff" if any(r.handoff for r, _ in sampling) else
               "eos" if any(r.eos_token_id is not None
                            for r, _ in sampling) else None)
        if why is not None:
            return produced + self._drain(why)
        return produced + self._settle()

    def _admit(self) -> None:
        for i in range(self.max_seqs):
            if not self.waiting:
                break
            if self.slots[i] is not None:
                continue
            req = self.waiting[0]
            if req.ctx is None:
                req.ctx = req.prompt
            need = self.kv.need(req, self.decode_block_size)
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
            if not self.kv.can_admit(req, need):
                break                      # FIFO: wait for pages to free
            self.waiting.popleft()
            req.slot = i
            if req.spilled is None:
                req.prefill_done = 0       # spilled reqs keep their rows
                req.pc_parent, req.pc_pages, req.pc_cached = ROOT_HASH, \
                    0, 0
            self.slots[i] = req
            if not self.kv.attach(req, need):
                # a tombstone revival failed mid-attach and the shrunken
                # match needs more fresh pages than the pool holds —
                # undo and retry this head next step (rare: the probe
                # assumed the revivals would land)
                self._unseat(req)
                self.waiting.appendleft(req)
                break
            st = self.host_stats
            st.admissions += 1
            # (its first tick is planned behind the work in flight)
            st.admissions_ahead += int(bool(self._queue))
            if req.spilled is not None:
                self._restore(req)

    def _unseat(self, r: Request, fold: bool = False) -> None:
        """Give ``r``'s slot back — the ONE place on the scheduler's
        side: its pages go through ``KVState.release``, the slot empties
        and the request forgets it.  ``fold``: its rows are lost, so it
        becomes a re-prefill continuation (a spilled, cancelled or
        finished request keeps its cursors)."""
        self.kv.release(r.slot)
        self.slots[r.slot] = None
        r.slot = -1
        if fold:
            r.fold()

    def _handoff_sweep(self) -> None:
        """Park every handoff-marked sequence whose prefill AND first
        token are done: spill its KV to the tiers (the decode replica's
        restore is then bit-identical to never having left) or — when
        the pages can't travel (tiering off, tiers full) — fold it to a
        re-prefill continuation, the degraded leg.  Either way the
        sequence leaves its slot and waits in ``_handoff_ready`` for
        the router's ``export_handoff`` pull."""
        for r in list(self.slots):
            if (r is None or not r.handoff or r.done
                    or r.prefill_done < r.ctx_len or not r.generated):
                continue
            if self._spill(r, reason="handoff"):
                self.waiting.remove(r)     # _spill parked it there
            else:
                # planned handoff, not pool exhaustion — fold inline
                # instead of via _evict (no eviction counter/log)
                self._unseat(r, fold=True)
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

        self._drain("pages")
        self._unseat(r, fold=True)
        self.waiting.append(r)             # back of the queue: the freed
        self.evictions += 1                # pages go to older work first
        if trace.enabled:
            trace.event("request_evict", cat="request", uid=r.uid,
                        ctx_tokens=int(r.ctx.size))
        logger.info(f"ragged engine: evicted uid={r.uid} "
                    f"({r.ctx.size} ctx tokens) — KV pool exhausted; "
                    "requeued as continuation")

    # -- tiered KV spill/restore: the scheduler's half -------------------

    def _spill(self, r, reason: str = "pages") -> bool:
        """Park ``r`` in the spill tiers (``KVState.spill``) and requeue
        it as a RESTORABLE continuation; False when the tiers can't take
        it (caller falls back to ``_evict``'s re-prefill).  ``reason``:
        why the work in flight is drained for it."""
        self._drain(reason)
        if not self.kv.spill(r, self.last_tokens[r.slot]):
            return False
        self._unseat(r)
        self.waiting.append(r)             # back of the queue, like evict
        return True

    def _restore(self, req) -> None:
        """Bring ``req``'s spilled rows back into its freshly attached
        pages (slot already assigned by ``_admit``); a restore that
        fails verification falls back to a plain re-prefill continuation
        at the FRONT of the queue."""
        last_tok = self.kv.restore(req)
        if last_tok is None:
            self._unseat(req, fold=True)
            self.waiting.appendleft(req)   # front: it already waited
        else:
            self.last_tokens[req.slot] = last_tok

    def audit_kv_sharing(self) -> Dict[str, int]:
        """Refcount-conservation audit over every holder of a pool page
        (:meth:`KVState.audit`): slot rows, resident prefix-index
        entries, spill-holds of parked requests."""
        return self.kv.audit(
            self.slots, itertools.chain(self.waiting, self._handoff_ready))

    def _flat_dest(self, slot: int, pos: int, table=None) -> int:
        table = self.page_table if table is None else table
        page = table[slot, pos // self.page_size]
        assert page > 0, "write into unallocated page"
        return int(page) * self.page_size + pos % self.page_size

    def _plan_rows(self):
        """Who runs in the next SplitFuse tick: one decode token per
        ready sequence plus prompt chunks for prefilling sequences, as
        far as the pool backs them.  A prefilling sequence takes the rest
        of its prompt where the tick's rows (and the pool) hold it, else
        the whole row tiles (:data:`ROW_TILE`) they hold where that gives
        up a few rows only (:func:`whole_tiles`: 1,024 of a tick of 1,032
        whatever the decode rows beside it, and the rows left go to no
        one), so a prompt advances by whole tiles up to its tail.  Grows
        pages and changes nothing else, so a plan can be thrown away and
        made again (``_page_limited``: some growth fell short;
        ``_stalled``: who sits the tick out for it).  Returns the prompt
        tokens each prefilling sequence takes, by uid, IN THE ORDER THE
        TICK PACKS THEM (whole-tile chunks, then the others; by uid
        within each); None when nothing can run."""
        with self.host_stats.leaf("plan.rows") as leaf:
            free = self.allocator.free_pages
            self._stalled = []
            self._page_limited = False
            decode_rs = []
            for r in self.slots:
                if r is None or r.done or r.prefill_done < r.ctx_len:
                    continue
                # the tick writes the last generated token at position
                # length-1, so pages must cover `length` tokens
                if self.kv.ensure(r.slot, r.length, r.length - 1):
                    decode_rs.append(r)
                else:
                    self._stalled.append(r)    # out of pages: sits it out
            prefill_rs = sorted(
                (r for r in self.slots
                 if r is not None and r.prefill_done < r.ctx_len),
                key=lambda r: r.uid)
            budget = self.T - len(decode_rs)
            takes: Dict[int, int] = {}
            for r in prefill_rs:
                rest = r.ctx_len - r.prefill_done
                take = whole_tiles(rest, budget, not takes)
                if take <= 0:
                    continue        # batch-budget-limited, not stalled
                if not self.kv.ensure(r.slot, r.prefill_done + take,
                                      r.prefill_done):
                    # partial growth: cover what the pool allows this tick
                    # (cold prefix-index pages count — KVState.ensure
                    # reclaims them on demand), in whole tiles too
                    self._page_limited = True
                    coverable = self.kv.coverable_tokens(r.slot)
                    take = whole_tiles(
                        rest, min(take, coverable - r.prefill_done),
                        not takes)
                    if take <= 0:
                        self._stalled.append(r)     # page-limited
                        continue
                    self.kv.ensure(r.slot, r.prefill_done + take,
                                   r.prefill_done)
                takes[r.uid] = take
                budget -= take
            self._page_limited |= bool(self._stalled)
            # whole-tile chunks lie first, so each starts on a tile of the
            # tick's rows; at most one sequence a tick crosses a boundary
            takes = dict(sorted(
                takes.items(), key=lambda kv: kv[1] % ROW_TILE != 0))
            # the kernels' tiles that the chunks span as they lie
            rows = tiles = 0
            for n in takes.values():
                tiles += (rows + n - 1) // ROW_TILE - rows // ROW_TILE + 1
                rows += n
            # ... and whether they lie in the tick's own tiles: booked when
            # the tick is dispatched, a plan made again counts once
            self._plan_aligned = tiles_aligned(takes.values(), self.T)
            leaf.add(decode_rows=len(decode_rs), prefill_seqs=len(takes),
                     pages_granted=free - self.allocator.free_pages,
                     page_limited=int(self._page_limited),
                     prompt_rows=rows, prompt_tiles=tiles,
                     tiles_aligned=int(self._plan_aligned))
            self.host_stats.prompt_rows += rows
            self.host_stats.prompt_tiles += tiles
            return takes if decode_rs or takes else None

    def _pack_tick(self, takes: Dict[int, int]):
        """``_plan_rows``'s tick as the arrays the fused program takes, all
        in ONE batch: ``(arrays, sampled, sampling, rows, counts)``.  The
        prompt chunks of ``takes`` lie first, in its order (the plan's:
        whole-tile chunks from row 0, so each starts on a row tile of the
        tick, then tails and part tiles), the one-row sequences behind
        them.
        A decode row whose token a tick in flight sampled reads it on the
        device (``tok_src``); ``sampling``: (request, packed sequence
        number) of who samples; ``sampled``: any of them by a draw, so the
        five sampler rows ride along and the sampled program runs."""
        with self.host_stats.leaf("plan.pack") as leaf:
            S = self.max_seqs
            token_ids = np.zeros((self.T,), np.int32)
            tok_src = np.full((self.T,), -1, np.int32)
            positions = np.zeros((self.T,), np.int32)
            # one table, or (a model whose window layers keep a pool of
            # their own) the first pool's and the second's, stacked
            tables = [self.page_table] + (
                [] if self.kv.window is None else [self.kv.window.page_table])
            n = len(tables)
            new_kv_dest = np.full((n, self.T), 0, np.int32)  # trash page row 0
            kv_lens = np.zeros((S,), np.int32)
            # metadata rows are indexed by PACKED sequence number j, not slot:
            # pack each active slot's page-table row as it is assigned a j
            page_indices = np.full((n, S, self.pages_per_seq), -1, np.int32)
            cu_q_lens = np.zeros((S + 1,), np.int32)
            sample_rows = np.zeros((S,), np.int32)
            sample_slot = np.full((S,), -1, np.int32)
            # each packed sequence's slot: where a model with per-slot state
            # finds and leaves it (no other model's program takes it)
            seq_slot = np.zeros((S,), np.int32)
            sampling: List[Tuple[Request, int]] = []
            # what the tick asks of the device, for the dispatch span and
            # the counters: a token at position p attends to p + 1 keys
            counts = dict(prefill_tokens=0, prefill_key_tokens=0,
                          decode_key_tokens=0)

            # the prompt chunks in the plan's order (whole tiles from row 0,
            # then the others), the one-row sequences behind them in slot
            # order: the kernels see sequences via cu_q_lens row j, and every
            # array indexed by j or by row t is built by this one loop
            stalled_uids = {r.uid for r in self._stalled}
            live = {r.uid: r for r in self.slots
                    if r is not None and not r.done
                    and r.uid not in stalled_uids}
            t = 0
            j = 0
            for r in ([live[u] for u in takes if u in live] +
                      [r for r in live.values()
                       if r.prefill_done >= r.ctx_len]):
                self._last_sched[r.slot] = self._sched_seq
                seq_slot[j] = r.slot
                if r.prefill_done >= r.ctx_len:                 # decode: 1 tok
                    p = min(r.length - 1, self.max_seq_len - 1)
                    if r.pending:
                        tok_src[t] = r.slot
                    else:
                        token_ids[t] = self.last_tokens[r.slot]
                    positions[t] = p
                    for i, table in enumerate(tables):
                        new_kv_dest[i, t] = self._flat_dest(r.slot, p, table)
                        page_indices[i, j] = table[r.slot]
                    kv_lens[j] = p + 1
                    counts["decode_key_tokens"] += p + 1
                    cu_q_lens[j + 1] = cu_q_lens[j] + 1
                    sample_rows[j] = t
                    sample_slot[j] = r.slot
                    sampling.append((r, j))
                    t += 1
                    j += 1
                else:                                           # prefill chunk
                    take = takes[r.uid]
                    lo = r.prefill_done
                    token_ids[t:t + take] = r.ctx[lo:lo + take]
                    pos = np.arange(lo, lo + take)
                    positions[t:t + take] = pos
                    for i, table in enumerate(tables):
                        pg = table[r.slot, pos // self.page_size]
                        assert (pg > 0).all(), "write into unallocated page"
                        new_kv_dest[i, t:t + take] = (pg * self.page_size +
                                                      pos % self.page_size)
                        page_indices[i, j] = table[r.slot]
                    r.prefill_done += take
                    counts["prefill_tokens"] += int(take)
                    counts["prefill_key_tokens"] += int(
                        take * lo + take * (take + 1) // 2)
                    # every freshly completed full page goes to the prefix
                    # index
                    self.kv.publish(r, r.ctx, r.prefill_done)
                    if trace.enabled:
                        trace.event("prefill_chunk", cat="request",
                                    uid=r.uid, take=int(take),
                                    prefill_done=int(r.prefill_done),
                                    ctx_len=int(r.ctx_len))
                    kv_lens[j] = r.prefill_done
                    cu_q_lens[j + 1] = cu_q_lens[j] + take
                    sample_rows[j] = t + take - 1
                    if r.prefill_done >= r.ctx_len:
                        self.request_latency.on_prefill_done(
                            r.uid, r.ctx_len - r.pc_cached, r.pc_cached)
                        sample_slot[j] = r.slot
                        sampling.append((r, j))
                    t += take
                    j += 1
            cu_q_lens[j + 1:] = cu_q_lens[j]
            if n == 1:
                page_indices, new_kv_dest = page_indices[0], new_kv_dest[0]
            arrays = (token_ids[None], tok_src[None], positions[None], kv_lens,
                      page_indices, cu_q_lens, np.asarray([j], np.int32),
                      new_kv_dest, sample_rows, sample_slot)
            if self.state_layers:
                arrays += (seq_slot,)
            sampled = any(r.do_sample for r, _ in sampling)
            if sampled:
                do_sample = np.zeros((S,), bool)
                temperature = np.ones((S,), np.float32)
                top_k = np.zeros((S,), np.int32)
                top_p = np.ones((S,), np.float32)
                seeds = np.zeros((S,), np.int32)    # per-row seed (uid)
                for r, seq_j in sampling:
                    do_sample[seq_j] = r.do_sample
                    temperature[seq_j] = r.temperature
                    top_k[seq_j] = r.top_k
                    top_p[seq_j] = r.top_p
                    seeds[seq_j] = r.uid
                arrays += (do_sample, temperature, top_k, top_p, seeds)
            leaf.add(rows=t, seqs=j, page_cells=j * self.pages_per_seq)
        with self.host_stats.leaf("plan.counts", rows=t):
            counts.update(self.cache_kind.counts(
                self.host_stats, positions[:t], np.diff(cu_q_lens[:j + 1])))
        return arrays, sampled, sampling, t, counts

    def _maybe_finish(self, req: Request) -> None:
        """``done`` by the count (a token still on the device counts: a
        finish by count or by length is known at dispatch), or by the
        newest token the host has."""
        if (len(req.generated) + req.pending >= req.max_new_tokens or
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
        for r in self.slots:
            # (a sequence whose last token is still on the device keeps
            # its slot until the harvest that brings it)
            if r is not None and r.done and not r.pending:
                if self.kv.pfx_cfg.include_generated:
                    # opt-in: publish full pages of generated tokens
                    # before the refs drop.  Decode pages come from a
                    # different compiled program than fused prefill, so
                    # the bit-parity contract is waived for hits on them
                    # (documented on the config knob).  The last token's
                    # row is never written.
                    self.kv.publish(
                        r, np.concatenate(
                            [r.ctx, np.asarray(r.generated, np.int32)[
                                r.ctx_len - r.prompt.size:]]),
                        r.length - 1)
                self.finished.append(r)
                self._unseat(r)
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
        """Device bytes held by the paged KV cache."""
        return self.kv.cache_bytes()

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
