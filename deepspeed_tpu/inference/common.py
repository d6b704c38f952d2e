"""Conventions shared by the v1 and v2 inference engines."""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.telemetry import trace
from deepspeed_tpu.telemetry.tracer import Span
from deepspeed_tpu.telemetry.metrics import metrics as _metrics


class _StageSpan(Span):
    """A serving-loop stage bracket: the tracer's span (profiler + ring)
    whose duration also lands in ``HostStageStats.seconds`` and the
    stage histogram."""

    __slots__ = ("_stats", "_stage")

    def __init__(self, stats: "HostStageStats", stage: str,
                 attrs: Optional[Dict[str, Any]]):
        super().__init__(trace, stats.SPAN_NAMES[stage], "serving", attrs)
        self._stats = stats
        self._stage = stage

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        st = self._stats
        st.seconds[self._stage] += self.dur
        st._book(self.name, self.dur)
        if _metrics.enabled:
            st._stage_hist(self._stage).observe(self.dur)
        return False


class _LeafSpan(Span):
    """A piece of a stage, bracketed inside it: the tracer's span alone
    (profiler + ring).  Its duration is the enclosing stage's already, so
    it reaches neither ``seconds`` nor the histogram; it is booked to the
    step in progress, for ``serving_stages()["fused_tick_host"]``."""

    __slots__ = ("_stats",)

    def __init__(self, stats: "HostStageStats", name: str,
                 attrs: Optional[Dict[str, Any]]):
        super().__init__(trace, "engine." + name, "serving", attrs)
        self._stats = stats

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        self._stats._book(self.name, self.dur)
        return False


class _StepSpan(Span):
    """``engine.step`` around one ``step()``.  Which program the step
    dispatches is known at its end only, so that is where its seconds are
    booked: wall, and every bracket inside it by span name, under
    ``HostStageStats.steps_by_program[<program>]``."""

    __slots__ = ("_stats",)

    def __init__(self, stats: "HostStageStats"):
        super().__init__(trace, "engine.step", "serving", None)
        self._stats = stats

    def __enter__(self):
        st = self._stats
        st._step_program = "none"
        st._step_spans.clear()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        st = self._stats
        rec = st.steps_by_program[st._step_program]
        rec["steps"] += 1
        rec["wall_s"] += self.dur
        by_span = rec["by_span_s"]
        for name, dur in st._step_spans.items():
            by_span[name] = by_span.get(name, 0.0) + dur
        return False


class HostStageStats:
    """Per-dispatch host-path breakdown for the serving engines.

    What a serving step costs beyond its device time is spent on the
    host, so both engines bracket every hot-loop stage:

    - ``plan``:     host-side numpy scheduling (admission, SplitFuse
                    packing, page growth, run-ahead projection)
    - ``upload``:   host->device metadata transfers
    - ``dispatch``: handing the jitted program to the async runtime
    - ``device``:   host BLOCKED waiting on device results (the only
                    sync points: harvests and run-ahead depth waits)
    - ``harvest``:  folding fetched tokens back into request state

    One bracket, three sinks: ``stage(name, **counts)`` opens ONE span
    that (a) is a ``jax.profiler.TraceAnnotation`` named
    ``engine.<name>`` (``device`` is ``engine.fetch``) with the counts
    as its stats — serving-loop spans appear in any ``jax.profiler``
    trace, on the profiler's own clock, with no knob; (b) lands in the
    tracer's ring under ``DSTPU_TRACE=1``; (c) adds its duration to
    ``seconds[name]`` and the stage histogram — read on the tracer's
    clock, ``time.perf_counter`` unless ``telemetry.configure(clock=)``
    replaced it, so ring and stage seconds agree.  Besides the stages
    above the v2 engine brackets ``rng_split`` (the eager key split
    before a decode block), ``sample`` (an eager ``sample_logits`` outside
    a program; the engine's own ticks sample inside theirs) and
    ``reap`` (``_reap`` and the handoff sweep after a tick's harvest),
    and opens ``engine.step`` around a whole ``step()``;
    the replica worker opens ``replica.op`` around each op and the
    router ``router.pump`` / ``router.fold`` / ``router.dispatch`` and,
    on the intake path, ``router.submit`` with ``router.prefix_hash``
    inside it.  ``benchmark/idle_report.py`` reads all of them.

    ``leaf(name, **counts)`` brackets a PIECE of a stage, inside the
    stage's bracket, as ``engine.<name>``: the v2 engine takes the plan of
    a fused tick apart so (``engine.plan.admit``: the handoff sweep and
    admission, ``waiting`` / ``admitted``; ``engine.plan.grow``: the page
    growth that decides between a decode block and a tick, ``rows``;
    ``engine.plan.rows``: ``_plan_rows``, ``decode_rows`` /
    ``prefill_seqs`` / ``pages_granted`` / ``page_limited`` /
    ``prompt_rows`` / ``prompt_tiles`` (the prompt rows handed out and the
    row tiles of 128 their chunks span as they lie in the tick) /
    ``tiles_aligned`` (1 where they lie in the tick's own tiles);
    ``engine.plan.pack``: ``_pack_tick``'s arrays, ``rows`` / ``seqs`` /
    ``page_cells``; ``engine.plan.counts``: the cache kind's host-side
    counts for the dispatch span, ``rows``; ``engine.plan.attrs``: the
    dispatch span's attributes, in a plan bracket of its own directly
    before the dispatch), and a decode block brackets the last two.  A
    leaf is a span and nothing else: its time is its stage's already, so
    it reaches neither ``seconds`` nor the histogram, and in a profile it
    inherits its stage's class (``benchmark/layer_metrics/host_spans.py``).
    No call of the loop outside ``engine.fetch`` was found to wait for the
    device (PERF.md section 6, PR 52), so there is no ``engine.wait`` span;
    one found later is bracketed so INSIDE its stage, its seconds booked
    to ``seconds["device"]`` (``fused_tick_host_ms`` already takes such
    spans off).

    ``step()`` books a step at its END, by the program it dispatched
    (``dispatched(program)``: ``fused``, ``decode_block``, else ``none``; a
    step that dispatched a block and then a tick is the tick's):
    ``steps_by_program`` holds the steps, their wall seconds
    and every bracket's seconds by span name (``engine.fetch``'s are the
    step's wait).  ``serving_stages()["fused_tick_host"]``
    divides the fused steps' by their number: ``host_ms`` (wall less
    wait), ``wait_ms`` and ``<span>_ms`` for every stage and leaf, on the
    tracer's clock, so a run with no profiler session gives the table
    that ``fused_tick_host_ms`` reads from a traced one; and the sums of
    the plans' ``prompt_rows`` and ``prompt_tiles`` with their quotient,
    ``prompt_rows_per_tile``: 128 where every chunk is whole tiles from
    row 0, 128 x 8/9 where every chunk buys a ninth tile for a few rows;
    and ``aligned_ticks`` with ``aligned_tick_share``, the ticks whose
    chunks lay in the tick's own row tiles, so that a matrix-state layer
    scanned them where they lay (``engine.plan.rows`` says ``tiles_aligned``
    tick by tick).

    ``serving_stages()`` reports per-dispatch milliseconds plus
    ``host_bound_fraction`` = host-stage time / (host + device-wait).
    That is the share of the HOST's bracketed time not spent blocked on
    the device — NOT the chip's idle share: with a two-deep decode
    pipeline the host can be blocked most of the time while the chip
    still idles between programs.  The chip's idle share comes from a
    profiler trace (``benchmark/layer_metrics/device_idle_pct.py``),
    and the spans above say which host stage each idle gap fell into.
    Counters make the pipelining contract testable: ``meta_uploads``
    and ``blocking_gets`` must stay flat across steady-state decode
    blocks when the pipeline is on.  ``prefill_tokens`` /
    ``prefill_key_tokens`` (prompt tokens dispatched in fused ticks,
    and the keys they attend to) and ``decode_key_token_steps`` (context
    tokens read, summed over decode token steps) are the same counts
    the ``engine.dispatch`` spans carry (a fused tick's span also says
    ``head_rows``: the rows its head multiplied, the ``max_seqs`` it
    samples and not the tick's ``T``); ``sample_s`` / ``rng_split_s``
    / ``reap_s`` are the seconds of the three brackets outside
    ``STAGES``.  ``fused_ticks`` counts SplitFuse ticks and
    ``fused_ticks_ahead`` those dispatched with earlier work of the
    v2 engine's queue still un-folded (their ``engine.dispatch`` spans say
    ``ahead=1``); ``decode_blocks`` / ``decode_blocks_ahead`` say the
    same of plain decode blocks and ``admissions`` /
    ``admissions_ahead`` of requests seated while work was in flight.
    ``drains`` counts the times the loop waited for the queue's newest
    entry with nothing dispatched behind it, because its next plan
    needed a token's value; ``drain_reasons`` says why, by name
    (``eos``, ``pages``, ``handoff``, ``api``).

    KV tiering adds ``spill`` (page gather + device_get + handoff to
    the tier store) and ``restore`` (tier fetch + verify + page upload
    + scatter); the v2 engine additionally merges the tier store's own
    flat stats as a ``kv_tiering`` sub-dict.

    The prefix cache adds ``prefix`` (index lookup + token
    verification + attach/COW bookkeeping at admission) and the
    ``prefix_*`` counters; when the index saw any lookup the v2 engine
    emits a ``prefix_cache`` sub-dict merging the index's own stats.
    """

    STAGES = ("plan", "upload", "dispatch", "device", "harvest", "spill",
              "restore", "prefix")
    # bracketed and reported in seconds, but no key of the per-dispatch
    # ``<stage>_ms`` table nor part of ``host_s`` (``sample`` and
    # ``reap`` overlap ``harvest`` where they nest in it)
    EXTRA = ("rng_split", "sample", "reap")
    SPAN_NAMES = {s: "engine." + ("fetch" if s == "device" else s)
                  for s in STAGES + EXTRA}
    # what a ``step()`` can have dispatched; a fused tick outranks the
    # decode block dispatched earlier in the same step
    PROGRAMS = ("fused", "decode_block", "none")

    def __init__(self, replica: str = ""):
        # scale-out serving runs several engines in one process; the
        # ``replica`` label keeps their registry children apart (the
        # solo-engine default is the empty label value, so a process
        # with one engine exports the same series it always did)
        self.replica = str(replica)
        self._hists: Dict[str, Any] = {}
        self._hist_fam = None
        self.reset()

    def set_replica(self, replica: str) -> None:
        """Re-label after construction (ReplicaSet assigns indices to
        engines built without one); drops cached children so the next
        bracket lands under the new label."""
        self.replica = str(replica)
        self._hists.clear()
        self._hist_fam = None

    def reset(self) -> None:
        self.seconds: Dict[str, float] = {
            s: 0.0 for s in self.STAGES + self.EXTRA}
        self.ticks = 0            # model ticks (a K-block counts K)
        self.dispatches = 0       # compiled-program launches
        self.meta_uploads = 0     # host->device metadata arrays sent
        self.blocking_gets = 0    # blocking device->host fetches
        self.harvests = 0         # deferred-harvest fold-backs
        self.prefix_hits = 0      # admissions that attached >=1 cached page
        self.prefix_misses = 0    # admissions that attached nothing
        self.prefix_hit_pages = 0   # cached pages attached
        self.prefix_hit_tokens = 0  # prefill tokens skipped via the cache
        self.prefix_cow_copies = 0  # copy-on-write page copies
        self.prefill_tokens = 0     # prompt tokens in fused ticks
        self.prefill_key_tokens = 0   # keys those tokens attend to
        self.decode_key_token_steps = 0   # context tokens x token steps
        self.fused_ticks = 0        # SplitFuse ticks dispatched
        # the prompt rows the plans of ticks handed out, and the row tiles
        # of 128 their chunks span as they lie in the tick (a chunk of
        # 1,028 rows from row 4 spans nine, one of 1,024 from row 0 eight)
        self.prompt_rows = 0
        self.prompt_tiles = 0
        # the ticks dispatched whose chunks lie in the tick's own row tiles
        # (``ragged_engine.py:tiles_aligned``): a matrix-state layer reads
        # such a tick's rows as its scan's tiles and gathers nothing
        self.aligned_ticks = 0
        # ... of them with earlier work un-folded (dispatched ahead)
        self.fused_ticks_ahead = 0
        self.decode_blocks = 0      # plain decode blocks dispatched
        self.decode_blocks_ahead = 0    # ... with work still in flight
        self.admissions = 0         # requests seated in a slot
        self.admissions_ahead = 0   # ... behind work still in flight
        # waits for the newest dispatched work with nothing queued
        # behind it (the chip idles for the host's turn), and why
        self.drains = 0
        self.drain_reasons: Dict[str, int] = {}
        # dropless expert layers, live rows only, summed over layers:
        self.moe_rows = 0           # token copies routed
        self.moe_experts_hit = 0    # experts with at least one row
        self.moe_load_max = 0       # rows of the fullest expert, ever
        # row-tile visits of the grouped matmul (ops/grouped_matmul.py)
        self.moe_visits = 0
        # (a sigmoid router with a selection bias) tokens whose chosen
        # set the bias changed
        self.moe_bias_swaps = 0
        # (layers that hold a share of their router's experts: the counts
        # above are then the HELD banks') every copy the router made, and
        # the experts held, summed as ``moe_experts_hit`` is
        self.moe_rows_routed = 0
        self.moe_experts_held = 0
        # a model with per-slot state: sequences a dispatch carried state
        # for, summed; and (block-selecting attention) the queries past
        # ``dense_len``, the keys their chosen blocks hold and the keys a
        # dense pass would read, a KV head
        self.state_rows = 0
        self.sparse_rows = 0
        self.sparse_key_tokens = 0
        self.sparse_visible_tokens = 0
        # (attention that chooses its keys by token) the queries, the keys
        # the indexer scores for them; of those with more than ``topk`` to
        # choose from: how many, the keys they attend, the keys they see
        self.index_rows = 0
        self.index_visible_tokens = 0
        self.sel_rows = 0
        self.sel_key_tokens = 0
        self.sel_visible_tokens = 0
        # (window layers with a pool of their own) that pool's pages in use,
        # their peak, the pages sequences gave back while they lived (and
        # how many of them the last dispatch's span had counted), beside the
        # first pool's pages in use
        self.window_pages_held = 0
        self.window_pages_peak = 0
        self.window_pages_released = 0
        self.window_pages_said = 0
        self.full_pages_held = 0
        # a looped model's exit gate, live rows only:
        self.loop_rows = 0          # rows at dispatch, summed
        self.loop_exit_cdf = []     # P(stopped by pass u), summed, u < U
        # the steps by the program they dispatched: how many, their wall
        # seconds, and the seconds of every bracket inside them by span
        # name (``engine.fetch``: blocked on the device)
        self.steps_by_program: Dict[str, Dict[str, Any]] = {
            p: {"steps": 0, "wall_s": 0.0, "by_span_s": {}}
            for p in self.PROGRAMS}
        self._step_program = "none"
        self._step_spans: Dict[str, float] = {}    # of the step in progress

    def stage(self, name: str, **counts) -> _StageSpan:
        """Bracket one stage of the hot loop; ``counts`` become the
        span's stats."""
        return _StageSpan(self, name, counts or None)

    def leaf(self, name: str, **counts) -> _LeafSpan:
        """Bracket a piece of a stage, INSIDE that stage's bracket, as
        ``engine.<name>``; ``counts`` become the span's stats, and
        ``Span.add`` takes those known afterwards."""
        return _LeafSpan(self, name, counts or None)

    def step(self) -> _StepSpan:
        """Bracket one ``step()`` of the v2 engine as ``engine.step``."""
        return _StepSpan(self)

    def dispatched(self, program: str) -> None:
        """Count one launch of a compiled program, and name the step in
        progress after it."""
        self.dispatches += 1
        if self._step_program != "fused":
            self._step_program = program

    def _book(self, span: str, dur: float) -> None:
        spans = self._step_spans
        spans[span] = spans.get(span, 0.0) + dur

    def _stage_hist(self, name: str):
        """Cached registry child for this stage (lookup once, then a
        plain attribute read per bracket)."""
        h = self._hists.get(name)
        if h is None or self._hist_fam is not _metrics.get(
                "dstpu_serving_stage_seconds"):
            self._hist_fam = _metrics.histogram(
                "dstpu_serving_stage_seconds",
                "Serving host-path stage bracket durations (s)",
                labels=("stage", "replica"))
            h = self._hist_fam.labels(stage=name, replica=self.replica)
            self._hists[name] = h
        return h

    def serving_stages(self) -> Dict[str, Any]:
        d = max(self.dispatches, 1)
        out: Dict[str, Any] = {
            f"{s}_ms": round(self.seconds[s] * 1e3 / d, 4)
            for s in self.STAGES}
        host = sum(self.seconds[s] for s in
                   ("plan", "upload", "dispatch", "harvest", "spill",
                    "restore", "prefix"))
        dev = self.seconds["device"]
        out["host_s"] = round(host, 4)
        out["device_wait_s"] = round(dev, 4)
        out["host_bound_fraction"] = (round(host / (host + dev), 4)
                                      if host + dev > 0 else None)
        out.update(ticks=self.ticks, dispatches=self.dispatches,
                   meta_uploads=self.meta_uploads,
                   blocking_gets=self.blocking_gets,
                   harvests=self.harvests,
                   prefill_tokens=self.prefill_tokens,
                   prefill_key_tokens=self.prefill_key_tokens,
                   decode_key_token_steps=self.decode_key_token_steps,
                   fused_ticks=self.fused_ticks,
                   fused_ticks_ahead=self.fused_ticks_ahead,
                   decode_blocks=self.decode_blocks,
                   decode_blocks_ahead=self.decode_blocks_ahead,
                   admissions=self.admissions,
                   admissions_ahead=self.admissions_ahead,
                   drains=self.drains,
                   **{f"{s}_s": round(self.seconds[s], 6)
                      for s in self.EXTRA})
        if self.drain_reasons:
            out["drain_reasons"] = dict(self.drain_reasons)
        out["fused_tick_host"] = self.fused_tick_host()
        return out


    def fused_tick_host(self) -> Dict[str, Any]:
        """``serving_stages()["fused_tick_host"]``: what the host spends on
        a fused tick, over the steps that dispatched one (``ticks``), one
        level of scalars.  A step's wall seconds less those it was blocked
        on the device (``wait_ms``), a tick, is ``host_ms``;
        ``<span>_ms`` splits the tick by stage bracket and by the leaves
        inside ``engine.plan``, each from its span's own duration, so the
        table needs no profiler session.  ``<program>_steps`` /
        ``_wall_s`` / ``_wait_s``: every step of the run by what it
        dispatched.  ``prompt_rows`` / ``prompt_tiles``: what
        ``engine.plan.rows`` said, summed, and ``prompt_rows_per_tile``
        their quotient, the rows a tile the chunk kernels span holds.
        ``aligned_ticks``: the ticks dispatched whose chunks lay in the
        tick's own row tiles (``ragged_engine.py:tiles_aligned``), which a
        matrix-state layer scans where they lie with no gather to tiles or
        back (``ops/linear_attention.py:scan_tick``); ``aligned_tick_share``
        of all that were dispatched (11 of 12 where prompts of 12 chunks
        follow one another: the tick that holds a tail beside the next
        head is the other)."""
        fetch = self.SPAN_NAMES["device"]
        out: Dict[str, Any] = {}
        for p, r in self.steps_by_program.items():
            out[f"{p}_steps"] = r["steps"]
            out[f"{p}_wall_s"] = round(r["wall_s"], 6)
            out[f"{p}_wait_s"] = round(r["by_span_s"].get(fetch, 0.0), 6)
        fused = self.steps_by_program["fused"]
        n = out["ticks"] = fused["steps"]
        if n:
            wait = fused["by_span_s"].get(fetch, 0.0)
            out["host_ms"] = round((fused["wall_s"] - wait) * 1e3 / n, 4)
            out["wait_ms"] = round(wait * 1e3 / n, 4)
            for name, s in sorted(fused["by_span_s"].items()):
                out[f"{name}_ms"] = round(s * 1e3 / n, 4)
        out["prompt_rows"] = self.prompt_rows
        out["prompt_tiles"] = self.prompt_tiles
        if self.prompt_tiles:
            out["prompt_rows_per_tile"] = round(
                self.prompt_rows / self.prompt_tiles, 4)
        out["aligned_ticks"] = self.aligned_ticks
        if self.fused_ticks:
            out["aligned_tick_share"] = round(
                self.aligned_ticks / self.fused_ticks, 4)
        return out


def kv_quant_block(cache, fmt: str, dequant_path: str,
                   num_pages: int) -> Dict[str, Any]:
    """``serving_stages()['kv_quant']`` sub-dict for a quantized paged
    pool: exact byte accounting (1-byte payload pages vs fp32 scale
    rows), the dequant-free read route taken on this backend, and
    written-scale statistics.  Fetches the scale leaves — call at
    stats/report time, never in the serving hot loop."""
    leaves = jax.tree_util.tree_leaves(cache)
    payload = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves
                  if np.dtype(leaf.dtype).itemsize == 1)
    scale_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves
                      if np.dtype(leaf.dtype).itemsize != 1)
    scales = [np.asarray(jax.device_get(leaf)).ravel() for leaf in leaves
              if np.dtype(leaf.dtype).itemsize != 1]
    flat = (np.concatenate(scales) if scales
            else np.zeros((0,), np.float32))
    # the quant write path floors every written scale at the smallest
    # normal f32, so exact zeros are rows never written
    nz = flat[flat != 0.0]
    return {
        "format": fmt,
        "dequant_path": dequant_path,
        "pool_bytes": payload + scale_bytes,
        "payload_bytes": payload,
        "scale_bytes": scale_bytes,
        "num_pages": int(num_pages),
        "scale_rows_written": int(nz.size),
        "scale_min": float(nz.min()) if nz.size else 0.0,
        "scale_max": float(nz.max()) if nz.size else 0.0,
        "scale_mean": float(nz.mean()) if nz.size else 0.0,
    }


def logits_of(out):
    """Models may return (logits, aux) tuples (e.g. Mixtral's router
    loss); serving wants the logits."""
    return out[0] if isinstance(out, tuple) else out


def sampled_rows(x, ragged_meta):
    """The rows of the body's output ``x [1, T, E]`` that the head, and a
    row-wise final norm before it, are to see.  A fused tick samples one
    row a packed sequence and says which in ``ragged_meta["sample_rows"]``
    ``[max_seqs]``: those rows are gathered here, ``[1, max_seqs, E]``, so
    no logit is computed that nothing reads.  Every other caller's metadata
    lacks the key (a decode block samples every row, training passes no
    metadata) and ``x`` returns as it came.  Every serving-form model
    calls this once on its body's output: before the final norm where that
    is row-wise and the head follows it, after the pass loop where the
    norm runs inside it (``models/ouro.py``).  The fused tick refuses a
    model that does not (``inference/v2/programs.py:_fused_forward``)."""
    rows = None if ragged_meta is None else ragged_meta.get("sample_rows")
    if rows is None:
        return x
    with jax.named_scope("head.rows"):
        return jnp.take(x, rows, axis=1)


def normalize_params(model, params: Any,
                     rng: Optional[jax.Array] = None,
                     plain_model=None):
    """Default-init when absent (benchmarking) and strip the flax
    ``{"params": ...}`` wrapper."""
    if params is None:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        init_model = plain_model if plain_model is not None else model
        params = jax.jit(init_model.init)(rng, np.zeros((1, 8), np.int32))
    if isinstance(params, dict) and "params" in params:
        params = params["params"]
    return params


def unroll_scan_params(params):
    """Scan-stacked layer params -> unrolled-layer params.

    Decode models run with ``scan_layers=False``: flax's scan-over-layers
    restacks the mutable KV-cache collection every decode step (profiled
    at ~2.4ms/step of full-cache copies on a 302MB GPT-2 cache, v5e —
    3.8x decode throughput once removed), while unrolled layers keep one
    independently-aliased cache per layer.  Training params stay stacked;
    this converts a scan subtree ``{K: {"block": leaves[L, ...]}}`` into
    ``{K_0: leaves[...], ..., K_{L-1}: ...}`` (the models' unrolled
    naming).  Call INSIDE the jitted decode program so the slices fuse
    instead of materializing copies.
    """
    import jax.tree_util as jtu

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if isinstance(v, dict) and set(v) == {"block"}:
                sub = walk(v["block"])
                L = jtu.tree_leaves(sub)[0].shape[0]
                for i in range(L):
                    out[f"{k}_{i}"] = jtu.tree_map(
                        lambda x, _i=i: x[_i], sub)
            else:
                out[k] = walk(v)
        return out

    return walk(params)
