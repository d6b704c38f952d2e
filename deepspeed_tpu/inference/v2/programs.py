"""The ragged engine's compiled programs, as functions of what they close
over.

Every program the serving loop dispatches is built here from a model module
and a few static sizes, so anything can lower or call one without an engine:
the engine caches what these return, ``chip_smoke.py`` checks logits through
the fused tick, a rehearsal can compile one for a described chip.

- :func:`build_fused_sample_step` — ONE ``[1, T]`` SplitFuse tick (a decode
  token for every ready sequence plus prompt chunks; raggedness lives in
  int32 metadata) that samples on the device and keeps every slot's newest
  token there.  XLA program ``jit_ragged_fused_step``;
  :func:`build_fused_step` is the same forward with its logits as the
  result, for whoever checks them.
- :func:`build_decode_block` — ``block`` decode ticks in a ``lax.scan`` with
  on-device, position-keyed sampling.  ``jit_ragged_decode_block``.

The names are how the profiler's trace and the benchmark's readers find
device time per program: they do not change.  Beside the programs live what
they are built from: the serving form of a model and its weights
(:func:`serving_config`, :func:`prepare_params`, :func:`place_params`), the
zeroed page pools (:func:`init_cache`, :func:`cache_sharding`,
:func:`paged_kv_page_bytes`) and the folding of the expert layers' and the
pass loop's counts.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.common import (logits_of, normalize_params,
                                            unroll_scan_params)
from deepspeed_tpu.inference.paged import (describe, kv_layers,
                                           kv_slots_per_layer, pages_for,
                                           split_cache, state_layers,
                                           window_pool, window_pool_layers)
from deepspeed_tpu.inference.quantization import (dequantize_param_tree,
                                                  quantize_param_tree)
from deepspeed_tpu.inference.sampling import (position_keys,
                                              sample_logits_batched)
from deepspeed_tpu.utils.logging import log_dist

KV_CACHE_DTYPES = ("none", "int8", "fp8", "fp8_e4m3")


def fold_moe_counts(counts: jax.Array) -> jax.Array:
    """``[n, 4]`` per-layer expert-layer counts -> ``[4]``, or the ``[ticks,
    4 or 5]`` of a block's :func:`moe_counts` as one: token copies routed,
    experts hit, the grouped matmul's row-tile visits (and the fifth entry,
    where there is one) add up, the fullest expert's rows take the
    largest."""
    return jnp.sum(counts, axis=0).at[2].set(jnp.max(counts[:, 2]))


def moe_counts(variables) -> Optional[jax.Array]:
    """One apply's ``moe_stats`` collection (each dropless expert layer
    sows ``[rows, experts_hit, load_max, visits]``) folded over its
    layers; None for a model without expert layers, so its programs gain
    no output.  Where the layers also sow ``bias_swaps`` (the sigmoid
    router with a selection bias: tokens whose chosen set the bias
    changed) their sum is a fifth entry.  Where the layers hold a share of
    their router's experts (``moe/layer.py``: ``expert_shards``) they sow
    ``held`` too, and the LAST TWO entries are its sums: every copy the
    router made, and the experts held (the first four are then what the
    held banks saw)."""
    flat = jax.tree_util.tree_flatten_with_path(
        variables.get("moe_stats", {}))[0]
    named = lambda name: [leaf for path, leaf in flat if any(   # noqa: E731
        getattr(k, "key", None) == name for k in path)]
    swaps, held, leaves = named("bias_swaps"), named("held"), named("counts")
    if not leaves:
        return None
    with jax.named_scope("counts"):
        counts = fold_moe_counts(jnp.stack(leaves))
        if swaps:
            counts = jnp.concatenate(
                [counts, jnp.sum(jnp.stack(swaps))[None]])
        if held:
            counts = jnp.concatenate([counts, jnp.sum(jnp.stack(held), 0)])
    return counts


# what a model may sow beside its cache: the dropless expert layers' counts
# and a looped model's exit gate
MUTABLE = ["cache", "moe_stats", "loop_stats"]


def loop_counts(variables) -> Optional[jax.Array]:
    """One apply's ``loop_stats`` collection: a looped model
    (``models/ouro.py``) sows, over the rows that are tokens, the summed
    probability of having stopped by the end of passes ``1..U-1``; None
    for every other model, so its programs gain no output."""
    leaves = jax.tree_util.tree_leaves(variables.get("loop_stats", {}))
    return leaves[0] if leaves else None


def serving_config(mcfg, *, max_seq_len: int, page_size: int,
                   num_pages: int, tensor_parallel: bool,
                   kv_cache_dtype: str, window_num_pages: int = 0):
    """A zoo model's config in its serving form: paged ragged decode over
    a pool of ``num_pages`` pages, layers unrolled (a scan over layers
    restacks the mutable cache every step).  ``window_num_pages``: the
    pages of the window layers' own pool, for a model that has one
    (``paged.window_pool_layers``) and for no other."""
    assert kv_cache_dtype in KV_CACHE_DTYPES, (
        f"kv_cache_dtype must be {'|'.join(KV_CACHE_DTYPES)}, got "
        f"{kv_cache_dtype!r}")
    own = ({"kv_window_num_pages": int(window_num_pages)}
           if window_pool_layers(mcfg) else {})
    return dataclasses.replace(
        mcfg, decode=True, ragged_decode=False, paged_decode=True,
        max_cache_len=max_seq_len, scan_layers=False,
        kv_page_size=page_size, kv_num_pages=num_pages,
        tensor_parallel=tensor_parallel, kv_cache_dtype=kv_cache_dtype,
        **own)


def _with_window_keys(meta, mcfg):
    """``meta`` with the window layers' table and destinations (copies of
    the first pool's: shapes are all a probe reads) where ``mcfg`` has a
    window pool."""
    if window_pool_layers(mcfg):
        meta.update(window_page_indices=meta["page_indices"],
                    window_new_kv_dest=meta["new_kv_dest"])
    return meta


def paged_kv_page_bytes(model, mcfg, page_size: int, kv_cache_dtype: str,
                        window: bool = False) -> int:
    """Exact device bytes ONE KV page costs across every cache leaf —
    for a quantized pool that is the 1-byte payload page plus its fp32
    scale rows.  Measured by an eval_shape probe of a 2-page pool
    rather than guessed from the layer count, so any model-zoo cache
    layout (extra leaves, fused layers) is accounted automatically.
    ``window``: a page of the window layers' own pool instead."""
    probe = type(model)(serving_config(
        mcfg, max_seq_len=2 * page_size, page_size=page_size, num_pages=2,
        tensor_parallel=False, kv_cache_dtype=kv_cache_dtype,
        window_num_pages=2))
    meta = _with_window_keys({
        "kv_lens": jnp.zeros((1,), jnp.int32),
        "page_indices": jnp.full((1, 2), -1, jnp.int32),
        "cu_q_lens": jnp.zeros((2,), jnp.int32),
        "num_seqs": jnp.zeros((1,), jnp.int32),
        "new_kv_dest": jnp.zeros((4,), jnp.int32)}, mcfg)
    ids = jnp.zeros((1, 4), jnp.int32)
    pos = jnp.zeros((1, 4), jnp.int32)
    shapes = jax.eval_shape(lambda: probe.init(
        jax.random.PRNGKey(0), ids, positions=pos, ragged_meta=meta))
    # per-slot state beside the pool is no page's cost
    leaves = (window_pool(shapes["cache"]) if window
              else split_cache(shapes["cache"])[0])
    total = sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                for leaf in leaves)
    assert total % 2 == 0, total
    return total // 2


def prepare_params(model, params, cfg, quantize_weights: Optional[str], *,
                   tp: int = 1):
    """The weights as the programs read them.  Returns ``(cfg, params,
    form)``: ``cfg`` is ``cfg`` or its w8a8 variant, ``form`` the
    ``unroll``/``wq``/``native`` keywords every ``build_*`` takes.

    ``quantize_weights``: None | "int8" | "fp8" | "fp6" | "w8a8" —
    weights persist quantized in HBM and dequantize in-jit at use
    (reference FP6-LLM cuda_linear / int8 quantized inference); "w8a8"
    (explicit opt-in: it quantizes ACTIVATIONS per row too, so numerics
    differ from weight-only "int8") runs the NATIVE path on models whose
    Dense layers consume quantized kernels: int8 stays on the per-tick
    read path (decode is weight-bandwidth-bound — tree-level dequant
    reads 2x the bytes), dotted on the MXU's int8 path."""
    mcfg = model.config
    unroll = bool(getattr(mcfg, "scan_layers", False))
    params = normalize_params(
        model, params,
        plain_model=type(model)(dataclasses.replace(mcfg, decode=False)))
    native = False
    if quantize_weights is not None:
        assert tp <= 1, (
            "quantize_weights does not compose with tensor-parallel "
            "serving yet — quantized leaves carry their own "
            "group-scale layout")
        from deepspeed_tpu.parallel import tensor_parallel as tp_lib

        if quantize_weights == "w8a8":
            assert getattr(type(model), "w8a8_native", False), (
                f"quantize_weights='w8a8' needs a model whose Dense "
                f"layers consume w8a8 kernels natively (the Llama "
                f"family: llama/mistral/qwen2); "
                f"{type(model).__name__} does not — use weight-only "
                f"'int8' instead")
            native = True
            cfg = dataclasses.replace(cfg, weight_quant="w8a8")
            if unroll:
                # unroll scan-stacked [L, ...] kernels NOW: the
                # per-channel w8a8 format is 2-D-kernel only, so a
                # stacked tree would silently fall back to the
                # dequant path for every block kernel
                params = (
                    {"params": unroll_scan_params(params["params"])}
                    if isinstance(params, dict) and "params" in params
                    else unroll_scan_params(params))
                unroll = False
        # unbox flax Partitioned metadata FIRST: the quantizer's
        # leaf-name check reads path tails, which inside a metadata
        # box are the box's own keys — boxed trees would silently
        # pass through unquantized
        if tp_lib.has_partitioning(params):
            params = tp_lib.unbox_params(params)
        params, b0, b1 = quantize_param_tree(params, quantize_weights)
        params = jax.device_put(params)
        log_dist(f"ragged engine weights -> {quantize_weights}"
                 f"{' (native int8 dots)' if native else ''}: "
                 f"{b0 / 2**20:.1f} MiB -> {b1 / 2**20:.1f} MiB "
                 f"({b0 / max(b1, 1):.2f}x)", ranks=[0])
    return cfg, params, dict(unroll=unroll, wq=quantize_weights,
                             native=native)


def place_params(params, *, mesh=None, tp: int = 1, device=None):
    """TP-shard (AutoTP name rules / flax metadata) over the `tensor`
    mesh axis, mirroring the v1 engine; otherwise replicate, or pin to
    a one-device replica slice's ``device``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel import tensor_parallel as tp_lib

    if tp <= 1:
        if tp_lib.has_partitioning(params):
            params = tp_lib.unbox_params(params)
        if device is not None:
            params = jax.device_put(params, device)
        return params
    if tp_lib.has_partitioning(params):
        specs = tp_lib.extract_partition_specs(
            {"params": params}, mesh.axis_names)["params"]
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
        specs = tp_lib.auto_tp_specs(params, tp)
        log_dist("ragged engine AutoTP: inferred tensor-parallel "
                 "sharding from parameter names", ranks=[0])
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    return jax.tree_util.tree_map(
        lambda x, sh: jax.device_put(jnp.asarray(x), sh), params,
        shardings)


def cache_sharding(leaf_shape, *, mesh=None, tp: int = 1, device=None):
    """KV page pools shard their combined-head dim over `tensor`
    (reference v2 KV sharding: heads split over the TP group); the
    quantized pools' [P, page, 2Hkv] scale buffers shard the same
    head dim.  A one-device replica slice pins them to its device;
    ``None`` leaves the default placement."""
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    if tp <= 1:
        return SingleDeviceSharding(device) if device is not None else None
    if len(leaf_shape) == 4:
        return NamedSharding(mesh, P(None, None, "tensor", None))
    if len(leaf_shape) == 3:
        return NamedSharding(mesh, P(None, None, "tensor"))
    return None


def init_cache(model, *, max_seqs: int, width: int, **placement):
    """Zeroed page buffers for every layer of ``model`` (a serving-form
    module; eval_shape, no params), each allocated where it lives
    (``placement``: :func:`cache_sharding`'s keywords) — a pool is most
    of a chip's memory.  ``width``: the fused batch's ``T``."""
    pages_per_seq = pages_for(model.config.max_cache_len,
                              model.config.kv_page_size)
    # host arrays put on the device: no program compiles to make them
    dummy_meta = _with_window_keys(jax.tree_util.tree_map(jnp.asarray, {
        "kv_lens": np.zeros((max_seqs,), np.int32),
        "page_indices": np.full((max_seqs, pages_per_seq), -1, np.int32),
        "cu_q_lens": np.zeros((max_seqs + 1,), np.int32),
        "num_seqs": np.zeros((1,), np.int32),
        "new_kv_dest": np.zeros((width,), np.int32)}), model.config)
    ids = jnp.zeros((1, width), jnp.int32)
    pos = jnp.zeros((1, width), jnp.int32)

    def _init():
        return model.init(jax.random.PRNGKey(0), ids,
                          positions=pos, ragged_meta=dummy_meta)

    shapes = jax.eval_shape(_init)
    assert "cache" in shapes
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype,
                            device=cache_sharding(s.shape, **placement)),
        shapes["cache"])


def _fused_forward(model, *, unroll: bool, wq: Optional[str], native: bool):
    """The body both fused ticks share: the ``[1, T]`` forward with its
    paged-KV update, and the logits of the one row a sequence may sample
    from (``sample_rows``, by packed sequence number).  The rows ride in
    the metadata, and the model's final norm and head see them alone
    (``inference/common.py:sampled_rows``): no ``[T, V]`` array exists.  A
    model whose head ignores them is refused when the tick is traced.
    Returns ``(sel [max_seqs, V], variables)``."""
    two_tables = window_pool_layers(model.config) > 0

    def forward(params, cache, token_ids, positions, kv_lens, page_indices,
                cu_q_lens, num_seqs, new_kv_dest, sample_rows, *seq_slot):
        if wq:
            params = dequantize_param_tree(params, native_w8a8=native)
        if unroll:
            params = unroll_scan_params(params)
        own = {}
        if two_tables:
            # a model whose window layers keep a pool of their own: both
            # tables ride in ``page_indices [2, S, pp]`` and both rows'
            # destinations in ``new_kv_dest [2, T]``, the first pool's first
            own = {"window_page_indices": page_indices[1],
                   "window_new_kv_dest": new_kv_dest[1]}
            page_indices, new_kv_dest = page_indices[0], new_kv_dest[0]
        meta = {"kv_lens": kv_lens, "page_indices": page_indices,
                "cu_q_lens": cu_q_lens, "num_seqs": num_seqs,
                "new_kv_dest": new_kv_dest, **own,
                # static: decode rows and prompt chunks packed by
                # ``cu_q_lens``, which is what inference/paged/kv_pages.py picks
                # the chunk kernel (and the decode kernel for the
                # one-query rows) by
                "fused_tick": True,
                # the one row a packed sequence samples from: the head
                # runs on these rows of the body's output and no other
                "sample_rows": sample_rows,
                # rows past the last sequence's end are padding
                "live": (jnp.arange(token_ids.shape[1]) <
                         cu_q_lens[num_seqs[0]])}
        if seq_slot:
            # a model with per-slot state: each packed sequence's slot
            meta["seq_slot"], = seq_slot
        out, vars_ = model.apply(
            {"params": params, "cache": cache}, token_ids,
            positions=positions, mutable=MUTABLE, ragged_meta=meta)
        sel = logits_of(out)[0]                         # [max_seqs, V]
        if sel.shape[0] != sample_rows.shape[0]:
            raise NotImplementedError(
                f"{type(model).__name__} ran its head on {sel.shape[0]} "
                f"rows of a fused tick that samples {sample_rows.shape[0]}"
                ": a serving-form model gathers ragged_meta['sample_rows'] "
                "before its head (inference/common.py:sampled_rows)")
        return sel, vars_

    return forward


def build_fused_step(model, *, unroll: bool = False,
                     wq: Optional[str] = None, native: bool = False):
    """The fused tick with its LOGITS as the result: fused decode +
    prefill chunk(s) forward, paged-KV update, and logits row selection.
    What checks read (``chip_smoke.py``); the serving loop dispatches
    :func:`build_fused_sample_step`.
    ``unroll``: the stored params are scan-stacked and unroll in-jit;
    ``wq``/``native``: they are stored quantized (``native``: int8 dots,
    no dequantized copy).  A model with per-slot state beside the pool
    (``paged.describe(cfg).state_layers``) takes one
    argument more, last: ``seq_slot [max_seqs]``, each packed sequence's
    slot."""
    forward = _fused_forward(model, unroll=unroll, wq=wq, native=native)

    def run(params, cache, token_ids, positions, kv_lens, page_indices,
            cu_q_lens, num_seqs, new_kv_dest, sample_rows, *seq_slot):
        sel, vars_ = forward(params, cache, token_ids, positions, kv_lens,
                             page_indices, cu_q_lens, num_seqs, new_kv_dest,
                             sample_rows, *seq_slot)
        return sel, vars_["cache"], moe_counts(vars_), loop_counts(vars_)

    # distinguishable XLA program name ("jit_ragged_fused_step") so
    # the profiler bridge can attribute device time per program
    run.__name__ = run.__qualname__ = "ragged_fused_step"
    return jax.jit(run, donate_argnums=(1,))


def build_fused_sample_step(model, *, sampled: bool, sample_base,
                            unroll: bool = False, wq: Optional[str] = None,
                            native: bool = False):
    """ONE jitted program for every tick of the serving loop: the fused
    tick of :func:`build_fused_step` (the same forward, the same XLA
    program name) that also SAMPLES, so its tokens need not reach the host
    before the next tick is planned.

    Beside the forward's arguments it takes ``last_tok [max_seqs]``, every
    slot's newest token as the device has it, and ``tok_src [1, T]``: a
    row whose entry is a slot takes its token id from ``last_tok`` there (a
    decode row whose token the tick before sampled and no fetch has
    brought yet), a row at -1 from ``token_ids``.  ``sample_slot
    [max_seqs]``, by packed sequence number, is the slot a sequence's
    sampled token is kept under, -1 where it samples nothing (a prompt
    chunk that is not the last).  A model with per-slot state beside the
    pool takes ``seq_slot [max_seqs]`` next, each packed sequence's slot.
    Returns the tokens by packed sequence number, the updated
    ``last_tok``, the cache and the counts.

    Two variants compile, as for the decode block: pure-greedy (no sort)
    and per-sequence ``sampled``, which takes the five sampler arrays.  The
    keys are position-keyed as the decode block's are, so a draw is the
    same bits whichever program makes it."""
    forward = _fused_forward(model, unroll=unroll, wq=wq, native=native)
    stateful = describe(model.config).state_layers > 0

    def run(params, cache, last_tok, token_ids, tok_src, positions,
            kv_lens, page_indices, cu_q_lens, num_seqs, new_kv_dest,
            sample_rows, sample_slot, *sampler):
        if stateful:                # the slot of each packed sequence
            seq_slot, sampler = sampler[:1], sampler[1:]
        else:
            seq_slot = ()
        token_ids = jnp.where(
            tok_src >= 0, jnp.take(last_tok, jnp.maximum(tok_src, 0)),
            token_ids)
        sel, vars_ = forward(params, cache, token_ids, positions, kv_lens,
                             page_indices, cu_q_lens, num_seqs, new_kv_dest,
                             sample_rows, *seq_slot)
        with jax.named_scope("sample"):
            if sampled:
                # do_sample, temperature, top_k, top_p
                *configs, seeds = sampler
                sub = position_keys(sample_base, seeds,
                                    jnp.take(positions[0], sample_rows))
            else:
                configs, sub = [None] * 4, None
            toks = sample_logits_batched(sel, sub, *configs)
            # a sequence that samples nothing is scattered nowhere
            last_tok = last_tok.at[
                jnp.where(sample_slot >= 0, sample_slot, last_tok.shape[0])
            ].set(toks, mode="drop")
        return (toks, last_tok, vars_["cache"], moe_counts(vars_),
                loop_counts(vars_))

    run.__name__ = run.__qualname__ = "ragged_fused_step"
    return jax.jit(run, donate_argnums=(1,))


def build_decode_block(model, *, sampled: bool, max_seqs: int, block: int,
                       page_size: int, max_seq_len: int, sample_base,
                       unroll: bool = False, wq: Optional[str] = None,
                       native: bool = False):
    """``block`` decode ticks per dispatch: ``lax.scan`` over fused
    [1, max_seqs] decode forwards with on-device sampling.  The host
    round trip the reference pays per generated token
    (``engine_v2.py:107`` put -> schedule -> logits) amortizes to
    1/``block``.  Two variants compile: pure-greedy (no sort) and
    per-seq ``sampled``."""
    S, page, max_len = max_seqs, page_size, max_seq_len
    two_tables = window_pool_layers(model.config) > 0

    def run(params, cache, last_tok, pos, active, remaining,
            page_table, eos_ids, do_sample, temperature, top_k, top_p,
            seeds, rng):
        if wq:
            params = dequantize_param_tree(params, native_w8a8=native)
        if unroll:
            params = unroll_scan_params(params)

        def dest_of(table, pos, active):
            dest_page = jnp.take_along_axis(
                jnp.maximum(table, 0), (pos // page)[:, None], axis=1)[:, 0]
            return jnp.where(active, dest_page * page + pos % page, 0)

        own_table = None
        if two_tables:
            # ``page_table [2, S, pp]``: the first pool's, then the window
            # layers' own (``_fused_forward``)
            page_table, own_table = page_table[0], page_table[1]

        def tick(carry, _):
            cache, last_tok, pos, active, remaining, rng = carry
            dest = dest_of(page_table, pos, active)
            kv_lens = jnp.where(active, pos + 1, 1)
            own = ({} if own_table is None else
                   {"window_page_indices": own_table,
                    "window_new_kv_dest": dest_of(own_table, pos, active)})
            meta = {"kv_lens": kv_lens,
                    "page_indices": page_table, **own,
                    "cu_q_lens": jnp.arange(S + 1, dtype=jnp.int32),
                    "num_seqs": jnp.asarray([S], jnp.int32),
                    "new_kv_dest": dest, "live": active,
                    # static: row s is slot s's one query, which is what
                    # inference/paged/kv_pages.py picks the decode kernel by
                    "one_query_a_slot": True}
            out, vars_ = model.apply(
                {"params": params, "cache": cache}, last_tok[None],
                positions=jnp.where(active, pos, 0)[None],
                mutable=MUTABLE, ragged_meta=meta)
            logits = logits_of(out)[0]              # [S, V]
            moe, loop = moe_counts(vars_), loop_counts(vars_)
            with jax.named_scope("sample"):
                rng, _ = jax.random.split(rng)
                # position-keyed per-row keys: the draw at cache position
                # `pos` is the same bits the fused tick would draw there,
                # no matter how this block was scheduled
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
                    rng), (nxt, produced, moe, loop)

        carry, (toks, mask, moe, loop) = jax.lax.scan(
            tick, (cache, last_tok, pos, active, remaining, rng),
            length=block)
        cache, last_tok, pos, active, remaining, rng = carry
        # the full carry returns so the pipelined host path can keep
        # it device-resident across dispatches (no re-upload)
        return (cache, last_tok, pos, active, remaining, toks, mask,
                None if moe is None else fold_moe_counts(moe),
                None if loop is None else jnp.sum(loop, axis=0))

    run.__name__ = run.__qualname__ = "ragged_decode_block"
    return jax.jit(run, donate_argnums=(1,))
