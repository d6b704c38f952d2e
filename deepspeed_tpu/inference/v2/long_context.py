"""Partial-residency long-context driver: the tiered KV store as
virtual memory for attention.

A live sequence whose KV exceeds the HBM pool keeps only the first
``sink_pages`` and the most recent ``window_pages`` of its page list
device-resident (the StreamingLLM observation: the hot set is sinks +
a recent window); the middle demotes in ``chunk_pages`` groups through
the existing host->NVMe tiers (digest-verified, quantized payloads
carried unchanged).  Parked columns become ``-1`` holes in the page
table — the attention references and the quantized Pallas kernel mask
holes while the surviving columns keep their true positions.

A full-attention tick over such a sequence is a chunked multi-dispatch
scan, LAYER-MAJOR (chunk-major orderings are mathematically inexact —
layer l+1's queries depend on layer l's FULL output):

    x = embed(tokens)
    for each layer l:
        carry = neutral
        for each parked group g:              # fixed [R] staging shape
            carry = fold(carry, stats(q_l(x), staged KV of g))
        x = block_l(x, carry)                 # resident rows + carry,
                                              # writes this tick's KV
    logits = lm_head(norm(x))

Chunk dispatches attend a STAGED dense KV block (the tier store's
``peek`` — a non-destructive verified page-in through the staging
ring) and sow the flash-attention ``(m, l, acc)`` carry; the finish
dispatch folds the accumulated carry into resident attention via the
explicit-carry paths of :mod:`deepspeed_tpu.inference.paged` /
:mod:`deepspeed_tpu.ops.ragged_paged_quant`.  With zero parked groups
the finish dispatch takes the plain softmax path — bit-identical to a
fully-resident engine, which is the parity contract the tests pin.

Exactly two query-shape families exist (prefill ticks take one page of
prompt tokens, decode ticks take one token), so the compiled-program
count is bounded and the steady state compiles nothing.

The driver owns no device state: pages and the parked middle (under
``mid-<uid>-<g>`` keys) belong to the engine's
:class:`~deepspeed_tpu.inference.v2.kv_state.KVState`, which moves them, and
sampling reuses the engine's position-keyed sampler (seeded sampling is
reproducible against a fully-resident control).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.telemetry import trace

__all__ = ["LongContextDriver"]


class _ChunkScan(nn.Module):
    """One layer's q-projection + staged-KV stats dispatch: RMSNorm +
    attention under the same submodule names as ``LlamaBlock``, so the
    engine's ``params['model']['layers_<l>']`` subtree applies
    directly.  The attention output is discarded — the dispatch exists
    for the ``carry`` collection its staged branch sows."""

    config: Any

    @nn.compact
    def __call__(self, x, positions, ragged_meta):
        from deepspeed_tpu.models.llama import LlamaAttention, RMSNorm

        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="input_layernorm")(x)
        LlamaAttention(cfg, name="self_attn")(h, positions, True,
                                              ragged_meta)
        return 0.0


class LongContextDriver:
    """Ticks partially-resident (``Request.lc``) sequences for a
    :class:`RaggedInferenceEngineV2` — one driver per engine, created
    lazily on the first long-context admission."""

    def __init__(self, engine):
        from deepspeed_tpu.models.llama import LlamaForCausalLM

        eng = engine
        self.kv = eng.kv
        t = self.kv.tier_cfg
        assert self.kv.tiering is not None and t.long_context, (
            "LongContextDriver needs kv_tiering.long_context=True")
        if eng.tp > 1:
            raise NotImplementedError(
                "long-context partial residency does not compose with "
                "tensor-parallel serving yet (the chunked scan threads "
                "an explicit attention carry the TP shard_map path "
                "does not)")
        if eng.param_form["wq"]:
            raise NotImplementedError(
                "long-context partial residency does not compose with "
                "quantize_weights yet — the per-layer dispatch applies "
                "raw param subtrees")
        assert not eng.param_form["unroll"], (
            "long-context needs unrolled layers_<i> params (the engine "
            "unrolls scan params itself in-jit; pass unrolled params)")
        if getattr(eng, "kv_slots", 1) > 1:
            raise NotImplementedError(
                "long-context partial residency reads num_hidden_layers as "
                "the number of K/V slots; a looped model "
                f"({type(eng.model).__name__}) has {eng.kv_slots} a layer")
        assert isinstance(eng.model, LlamaForCausalLM), (
            "long-context partial residency supports llama-family "
            "models (the per-layer chunked scan mirrors LlamaBlock)")
        assert ("model" in eng.params
                and "layers_0" in eng.params["model"]), (
            "params must be llama-shaped: model/layers_<i>/...")
        self.eng = eng
        self.cfg = eng.cfg
        self.L = int(self.cfg.num_hidden_layers)
        self.H = int(self.cfg.num_attention_heads)
        self.D = int(self.cfg.head_dim)
        self.sink = int(t.sink_pages)
        self.chunk = int(t.chunk_pages)       # compiled staging shape
        self.R = self.chunk * eng.page_size   # staged rows per dispatch
        self._quant = eng.kv_cache_dtype != "none"
        self._fns: Dict[Tuple, Any] = {}
        self._neutrals: Dict[int, Tuple] = {}
        self._kpos_cache: Dict[int, jax.Array] = {}
        # map layer index -> position of its kv_pages / kv_scales leaf
        # in the cache's tree_leaves order (spill payloads travel as
        # flat leaf lists; dict keys sort "layers_10" before "layers_2")
        self._leaf_idx: Dict[int, List[Optional[int]]] = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(self.kv.cache)
        for i, (path, _leaf) in enumerate(flat):
            keys = [str(getattr(k, "key", k)) for k in path]
            layer = next((k for k in keys if k.startswith("layers_")),
                         None)
            if layer is None:
                continue
            li = int(layer.split("_", 1)[1])
            slot = self._leaf_idx.setdefault(li, [None, None])
            if keys[-1] == "kv_pages":
                slot[0] = i
            elif keys[-1] == "kv_scales":
                slot[1] = i
        assert all(v[0] is not None for v in self._leaf_idx.values())

    # -- residency bookkeeping -------------------------------------------

    def _window(self) -> int:
        # read fresh each tick: kv.window_pages is an online knob
        return max(int(self.kv.tier_cfg.window_pages), 1)

    def _key(self, r, g: int) -> str:
        return f"mid-{r.uid}-{g}"

    def _grow(self, r, lo: int, hi: int) -> bool:
        """Pages for write positions ``[lo, hi)`` — always at/past the
        frontier, never a parked column.  False = pool dry this tick."""
        eng = self.eng
        for col in range(lo // eng.page_size,
                         (hi - 1) // eng.page_size + 1):
            if not self.kv.grow_col(r.slot, col):
                others = any(s is not None and s is not r and not s.done
                             for s in eng.slots)
                if others or eng.waiting:
                    return False           # a reap may free pages; wait
                raise RuntimeError(
                    f"long-context resident window cannot grow for "
                    f"uid={r.uid}: the HBM pool "
                    f"({eng.num_pages - 1} usable pages) is exhausted "
                    f"and the spill tiers can't take the parked middle "
                    "— raise num_pages, raise kv_tiering host_pages/"
                    "nvme_pages, or shrink sink_pages/window_pages/"
                    "chunk_pages")
        return True

    def _park(self, r, written: int, frontier_col: int) -> None:
        """Demote every FULLY-WRITTEN group whose columns sit entirely
        below ``frontier_col - window_pages`` into the tiers (group g =
        columns ``[sink + g*chunk, sink + (g+1)*chunk)``; parked groups
        are always a contiguous prefix of the middle)."""
        eng = self.eng
        window = self._window()
        while True:
            g = r.lc_parked
            col0 = self.sink + g * self.chunk
            end = col0 + self.chunk
            if end * eng.page_size > written:
                return                      # group not fully written yet
            if end > frontier_col - window:
                return                      # inside the resident window
            if not self.kv.park(r.slot, col0, end, self._key(r, g)):
                return                      # tiers full: stay resident
            r.lc_parked += 1
            if trace.enabled:
                trace.event("lc_park", cat="kv", uid=r.uid, group=g,
                            pages=self.chunk,
                            parked_pages=r.lc_parked * self.chunk)

    def residency(self, r) -> Dict[str, int]:
        """Resident vs parked page split for ``r`` (bench/monitor
        surface)."""
        resident = int((self.kv.page_table[r.slot] >= 0).sum())
        return {"resident_pages": resident,
                "parked_pages": r.lc_parked * self.chunk}

    # -- compiled dispatch family ----------------------------------------

    def _neutral(self, Tq: int):
        if Tq not in self._neutrals:
            from deepspeed_tpu.inference.paged import neutral_carry
            self._neutrals[Tq] = tuple(
                jnp.asarray(a) for a in neutral_carry(Tq, self.H,
                                                      self.D))
        return self._neutrals[Tq]

    def _kpos(self, g: int) -> jax.Array:
        if g not in self._kpos_cache:
            lo = (self.sink + g * self.chunk) * self.eng.page_size
            self._kpos_cache[g] = jnp.arange(lo, lo + self.R,
                                             dtype=jnp.int32)
        return self._kpos_cache[g]

    def _embed_fn(self, Tq: int):
        key = ("embed", Tq)
        if key not in self._fns:
            cfg = self.cfg
            mod = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                           dtype=cfg.dtype, param_dtype=cfg.param_dtype)

            def run(ep, ids):
                return mod.apply({"params": ep}, ids)

            run.__name__ = run.__qualname__ = f"lc_embed_t{Tq}"
            self._fns[key] = jax.jit(run)
        return self._fns[key]

    def _chunk_fn(self, Tq: int):
        key = ("chunk", Tq)
        if key not in self._fns:
            mod = _ChunkScan(self.cfg)
            quant = self._quant

            def run(lp, x, positions, staged_kv, staged_scales, kpos,
                    qpos, cm, cl, cacc):
                meta = {"staged_kv": staged_kv, "staged_kpos": kpos,
                        "staged_qpos": qpos, "carry_m": cm,
                        "carry_l": cl, "carry_acc": cacc}
                if quant:
                    meta["staged_scales"] = staged_scales
                _, vars_ = mod.apply({"params": lp}, x, positions,
                                     meta, mutable=["carry"])
                return vars_["carry"]["self_attn"]["stats"][0]

            run.__name__ = run.__qualname__ = f"lc_chunk_t{Tq}"
            self._fns[key] = jax.jit(run)
        return self._fns[key]

    def _finish_fn(self, Tq: int, has_carry: bool):
        key = ("finish", Tq, has_carry)
        if key not in self._fns:
            from deepspeed_tpu.models.llama import LlamaBlock
            mod = LlamaBlock(self.cfg)

            def run(lp, cache_l, x, positions, kv_lens, page_indices,
                    cu_q_lens, num_seqs, new_kv_dest, *carry):
                meta = {"kv_lens": kv_lens,
                        "page_indices": page_indices,
                        "cu_q_lens": cu_q_lens, "num_seqs": num_seqs,
                        "new_kv_dest": new_kv_dest}
                if has_carry:
                    meta["carry_m"], meta["carry_l"], \
                        meta["carry_acc"] = carry
                out, vars_ = mod.apply(
                    {"params": lp, "cache": cache_l}, x, positions,
                    True, meta, mutable=["cache"])
                return out, vars_["cache"]

            run.__name__ = run.__qualname__ = (
                f"lc_finish_t{Tq}{'_carry' if has_carry else ''}")
            self._fns[key] = jax.jit(run, donate_argnums=(1,))
        return self._fns[key]

    def _head_fn(self, Tq: int):
        key = ("head", Tq)
        if key not in self._fns:
            from deepspeed_tpu.models.llama import RMSNorm
            cfg = self.cfg
            norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype)
            dense = nn.Dense(cfg.vocab_size, use_bias=False,
                             dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype)

            def run(norm_p, head_p, x, row):
                xr = jnp.take(x, row, axis=1)           # [1, E]
                h = norm.apply({"params": norm_p}, xr)
                return dense.apply({"params": head_p}, h)   # [1, V]

            run.__name__ = run.__qualname__ = f"lc_head_t{Tq}"
            self._fns[key] = jax.jit(run)
        return self._fns[key]

    def _layer_cache(self, l: int):
        return self.kv.cache["model"][f"layers_{l}"]

    def _set_layer_cache(self, l: int, sub) -> None:
        c = self.kv.cache
        name = f"layers_{l}"
        if isinstance(c, dict):
            m = dict(c["model"])
            m[name] = sub
            self.kv.cache = {**c, "model": m}
        else:                                  # flax FrozenDict
            self.kv.cache = c.copy(
                {"model": c["model"].copy({name: sub})})

    # -- the tick ---------------------------------------------------------

    def tick(self, r) -> int:
        """One prefill chunk (``page_size`` prompt tokens) or one
        decode token for a partially-resident sequence; returns tokens
        produced (0 for non-final prefill ticks or a page-stalled
        wait)."""
        eng = self.eng
        page = eng.page_size
        prefilling = r.prefill_done < r.ctx_len
        if prefilling:
            lo = r.prefill_done
            take = min(page, r.ctx_len - lo)
            Tq = page
            written = lo
            tokens = np.zeros((Tq,), np.int32)
            tokens[:take] = r.ctx[lo:lo + take]
        else:
            lo = r.length - 1                 # this tick's write position
            take = 1
            Tq = 1
            written = lo
            tokens = np.asarray([eng.last_tokens[r.slot]], np.int32)
        hi = lo + take                        # tokens written after tick
        kv_len = hi
        if not self._grow(r, lo, hi):
            return 0                          # pool dry: sit the tick out
        frontier_col = (hi - 1) // page
        self._park(r, written, frontier_col)

        qpos = np.full((Tq,), -1, np.int32)   # pad rows mask every key
        qpos[:take] = np.arange(lo, hi)
        positions = np.zeros((Tq,), np.int32)
        positions[:take] = np.arange(lo, hi)

        n_parked = r.lc_parked
        groups: List[int] = []
        w = self.cfg.sliding_window
        for g in range(n_parked):
            if w is not None:
                kmax = (self.sink + (g + 1) * self.chunk) * page - 1
                if kmax <= lo - int(w):
                    continue                  # sliding window: out of reach
            groups.append(g)
        if n_parked:
            # read-ahead for THIS tick's peeks, bounded by the staging
            # ring; the tail re-issue below overlaps the NEXT tick
            self.kv.tiering.prefetch([self._key(r, g) for g in groups])
        staged: Dict[int, List[np.ndarray]] = {
            g: self.kv.tiering.peek(self._key(r, g)) for g in groups}

        params = eng.params
        x = self._embed_fn(Tq)(params["model"]["embed_tokens"],
                               eng.upload(tokens)[None])
        qpos_dev = eng.upload(qpos)
        pos_dev = eng.upload(positions)
        kv_lens = eng.upload(np.asarray([kv_len], np.int32))
        page_indices = eng.upload(self.kv.page_table[r.slot][None])
        cu_q_lens = eng.upload(np.asarray([0, take], np.int32))
        num_seqs = eng.upload(np.asarray([1], np.int32))
        dest = np.zeros((Tq,), np.int32)      # pad rows -> trash page 0
        pos_r = np.arange(lo, hi)
        pg = self.kv.page_table[r.slot, pos_r // page]
        assert (pg > 0).all(), "write into unallocated page"
        dest[:take] = pg * page + pos_r % page
        dest_dev = eng.upload(dest)

        chunk_fn = self._chunk_fn(Tq)
        for l in range(self.L):
            lp = params["model"][f"layers_{l}"]
            carry = None
            for g in groups:
                arrs = staged[g]
                kv_i, sc_i = self._leaf_idx[l]
                staged_kv = eng.upload(
                    arrs[kv_i].reshape(self.R, -1, self.D))
                scales = (eng.upload(
                    arrs[sc_i].reshape(self.R, -1))
                    if self._quant else None)
                c = carry if carry is not None else self._neutral(Tq)
                lp_attn = {"input_layernorm": lp["input_layernorm"],
                           "self_attn": lp["self_attn"]}
                carry = chunk_fn(lp_attn, x, pos_dev, staged_kv,
                                 scales, self._kpos(g), qpos_dev, *c)
            if carry is None:
                x, sub = self._finish_fn(Tq, False)(
                    lp, self._layer_cache(l), x, pos_dev, kv_lens,
                    page_indices, cu_q_lens, num_seqs, dest_dev)
            else:
                x, sub = self._finish_fn(Tq, True)(
                    lp, self._layer_cache(l), x, pos_dev, kv_lens,
                    page_indices, cu_q_lens, num_seqs, dest_dev,
                    *carry)
            self._set_layer_cache(l, sub)

        if trace.enabled:
            trace.event("lc_tick", cat="kv", uid=r.uid, q_tokens=take,
                        kv_len=int(kv_len), parked_groups=len(groups),
                        staged_dispatches=len(groups) * self.L)
        eng.host_stats.dispatches += 1 + len(groups) * self.L
        eng.host_stats.ticks += 1

        finishes = prefilling and hi >= r.ctx_len
        produced = 0
        if prefilling:
            r.prefill_done = hi
            if finishes:
                eng.request_latency.on_prefill_done(r.uid, r.ctx_len, 0)
        if finishes or not prefilling:
            sel = self._head_fn(Tq)(params["model"]["norm"],
                                    params["lm_head"], x,
                                    jnp.int32(take - 1))
            produced = eng.sample(sel, [(r, 0, True)])
        # read-ahead for the NEXT tick: decode revisits the same groups,
        # so the NVMe->host copies overlap host-side sampling/planning
        if n_parked:
            self.kv.tiering.prefetch(
                [self._key(r, g) for g in groups]
                [:max(eng.prefetch_lookahead, 1)])
        return produced
