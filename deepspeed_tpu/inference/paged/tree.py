"""The cache-tree contract ``inference/v2/kv_state.py`` stands on, and what
two cache kinds share without importing each other.

Every leaf of a model's ``cache`` collection is a page pool (leading dim:
pages) but for **per-slot state** that is no page: a ``[max_seqs, ...]`` leaf
named :data:`SLOT_STATE`, indexed by slot (``conv_state.py``,
``linear_state.py``, ``delta_state.py``; a layer may keep several, each in a
module of its own).  ``KVState`` tells the two apart by that name and knows
nothing else of a leaf.  A model whose window layers keep a pool OF THEIR OWN
beside its full layers' (``window_pages.py``) names those leaves
:data:`WINDOW_POOL`: they lead with THEIR page count and are indexed through
a page table of their own, so no verb that moves ``leaf[page]`` by the first
table's pages touches them.  :class:`Part` is what the layers of one kind add
to the description the engine reads (``paged.describe``)."""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

_LANES = 128            # of the chip's tiles: a pool's minor dim is whole ones
# the name of a per-slot state leaf in the ``cache`` collection
SLOT_STATE = "slot_state"


# and of a window layer's K/V pages, in the pool the window layers share
WINDOW_POOL = "window_kv_pages"


def _named(path, name: str) -> bool:
    return any(getattr(k, "key", None) == name for k in path)


def is_slot_state(path) -> bool:
    """Is the cache leaf at ``path`` (a ``tree_flatten_with_path`` key
    path) per-slot state and not a page pool?"""
    return _named(path, SLOT_STATE)


def window_pool_layers(cfg) -> int:
    """The layers of a model that keep their K/V in the window layers' own
    pool: the ``sliding_attention`` entries of a config whose
    ``layer_types`` names ``full_attention`` layers beside them; 0 for
    every other model (one window for the whole model stays on the one
    pool: ROADMAP, Design debts)."""
    kinds = list(getattr(cfg, "layer_types", None) or ())
    kinds = kinds[:int(cfg.num_hidden_layers)]
    return kinds.count("sliding_attention") if "full_attention" in kinds else 0


def split_cache(cache):
    """``(pools, states)``: the leaves of a cache tree that are pages of the
    first pool (leading dim: its pages) and those that are per-slot state
    (leading dim: slots), each in the tree's own leaf order.  The window
    layers' pool is neither (:func:`window_pool`)."""
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    return ([l for p, l in flat
             if not is_slot_state(p) and not _named(p, WINDOW_POOL)],
            [l for p, l in flat if is_slot_state(p)])


def window_pool(cache):
    """The leaves of the window layers' own pool (none: the model has
    none), in the tree's leaf order."""
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    return [l for p, l in flat if _named(p, WINDOW_POOL)]


def map_cache(cache, pool_fn=None, state_fn=None):
    """``cache`` with ``pool_fn`` applied to every leaf of the first pool
    and ``state_fn`` to every per-slot state leaf (None: left as it is);
    the window layers' pool is left as it is."""
    keep = lambda l: l
    pool_fn, state_fn = pool_fn or keep, state_fn or keep
    return jax.tree_util.tree_map_with_path(
        lambda p, l: (state_fn(l) if is_slot_state(p) else
                      l if _named(p, WINDOW_POOL) else pool_fn(l)), cache)


def _seq_of_rows(cu: jax.Array, T: int) -> jax.Array:
    """The packed sequence each of ``T`` rows lies in, by ``cu_q_lens``."""
    S = cu.shape[0] - 1
    t_idx = jnp.arange(T, dtype=jnp.int32)
    return jnp.minimum(jnp.sum(
        (t_idx[:, None] >= cu[None, 1:]).astype(jnp.int32), axis=1), S - 1)


def _one_row_seqs(ragged_meta, T: int):
    """``(one [S], first [S])``: which packed sequences of a ragged batch
    have exactly one query row, and every sequence's first row."""
    cu = ragged_meta["cu_q_lens"]
    S = cu.shape[0] - 1
    first = jnp.minimum(cu[:-1], T - 1)
    one = jnp.logical_and(jnp.arange(S) < ragged_meta["num_seqs"][0],
                          cu[1:] - cu[:-1] == 1)
    return one, first


def scoped_step(scope: str, fn, like, any_on=None):
    """``fn()`` under the named scope ``scope``: one step of a one-row path
    (its scores, its choice, its attention).  Where ``any_on`` (a traced
    scalar: has the batch a sequence of one row at all) is given, a batch
    that has none pays for no step of it and gets zeros shaped as ``like``
    (a tree of ``jax.ShapeDtypeStruct``)."""
    with jax.named_scope(scope):
        if any_on is None:
            return fn()
        return jax.lax.cond(any_on, fn, lambda: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), like))


def state_bytes_per_seq(cache) -> int:
    """Bytes of per-slot state one sequence owns (0: the model has none)."""
    return sum(math.prod(leaf.shape[1:]) * leaf.dtype.itemsize
               for leaf in split_cache(cache)[1])


@dataclasses.dataclass(frozen=True)
class Part:
    """What ``layers`` of a model's ``of`` layers, all of one cache kind, add
    to ``paged.CacheKind``; a kind's file subclasses it and answers what it
    has an answer to.  ``st``: the engine's ``HostStageStats``; ``cache``:
    its allocated cache tree."""
    layers: int
    of: int

    def refusals(self, model: str):
        """``(model_is, {feature: why})``: what ``model`` keeps, and why each
        named feature cannot carry it; None: every feature can."""

    def span_attrs(self, cache):
        """What every ``engine.dispatch`` span says of these layers."""
        return {}

    def counts(self, st, positions, q_lens=None):
        """What one dispatch's rows ask of these layers: for its span, and
        added to ``st``.  A decode block: ``positions [rows, ticks]``, each
        row its sequence's only one; a fused tick: ``positions`` of its
        packed rows, ``q_lens`` a sequence."""
        return {}

    def kernels(self, route: str):
        """Their entries of ``serving_stages()["kernels"]``; ``route``: the
        read path this backend gives a per-head K/V pool."""
        return {}

    def stages(self, st, cache, cfg, kernels, pages_peak: int):
        """Their block(s) of ``serving_stages()``."""
        return {}


# KVState owns the state as it owns pages; what would have to snapshot or
# share it and cannot yet is refused by name, not served wrongly
SLOT_STATE_IS = "{model} keeps per-slot state in {p.layers} of its {p.of} layers"
SLOT_STATE_REFUSES = {
    "prefix_cache": ", and a shared prefix (or its copy-on-write page) brings "
    "keys and values only — a sequence that starts behind one needs the "
    "state AT the prefix's end, which the index does not keep",
    "quantized": "; its pool is full-width (heads of 64 are stored padded to "
    "the kernels' 128 lanes, which the quantized pool's kernel does not "
    "know)",
    "tensor_parallel": ", and the cache's sharding rule knows page pools only"}


# a matrix a head is megabytes a sequence: no entry of a request's record in
# the spill tiers
MATRIX_STATE_REFUSES = {
    "tiering": ", and a spilled request's record would carry megabytes of "
    "state a sequence beside its pages (ROADMAP R5(d): a tier for matrix "
    "state)"}


class SlotState(Part):
    """Layers that keep per-slot state beside the pool and no K/V."""

    def refusals(self, model):
        return SLOT_STATE_IS.format(model=model, p=self), SLOT_STATE_REFUSES

    def span_attrs(self, cache):
        return {"state_layers": self.layers,
                "state_bytes": state_bytes_per_seq(cache)}

    def counts(self, st, positions, q_lens=None):
        seqs = len(positions if q_lens is None else q_lens)
        st.state_rows += seqs
        return {"state_rows": seqs}
