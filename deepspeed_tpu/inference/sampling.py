"""Token sampling for the generate loop.

The reference defers sampling to HuggingFace ``generate`` (v1,
``inference/engine.py:554``) or implements greedy/top-p in its ragged
logits-gather kernels (v2).  Here sampling is a pure jittable function over
the last-position logits so the whole generate loop stays inside one XLA
program.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def position_keys(base: jax.Array, seeds: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """Per-row PRNG keys [B, 2] for scheduling-invariant sampling: the
    key for one drawn token is a pure function of (engine base key,
    request seed, cache position), so the SAME token of the SAME
    request samples identically no matter how requests were batched,
    chunked, evicted, or prefix-cache-skipped along the way.  This is
    what lets seeded sampling stay bit-identical between prefix-cache
    on and off runs, whose dispatch sequences differ."""
    def one(seed, pos):
        return jax.random.fold_in(jax.random.fold_in(base, seed), pos)

    return jax.vmap(one)(seeds, positions)


def sample_logits(logits: jax.Array, rng: Optional[jax.Array], *,
                  do_sample: bool = False, temperature: float = 1.0,
                  top_k: int = 0, top_p: float = 1.0) -> jax.Array:
    """Next token ids [B] from logits [B, V].

    ``do_sample``/``top_k`` are static (change recompiles); temperature and
    top_p are folded in as constants of the compiled program too since they
    arrive as Python floats.

    ``rng`` may be one key (shared draw over the batch) or per-row keys
    ``[B, 2]`` from :func:`position_keys` (detected by ndim).
    """
    logits = logits.astype(jnp.float32)
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if temperature != 1.0:
        logits = logits / jnp.float32(max(temperature, 1e-6))
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]      # [B, 1]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]  # desc
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens while the mass BEFORE them is < top_p (always >=1 kept)
        keep_sorted = (cum - probs) < top_p
        kth_idx = jnp.sum(keep_sorted, axis=-1, keepdims=True) - 1
        cutoff = jnp.take_along_axis(sorted_logits, kth_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    assert rng is not None, "sampling needs an rng"
    if rng.ndim == 2:                                    # per-row keys
        return jax.vmap(lambda k, l: jax.random.categorical(k, l))(
            rng, logits).astype(jnp.int32)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def filter_logits_batched(logits: jax.Array, temperature: jax.Array,
                          top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Per-row temperature/top-k/top-p filter over [S, V] float32 logits:
    kept entries scaled by temperature, filtered entries at ``-inf``.
    Softmax of the result is the distribution
    :func:`sample_logits_batched` draws from."""
    S, V = logits.shape
    lg = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # top-k: threshold at the k-th largest value (k<=0 -> keep all)
    k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
    sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(sorted_lg, (k - 1)[:, None], axis=-1)
    lg = jnp.where(lg < kth, -jnp.inf, lg)
    # top-p on the top-k-filtered distribution (matches sample_logits'
    # sequential filter semantics) — re-sort so masked rows drop out
    sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_lg, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < jnp.clip(top_p, 0.0, 1.0)[:, None]
    kth_idx = jnp.maximum(jnp.sum(keep_sorted, axis=-1, keepdims=True) - 1,
                          0)
    cutoff = jnp.take_along_axis(sorted_lg, kth_idx, axis=-1)
    return jnp.where(lg < cutoff, -jnp.inf, lg)


def sample_logits_batched(logits: jax.Array, rng: Optional[jax.Array],
                          do_sample: jax.Array, temperature: jax.Array,
                          top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Per-ROW sampling configs, fully on device: next token ids [S] from
    logits [S, V] with ``do_sample``/``temperature``/``top_k``/``top_p``
    as [S] arrays (so one compiled program serves a continuous batch of
    requests with heterogeneous sampling settings — the v2 engine's
    on-device multi-tick decode needs this; the reference samples host-side
    per request).

    ``rng=None`` compiles the pure-greedy program (no sort).  ``top_k <= 0``
    and ``top_p >= 1`` disable their filters per row.  ``rng`` may be one
    key or per-row keys ``[S, 2]`` (:func:`position_keys`).
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if rng is None:
        return greedy
    lg = filter_logits_batched(logits, temperature, top_k, top_p)
    if rng.ndim == 2:                                    # per-row keys
        sampled = jax.vmap(lambda k, l: jax.random.categorical(k, l))(
            rng, lg).astype(jnp.int32)
    else:
        sampled = jax.random.categorical(rng, lg, axis=-1).astype(jnp.int32)
    return jnp.where(do_sample, sampled, greedy)
