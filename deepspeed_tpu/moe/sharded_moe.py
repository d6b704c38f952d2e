"""MoE gating and dispatch math.

TPU-native re-design of ``deepspeed/moe/sharded_moe.py`` (``top1gating:183``,
``top2gating:290``, ``topkgating:374``, ``MOELayer:533``, ``_capacity:161``).
Same einsum formulation — combine/dispatch tensors ``[tokens, experts,
capacity]`` with capacity-factor padding so shapes stay static under jit —
but the all-to-all dispatch is *implicit*: the dispatched tensor is
sharding-constrained onto the ``expert`` mesh axis and XLA/GSPMD emits the
all-to-all the reference issues by hand (``_AllToAll:96``), riding ICI.

Capacity here is computed from the GLOBAL token count (the reference uses
per-rank counts; global capacity is the natural formulation when dispatch is
a sharded einsum — same expected load, no per-rank imbalance artifacts).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class GatingResult(NamedTuple):
    l_aux: jax.Array          # scalar load-balancing loss
    combine: jax.Array        # [G, E, C] float combine weights
    dispatch: jax.Array       # [G, E, C] bool dispatch mask
    exp_counts: jax.Array     # [E] tokens routed per expert (pre-drop)
    # sparse routing view (the gather/scatter dispatch path; under jit the
    # dense combine/dispatch tensors are dead-code-eliminated when only
    # these are consumed): per choice k and token g —
    experts: jax.Array        # [k, G] int32 selected expert
    positions: jax.Array      # [k, G] int32 slot within the expert buffer
    weights: jax.Array        # [k, G] f32 renormalized combine weight
    #                           (0 for capacity-dropped choices)
    # (capacity C is static — recover it as combine.shape[-1])


def capacity(num_tokens: int, num_experts: int, capacity_factor: float,
             min_capacity: int, k: int = 1) -> int:
    """Static per-expert capacity (reference ``_capacity``,
    ``sharded_moe.py:161``; scaled by k so top-k routing has room)."""
    cap = int(np.ceil(k * capacity_factor * num_tokens / num_experts))
    return max(cap, min_capacity)


def topkgating(logits: jax.Array, k: int = 1,
               capacity_factor: float = 1.0, min_capacity: int = 4,
               drop_tokens: bool = True,
               noise_rng: Optional[jax.Array] = None,
               noise_eps: float = 1e-2,
               normalize_weights: bool = True) -> GatingResult:
    """Top-k gating with capacity-bounded dispatch.

    Covers the reference's ``top1gating``/``top2gating``/``topkgating``:
    iterative argmax selection, position-in-expert via token cumsum, gate
    normalization over the selected experts (top2-style), capacity drop, and
    the switch-transformer load-balancing aux loss from the first choice.

    ``normalize_weights=False`` keeps the raw softmax gate values of the
    selected experts (Qwen2-MoE ``norm_topk_prob=False``; the reference's
    topkgating exposes the same toggle, ``sharded_moe.py:374``).
    """
    G, E = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    select_from = logits.astype(jnp.float32)
    if noise_rng is not None:  # multiplicative jitter (reference noisy_gate)
        select_from = select_from * jax.random.uniform(
            noise_rng, select_from.shape, minval=1.0 - noise_eps,
            maxval=1.0 + noise_eps)

    masks, indices = [], []
    remaining = select_from
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        masks.append(mask)
        indices.append(idx.astype(jnp.int32))
        remaining = jnp.where(mask > 0, -jnp.inf, remaining)

    # aux loss: fraction of tokens * fraction of router prob per expert
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(masks[0], axis=0)
    l_aux = jnp.sum(me * ce) * E
    exp_counts = sum(jnp.sum(m, axis=0) for m in masks)

    if drop_tokens:
        C = capacity(G, E, capacity_factor, min_capacity, k=k)
    else:
        C = G  # worst case: every token to one expert

    # position of each token within its expert's capacity buffer: cumsum
    # over tokens, with later choices placed after all earlier choices
    positions, keeps = [], []
    offset = jnp.zeros((E,), jnp.float32)
    for mask in masks:
        loc = jnp.cumsum(mask, axis=0) - mask + offset[None, :]  # [G, E]
        offset = offset + jnp.sum(mask, axis=0)
        pos = jnp.sum(loc * mask, axis=-1).astype(jnp.int32)     # [G]
        positions.append(pos)
        keeps.append((pos < C).astype(jnp.float32))

    # gate values of the selected experts, normalized over the *surviving*
    # selection: the reference zeroes capacity-dropped choices in the masks
    # BEFORE computing gates1_s/gates2_s (top2gating, sharded_moe.py:290), so
    # when one choice drops the other absorbs the full weight (sums to 1)
    gate_k = [jnp.sum(gates * m, axis=-1) for m in masks]        # k x [G]
    if normalize_weights:
        denom = sum(g * keep for g, keep in zip(gate_k, keeps))
        denom = jnp.maximum(denom, jnp.finfo(jnp.float32).eps)
    else:
        denom = jnp.ones_like(gate_k[0])

    combine = jnp.zeros((G, E, C), jnp.float32)
    weights_k = []
    for mask, g, pos, keep in zip(masks, gate_k, positions, keeps):
        w = g * keep / denom                                      # [G]
        weights_k.append(w)
        combine = combine + (w[:, None, None] * mask[:, :, None] *
                             jax.nn.one_hot(pos, C, dtype=jnp.float32
                                            )[:, None, :])
    dispatch = combine > 0
    return GatingResult(l_aux=l_aux, combine=combine, dispatch=dispatch,
                        exp_counts=exp_counts,
                        experts=jnp.stack(indices),
                        positions=jnp.stack(positions),
                        weights=jnp.stack(weights_k))


def top1gating(logits, capacity_factor: float = 1.0, min_capacity: int = 4,
               **kw) -> GatingResult:
    return topkgating(logits, k=1, capacity_factor=capacity_factor,
                      min_capacity=min_capacity, **kw)


def top2gating(logits, capacity_factor: float = 1.0, min_capacity: int = 4,
               **kw) -> GatingResult:
    return topkgating(logits, k=2, capacity_factor=capacity_factor,
                      min_capacity=min_capacity, **kw)


def moe_dispatch(x: jax.Array, dispatch: jax.Array) -> jax.Array:
    """[G, M] tokens -> [E, C, M] expert buffers (reference
    ``einsum("sec,sm->ecm")``)."""
    return jnp.einsum("gec,gm->ecm", dispatch.astype(x.dtype), x)


def moe_combine(expert_out: jax.Array, combine: jax.Array) -> jax.Array:
    """[E, C, M] expert outputs -> [G, M] tokens (reference
    ``einsum("sec,ecm->sm")``)."""
    return jnp.einsum("gec,ecm->gm", combine.astype(expert_out.dtype),
                      expert_out)


def _dest_slots(gr: GatingResult, num_experts: int, cap: int) -> jax.Array:
    """[k, G] flat destination slot per routed copy; capacity-dropped
    copies point one past the end (scatter mode='drop' discards them)."""
    dest = gr.experts * cap + gr.positions
    return jnp.where(gr.weights > 0, dest, num_experts * cap)


def moe_dispatch_gather(x: jax.Array, gr: GatingResult,
                        num_experts: int) -> jax.Array:
    """[G, M] tokens -> [E, C, M] expert buffers by row scatter.

    Same result as :func:`moe_dispatch` with ~1% of the FLOPs, but NOTE:
    measured on TPU v5e the scatter lowering is ~20x SLOWER than the
    dense einsum (the einsum rides the MXU; the row scatter does not) —
    this path is for CPU/debug and as a parity oracle.  (expert,
    position) pairs are unique across choices by construction (later
    choices are offset past all earlier choices' counts), so the scatter
    has no collisions."""
    k, G = gr.weights.shape
    E, M = num_experts, x.shape[-1]
    C = gr.combine.shape[-1]
    dest = _dest_slots(gr, E, C).reshape(-1)                # [k*G]
    xk = jnp.broadcast_to(x[None], (k, G, M)).reshape(k * G, M)
    buf = jnp.zeros((E * C, M), x.dtype)
    # no unique_indices promise: dropped copies all alias the same
    # out-of-bounds slot before mode="drop" discards them
    buf = buf.at[dest].set(xk, mode="drop")
    return buf.reshape(E, C, M)


def moe_combine_gather(expert_out: jax.Array, gr: GatingResult
                       ) -> jax.Array:
    """[E, C, M] expert outputs -> [G, M] by row gather + weighted sum
    over the k choices (inverse of :func:`moe_dispatch_gather`)."""
    E, C, M = expert_out.shape
    flat = expert_out.reshape(E * C, M)
    dest = _dest_slots(gr, E, C)                            # [k, G]
    rows = flat.at[dest].get(mode="fill", fill_value=0)     # [k, G, M]
    w = gr.weights.astype(expert_out.dtype)[:, :, None]
    return jnp.sum(w * rows, axis=0)


# ---------------------------------------------------------------------------
# Sorted (gather-only) dispatch — the megablocks idea, TPU-shaped
# ---------------------------------------------------------------------------
# The dense one-hot dispatch/combine einsums cost G*E*C*M MACs each, and with
# C = k*G/E that is QUADRATIC in the token count — at the bench shapes it ties
# the FFN itself once micro-batches grow, and the [G, E, C] one-hots become
# multi-hundred-MB temporaries.  The reference's answer is a grouped CUTLASS
# GEMM over expert-sorted rows (inference/v2/kernels/cutlass_ops/moe_gemm/
# moe_gemm.cu); the TPU-native answer below reproduces the same sorted-rows
# layout with a stable argsort + row GATHERS (cost linear in G) feeding the
# SAME dense batched [E, C, M] FFN einsums that already ride the MXU.
#
# TPU scatter lowering is catastrophic (measured 20x the einsum path), so no
# scatter appears anywhere — including the BACKWARD: both permutation ops are
# custom-VJP'd so their gradients are gathers too (the inverse permutation is
# known statically from the forward plan).
#
# Ordering parity: within an expert, stable argsort over copy ids (choice-
# major, then token) reproduces exactly the position ordering topkgating
# computes (per-choice offset + token cumsum), so capacity drops select the
# SAME copies as the einsum path and outputs match bit-for-bit (modulo bf16
# summation order in the FFN).


class RoutingPlan(NamedTuple):
    slot_token: jax.Array     # [E, C] int32: token id filling each slot
    #                           (G = sentinel "empty"; rows gathered as 0)
    slot_of_copy: jax.Array   # [k, G] int32: flat slot e*C + c per copy
    #                           (E*C = sentinel "dropped")


def routing_plan(gr: GatingResult, num_experts: int) -> RoutingPlan:
    """Integer-only routing plan; no scatter, all O(kG log kG) sort work."""
    k, G = gr.experts.shape
    E = num_experts
    C = gr.combine.shape[-1]     # static; shape access does not materialize
    ec = gr.experts.reshape(-1)                       # [kG] expert per copy
    sort_idx = jnp.argsort(ec, stable=True)           # sorted copy ids
    inv = jnp.argsort(sort_idx, stable=True)          # copy -> sorted pos
    gs = jnp.sum(jax.nn.one_hot(ec, E, dtype=jnp.int32), axis=0)
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(gs)[:-1].astype(jnp.int32)])
    cpos = jnp.arange(C, dtype=jnp.int32)[None, :]    # [1, C]
    src_pos = off[:, None] + cpos                     # [E, C] sorted position
    valid = cpos < gs[:, None]
    tok = jnp.tile(jnp.arange(G, dtype=jnp.int32), (k,))
    tok_sorted = jnp.take(tok, sort_idx, axis=0)
    slot_token = jnp.where(
        valid,
        jnp.take(tok_sorted, jnp.clip(src_pos, 0, k * G - 1).reshape(-1),
                 axis=0).reshape(E, C),
        G)
    c_of_copy = inv - jnp.take(off, ec, axis=0)
    slot_of_copy = jnp.where(c_of_copy < C, ec * C + c_of_copy, E * C)
    return RoutingPlan(slot_token=slot_token,
                       slot_of_copy=slot_of_copy.reshape(k, G))


def _take_rows(src: jax.Array, idx: jax.Array) -> jax.Array:
    return jnp.take(src, idx, axis=0)


def _pad_rows(x: jax.Array) -> jax.Array:
    """Append one zero row so sentinel indices gather zeros."""
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])


@jax.custom_vjp
def sorted_dispatch(x: jax.Array, slot_token: jax.Array,
                    slot_of_copy: jax.Array) -> jax.Array:
    """[G, M] tokens -> [E, C, M] expert buffers by row gather."""
    E, C = slot_token.shape
    return _take_rows(_pad_rows(x), slot_token.reshape(-1)).reshape(
        E, C, x.shape[-1])


def _sd_fwd(x, slot_token, slot_of_copy):
    return sorted_dispatch(x, slot_token, slot_of_copy), (slot_of_copy,)


def _sd_bwd(res, d):
    (slot_of_copy,) = res
    k = slot_of_copy.shape[0]
    E, C, M = d.shape
    dflat = _pad_rows(d.reshape(E * C, M))
    # d x[g] = sum over g's surviving copies of d disp[slot]; dropped copies
    # hit the zero sentinel row — a gather per choice, never a scatter
    dx = sum(_take_rows(dflat, slot_of_copy[j]) for j in range(k))
    return dx, None, None


sorted_dispatch.defvjp(_sd_fwd, _sd_bwd)


@jax.custom_vjp
def sorted_combine(expert_out: jax.Array, weights: jax.Array,
                   slot_token: jax.Array, slot_of_copy: jax.Array
                   ) -> jax.Array:
    """[E, C, M] expert outputs -> [G, M]: gather each copy's row, weighted
    sum over the k choices (weights are the gating's renormalized combine
    weights, 0 for capacity-dropped copies)."""
    E, C, M = expert_out.shape
    flat = _pad_rows(expert_out.reshape(E * C, M))
    rows = _take_rows(flat, slot_of_copy.reshape(-1)).reshape(
        slot_of_copy.shape + (M,))                    # [k, G, M]
    return jnp.sum(weights.astype(expert_out.dtype)[..., None] * rows,
                   axis=0)


def _sc_fwd(expert_out, weights, slot_token, slot_of_copy):
    return (sorted_combine(expert_out, weights, slot_token, slot_of_copy),
            (expert_out, weights, slot_token, slot_of_copy))


def _sc_bwd(res, dy):
    expert_out, weights, slot_token, slot_of_copy = res
    E, C, M = expert_out.shape
    k, G = weights.shape
    # d out[e,c] = w_of_slot * dy[token_of_slot]: both gathers.  The weight
    # of the copy occupying slot s is recovered per choice j by checking
    # whether token slot_token[s]'s j-th copy landed in s.
    flat_slots = jnp.arange(E * C, dtype=jnp.int32).reshape(E, C)
    d_rows = _take_rows(_pad_rows(dy), slot_token.reshape(-1)).reshape(
        E, C, M)
    w_slot = jnp.zeros((E, C), dy.dtype)
    for j in range(k):
        wj = _take_rows(
            jnp.concatenate([weights[j].astype(dy.dtype),
                             jnp.zeros((1,), dy.dtype)]),
            slot_token.reshape(-1)).reshape(E, C)
        copy_slot = _take_rows(
            jnp.concatenate([slot_of_copy[j],
                             jnp.full((1,), -1, jnp.int32)]),
            slot_token.reshape(-1)).reshape(E, C)
        w_slot = w_slot + jnp.where(copy_slot == flat_slots, wj, 0)
    dout = d_rows * w_slot[..., None]
    # d weights[j,g] = dy[g] . out_flat[slot_of_copy[j,g]]
    rows = _take_rows(_pad_rows(expert_out.reshape(E * C, M)),
                      slot_of_copy.reshape(-1)).reshape(k, G, M)
    dw = jnp.sum(rows.astype(jnp.float32) * dy.astype(jnp.float32)[None],
                 axis=-1)
    return dout, dw.astype(weights.dtype), None, None


sorted_combine.defvjp(_sc_fwd, _sc_bwd)


# ---------------------------------------------------------------------------
# Dropless grouped dispatch: the serving path
# ---------------------------------------------------------------------------
# Every path above is capacity-based: a token is dropped when its expert's
# buffer is full, and WHICH token depends on the other rows of the batch.  A
# served token's logits may depend on no other row of its tick, so serving
# takes none of them.  Here the routed copies are sorted by expert into ONE
# ``[k*G, M]`` buffer with no padding between experts, and each expert's
# contiguous run of rows meets its weight in a grouped matmul (the
# reference's ``moe_gemm``): ``ops/grouped_matmul.py``, a tiled Pallas
# kernel (megablox ``gmm``'s forward) on one TPU device and its twin
# ``jax.lax.ragged_dot`` elsewhere, which the v5e compiler lowers to a
# Mosaic kernel of its own (``ragged-dot`` custom calls; the Pallas kernel
# takes about three quarters of their time, PERF.md §6 PR 29) and the CPU
# backend to plain XLA; neither builds a dense ``[E, G, M]``
# expansion.  Work and memory are linear in the routed copies; no
# capacity, no ``[G, E, C]``.

GROUPED_MATMUL = "moe_grouped_matmul"


def grouped_matmul_route() -> str:
    """Whether the grouped matmul runs as a Mosaic kernel or as XLA's
    expansion: the backend's answer alone, recorded like every kernel
    switch (``ops/routes.py``).  WHICH Mosaic kernel (the Pallas one and
    its tiles, or the compiler's lowering of ``ragged_dot`` where a call
    fell back) is the second key's to say
    (``ops/grouped_matmul.py:KERNEL``)."""
    from deepspeed_tpu.ops import routes

    return routes.take(GROUPED_MATMUL, pallas="ragged-dot-mosaic",
                       xla="ragged-dot-xla")


def topk_routing(logits: jax.Array, k: int, normalize_weights: bool
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(experts [G, k] int32, weights [G, k] f32, l_aux)`` of a float32
    softmax router: the k largest, ties to the lower expert as the
    iterative argmax of :func:`topkgating` breaks them; their softmax
    values as they are, or renormalized over the k."""
    logits = logits.astype(jnp.float32)
    E = logits.shape[-1]
    gates = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(logits, k)
    weights = jnp.take_along_axis(gates, experts, axis=-1)
    if normalize_weights:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True),
            jnp.finfo(jnp.float32).eps)
    ce = jnp.mean(jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32), 0)
    l_aux = jnp.sum(jnp.mean(gates, axis=0) * ce) * E
    return experts.astype(jnp.int32), weights, l_aux


def sigmoid_bias_routing(logits: jax.Array, bias: Optional[jax.Array],
                         k: int, normalize_weights: bool,
                         scaling_factor: float = 1.0, eps: float = 1e-6
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(experts [G, k] int32, weights [G, k] f32, bias_swaps)`` of a
    float32 sigmoid router with a per-expert SELECTION bias (DeepSeek-V3's
    auxiliary-loss-free balancing; LFM2-MoE's ``use_expert_bias``): the
    scores are ``sigmoid(logits)``, the chosen set the k largest of
    ``score + bias``, and the weights the chosen SCORES, without the bias,
    renormalised over the k (``+ eps`` in the denominator: 1e-6 in
    LFM2-MoE's code, 1e-20 in the DeepSeek-V3 family's) or as they are,
    times ``scaling_factor``.  ``bias_swaps [G]`` marks the tokens
    whose chosen set is not the k largest scores: what the bias changed."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, plain = jax.lax.top_k(scores, k)
    if bias is None:
        experts = plain
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalize_weights:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    E = logits.shape[-1]
    chosen = lambda idx: jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.int32), 1)
    swaps = jnp.any(chosen(experts) != chosen(plain), axis=-1)
    return experts.astype(jnp.int32), weights * scaling_factor, swaps


def grouped_moe(x: jax.Array, experts: jax.Array, weights: jax.Array,
                num_experts: int, expert_ffn, live: Optional[jax.Array],
                first_held: Optional[int] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Dropless routed FFN.  ``x [G, M]``; ``experts``/``weights [G, k]``;
    ``expert_ffn(rows [k*G, M], group_sizes [E]) -> [k*G, M]`` runs the
    grouped matmuls over expert-sorted rows; ``live [G]`` (or None: all)
    marks the rows that are tokens — padding of a tick and inactive decode
    slots take no expert row and come back as zeros.

    Returns ``(y [G, M], counts int32[4])``: token copies routed, experts
    with at least one row, rows of the fullest expert, row-tile visits of
    the grouped matmul (``ops/grouped_matmul.py:visits``).

    ``first_held`` (None: the layer holds every expert the router names):
    the layer holds the ``num_experts`` banks of experts ``[first_held,
    first_held + num_experts)`` of a wider router (:func:`held_moe`).
    """
    from deepspeed_tpu.ops import grouped_matmul

    if first_held is not None:
        return held_moe(x, experts - first_held, weights, num_experts,
                        expert_ffn, live)
    G, k = experts.shape
    E = num_experts
    # the scopes name the four stages for ``telemetry/device_scopes.py``:
    # this is a plain function, so no module path tells them apart
    with jax.named_scope("moe.dispatch"):
        ec = experts.reshape(-1)                      # copy = token * k + j
        if live is not None:
            # a dead row's copies sort past every expert and join no group
            ec = jnp.where(jnp.repeat(live, k), ec, E)
        order = jnp.argsort(ec, stable=True)          # sorted pos -> copy
        group_sizes = jnp.sum(jax.nn.one_hot(ec, E, dtype=jnp.int32),
                              axis=0)
        sorted_rows = jnp.take(x, order // k, axis=0)
    with jax.named_scope("moe.experts"):
        out = expert_ffn(sorted_rows, group_sizes)
    with jax.named_scope("moe.combine"):
        # combine by gather: copy -> its row of the sorted buffer
        rows = jnp.take(out, jnp.argsort(order), axis=0).reshape(G, k, -1)
        y = jnp.sum(weights.astype(out.dtype)[..., None] * rows, axis=1)
        if live is not None:
            # rows past the last group are whatever the kernel left there
            y = jnp.where(live[:, None], y, 0)
        counts = jnp.stack([jnp.sum(group_sizes),
                            jnp.sum((group_sizes > 0).astype(jnp.int32)),
                            jnp.max(group_sizes),
                            grouped_matmul.visits(group_sizes)])
    return y, counts


# rows of the sorted buffer a held layer's first form has room for, as a
# share of the routed copies: a chip of eight sees an eighth of them under a
# uniform router, and seeded routers skew
HELD_ROOM = 4


def held_moe(x: jax.Array, experts: jax.Array, weights: jax.Array,
             num_experts: int, expert_ffn, live: Optional[jax.Array]
             ) -> Tuple[jax.Array, jax.Array]:
    """:func:`grouped_moe` for a layer that HOLDS ``num_experts`` of the
    experts its router names (one chip's share of a layer that several
    share): ``experts`` counts from the first one held, so a copy routed to
    an absent expert lies outside ``[0, num_experts)``, joins no group,
    takes no row and adds nothing.  What the absent experts would have
    added is some other chip's to compute and is left out.

    About ``held / routed`` of the copies are live, so the sorted buffer is
    not built whole: the copies are sorted (absent ones last) and the FIRST
    ``B`` rows gathered, multiplied and combined, ``B`` a quarter of the
    copies (:data:`HELD_ROOM`) where the live ones fit, else all of them
    (a ``cond`` on their count, on the device): dropless whatever the
    router does, and a tick pays for the copies it has.  The combine is one
    product on the MXU, ``W [G, B] x out [B, M]`` with ``W`` a row's weight
    at its token, where the whole layer gathers ``k`` rows a token: most of
    a token's ``k`` copies are absent here.

    Returns ``(y, counts int32[4])`` as :func:`grouped_moe`: the counts are
    of what the held banks saw."""
    from deepspeed_tpu.ops import grouped_matmul

    G, k = experts.shape
    E = num_experts
    with jax.named_scope("moe.dispatch"):
        ec = experts.reshape(-1)                      # copy = token * k + j
        held = jnp.logical_and(ec >= 0, ec < E)
        if live is not None:
            held = jnp.logical_and(held, jnp.repeat(live, k))
        ec = jnp.where(held, ec, E)
        order = jnp.argsort(ec, stable=True)          # sorted pos -> copy
        group_sizes = jnp.sum(jax.nn.one_hot(ec, E, dtype=jnp.int32),
                              axis=0)
        rows_live = jnp.sum(group_sizes)
        w_sorted = jnp.take(jnp.where(held, weights.reshape(-1), 0.0),
                            order)

    def first(room: int):
        def run():
            with jax.named_scope("moe.dispatch"):
                token = order[:room] // k
                sorted_rows = jnp.take(x, token, axis=0)
            with jax.named_scope("moe.experts"):
                out = expert_ffn(sorted_rows, group_sizes)
            with jax.named_scope("moe.combine"):
                # rows past the last group are whatever the kernel left
                # there: their weight is zero AND they are zeroed
                at = jnp.arange(room) < rows_live
                out = jnp.where(at[:, None], out, 0)
                w = jnp.where(at, w_sorted[:room], 0.0).astype(out.dtype)
                onto = jnp.where(jnp.arange(G)[:, None] == token[None, :],
                                 w[None, :], 0)
                return jnp.dot(
                    onto, out, preferred_element_type=jnp.float32,
                    precision=(jax.lax.Precision.HIGHEST
                               if out.dtype == jnp.float32 else None)
                ).astype(out.dtype)
        return run

    room = -(-G * k // HELD_ROOM)
    y = jax.lax.cond(rows_live <= room, first(room), first(G * k))
    with jax.named_scope("moe.combine"):
        counts = jnp.stack([rows_live,
                            jnp.sum((group_sizes > 0).astype(jnp.int32)),
                            jnp.max(group_sizes),
                            grouped_matmul.visits(group_sizes)])
    return y, counts
