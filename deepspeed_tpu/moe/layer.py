"""Mixture-of-Experts layer.

TPU-native re-design of the reference MoE stack (``deepspeed/moe/layer.py:17
MoE``, ``sharded_moe.py:533 MOELayer``, ``TopKGate:449``): the reference
builds per-rank expert modules and issues explicit all-to-alls
(``_AllToAll:96``) between gate, experts, and combine; here the experts are
ONE stacked parameter tensor ``[E, ...]`` whose leading axis is annotated
onto the ``expert`` mesh axis.  Two multi-chip dispatch formulations exist:

- ``alltoall`` (the default on any multi-device mesh): the reference's own
  architecture — per-shard linear (sorted, gather-only) dispatch into
  ``[E, C_local, M]`` buffers, an explicit ``lax.all_to_all`` over the
  ``expert`` mesh axis (``_AllToAll:96``), local expert FFNs, and the
  inverse all-to-all — expressed as a ``jax.shard_map`` manual over the
  token-sharding axes while the ``tensor`` axis stays under automatic
  GSPMD (Megatron TP of the expert FFN still works).  Cost is LINEAR in
  tokens; capacity is per shard, matching the reference's per-rank counts.
- ``einsum``: dense one-hot dispatch/combine einsums sharding-constrained
  onto the expert axis so GSPMD inserts the all-to-alls.  Quadratic in
  token count (C ~ kG/E) — kept as the parity oracle and for meshes whose
  token sharding the alltoall path cannot express.

Expert-parallel composition mirrors ``groups.py:236
_create_expert_and_data_parallel``: the ``expert`` mesh axis carries both
the expert shards and (being a ZeRO axis) a slice of the data batch, so
ep_size experts x dp replicas works out of the box; MoE-aware ZeRO
(``stage_1_and_2.py:616 _configure_moe_settings``) falls out of the
sharding-plan composition — expert params keep their ``expert`` axis and
ZeRO claims a *different* dim.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from deepspeed_tpu.utils.compat import shard_map as _shard_map_compat

from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
from deepspeed_tpu.moe.sharded_moe import (grouped_matmul_route, grouped_moe,
                                           moe_combine, moe_combine_gather,
                                           moe_dispatch, moe_dispatch_gather,
                                           routing_plan, sorted_combine,
                                           sigmoid_bias_routing,
                                           sorted_dispatch, topk_routing,
                                           topkgating)
from deepspeed_tpu.utils.sharding import maybe_constrain as _maybe_constrain

EXPERT_AXIS = "expert"

# Engine-pinned topology for dispatch_impl='auto' (see
# MoE._resolve_dispatch): set by DeepSpeedEngine at build time so the
# resolution does not depend on WHEN flax traces the layer.
_AUTO_PIN_TOPO = None


def pin_auto_dispatch(topology) -> None:
    """Pin the topology that ``dispatch_impl='auto'`` resolves against
    when no live topology is installed at trace time.  The engine calls
    this at build; pass ``None`` to clear (tests)."""
    global _AUTO_PIN_TOPO
    _AUTO_PIN_TOPO = topology
# every mesh axis the flattened token dim may be sharded over (the engine's
# batch spec: data x data_sub x expert, plus seq under sequence parallelism)
TOKEN_AXES = ("data", "data_sub", "expert", "seq")


class MoE(nn.Module):
    """Top-k routed MoE FFN: gate -> dispatch -> experts -> combine.

    Returns ``(y, l_aux)``; the caller plumbs ``l_aux`` into the training
    loss (reference stores it on the layer and the engine collects it).
    """

    hidden_size: int
    num_experts: int
    intermediate_size: int
    k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    drop_tokens: bool = True
    # the expert body: "swiglu" (Mixtral: w2(silu(w1 x) * w3 x)), "reglu"
    # (SmallThinker: w2(relu(w1 x) * w3 x), the same three banks) or "gelu"
    # (the biased pair w2 gelu(w1 x + b1) + b2); any other value raises
    activation: str = "swiglu"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    expert_parallel: bool = True           # annotate the expert mesh axis
    tensor_parallel: bool = False          # shard expert FFN over `tensor`
    noisy_gate_policy: Optional[str] = None  # None | "Jitter"
    # renormalize the selected experts' gates to sum to 1 (Mixtral); False
    # keeps raw softmax gates (Qwen2-MoE norm_topk_prob=False)
    normalize_weights: bool = True
    # "sorted": expert-sorted row gathers feeding the dense batched FFN —
    # linear in token count, no [G, E, C] one-hots, no scatter anywhere
    # (fwd or bwd); the TPU equivalent of the reference's grouped MoE
    # GEMM (cutlass_ops/moe_gemm).  Single-device only: the plan's global
    # argsort defeats GSPMD partitioning of sharded token axes.
    # "alltoall": the multi-chip linear path — per-shard sorted dispatch +
    # explicit lax.all_to_all over the expert axis under shard_map (the
    # reference MOELayer architecture, sharded_moe.py:533).
    # "einsum" is the reference's dense one-hot dispatch: G*E*C*M MACs
    # each way (QUADRATIC in G since C ~ kG/E) but expressed purely as
    # einsums, which GSPMD shards over any mesh — the parity oracle.
    # "gather" is the row-scatter path: measured ~20x slower on v5e (TPU
    # scatter lowering), CPU/debug only.
    # "grouped" is the DROPLESS path every serving config takes (the
    # model blocks select it from ``decode``/``paged_decode``): expert-
    # sorted rows with no padding into a grouped matmul over the stacked
    # banks (``ops/grouped_matmul.py``: a tiled Pallas kernel on one TPU
    # device, ``jax.lax.ragged_dot`` elsewhere), combine by gather — no
    # capacity, so a row's output depends on no other row.  Forward only.
    # "auto" (default) resolves to "sorted" on single-device topologies
    # and "alltoall" on multi-device meshes (falling back to "einsum"
    # when the expert count does not divide over the expert axis).
    dispatch_impl: str = "auto"
    # how the router scores: "softmax" (top-k of the logits, softmax
    # values as weights) or "sigmoid" (DeepSeek-V3 / LFM2-MoE: top-k of
    # ``sigmoid + expert_bias``, the sigmoids themselves as weights, times
    # ``routed_scaling_factor``).  ``expert_bias`` is a float32 leaf of
    # ``params`` that no gradient trains (load balancing moves it); its
    # first values are drawn at ``expert_bias_init_std``, because under a
    # zero bias every bias mistake is invisible.  Served only ("grouped")
    scoring: str = "softmax"
    expert_bias: bool = False
    expert_bias_init_std: float = 0.0
    routed_scaling_factor: float = 1.0
    # what the sigmoid router adds to the renormalising sum of the chosen
    # scores (LFM2-MoE's code 1e-6, the DeepSeek-V3 family's 1e-20)
    routed_weight_eps: float = 1e-6
    # first values of the experts' down-projection ``w2``, as a share of
    # lecun-normal's (a family's own: ``models/lfm2_moe.py`` says why)
    down_init_scale: float = 1.0
    # one chip's share of a layer that ``expert_shards`` chips share
    # ("grouped" only): ``num_experts`` is the number HELD here, experts
    # ``[expert_shard * num_experts, (expert_shard + 1) * num_experts)`` of
    # a router ``expert_shards * num_experts`` wide.  A copy routed to an
    # absent expert takes no row and adds nothing: what the other chips'
    # experts would have added is theirs to compute, and nothing here stands
    # in for them or for the exchange (``sharded_moe.py:held_moe``)
    expert_shards: int = 1
    expert_shard: int = 0

    def _can_alltoall(self, topo, n_tokens: int) -> bool:
        ep = int(topo.mesh.shape.get(EXPERT_AXIS, 1))
        if self.num_experts % max(ep, 1) != 0:
            return False
        tok = 1
        for a in TOKEN_AXES:
            tok *= int(topo.mesh.shape.get(a, 1))
        # shard_map needs the flat token dim to divide over its axes
        # (tiny decode batches under a big training mesh fall back)
        return n_tokens % tok == 0

    def _resolve_dispatch(self, n_tokens: int) -> str:
        if self.dispatch_impl != "auto":
            return self.dispatch_impl
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.utils.logging import log_dist

        # engine-pinned topology first: DeepSpeedEngine resolves 'auto'
        # at BUILD time via pin_auto_dispatch, so a model traced before
        # the mesh installs (or after a transient mesh teardown) cannot
        # silently bake in the single-device choice.  A live topology
        # still wins — it is the mesh this trace actually runs under.
        topo = dist.peek_topology() or _AUTO_PIN_TOPO
        if topo is not None and topo.mesh.size > 1:
            impl = ("alltoall" if self._can_alltoall(topo, n_tokens)
                    else "einsum")
        else:
            impl = "sorted"
        log_dist(f"MoE dispatch_impl=auto -> {impl!r} "
                 f"(topology={'none' if topo is None else topo.mesh.shape})",
                 ranks=[0])
        return impl

    # -- expert FFN (shared by every dispatch impl) ----------------------

    def _expert_params(self):
        cfg = self
        M, E, I = cfg.hidden_size, cfg.num_experts, cfg.intermediate_size
        ep = EXPERT_AXIS if cfg.expert_parallel else None
        tp = "tensor" if cfg.tensor_parallel else None

        def expert_param(name, shape, spec, bias: bool = False):
            init = (nn.initializers.zeros_init() if bias else
                    nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                                 batch_axis=(0,)))
            if name == "w2" and cfg.down_init_scale != 1.0:
                lecun, scale = init, cfg.down_init_scale
                init = lambda key, shape, dtype: (    # noqa: E731
                    scale * lecun(key, shape, jnp.float32)).astype(dtype)
            if any(s is not None for s in spec):
                init = nn.with_partitioning(init, spec)
            return self.param(name, init, shape, cfg.param_dtype)

        if cfg._glu_gate() is not None:          # the gated bodies' banks
            return {"w1": expert_param("w1", (E, M, I), (ep, None, tp)),
                    "w3": expert_param("w3", (E, M, I), (ep, None, tp)),
                    "w2": expert_param("w2", (E, I, M), (ep, tp, None))}
        return {"w1": expert_param("w1", (E, M, I), (ep, None, tp)),
                "b1": expert_param("b1", (E, I), (ep, tp), bias=True),
                "w2": expert_param("w2", (E, I, M), (ep, tp, None)),
                "b2": expert_param("b2", (E, M), (ep, None), bias=True)}

    def _glu_gate(self):
        """What a gated body puts on ``w1 x`` (``swiglu``: silu, ``reglu``:
        relu); None for the biased GELU pair; any other name raises."""
        gates = {"swiglu": nn.silu, "reglu": nn.relu, "gelu": None}
        if self.activation not in gates:
            raise ValueError(
                f"unknown MoE activation {self.activation!r}: one of "
                f"{sorted(gates)}")
        return gates[self.activation]

    def _expert_ffn(self, disp: jax.Array, w) -> jax.Array:
        """[E?, C, M] dispatched tokens -> [E?, C, M] expert outputs (the
        leading dim is global E on the einsum path, local E/ep under the
        alltoall shard_map)."""
        dt, gate = self.dtype, self._glu_gate()
        if gate is not None:
            h = jnp.einsum("ecm,emi->eci", disp, w["w1"].astype(dt))
            u = jnp.einsum("ecm,emi->eci", disp, w["w3"].astype(dt))
            return jnp.einsum("eci,eim->ecm", gate(h) * u,
                              w["w2"].astype(dt))
        h = jnp.einsum("ecm,emi->eci", disp, w["w1"].astype(dt))
        h = jax.nn.gelu(h + w["b1"].astype(dt)[:, None])
        out = jnp.einsum("eci,eim->ecm", h, w["w2"].astype(dt))
        return out + w["b2"].astype(dt)[:, None]

    def _grouped_ffn(self, rows: jax.Array, group_sizes: jax.Array, w
                     ) -> jax.Array:
        """[R, M] expert-sorted rows -> [R, M]: the same FFN as
        :meth:`_expert_ffn`, each expert's run of rows against its own
        bank."""
        dt, gate = self.dtype, self._glu_gate()
        grouped_matmul_route()
        dot = lambda a, b: grouped_matmul(a, b.astype(dt), group_sizes)
        if gate is not None:
            return dot(gate(dot(rows, w["w1"])) * dot(rows, w["w3"]),
                       w["w2"])
        # a row's bias is its expert's: expand by the same group sizes
        total = rows.shape[0]
        bias = lambda b: jnp.repeat(b.astype(dt), group_sizes, axis=0,
                                    total_repeat_length=total)
        h = jax.nn.gelu(dot(rows, w["w1"]) + bias(w["b1"]))
        return dot(h, w["w2"]) + bias(w["b2"])

    # -- gating (shared) -------------------------------------------------

    def _gate(self, x: jax.Array, wg: jax.Array,
              noise_rng: Optional[jax.Array], is_training: bool):
        """Returns ``(GatingResult, fp32 logits)`` — the alltoall path
        needs the logits again for the global aux-loss pmean."""
        logits = x.astype(jnp.float32) @ wg                      # [G, E]
        return topkgating(
            logits, k=self.k,
            capacity_factor=(self.capacity_factor if is_training
                             else self.eval_capacity_factor),
            min_capacity=self.min_capacity, drop_tokens=self.drop_tokens,
            noise_rng=noise_rng,
            normalize_weights=self.normalize_weights), logits

    # -- the multi-chip linear path --------------------------------------

    def _alltoall_moe(self, x: jax.Array, wg: jax.Array, w,
                     noise_rng: Optional[jax.Array], is_training: bool
                     ) -> Tuple[jax.Array, jax.Array]:
        """Per-shard sorted dispatch + explicit all-to-all over ``expert``
        (reference ``_AllToAll:96`` + per-rank capacity, MOELayer:533).

        shard_map is manual over the token-sharding axes only; ``tensor``
        stays automatic so GSPMD still partitions the expert FFN einsums
        (Megatron TP) and inserts their psum.  Expert weights enter
        expert-sharded (any ZeRO sharding is gathered at the constraint
        below — the same per-layer gather ZeRO-3 implies)."""
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.sequence.layer import resolve_mesh

        cfg = self
        E = cfg.num_experts
        pinned = _AUTO_PIN_TOPO
        if dist.peek_topology() is None and pinned is not None:
            # traced without a live topology: the engine-pinned mesh is
            # the one this program will run under
            mesh = pinned.mesh
        else:
            mesh = resolve_mesh(None, EXPERT_AXIS)
        token_axes = tuple(a for a in TOKEN_AXES
                           if a in mesh.axis_names and
                           int(mesh.shape.get(a, 1)) > 1)
        # replicated experts (expert_parallel=False) need no all-to-all:
        # every shard holds all E experts and computes its own tokens
        ep = (int(mesh.shape.get(EXPERT_AXIS, 1)) if cfg.expert_parallel
              else 1)

        # gather any ZeRO shard dims; keep expert (+ tensor, automatic)
        ep_name = EXPERT_AXIS if cfg.expert_parallel else None
        tp = "tensor" if cfg.tensor_parallel else None
        w = dict(w)
        for k_, v in w.items():
            spec = [None] * v.ndim
            spec[0] = ep_name
            if tp is not None and v.ndim == 3:
                spec[2 if k_ in ("w1", "w3") else 1] = tp
            elif tp is not None and k_ == "b1":
                spec[1] = tp
            w[k_] = _maybe_constrain(v, tuple(spec))
        w_keys = sorted(w)
        w_vals = [w[k_] for k_ in w_keys]

        def wspec(v):
            s = [None] * v.ndim
            s[0] = ep_name
            return P(*s)

        if not token_axes:
            token_axes = None          # mesh.size>1 but batch unsharded
        has_rng = noise_rng is not None

        def body(x_l, wg_, *rest):
            rng = rest[0] if has_rng else None
            w_l = rest[1:] if has_rng else rest
            wd = dict(zip(w_keys, w_l))
            if rng is not None and token_axes:
                rng = jax.random.fold_in(
                    rng, jax.lax.axis_index(token_axes))
            gr, logits = self._gate(x_l, wg_, rng, is_training)
            plan = routing_plan(gr, E)
            disp = sorted_dispatch(x_l.astype(cfg.dtype), plan.slot_token,
                                   plan.slot_of_copy)        # [E, C_l, M]
            if ep > 1:
                # reference _AllToAll fwd: expert-major buffers scatter to
                # their owning rank; each rank concatenates the C_l slices
                # it receives from every peer -> [E_l, ep*C_l, M]
                disp = jax.lax.all_to_all(disp, EXPERT_AXIS, split_axis=0,
                                          concat_axis=1, tiled=True)
            out = self._expert_ffn(disp, wd)
            if ep > 1:
                out = jax.lax.all_to_all(out, EXPERT_AXIS, split_axis=1,
                                         concat_axis=0, tiled=True)
            y = sorted_combine(out, gr.weights, plan.slot_token,
                               plan.slot_of_copy)
            l_aux = gr.l_aux
            if token_axes:
                # GLOBAL aux loss: average the per-expert token fraction
                # and router-prob fraction over every token shard BEFORE
                # the product, matching the global einsum formulation
                # bit-for-bit (mean of per-shard products differs —
                # product of means is nonlinear)
                me = jax.lax.pmean(
                    jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0),
                    token_axes)
                ce = jax.lax.pmean(
                    jnp.mean(jax.nn.one_hot(gr.experts[0], E,
                                            dtype=jnp.float32), axis=0),
                    token_axes)
                l_aux = jnp.sum(me * ce) * E
            return y, l_aux

        manual = set(token_axes or ()) | {EXPERT_AXIS}
        tok_spec = P(token_axes, None)
        rng_args = (noise_rng,) if has_rng else ()
        rng_specs = (P(),) if has_rng else ()
        sm = _shard_map_compat(
            body, mesh=mesh,
            in_specs=(tok_spec, P()) + rng_specs +
                     tuple(wspec(v) for v in w_vals),
            out_specs=(tok_spec, P()),
            axis_names=manual, check_vma=False)
        # jit so eager callers (flax init, unit tests) route through the
        # jit lowering — jax's EAGER partial-manual shard_map impl trips
        # over meshes with extra (non-manual) axes; under an outer jit
        # this inlines
        return jax.jit(sm)(x, wg, *rng_args, *w_vals)

    # -- forward ---------------------------------------------------------

    @nn.compact
    def __call__(self, x: jax.Array, is_training: bool = True,
                 live: Optional[jax.Array] = None, *,
                 route_only: bool = False,
                 routing: Optional[Tuple[jax.Array, jax.Array]] = None):
        """``live`` (flat bool, one per row of ``x``; None: all) is read
        by the dropless path only: rows that are no token take no expert
        row and count in no counter.

        A router that reads another tensor than the rows it dispatches
        (SmallThinker: the layer's input, before its attention) takes two
        calls of the SAME module, dropless path only: ``routing = moe(h,
        route_only=True)`` where the router's input is at hand, its matmul
        and top-k issued there, then ``moe(x, live=live, routing=routing)``
        with the rows.  A caller that passes neither routes from ``x``."""
        cfg = self
        orig_shape = x.shape
        M, E = cfg.hidden_size, cfg.num_experts
        x = x.reshape(-1, M)                                     # [G, M]

        # router in fp32 (reference TopKGate keeps the gate fp32,
        # sharded_moe.py:449) — routing decisions are precision-sensitive
        routed = E * cfg.expert_shards          # the router's width
        wg = self.param("gate", nn.initializers.lecun_normal(), (M, routed),
                        jnp.float32)
        # the call that only routes makes no bank
        w = None if route_only else self._expert_params()
        bias = (self.param(
            "expert_bias", nn.initializers.normal(cfg.expert_bias_init_std),
            (routed,), jnp.float32) if cfg.expert_bias else None)

        noise_rng = None
        if (cfg.noisy_gate_policy == "Jitter" and is_training
                and self.has_rng("gating")):
            noise_rng = self.make_rng("gating")

        impl = cfg._resolve_dispatch(x.shape[0])
        sigmoid = cfg.scoring == "sigmoid"
        if sigmoid and impl != "grouped":
            raise NotImplementedError(
                f"scoring='sigmoid' with dispatch_impl={impl!r}: the "
                "sigmoid router with a selection bias has its dropless "
                "serving form only (its training form needs the bias's "
                "balancing update and an auxiliary loss of its own)")
        assert cfg.scoring in ("softmax", "sigmoid"), cfg.scoring
        assert 0 <= cfg.expert_shard < cfg.expert_shards, (
            cfg.expert_shard, cfg.expert_shards)
        if cfg.expert_shards > 1 and impl != "grouped":
            raise NotImplementedError(
                f"expert_shards={cfg.expert_shards} with dispatch_impl="
                f"{impl!r}: a layer that holds a share of its router's "
                "experts has its dropless serving form only")
        if (route_only or routing is not None) and (
                impl != "grouped" or sigmoid):
            raise NotImplementedError(
                f"a router input apart from the dispatched rows with "
                f"dispatch_impl={impl!r}, scoring={cfg.scoring!r}: the "
                "dropless softmax form only")
        if impl == "grouped" and routing is not None:
            experts, weights = routing
            l_aux = jnp.zeros((), jnp.float32)
        elif impl == "grouped":
            # float32 for real: a TPU's default f32 matmul is one bf16
            # pass, and a served token's experts are not to turn on that
            with jax.named_scope("moe.route"):
                logits = jnp.dot(x.astype(jnp.float32), wg,
                                 precision=jax.lax.Precision.HIGHEST)
                if sigmoid:
                    experts, weights, swaps = sigmoid_bias_routing(
                        logits, bias, cfg.k, cfg.normalize_weights,
                        cfg.routed_scaling_factor, cfg.routed_weight_eps)
                    l_aux = jnp.zeros((), jnp.float32)
                    if live is not None:
                        swaps = swaps & live.reshape(-1)
                    if not self.is_initializing():
                        # tokens whose chosen set the bias changed
                        self.sow("moe_stats", "bias_swaps",
                                 jnp.sum(swaps.astype(jnp.int32)))
                else:
                    experts, weights, l_aux = topk_routing(
                        logits, cfg.k, cfg.normalize_weights)
        if route_only:
            return experts, weights
        if impl == "grouped":
            flat_live = None if live is None else live.reshape(-1)
            y, counts = grouped_moe(
                x.astype(cfg.dtype), experts, weights, E,
                lambda rows, gs: self._grouped_ffn(rows, gs, w), flat_live,
                first_held=(cfg.expert_shard * E if cfg.expert_shards > 1
                            else None))
            # what the tick routed, for whoever asked (the serving
            # engine's spans); a no-op unless the caller made the
            # collection mutable
            if not self.is_initializing():
                self.sow("moe_stats", "counts", counts)
                if cfg.expert_shards > 1:
                    # ``counts`` are what the HELD banks saw; beside them
                    # every copy the router made, and the experts held
                    tokens = (x.shape[0] if flat_live is None
                              else jnp.sum(flat_live.astype(jnp.int32)))
                    self.sow("moe_stats", "held", jnp.stack(
                        [jnp.asarray(tokens * cfg.k, jnp.int32),
                         jnp.asarray(E, jnp.int32)]))
            return y.reshape(orig_shape), l_aux
        if impl == "alltoall":
            with jax.named_scope("moe.alltoall"):
                y, l_aux = self._alltoall_moe(x, wg, w, noise_rng,
                                              is_training)
            return y.reshape(orig_shape), l_aux.astype(jnp.float32)

        with jax.named_scope("moe.route"):
            gr, _ = self._gate(x, wg, noise_rng, is_training)

        ep = EXPERT_AXIS if cfg.expert_parallel else None

        # dispatch: [G, M] -> [E, C, M]; the sharding constraint onto the
        # expert axis is the reference's first all-to-all (_AllToAll fwd)
        x_d = x.astype(cfg.dtype)      # one cast shared by all impls
        plan = None
        with jax.named_scope("moe.dispatch"):
            if impl == "gather":
                disp = moe_dispatch_gather(x_d, gr, cfg.num_experts)
            elif impl == "einsum":
                disp = moe_dispatch(x_d, gr.dispatch.astype(cfg.dtype))
            elif impl == "sorted":
                plan = routing_plan(gr, cfg.num_experts)
                disp = sorted_dispatch(x_d, plan.slot_token,
                                       plan.slot_of_copy)
            else:
                raise ValueError(f"unknown dispatch_impl {impl!r}")
            disp = _maybe_constrain(disp, (ep, None, None))

        with jax.named_scope("moe.experts"):
            out = self._expert_ffn(disp, w)

        with jax.named_scope("moe.combine"):
            out = _maybe_constrain(out, (ep, None, None))
            # combine: [E, C, M] -> [G, M] (the second all-to-all)
            if impl == "gather":
                y = moe_combine_gather(out, gr)
            elif impl == "sorted":
                y = sorted_combine(out, gr.weights, plan.slot_token,
                                   plan.slot_of_copy)
            else:
                y = moe_combine(out, gr.combine.astype(cfg.dtype))
        return y.reshape(orig_shape), gr.l_aux.astype(jnp.float32)
