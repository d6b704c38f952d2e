"""ZeRO as sharding layouts.

TPU-native re-design of the reference's ZeRO optimizers
(``runtime/zero/stage_1_and_2.py:97``, ``runtime/zero/stage3.py:112``,
``runtime/zero/partition_parameters.py``): on TPU the three stages are
*sharding layouts* on the train state, and XLA/GSPMD emits the collectives
the reference issues by hand (reduce-scatter of grads ≡ the
``average_tensor`` hot loop; per-layer all-gather ≡ the param coordinator's
``fetch_sub_module``):

- stage 0: params/grads/opt-state replicated; grads all-reduced.
- stage 1: optimizer state sharded over the ZeRO axes.
- stage 2: stage 1 + gradients constrained to the sharded layout, so XLA
  reduce-scatters instead of all-reducing (``psum_scatter`` on the wire).
- stage 3: parameters sharded too; all-gather materializes each layer's
  params at use (FSDP). Small params stay replicated below
  ``stage3_param_persistence_threshold`` — same knob, same semantics: they
  are "persistent" exceptions that never pay a gather.

No module hooks, no prefetch tracer: XLA's latency-hiding scheduler overlaps
the gathers; scan-over-layers in the model bounds live parameters the way
``stage3_max_live_parameters`` does.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu.comm as dist
from deepspeed_tpu.parallel.topology import MeshTopology
from deepspeed_tpu.telemetry import trace
from deepspeed_tpu.utils.compat import get_abstract_mesh
from deepspeed_tpu.utils.logging import log_dist

# the plan whose step is being traced (``ZeroShardingPlan.counting_pins``)
_counting_plan: contextvars.ContextVar = contextvars.ContextVar(
    "zero_counting_plan", default=None)


def batch_axes(topology: MeshTopology) -> Tuple[str, ...]:
    """The mesh axes a batch dim is split over: those larger than 1."""
    return tuple(a for a in ("data", "data_sub", "expert")
                 if topology.axis_size(a) > 1)


class ZeroShardingPlan:
    """Computes per-leaf PartitionSpecs for a given stage and topology.

    The plan fixes four layouts: parameters (``param_specs``), gradients
    (``grad_specs``), the batch (``batch_spec``) and activations
    (:func:`pin_activation`, which models call on the values whose place
    matters).  The fourth is needed because the first three leave the
    partitioner free to trade a weight gather for activation traffic: with
    a stage-3 weight sharded over ``data`` it may keep the weight where it
    is and move the activations instead, running a layer tensor-parallel
    over the data axis behind all-to-alls and gathers of the global batch.
    ZeRO means the opposite: activations stay on the batch shard they were
    born on, weights are gathered to them and gradients reduce-scattered
    back."""

    def __init__(self, topology: MeshTopology, stage: int,
                 persistence_threshold: int = 100_000,
                 hpz_partition_size: int = 1):
        assert stage in (0, 1, 2, 3)
        self.topology = topology
        self.stage = stage
        self.persistence_threshold = persistence_threshold
        self.hpz_partition_size = hpz_partition_size
        # ZeRO partitions over data+expert+seq (the reference's
        # seq_data_parallel_group, engine.py:1603)
        self.axes: Tuple[str, ...] = tuple(
            a for a in topology.zero_axes if topology.axis_size(a) > 1)
        self.partitions = int(np.prod(
            [topology.axis_size(a) for a in self.axes])) if self.axes else 1
        # hpZ (ZeRO++ secondary partition, groups.py:650): stage-3 PARAMS
        # shard only over the node-local data_sub axis — cheap all-gathers
        # over intra-node ICI — while grads/opt state keep the full extent
        self.param_axes: Tuple[str, ...] = self.axes
        if hpz_partition_size > 1 and stage >= 3:
            from deepspeed_tpu.parallel.topology import HPZ_AXIS

            if topology.hpz_partition_size != hpz_partition_size:
                raise ValueError(
                    f"zero_hpz_partition_size={hpz_partition_size} but the "
                    f"mesh's data_sub axis is {topology.hpz_partition_size} "
                    "wide — build the mesh with initialize_mesh(..., "
                    "hpz=<size>) (the engine does this automatically)")
            self.param_axes = tuple(a for a in self.axes if a == HPZ_AXIS)
        # pin_activation sites that engaged in the newest traced step
        # (counting_pins); None until a step has been traced
        self.activation_pins: Optional[int] = None

    # -- per-leaf spec ----------------------------------------------------

    def leaf_spec(self, shape: Tuple[int, ...], sharded: bool,
                  base: Optional[P] = None,
                  axes: Optional[Tuple[str, ...]] = None) -> P:
        """PartitionSpec for one array of ``shape``.

        ``base`` carries pre-existing model-parallel sharding (TP/expert axis
        names from flax metadata or AutoTP); ZeRO composes with it by
        claiming one of the still-unsharded dims.  TP sharding is always
        preserved, even when ZeRO itself doesn't shard this tree.
        """
        ndim = len(shape)
        spec = list(base) if base is not None else []
        spec = spec[:ndim] + [None] * (ndim - len(spec))
        has_base = any(s is not None for s in spec)
        my_axes = self.axes if axes is None else axes

        def out():
            return P(*spec) if has_base else P()

        if not sharded or not my_axes or ndim == 0:
            return out()
        if int(np.prod(shape)) <= self.persistence_threshold and not has_base:
            return P()  # persistent (replicated) small param
        # ZeRO may only claim axes the base spec doesn't already use
        base_axes = set()
        for s in spec:
            for ax in (s,) if isinstance(s, str) else (s or ()):
                base_axes.add(ax)
        axes = tuple(a for a in my_axes if a not in base_axes)
        if not axes:
            return out()
        partitions = int(np.prod([self.topology.axis_size(a) for a in axes]))
        best, best_size = None, 0
        for i, d in enumerate(shape):
            if spec[i] is None and d % partitions == 0 and d > best_size:
                best, best_size = i, d
        if best is None:
            return out()
        spec[best] = axes if len(axes) > 1 else axes[0]
        return P(*spec)

    # -- tree-level specs -------------------------------------------------

    def _specs(self, params, sharded: bool, base_specs, axes=None):
        if base_specs is None:
            return jax.tree_util.tree_map(
                lambda x: self.leaf_spec(x.shape, sharded, axes=axes), params)
        return jax.tree_util.tree_map(
            lambda x, b: self.leaf_spec(x.shape, sharded, b, axes=axes),
            params, base_specs)

    def param_specs(self, params, base_specs=None):
        """Stage 3 shards params (over ``param_axes`` — restricted to the
        node-local sub-axis under hpZ); stages 0-2 keep only the base (TP)
        spec."""
        return self._specs(params, self.stage >= 3, base_specs,
                           axes=self.param_axes)

    def grad_specs(self, params, base_specs=None):
        """Stage >= 2 keeps grads in the sharded layout (reduce-scatter)."""
        return self._specs(params, self.stage >= 2, base_specs)

    def moment_specs(self, params, base_specs=None):
        """Per-param layout of the optimizer moments (stage >= 1 sharded) —
        the layout the fused-optimizer shard_map runs in: each device updates
        its own shard of (g, p, m, v), the reference's stage-1/2 ``step``
        partition semantics (stage_1_and_2.py ~1800s)."""
        return self._specs(params, self.stage >= 1, base_specs)

    @staticmethod
    def _path_key(kp) -> Tuple[str, ...]:
        return tuple(str(k) for k in kp)

    def opt_state_specs(self, opt_state, base_specs=None):
        """Stage >= 1 shards optimizer moments.

        Moment trees inside optax states mirror the param tree, so each opt
        leaf inherits the base (TP) spec of the param whose tree path is a
        suffix of the opt leaf's path; scalars and unmatched leaves fall back
        to shape-based ZeRO sharding only.
        """
        sharded = self.stage >= 1
        suffix_map = {}
        if base_specs is not None:
            for kp, spec in jax.tree_util.tree_flatten_with_path(
                    base_specs, is_leaf=lambda x: isinstance(x, P))[0]:
                suffix_map[self._path_key(kp)] = spec

        def spec_for(kp, leaf):
            shape = tuple(getattr(leaf, "shape", ()))
            base = None
            keys = self._path_key(kp)
            for i in range(len(keys)):
                if keys[i:] in suffix_map:
                    base = suffix_map[keys[i:]]
                    break
            return self.leaf_spec(shape, sharded, base)

        return jax.tree_util.tree_map_with_path(spec_for, opt_state)

    # -- shardings --------------------------------------------------------

    def _to_sharding(self, spec_tree):
        mesh = self.topology.mesh
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    def param_shardings(self, params, base_specs=None):
        return self._to_sharding(self.param_specs(params, base_specs))

    def grad_shardings(self, params, base_specs=None):
        return self._to_sharding(self.grad_specs(params, base_specs))

    def opt_state_shardings(self, opt_state, base_specs=None):
        return self._to_sharding(self.opt_state_specs(opt_state, base_specs))

    def batch_spec(self, batch_ndim: int, has_gas_dim: bool = False,
                   dtype=None) -> P:
        """Batch arrays shard their batch dim over (data, expert); with
        sequence parallelism active the dim after batch (the sequence dim of
        [B, S] token arrays) shards over ``seq`` — inputs then arrive
        seq-sharded exactly like the reference's Ulysses input contract
        ([s/P, b, h], ``sequence/layer.py``).

        The seq rule applies only to INTEGER arrays (token ids / masks /
        position ids): a float [B, features] input has no sequence dim,
        and guessing one would mis-shard it.  Pass ``dtype`` to engage
        the check; ``dtype=None`` keeps the token-array assumption for
        backward compatibility."""
        axes = batch_axes(self.topology)
        specs = []
        if has_gas_dim:
            specs.append(None)  # scan (GAS) dim never sharded
        specs.append(axes if len(axes) > 1 else (axes[0] if axes else None))
        token_like = dtype is None or np.issubdtype(np.dtype(dtype),
                                                    np.integer)
        if (len(specs) < batch_ndim and token_like and
                self.topology.axis_size("seq") > 1):
            specs.append("seq")
        while len(specs) < batch_ndim:
            specs.append(None)
        return P(*specs)

    def batch_sharding(self, batch_ndim: int, has_gas_dim: bool = False,
                       dtype=None) -> NamedSharding:
        return NamedSharding(self.topology.mesh,
                             self.batch_spec(batch_ndim, has_gas_dim,
                                             dtype=dtype))

    def describe(self, params, base_specs=None) -> str:
        n_sharded = 0
        n_total = 0
        bytes_sharded = 0
        bytes_total = 0
        for leaf, spec in zip(jax.tree_util.tree_leaves(params),
                              jax.tree_util.tree_leaves(
                                  self.param_specs(params, base_specs),
                                  is_leaf=lambda x: isinstance(x, P))):
            n_total += 1
            sz = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            bytes_total += sz
            if any(s is not None for s in spec):
                n_sharded += 1
                bytes_sharded += sz
        pins = ("" if self.activation_pins is None else
                f", {self.activation_pins} activation pins in the traced step")
        return (f"ZeRO stage {self.stage}: {n_sharded}/{n_total} param tensors "
                f"sharded over {self.axes} ({self.partitions} partitions), "
                f"{bytes_sharded / max(bytes_total, 1):.0%} of param bytes"
                + pins)

    @contextlib.contextmanager
    def counting_pins(self):
        """Count into ``activation_pins`` the :func:`pin_activation` sites
        that engage while a step of this plan is traced.  A model that
        trains under ZeRO on more than one device and reads 0 here has its
        activations placed by the partitioner's cost model alone."""
        self.activation_pins = 0
        token = _counting_plan.set(self)
        try:
            yield
        finally:
            _counting_plan.reset(token)
            trace.event("zero.activation_pins", cat="zero",
                        stage=self.stage, pins=self.activation_pins)


def pin_activation(x):
    """Pin an activation to the plan's batch layout: its leading (batch)
    dim over the axes ``ZeroShardingPlan.batch_spec`` splits a batch over,
    every other dim left to the partitioner (a ``tensor`` or ``seq`` split
    of those stays the model's affair).  The constraint transposes to the
    same constraint on the cotangent, so pinning forward values places the
    backward too.

    Returns ``x`` itself, untouched, where there is nothing to pin to: no
    installed topology, one device, no batch axis larger than 1, a batch
    dim the axes do not divide, or a region that is already manual
    (pipeline, Ulysses, ring)."""
    topo = dist.peek_topology()
    axes = batch_axes(topo) if topo is not None else ()
    if not axes or \
            x.shape[0] % int(np.prod([topo.axis_size(a) for a in axes])) or \
            get_abstract_mesh().manual_axes:
        return x
    plan = _counting_plan.get()
    if plan is not None:
        plan.activation_pins += 1
    spec = P(axes, *[P.UNCONSTRAINED] * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, NamedSharding(topo.mesh, spec))


def constrain_tree(tree, spec_tree, mesh: Mesh):
    """Apply ``with_sharding_constraint`` leaf-wise (used on grads inside the
    step for stage >= 2)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
        tree, spec_tree,
        is_leaf=lambda x: isinstance(x, P))


