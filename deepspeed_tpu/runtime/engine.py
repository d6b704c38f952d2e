"""The training engine.

TPU-native re-design of ``deepspeed/runtime/engine.py:184``
(``DeepSpeedEngine``) and ``deepspeed.initialize``
(``deepspeed/__init__.py:69``).  The reference wraps an ``nn.Module`` and
intercepts ``forward/backward/step`` with hooks; here the engine owns ONE
jitted ``train_step(state, batch, lr)`` that fuses forward, backward,
gradient accumulation (a ``lax.scan`` over micro-batches), ZeRO-sharded
update, loss scaling, clipping, and overflow skip — the whole of SURVEY
§3.2's call stack compiled into a single XLA program per shape.

The imperative ``forward()/backward()/step()`` triple is kept for API
parity (documented divergence: ``train_batch`` is the fast path; the
imperative mode runs forward twice — once for the returned loss, once
inside value_and_grad).
"""
from __future__ import annotations

import os
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from deepspeed_tpu.utils.compat import shard_map as _shard_map_compat

import deepspeed_tpu.comm as dist
from deepspeed_tpu.config import DeepSpeedConfig, load_config
from deepspeed_tpu.parallel.topology import MeshTopology
from deepspeed_tpu.resilience.distributed import CollectiveTimeout
from deepspeed_tpu.resilience.guards import SwapCorruptionError
from deepspeed_tpu.runtime import precision as prec
from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,
                                              RepeatingLoader, shard_batch)
from deepspeed_tpu.runtime.lr_schedules import LRScheduler, get_schedule_fn
from deepspeed_tpu.runtime.optimizers import (build_optimizer,
                                              is_fused_optimizer)
from deepspeed_tpu.runtime.train_state import TrainState
from deepspeed_tpu.runtime.zero import ZeroShardingPlan, constrain_tree
from deepspeed_tpu.telemetry import device_scopes
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (BACKWARD_GLOBAL_TIMER,
                                       FORWARD_GLOBAL_TIMER,
                                       STEP_GLOBAL_TIMER,
                                       SynchronizedWallClockTimer,
                                       ThroughputTimer)

LossFn = Callable[[Any, Any, jax.Array], jax.Array]


def initialize(args=None,
               model: Any = None,
               optimizer: Optional[str] = None,
               model_parameters: Any = None,
               training_data: Any = None,
               lr_scheduler: Any = None,
               topology: Optional[MeshTopology] = None,
               dist_init_required: Optional[bool] = None,
               config: Any = None,
               config_params: Any = None,
               example_batch: Any = None,
               rng: Optional[jax.Array] = None,
               mpu: Any = None,
               engine_cls: Any = None,
               engine_kwargs: Optional[Dict] = None):
    """Create a training engine (reference ``deepspeed.initialize``,
    ``deepspeed/__init__.py:69``; same return arity).

    ``model`` is either
    - a flax ``nn.Module`` whose ``__call__(batch)`` returns the scalar
      loss (needs ``example_batch`` for init), or
    - a loss function ``loss_fn(params, batch, rng) -> scalar`` with the
      params pytree passed via ``model_parameters``.

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    """
    assert model is not None, "deepspeed_tpu.initialize: model is required"
    dist.init_distributed()
    if topology is None:
        topology = dist.get_topology()
    else:
        dist.set_topology(topology)

    # batch accounting: samples split over data x expert ranks only — seq
    # ranks hold the same samples and split the sequence dim (Ulysses input
    # contract), so sp does NOT divide the batch
    ds_config = load_config(
        config if config is not None else config_params,
        dp_world_size=topology.data_parallel_size *
        topology.expert_parallel_size)

    # hpZ (ZeRO++): rebuild the mesh with the data axis split into
    # data x data_sub so stage-3 params can shard node-locally
    hpz = ds_config.zero_optimization.zero_hpz_partition_size
    if (hpz > 1 and ds_config.zero_optimization.stage >= 3 and
            topology.hpz_partition_size != hpz):
        topology = MeshTopology(
            dp=topology.data_parallel_size,
            tp=topology.tensor_parallel_size,
            pp=topology.pipe_parallel_size,
            sp=topology.sequence_parallel_size,
            ep=topology.expert_parallel_size,
            hpz=hpz,
            devices=list(topology.mesh.devices.flatten()))
        dist.set_topology(topology)
        log_dist(f"hpZ: split data axis -> {topology.describe()}", ranks=[0])

    cls = engine_cls or DeepSpeedEngine
    engine = cls(model=model,
                 model_parameters=model_parameters,
                 config=ds_config,
                 topology=topology,
                 optimizer_name=optimizer,
                 lr_scheduler=lr_scheduler,
                 training_data=training_data,
                 example_batch=example_batch,
                 rng=rng,
                 **(engine_kwargs or {}))
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


class DeviceBatch:
    """Marker wrapper for a batch already staged on device in the engine's
    [gas, micro, ...] layout (see ``DeepSpeedEngine.put_batch``)."""

    __slots__ = ("tree",)

    def __init__(self, tree):
        self.tree = tree


class OptimizerHandle:
    """Small view object returned as the ``optimizer`` element of the
    ``initialize`` tuple (the reference returns its wrapped optimizer; here
    state lives in the engine)."""

    def __init__(self, engine: "DeepSpeedEngine"):
        self._engine = engine

    @property
    def param_groups(self):
        return [{"lr": self._engine.get_lr()[0]}]

    def state_dict(self):
        return jax.device_get(self._engine.state.opt_state)

    def __repr__(self):  # pragma: no cover
        return f"OptimizerHandle({self._engine.optimizer_name})"


class DeepSpeedEngine:
    """Owns config, topology, sharded train state, and the compiled steps."""

    def __init__(self, model, model_parameters, config: DeepSpeedConfig,
                 topology: MeshTopology, optimizer_name: Optional[str] = None,
                 lr_scheduler=None, training_data=None, example_batch=None,
                 rng: Optional[jax.Array] = None):
        self.config = config
        self.topology = topology
        self.mesh = topology.mesh
        # resolve MoE dispatch_impl='auto' against THIS mesh no matter
        # when flax traces the layers (a trace issued before/without the
        # live topology would otherwise bake in the single-device choice)
        from deepspeed_tpu.moe.layer import pin_auto_dispatch

        pin_auto_dispatch(topology)
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0

        self.compute_dtype = prec.compute_dtype_from_config(config)
        self.dynamic_loss_scale = (config.fp16.enabled and
                                   config.fp16.loss_scale == 0)
        # master fp32 weights whenever compute dtype is lower precision
        self.master_weights = (config.fp16.enabled or
                               (config.bf16.enabled and config.bf16.master_weights))

        if rng is None:
            rng = jax.random.PRNGKey(config.seed)

        # -- resolve model -> (loss_fn, params) ---------------------------
        self.module = None
        self._init_rngs = None                 # set => deferred sharded init
        if hasattr(model, "init") and hasattr(model, "apply"):  # flax Module
            model = self._apply_activation_checkpointing_config(model)
            self.module = model
            assert example_batch is not None, \
                "flax-module path needs example_batch for init"
            init_rng, rng = jax.random.split(rng)
            if model_parameters is None:
                # zero.Init equivalent (partition_parameters.py:824): params
                # are born sharded.  Here: shapes only via eval_shape; the
                # real init runs later under jit with out_shardings from the
                # ZeRO plan, so no device or host ever materializes the
                # full unsharded model.
                self._init_rngs = {"params": init_rng, "dropout": init_rng}
                model_parameters = jax.eval_shape(
                    model.init, self._init_rngs, example_batch)

            def loss_fn(params, batch, step_rng):
                return model.apply(params, batch, rngs={"dropout": step_rng})
            self.loss_fn: LossFn = loss_fn
        elif callable(model):
            assert model_parameters is not None, \
                "loss-fn path needs model_parameters"
            self.loss_fn = model
        else:
            raise TypeError(f"Unsupported model type {type(model)}")

        # -- optimizer ----------------------------------------------------
        opt_cfg = config.optimizer
        self.optimizer_name = (optimizer_name or
                               (opt_cfg.type if opt_cfg else "adamw"))
        opt_params = dict(opt_cfg.params) if opt_cfg else {}
        self.tx, base_lr = build_optimizer(self.optimizer_name, opt_params)
        self._onebit_axes = self._resolve_onebit(topology, opt_params)

        # -- lr schedule --------------------------------------------------
        if lr_scheduler is None:
            sched_cfg = config.scheduler
            sched_fn = get_schedule_fn(
                sched_cfg.type if sched_cfg else None,
                dict(sched_cfg.params) if sched_cfg else {}, base_lr=base_lr)
            lr_scheduler = LRScheduler(sched_fn)
        self.lr_scheduler = lr_scheduler

        # -- tensor-parallel base specs (flax metadata or AutoTP) ---------
        from deepspeed_tpu.parallel import tensor_parallel as tp_lib

        self.base_specs = None
        params_boxed = tp_lib.has_partitioning(model_parameters)
        if params_boxed:
            self.base_specs = tp_lib.extract_partition_specs(
                model_parameters, self.mesh.axis_names)
            model_parameters = tp_lib.unbox_params(model_parameters)
        elif topology.tensor_parallel_size > 1:
            # AutoTP (module_inject/auto_tp.py equivalent): infer specs from
            # parameter names when the model carries no annotations
            self.base_specs = tp_lib.auto_tp_specs(
                model_parameters, topology.tensor_parallel_size)
            log_dist("AutoTP: inferred tensor-parallel sharding from "
                     "parameter names", ranks=[0])
        # pipeline-stage params: stage dim -> `pipe` axis (no-op otherwise)
        from deepspeed_tpu.parallel.pipeline import (apply_pipeline_specs,
                                                     validate_pipeline_layout)

        self.base_specs = apply_pipeline_specs(model_parameters,
                                               self.base_specs)
        validate_pipeline_layout(model_parameters, topology)

        # -- ZeRO sharding plan + state materialization -------------------
        zcfg = config.zero_optimization
        self.zero_stage = zcfg.stage
        self.plan = ZeroShardingPlan(
            topology, zcfg.stage,
            persistence_threshold=zcfg.stage3_param_persistence_threshold,
            hpz_partition_size=zcfg.zero_hpz_partition_size)

        master_dtype = jnp.float32 if self.master_weights else self.compute_dtype

        def to_master(x):
            return (x.astype(master_dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x)

        # -- ZeRO-Offload (swap_tensor/partitioned_*_swapper equivalents):
        # state placed in host memory via memory_kind="pinned_host"; XLA
        # streams it to the chip inside the step.  TPU-only: the CPU
        # backend cannot compile host-placement annotations.
        offl_o, offl_p = zcfg.offload_optimizer, zcfg.offload_param
        want_opt_off = bool(offl_o and offl_o.device == "cpu")
        # NVMe tier (ZeRO-Infinity, swap_tensor/partitioned_optimizer_
        # swapper.py): moments on local SSD, streamed through the device
        # per step by the native AIO engine.  Adam-family only (the
        # reference swapper equally assumes two-moment CPU-Adam state).
        # Multi-process capable: each process swaps only its addressable
        # ZeRO shards into per-shard files (reference rank-local
        # partition semantics).
        self.nvme_swapper = None
        want_opt_nvme = bool(offl_o and offl_o.device == "nvme")
        if want_opt_nvme:
            adam_family = (self.optimizer_name or "adamw").lower() in (
                "adam", "adamw", "fusedadam")
            if not adam_family or self._onebit_axes is not None:
                logger.warning(
                    "offload_optimizer.device=nvme needs an Adam-family "
                    "optimizer; keeping optimizer state in device memory")
                want_opt_nvme = False
            elif not offl_o.nvme_path:
                # a shared default path would let concurrent jobs clobber
                # each other's moment files (the reference swapper equally
                # requires nvme_path)
                raise ValueError(
                    "offload_optimizer.device=nvme requires "
                    "offload_optimizer.nvme_path")
        want_param_off = bool(offl_p and offl_p.device == "cpu" and
                              zcfg.stage >= 3)
        if offl_p and offl_p.device == "cpu" and zcfg.stage < 3:
            logger.warning(
                f"offload_param.device=cpu requires zero stage 3 (params "
                f"are not partitioned at stage {zcfg.stage}); IGNORED")
        host_mem_ok = self.mesh.devices.flat[0].platform != "cpu"
        if (want_opt_off or want_param_off) and not host_mem_ok:
            logger.warning(
                "offload to cpu requested but this backend cannot compile "
                "pinned_host placement; keeping state in device memory")
        self.offload_optimizer = want_opt_off and host_mem_ok
        self.offload_param = want_param_off and host_mem_ok

        def to_host(shardings):
            return jax.tree_util.tree_map(
                lambda s: s.with_memory_kind("pinned_host"), shardings)

        param_shardings = self.plan.param_shardings(model_parameters,
                                                    self.base_specs)
        # in-graph H2D fetch: host-resident operands must be explicitly
        # transferred before compute ops (XLA does not auto-stream them)
        self._fetch_params = lambda p: p
        self._fetch_opt = lambda o: o
        if self.offload_param:
            dev_shardings = param_shardings
            param_shardings = to_host(param_shardings)
            self._fetch_params = (
                lambda p, _s=dev_shardings: jax.device_put(p, _s))
            log_dist("ZeRO-Offload: params resident in host memory "
                     "(pinned_host)", ranks=[0])
        if self._init_rngs is not None:
            # deferred init: each device computes/receives only its shard
            def sharded_init(rngs, batch):
                p = model.init(rngs, batch)
                if params_boxed:
                    p = tp_lib.unbox_params(p)
                return jax.tree_util.tree_map(to_master, p)

            params = jax.jit(sharded_init, out_shardings=param_shardings)(
                self._init_rngs, example_batch)
        else:
            # user-provided params: already materialized; cast on host and
            # place leaf-by-leaf against the plan (no second full-tree copy)
            def put(x, s):
                x = np.asarray(x)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(master_dtype)
                return jax.device_put(x, s)

            params = jax.tree_util.tree_map(put, model_parameters,
                                            param_shardings)
        self._grad_spec_tree = self.plan.grad_specs(params, self.base_specs)

        # streamed host-moment tier: offload_optimizer=cpu TOGETHER with
        # offload_param=cpu is the "model far beyond HBM" configuration —
        # the fused single-program path materializes every gradient
        # before the first moment write there (measured 41G of HBM at
        # 7B), so Adam moments stream through the device bucket-by-bucket
        # from pinned host memory instead (reference CPU-Adam +
        # offload_optimizer semantics, zero/stage3.py)
        _p_cfg = dict(opt_cfg.params) if opt_cfg else {}
        _name = (self.optimizer_name or "adamw").lower()
        _adam_family = _name in ("adam", "adamw", "fusedadam")
        # mirror exactly what the device-resident transform the swapped
        # tiers replace would have done: the fused Pallas path honors
        # adam_w_mode (default: decoupled unless plain "Adam" —
        # optimizers.py:84), while the optax fallback always decouples
        # (documented divergence) regardless of the flag
        if is_fused_optimizer(_name, _p_cfg):
            _adam_w = bool(_p_cfg.get("adam_w_mode", _name != "adam"))
        else:
            _adam_w = True
        want_opt_stream = (self.offload_optimizer and self.offload_param
                           and _adam_family
                           and self._onebit_axes is None
                           and jax.process_count() == 1)
        if want_opt_nvme:
            from deepspeed_tpu.runtime.swap_tensor import NvmeOptimizerSwapper

            self.nvme_swapper = NvmeOptimizerSwapper(
                offl_o.nvme_path, params,
                betas=tuple(_p_cfg.get("betas", (0.9, 0.999))),
                eps=float(_p_cfg.get("eps", 1e-8)),
                weight_decay=float(_p_cfg.get("weight_decay", 0.0)),
                adam_w_mode=_adam_w,
                aio_block_size=config.aio.block_size,
                aio_thread_count=config.aio.thread_count,
                aio_queue_depth=config.aio.queue_depth,
                aio_use_odirect=config.aio.use_odirect,
                pipeline_read=offl_o.pipeline_read,
                pipeline_write=offl_o.pipeline_write,
                buffer_count=offl_o.buffer_count,
                sdc_verify=config.resilience.sdc.verify_on_read,
                sdc_checksum=config.resilience.sdc.checksum,
                sdc_max_reread=config.resilience.sdc.max_reread_retries)
            opt_state, opt_shardings, opt_specs = (), (), None
        elif want_opt_stream:
            from deepspeed_tpu.runtime.swap_tensor import HostMomentSwapper

            self.nvme_swapper = HostMomentSwapper(
                params,
                betas=tuple(_p_cfg.get("betas", (0.9, 0.999))),
                eps=float(_p_cfg.get("eps", 1e-8)),
                weight_decay=float(_p_cfg.get("weight_decay", 0.0)),
                adam_w_mode=_adam_w)
            opt_state, opt_shardings, opt_specs = (), (), None
        elif self._onebit_axes is not None:
            opt_state, opt_shardings = self._init_onebit_opt_state(params)
            opt_specs = None
        else:
            opt_shapes = jax.eval_shape(self.tx.init, params)
            opt_specs = self.plan.opt_state_specs(opt_shapes, self.base_specs)
            opt_shardings = self.plan.opt_state_shardings(opt_shapes,
                                                          self.base_specs)
        if self.offload_optimizer and self._onebit_axes is not None:
            logger.warning("offload_optimizer is not supported on the "
                           "1-bit compressed path; keeping state on device")
            self.offload_optimizer = False
        if self.offload_optimizer and self.nvme_swapper is None:
            dev_opt_shardings = opt_shardings
            opt_shardings = to_host(opt_shardings)
            self._fetch_opt = (
                lambda o, _s=dev_opt_shardings: jax.device_put(o, _s))
            log_dist("ZeRO-Offload: optimizer state resident in host "
                     "memory (pinned_host)", ranks=[0])
        if self._onebit_axes is None and self.nvme_swapper is None:
            opt_state = jax.jit(self.tx.init,
                                out_shardings=opt_shardings)(params)

        # Fused Pallas optimizers have no GSPMD partitioning rule; run the
        # update inside shard_map over the ZeRO moment layout so each device
        # updates only its own shard (stage_1_and_2.py step semantics: shard
        # update + all-gather of the result, which XLA inserts when the
        # engine applies p - lr*u against less-sharded params).
        self._tx_update = self.tx.update
        if self._onebit_axes is None and self.nvme_swapper is None and \
                is_fused_optimizer(
                self.optimizer_name, opt_cfg.params if opt_cfg else {}):
            moment_specs = self.plan.moment_specs(params, self.base_specs)
            self._tx_update = _shard_map_compat(
                self.tx.update, mesh=self.mesh,
                in_specs=(moment_specs, opt_specs, moment_specs),
                out_specs=(moment_specs, opt_specs),
                check_vma=False)

        scale_state = prec.init_loss_scale(config.fp16)
        # the scalar leaves start where _state_shardings (below) puts
        # them: a leaf without the mesh in its type is a different jit
        # cache key from the step's own output, and the whole train step
        # would compile a second time on step 2
        self.state = TrainState(
            step=jax.device_put(jnp.asarray(0, jnp.int32), self._repl()),
            params=params,
            opt_state=opt_state,
            scale=jax.device_put(scale_state, self._repl()),
            rng=jax.device_put(rng, self._repl()),
            skipped_steps=jax.device_put(jnp.asarray(0, jnp.int32),
                                         self._repl()))
        log_dist(self.plan.describe(params, self.base_specs), ranks=[0])

        self._state_shardings = TrainState(
            step=self._repl(), params=param_shardings,
            opt_state=opt_shardings,
            scale=jax.tree_util.tree_map(lambda _: self._repl(), scale_state),
            rng=self._repl(),
            skipped_steps=self._repl())

        # -- data ---------------------------------------------------------
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = RepeatingLoader(DeepSpeedDataLoader(
                training_data, batch_size=config.train_batch_size,
                seed=config.seed, drop_last=config.dataloader_drop_last,
                world_size=self.topology.world_size))
        self._data_iter = None

        # -- compiled steps (built lazily per batch structure) ------------
        self._train_step_fn = None
        self._eval_step_fn = None
        self._grad_step_fn = None
        self._nvme_grad_step_fn = None
        self._apply_step_fn = None
        self._pending_grads = None
        self._pending_loss = None
        self._profile_batch = None
        self._lr_cached_value = None
        self._lr_cached_dev = None

        # -- observability -------------------------------------------------
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print)
        self.monitor = None
        try:
            from deepspeed_tpu.monitor.monitor import MonitorMaster

            self.monitor = MonitorMaster(config.monitor_config)
        except Exception as e:
            logger.warning(f"monitor setup failed; metric logging disabled: {e}")
        dist.configure(config.comms_logger)

        # legacy curriculum learning (reference engine
        # curriculum_enabled_legacy path): seqlen difficulty truncates
        # token batches; difficulty_step quantizes compile shapes
        self.curriculum_scheduler = None
        if config.curriculum_learning.enabled:
            from deepspeed_tpu.data_pipeline import CurriculumScheduler

            if config.curriculum_learning.curriculum_type != "seqlen":
                raise ValueError(
                    "curriculum_learning.curriculum_type="
                    f"{config.curriculum_learning.curriculum_type!r}: the "
                    "engine-wired legacy path supports 'seqlen' (other "
                    "metrics go through deepspeed_tpu.data_pipeline."
                    "DeepSpeedDataSampler)")
            self.curriculum_scheduler = CurriculumScheduler(
                config.curriculum_learning.model_dump())
            log_dist("curriculum learning: seqlen "
                     f"{config.curriculum_learning.min_difficulty} -> "
                     f"{config.curriculum_learning.max_difficulty} "
                     f"({config.curriculum_learning.schedule_type})",
                     ranks=[0])

        # -- resilience guards (resilience/guards.py) ---------------------
        self._skip_guard = None
        # check_grad_finite extends the consecutive-skip abort to
        # bf16/fp32 runs (their non-finite sweep is built into the
        # train step when the knob is on); when both knobs are set the
        # tighter bound wins
        _guard_bounds = [b for b in (
            config.resilience.max_consecutive_skips,
            config.resilience.check_grad_finite) if b > 0]
        if _guard_bounds:
            from deepspeed_tpu.resilience import SkippedStepGuard

            self._skip_guard = SkippedStepGuard(min(_guard_bounds))
        self._preemption_prev_handlers = None
        self._preemption_save_dir = None
        self.preempted = False
        self.swap_corrupted = False
        # -- distributed health (resilience/distributed.py) ---------------
        self.comm_timed_out = False
        self._desync = None
        rc = config.resilience.comm
        if rc.collective_timeout_s > 0:
            from deepspeed_tpu.comm import watchdog as _cwd

            _cwd.configure(rc.collective_timeout_s)
            log_dist(f"collective watchdog armed: "
                     f"{rc.collective_timeout_s:.1f}s deadline", ranks=[0])
        if rc.desync_interval > 0:
            from deepspeed_tpu.resilience import DesyncDetector

            self._desync = DesyncDetector(rc.desync_interval,
                                          rc.desync_tolerance)

        self.optimizer = OptimizerHandle(self)
        log_dist(
            f"DeepSpeedEngine: zero_stage={self.zero_stage} "
            f"dtype={self.compute_dtype.__name__} "
            f"micro={config.train_micro_batch_size_per_gpu} "
            f"gas={config.gradient_accumulation_steps} "
            f"train_batch={config.train_batch_size}", ranks=[0])

    # ------------------------------------------------------------------

    def _resolve_onebit(self, topology, opt_params):
        """1-bit optimizer family routing (reference
        ``runtime/fp16/onebit/adam.py:14``): when eligible, swap ``self.tx``
        for the compressed-momentum transform and return the comm axes the
        shard_map train step runs over.  Eligibility mirrors the
        reference's restrictions — ZeRO stage 0 (OnebitAdam asserts
        non-ZeRO), pure DP (no tp/pp/sp/ep), no fp16 loss scaling — plus
        >1 data member (nothing to compress otherwise)."""
        name = self.optimizer_name.lower()
        if name not in ("onebitadam", "onebitlamb", "zerooneadam"):
            return None
        n_dp = topology.zero_partition_count()
        blockers = []
        if name == "zerooneadam":
            blockers.append("0/1 Adam's local-step phase holds per-member "
                            "params, incompatible with the replicated "
                            "engine state (use the transform standalone)")
        if self.config.zero_optimization.stage != 0:
            blockers.append(f"zero stage "
                            f"{self.config.zero_optimization.stage} != 0")
        for ax_attr, label in (("tensor_parallel_size", "tp"),
                               ("pipe_parallel_size", "pp")):
            if getattr(topology, ax_attr) > 1:
                blockers.append(f"{label} > 1")
        for ax in ("seq", "expert"):
            if topology.axis_size(ax) > 1:
                blockers.append(f"{ax} axis > 1")
        if self.config.fp16.enabled:
            blockers.append("fp16 dynamic loss scaling")
        if n_dp <= 1:
            blockers.append("single data-parallel member")
        if blockers:
            logger.warning(
                f"{self.optimizer_name}: compressed-communication path "
                f"disabled ({'; '.join(blockers)}); using the uncompressed "
                "base optimizer (same warmup-stage math, full-precision "
                "wire)")
            return None
        from deepspeed_tpu.parallel.topology import DATA_AXIS, HPZ_AXIS
        from deepspeed_tpu.runtime.onebit import (scale_by_onebit_adam,
                                                  scale_by_onebit_lamb)

        axes = tuple(a for a in (DATA_AXIS, HPZ_AXIS)
                     if topology.axis_size(a) > 1)
        betas = tuple(opt_params.get("betas", (0.9, 0.999)))
        kw = dict(b1=betas[0], b2=betas[1],
                  freeze_step=int(opt_params.get("freeze_step", 100000)),
                  weight_decay=float(opt_params.get("weight_decay", 0.0)),
                  group=axes)
        if name == "onebitlamb":
            self.tx = scale_by_onebit_lamb(
                eps=float(opt_params.get("eps", 1e-6)), **kw)
        else:
            self.tx = scale_by_onebit_adam(
                eps=float(opt_params.get("eps", 1e-8)), **kw)
        if self.config.gradient_clipping:
            logger.warning(
                f"{self.optimizer_name}: gradient_clipping is not supported "
                "on the compressed path (the reference raises for "
                "max_grad_norm); clipping is skipped")
        log_dist(f"{self.optimizer_name}: 1-bit compressed momentum "
                 f"all-reduce active over axes {axes} "
                 f"(freeze_step={kw['freeze_step']})", ranks=[0])
        return axes

    def _apply_activation_checkpointing_config(self, model):
        """Honor the ``activation_checkpointing`` JSON subtree (reference
        ``runtime/activation_checkpointing/checkpointing.py`` configure):
        when explicitly set, rebuild the model's dataclass config with the
        matching ``nn.remat`` policy so the knob actually drives remat."""
        import dataclasses

        if "activation_checkpointing" not in self.config.model_fields_set:
            return model
        acfg = self.config.activation_checkpointing
        if acfg.cpu_checkpointing or acfg.contiguous_memory_optimization:
            logger.warning(
                "activation_checkpointing: cpu_checkpointing / "
                "contiguous_memory_optimization are no-ops on TPU (XLA "
                "owns activation placement and memory layout)")
        # only an explicit policy (or partition_activations, whose TPU
        # equivalent is remat) changes remat behavior — other fields in the
        # block (profile, ...) must not silently enable checkpointing
        if ("policy" not in acfg.model_fields_set and
                not acfg.partition_activations):
            return model
        mc = getattr(model, "config", None)
        if not (dataclasses.is_dataclass(mc) and
                all(any(f.name == n for f in dataclasses.fields(mc))
                    for n in ("remat", "remat_policy"))):
            logger.warning(
                "activation_checkpointing set but the model carries no "
                "remat-capable dataclass config; knob has no effect")
            return model
        # config policy names -> (model remat_policy, remat on?)
        mapping = {"nothing_saveable": ("full", True),
                   "dots_saveable": ("dots_saveable", True),
                   "everything_saveable": ("none", False)}
        if acfg.policy not in mapping:
            raise ValueError(
                f"activation_checkpointing.policy={acfg.policy!r}: expected "
                f"one of {sorted(mapping)}")
        remat_policy, remat = mapping[acfg.policy]
        if (mc.remat, mc.remat_policy) == (remat, remat_policy):
            return model
        log_dist(f"activation_checkpointing: policy={acfg.policy} -> "
                 f"remat={remat} remat_policy={remat_policy}", ranks=[0])
        # clone preserves every other module field (a module may carry more
        # than its config)
        return model.clone(config=dataclasses.replace(
            mc, remat=remat, remat_policy=remat_policy))

    def _repl(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def gas(self) -> int:
        return self.config.gradient_accumulation_steps

    def get_lr(self):
        return self.lr_scheduler.get_lr()

    @property
    def loss_scale(self) -> float:
        return float(jax.device_get(self.state.scale.loss_scale))

    @property
    def skipped_steps(self) -> int:
        return int(jax.device_get(self.state.skipped_steps))

    # ------------------------------------------------------------------
    # Compiled step builders
    # ------------------------------------------------------------------

    def _init_onebit_opt_state(self, params):
        """Global layout for :class:`OnebitState`: moments replicated (stage
        0), error-feedback accumulators stored with a leading member axis
        sharded over the comm axes (each member owns exactly its own error
        — the reference keeps them as per-rank tensors)."""
        axes = self._onebit_axes
        n = int(np.prod([self.topology.axis_size(a) for a in axes]))
        shapes = jax.eval_shape(self.tx.init, params)
        err_sharding = NamedSharding(self.mesh, P(axes))
        shardings = jax.tree_util.tree_map(
            lambda _: self._repl(), shapes)._replace(
            worker_error=jax.tree_util.tree_map(
                lambda _: err_sharding, shapes.worker_error),
            server_error=jax.tree_util.tree_map(
                lambda _: err_sharding, shapes.server_error))

        def init_global(p):
            s = self.tx.init(p)
            return s._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda e: jnp.broadcast_to(e[None], (n,) + e.shape),
                    s.worker_error),
                server_error=jax.tree_util.tree_map(
                    lambda e: jnp.broadcast_to(e[None], (n,) + e.shape),
                    s.server_error))

        state = jax.jit(init_global, out_shardings=shardings)(params)
        return state, shardings

    def _build_onebit_train_step(self, gbatch):
        """shard_map train step for the 1-bit family: the data axes are
        MANUAL, so gradients stay member-local (no GSPMD psum in backward)
        and the only cross-member traffic is the transform's compressed
        momentum all-reduce — the reference ``OnebitAdam.step`` wire
        pattern, fused into the one compiled program."""
        axes = self._onebit_axes
        mesh = self.mesh
        loss_fn = self.loss_fn
        tx = self.tx
        gas = self.gas
        compute_dtype = self.compute_dtype

        def cast_params(p):
            return prec.cast_tree(p, compute_dtype)

        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
        state_specs = TrainState(
            step=P(), params=repl(self.state.params),
            opt_state=repl(self.state.opt_state)._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda _: P(axes), self.state.opt_state.worker_error),
                server_error=jax.tree_util.tree_map(
                    lambda _: P(axes), self.state.opt_state.server_error)),
            scale=repl(self.state.scale), rng=P(), skipped_steps=P())
        batch_specs = jax.tree_util.tree_map(
            lambda x: P(*((None, axes) + (None,) * (x.ndim - 2))), gbatch)
        metric_specs = {k: P() for k in ("loss", "grad_norm", "overflow",
                                         "loss_scale")}

        def member_step(state: TrainState, batch, lr):
            rng, new_rng = jax.random.split(state.rng)
            if len(axes) == 1:
                member = jax.lax.axis_index(axes[0])
            else:
                member = (jax.lax.axis_index(axes[0]) *
                          jax.lax.axis_size(axes[1]) +
                          jax.lax.axis_index(axes[1]))
            params = state.params

            def micro_grads(mb, idx):
                mrng = jax.random.fold_in(jax.random.fold_in(rng, idx),
                                          member)

                def local_loss(p):
                    return loss_fn(cast_params(p), mb, mrng).astype(
                        jnp.float32)

                loss, grads = jax.value_and_grad(local_loss)(params)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
                return grads, loss

            if gas == 1:
                grads, loss_sum = micro_grads(
                    jax.tree_util.tree_map(lambda x: x[0], batch), 0)
            else:
                def micro_step(carry, xs):
                    grads_acc, loss_acc = carry
                    mb, idx = xs
                    g, l = micro_grads(mb, idx)
                    return (jax.tree_util.tree_map(jnp.add, grads_acc, g),
                            loss_acc + l), None

                zero_grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss_sum), _ = jax.lax.scan(
                    micro_step, (zero_grads, jnp.asarray(0.0, jnp.float32)),
                    (batch, jnp.arange(gas)))
                grads = jax.tree_util.tree_map(lambda g: g / gas, grads)

            opt_in = state.opt_state._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda e: e[0], state.opt_state.worker_error),
                server_error=jax.tree_util.tree_map(
                    lambda e: e[0], state.opt_state.server_error))
            updates, new_opt = tx.update(grads, opt_in, params)
            new_params = jax.tree_util.tree_map(
                lambda p, u: (p - lr * u.astype(jnp.float32)).astype(p.dtype),
                params, updates)
            new_opt = new_opt._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda e: e[None], new_opt.worker_error),
                server_error=jax.tree_util.tree_map(
                    lambda e: e[None], new_opt.server_error))

            loss = jax.lax.pmean(loss_sum / gas, axes)
            # norm of the member-local gradient, RMS-averaged across members
            grad_norm = jnp.sqrt(jax.lax.pmean(
                prec.global_norm(grads) ** 2, axes))
            new_state = TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt,
                scale=state.scale, rng=new_rng,
                skipped_steps=state.skipped_steps)
            metrics = {"loss": loss, "grad_norm": grad_norm,
                       "overflow": jnp.asarray(False),
                       "loss_scale": state.scale.loss_scale}
            return new_state, metrics

        sharded = _shard_map_compat(
            member_step, mesh=mesh,
            in_specs=(state_specs, batch_specs, P()),
            out_specs=(state_specs, metric_specs), check_vma=False)
        metric_shardings = {k: self._repl() for k in metric_specs}
        return jax.jit(sharded,
                       in_shardings=(self._state_shardings, None, None),
                       out_shardings=(self._state_shardings,
                                      metric_shardings),
                       donate_argnums=(0,))

    def _build_train_step(self):
        plan = self.plan
        base_specs = self.base_specs
        mesh = self.mesh
        loss_fn = self.loss_fn
        tx_update = self._tx_update
        gas = self.gas
        compute_dtype = self.compute_dtype
        clip = self.config.gradient_clipping
        fp16 = self.config.fp16
        dynamic = self.dynamic_loss_scale
        grad_specs = self._grad_spec_tree
        fetch_params = self._fetch_params
        fetch_opt = self._fetch_opt

        def cast_params(p):
            return prec.cast_tree(p, compute_dtype)

        # overflow scanning exists for fp16 loss-scaling; bf16/fp32 training
        # never skips steps (reference bf16_optimizer has no overflow path),
        # so skip the full-gradient inf/nan sweep there — unless
        # resilience.check_grad_finite folds it in (non-finite bf16/fp32
        # steps then skip, and N consecutive ones abort via the guard)
        check_overflow = (self.config.fp16.enabled
                          or self.config.resilience.check_grad_finite > 0)

        def step_body(state: TrainState, batch, lr):
            rng, new_rng = jax.random.split(state.rng)
            scale = state.scale.loss_scale
            # ZeRO-Offload: explicit H2D fetch of host-resident state
            with jax.named_scope("zero.fetch"):
                live_params = fetch_params(state.params)
                live_opt = fetch_opt(state.opt_state)

            def micro_grads(mb, idx):
                mrng = jax.random.fold_in(rng, idx)

                def scaled_loss(p):
                    loss = loss_fn(cast_params(p), mb, mrng)
                    return (loss * scale.astype(loss.dtype)).astype(jnp.float32)

                loss_s, grads = jax.value_and_grad(scaled_loss)(live_params)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
                # ZeRO >= 2: keep accumulated grads in the sharded layout so
                # XLA reduce-scatters each micro-batch (stage_1_and_2.py
                # average_tensor hot loop equivalent)
                return constrain_tree(grads, grad_specs, mesh), loss_s

            if gas == 1:
                # fast path: no accumulation buffers, no scan
                grads, loss_sum = micro_grads(
                    jax.tree_util.tree_map(lambda x: x[0], batch), 0)
            else:
                def micro_step(carry, xs):
                    grads_acc, loss_acc = carry
                    mb, idx = xs
                    grads, loss_s = micro_grads(mb, idx)
                    grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc,
                                                       grads)
                    return (grads_acc, loss_acc + loss_s), None

                zero_grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), live_params)
                zero_grads = constrain_tree(zero_grads, grad_specs, mesh)
                idxs = jnp.arange(gas)
                (grads, loss_sum), _ = jax.lax.scan(
                    micro_step, (zero_grads, jnp.asarray(0.0, jnp.float32)),
                    (batch, idxs))

            # unscale (loss scale) and average (GAS); data-parallel averaging
            # already happened inside the mean loss over the global batch
            if check_overflow or gas > 1:  # loss was scaled / accumulated
                inv = 1.0 / (scale * gas)
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)

            with jax.named_scope("optim"):
                grad_norm = prec.global_norm(grads)
                if clip and clip > 0:
                    grads, _ = prec.clip_by_global_norm(grads, clip,
                                                        grad_norm)

                if check_overflow:
                    overflow = prec.has_inf_or_nan(grads)
                    safe_grads = jax.tree_util.tree_map(
                        lambda g: jnp.where(overflow, jnp.zeros_like(g), g),
                        grads)
                    updates, new_opt = tx_update(safe_grads, live_opt,
                                                 live_params)
                    new_params = jax.tree_util.tree_map(
                        lambda p, u: jnp.where(overflow, p,
                                               (p - lr * u.astype(jnp.float32)
                                                ).astype(p.dtype)),
                        live_params, updates)
                    new_opt = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(overflow, o, n), new_opt,
                        live_opt)
                else:
                    overflow = jnp.asarray(False)
                    updates, new_opt = tx_update(grads, live_opt,
                                                 live_params)
                    new_params = jax.tree_util.tree_map(
                        lambda p, u: (p - lr * u.astype(jnp.float32)
                                      ).astype(p.dtype),
                        live_params, updates)

            new_scale = prec.update_loss_scale(
                state.scale, overflow, dynamic,
                loss_scale_window=fp16.loss_scale_window,
                min_loss_scale=fp16.min_loss_scale,
                consecutive_hysteresis=fp16.consecutive_hysteresis,
                init_hysteresis=fp16.hysteresis)

            new_state = TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                scale=new_scale,
                rng=new_rng,
                skipped_steps=state.skipped_steps + overflow.astype(jnp.int32))
            metrics = {
                "loss": loss_sum / (scale * gas),
                # grads were already unscaled by 1/(scale*gas) above, so the
                # norm is reported as-is
                "grad_norm": grad_norm,
                "overflow": overflow,
                "loss_scale": new_scale.loss_scale,
            }
            return new_state, metrics

        def train_step(state: TrainState, batch, lr):
            # runs while the step is traced: the plan counts the activation
            # pins that engage, and its line is logged again with the count
            with plan.counting_pins():
                out = step_body(state, batch, lr)
            log_dist(plan.describe(state.params, base_specs), ranks=[0])
            return out

        metric_shardings = {k: self._repl()
                            for k in ("loss", "grad_norm", "overflow",
                                      "loss_scale")}
        return jax.jit(
            train_step,
            in_shardings=(self._state_shardings, None, None),
            out_shardings=(self._state_shardings, metric_shardings),
            donate_argnums=(0,))

    def lower_train_step(self, batch: Any):
        """The fused ``train_batch`` program for ``batch``, lowered but
        not run: ``.compile().as_text()`` shows which kernels and
        collectives the compiler put in, ``.compile().memory_analysis()``
        what it needs on each device."""
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        return self._train_step_fn.lower(
            self.state, self._to_gas_batch(batch), self._lr_device())

    def _build_eval_step(self):
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype
        fetch_params = self._fetch_params

        def eval_step(state: TrainState, batch, rng):
            params = prec.cast_tree(fetch_params(state.params), compute_dtype)
            return loss_fn(params, batch, rng)

        return jax.jit(eval_step, out_shardings=self._repl())

    def _build_grad_step(self, host_grads: bool = False,
                         with_gmetrics: bool = False):
        """Imperative-mode micro step: grads for ONE micro-batch.

        ``host_grads=True`` (ZeRO-Infinity: offload_param + NVMe
        optimizer) lands the grads in pinned host memory via
        out_shardings — with unrolled layers XLA streams each layer's
        grad out as the backward produces it, so HBM never holds the
        full grad tree (the reference's offload grad buffers,
        ``zero/stage3.py`` partitioned gradient offload).  Grads keep
        the PARAM dtype in this mode (bf16 on the wire; the fp32
        accumulation fidelity lives in the NVMe moments, which cast per
        leaf — the measured fp32-cast temps are what pushed a 7B step
        80MB past a 16GB chip)."""
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype
        mesh = self.mesh
        grad_spec_tree = self._grad_spec_tree
        fetch_params = self._fetch_params

        def grad_step(state: TrainState, batch, rng):
            scale = state.scale.loss_scale

            def scaled_loss(p):
                loss = loss_fn(prec.cast_tree(p, compute_dtype), batch, rng)
                return (loss * scale.astype(loss.dtype)).astype(jnp.float32)

            loss_s, grads = jax.value_and_grad(scaled_loss)(
                fetch_params(state.params))
            if not host_grads:
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
            grads = constrain_tree(grads, grad_spec_tree, mesh)
            if with_gmetrics:
                # overflow/norm folded into the SAME program, computed
                # while the grads are still on device — the NVMe tier
                # would otherwise re-stream the full host grad tree (or
                # device_get two scalars per leaf) just for these two
                # reductions
                finite = jnp.array(True)
                sumsq = jnp.float32(0.0)
                for g in jax.tree_util.tree_leaves(grads):
                    finite &= jnp.isfinite(g).all()
                    sumsq += jnp.sum(jnp.square(g.astype(jnp.float32)))
                return loss_s / scale, grads, finite, sumsq
            return loss_s / scale, grads

        if not host_grads:
            return jax.jit(grad_step)
        host = jax.tree_util.tree_map(
            lambda s: s.with_memory_kind("pinned_host"),
            self._state_shardings.params)
        opts = None
        if jax.devices()[0].platform != "cpu":
            # the latency-hiding scheduler prefetches several layers'
            # host->HBM param copies concurrently — measured +2.7G over
            # budget at 7B on a 16GB chip; a serialized copy schedule
            # trades overlap for fitting (this tier is streaming-bound
            # anyway)
            opts = {"xla_tpu_enable_latency_hiding_scheduler": "false"}
        outs = (None, host, None, None) if with_gmetrics else (None, host)
        return jax.jit(grad_step, out_shardings=outs,
                       compiler_options=opts)

    def _build_apply_step(self):
        tx_update = self._tx_update
        plan = self.plan
        clip = self.config.gradient_clipping
        fp16 = self.config.fp16
        dynamic = self.dynamic_loss_scale
        gas = self.gas
        fetch_params = self._fetch_params
        fetch_opt = self._fetch_opt

        def apply_step(state: TrainState, grads, lr):
            scale = state.scale.loss_scale
            live_params = fetch_params(state.params)
            live_opt = fetch_opt(state.opt_state)
            inv = 1.0 / (scale * gas)
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            overflow = prec.has_inf_or_nan(grads)
            grad_norm = prec.global_norm(grads)
            if clip and clip > 0:
                grads, _ = prec.clip_by_global_norm(grads, clip, grad_norm)
            safe = jax.tree_util.tree_map(
                lambda g: jnp.where(overflow, jnp.zeros_like(g), g), grads)
            updates, new_opt = tx_update(safe, live_opt, live_params)
            new_params = jax.tree_util.tree_map(
                lambda p, u: jnp.where(overflow, p,
                                       (p - lr * u.astype(jnp.float32)
                                        ).astype(p.dtype)),
                live_params, updates)
            new_opt = jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new_opt,
                live_opt)
            new_scale = prec.update_loss_scale(
                state.scale, overflow, dynamic,
                loss_scale_window=fp16.loss_scale_window,
                min_loss_scale=fp16.min_loss_scale,
                consecutive_hysteresis=fp16.consecutive_hysteresis,
                init_hysteresis=fp16.hysteresis)
            rng, new_rng = jax.random.split(state.rng)
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt, scale=new_scale,
                                   rng=new_rng,
                                   skipped_steps=state.skipped_steps +
                                   overflow.astype(jnp.int32))
            return new_state, {"grad_norm": grad_norm,
                               "overflow": overflow,
                               "loss_scale": new_scale.loss_scale}

        metric_shardings = {k: self._repl()
                            for k in ("grad_norm", "overflow", "loss_scale")}
        return jax.jit(apply_step,
                       in_shardings=(self._state_shardings, None, None),
                       out_shardings=(self._state_shardings,
                                      metric_shardings),
                       donate_argnums=(0,))

    # ------------------------------------------------------------------
    # NVMe-swapped optimizer step (ZeRO-Infinity tier)
    # ------------------------------------------------------------------

    def _nvme_train_step(self, gbatch, lr):
        """fwd+bwd per micro-batch on device, then the swapped optimizer
        step streaming Adam moments NVMe→HBM→NVMe (reference
        ``pipelined_optimizer_swapper`` semantics; see
        ``runtime/swap_tensor.py``)."""
        host_grads = bool(self.offload_param)
        # gas==1: overflow/norm fold into the grad-step program for free
        # (the metrics of the single micro ARE the final metrics).
        # gas>1 needs the norm of the SUM — fused per-micro reductions
        # would be paid and discarded, so skip them there.
        fused_metrics = self.gas == 1
        if getattr(self, "_nvme_grad_step_fn", None) is None:
            self._nvme_grad_step_fn = self._build_grad_step(
                host_grads=host_grads, with_gmetrics=fused_metrics)
        state = self.state
        rng = state.rng
        loss_sum, grads = None, None
        gmetrics = None
        for i in range(self.gas):
            mb = jax.tree_util.tree_map(lambda x: x[i], gbatch)
            rng, sub = jax.random.split(rng)
            if fused_metrics:
                loss, g, f, s2 = self._nvme_grad_step_fn(state, mb, sub)
                gmetrics = (~f, jnp.sqrt(s2))
            else:
                loss, g = self._nvme_grad_step_fn(state, mb, sub)
            loss_sum = loss if loss_sum is None else loss_sum + loss
            if grads is None:
                grads = g
            elif host_grads:
                grads = self._host_tree_add(grads, g)
            else:
                grads = jax.tree_util.tree_map(jnp.add, grads, g)
        # overlap the swap pipeline's HEAD with the in-flight bwd: the
        # grad dispatches above are async, so the first read window's
        # NVMe traffic (and any deferred write-back from the previous
        # step) runs while the device is still computing — the first
        # bucket's swap-in is free by the time apply() starts
        if hasattr(self.nvme_swapper, "start_prefetch"):
            self.nvme_swapper.start_prefetch()
        new_state, metrics = self._nvme_apply_grads(
            grads, lr, rng, leafwise=host_grads, gmetrics=gmetrics)
        metrics["loss"] = loss_sum / self.gas
        return new_state, metrics

    def _host_tree_add(self, a, b):
        """Leaf-by-leaf accumulate with pinned-host outputs: a whole-tree
        jitted add would stage the full fp32 grad tree in HBM, undoing
        the host-grad streaming."""
        if getattr(self, "_host_add_fn", None) is None:
            self._host_add_fn = {}
        flat_a, tree = jax.tree_util.tree_flatten(a)
        flat_b = jax.tree_util.tree_leaves(b)
        out = []
        for x, y in zip(flat_a, flat_b):
            sh = x.sharding.with_memory_kind("pinned_host")
            # sharding in the key: same-shape leaves with different specs
            # (e.g. col- vs row-parallel kernels) must not alias one
            # cached out_sharding
            key = (x.shape, str(x.dtype), sh)
            if key not in self._host_add_fn:
                self._host_add_fn[key] = jax.jit(
                    jnp.add, out_shardings=sh, donate_argnums=(0,))
            out.append(self._host_add_fn[key](x, y))
        return jax.tree_util.tree_unflatten(tree, out)

    def _nvme_apply_grads(self, grads, lr, rng, leafwise: bool = False,
                          gmetrics=None):
        """Overflow check + loss-scale update on device, then the
        bucketed/leafwise swapped Adam update (skipped entirely on
        overflow — the moments on disk are the authoritative state and
        simply stay put).

        ``leafwise``: grads live in pinned host memory — compute the
        overflow/norm reductions one leaf at a time so HBM holds one
        leaf, not the tree.  ``gmetrics``: (overflow, norm_raw) already
        computed (fused into the grad step) — skips the reduction pass
        entirely."""
        state = self.state
        if gmetrics is not None:
            overflow, norm_raw = gmetrics
        elif leafwise:
            if getattr(self, "_nvme_leaf_metric_fn", None) is None:
                # scalar accumulation stays ON DEVICE across the loop —
                # per-leaf blocking transfers turn this into one
                # round-trip per leaf (minutes at 7B through a remote
                # runtime); lazy chaining is one blocking read total
                self._nvme_leaf_metric_fn = jax.jit(
                    lambda g, fin, ss: (
                        fin & jnp.isfinite(g).all(),
                        ss + jnp.sum(jnp.square(g.astype(jnp.float32)))))
            fin = jnp.array(True)
            ss = jnp.float32(0.0)
            for leaf in jax.tree_util.tree_leaves(grads):
                fin, ss = self._nvme_leaf_metric_fn(leaf, fin, ss)
            overflow = ~fin
            norm_raw = jnp.sqrt(ss)
        else:
            if getattr(self, "_nvme_metrics_fn", None) is None:
                self._nvme_metrics_fn = jax.jit(
                    lambda g: (prec.has_inf_or_nan(g),
                               prec.global_norm(g)))
            overflow, norm_raw = self._nvme_metrics_fn(grads)
        # ONE blocking transfer for all three scalars: each device_get
        # is a full host<->device round-trip, and this sync is also the
        # barrier the swap prefetch overlaps — keep it singular
        scale_f, ovf, norm = jax.device_get(
            (state.scale.loss_scale, overflow, norm_raw))
        scale_f = float(scale_f)
        ovf = bool(ovf)
        inv = 1.0 / (scale_f * self.gas)
        norm = float(norm) * inv
        gscale = inv
        clip = self.config.gradient_clipping
        if clip and clip > 0:
            gscale *= min(1.0, clip / (norm + 1e-6))
        fp16 = self.config.fp16
        new_scale = prec.update_loss_scale(
            state.scale, overflow, self.dynamic_loss_scale,
            loss_scale_window=fp16.loss_scale_window,
            min_loss_scale=fp16.min_loss_scale,
            consecutive_hysteresis=fp16.consecutive_hysteresis,
            init_hysteresis=fp16.hysteresis)
        if ovf:
            new_params = state.params
            if hasattr(self.nvme_swapper, "cancel_prefetch"):
                # the skipped step must not leak its prefetched reads
                # into the next step's buffer pool
                self.nvme_swapper.cancel_prefetch()
        else:
            new_params = self.nvme_swapper.apply(state.params, grads,
                                                 lr=lr, gscale=gscale)
            stats = getattr(self.nvme_swapper, "stage_stats", None)
            if stats and self.config.wall_clock_breakdown:
                # per-stage swap waits join the breakdown timer group —
                # link-boundedness (and the SDC verify residual) is
                # measurable, not asserted
                for name in ("swap_in_wait", "bucket_update",
                             "swap_out_wait", "swap_verify"):
                    if stats.get(f"{name}_s") is not None:
                        self.timers(name).record(stats[f"{name}_s"])
        rng, new_rng = jax.random.split(rng)
        new_state = TrainState(
            step=state.step + 1, params=new_params,
            opt_state=state.opt_state, scale=new_scale, rng=new_rng,
            skipped_steps=state.skipped_steps + jnp.asarray(int(ovf),
                                                            jnp.int32))
        return new_state, {"grad_norm": norm, "overflow": ovf,
                           "loss_scale": new_scale.loss_scale}

    # ------------------------------------------------------------------
    # Batch plumbing
    # ------------------------------------------------------------------

    def _apply_curriculum(self, batch):
        """Truncate token batches to the current seqlen difficulty
        (reference ``engine.py curriculum_enabled_legacy`` +
        megatron-side truncation).  A DeviceBatch is already staged at
        full length and passes through untouched."""
        if isinstance(batch, DeviceBatch):
            return batch
        d = self.curriculum_scheduler.update_difficulty(
            self.global_steps + 1)

        def trunc(x):
            x = np.asarray(x)
            return x[:, :d] if x.ndim >= 2 and x.shape[1] > d else x

        return jax.tree_util.tree_map(trunc, batch)

    def set_custom_curriculum_learning_schedule(self, schedule_fn) -> None:
        """Reference ``engine.set_custom_curriculum_learning_schedule``."""
        assert self.curriculum_scheduler is not None, (
            "curriculum_learning is not enabled")
        self.curriculum_scheduler.set_custom_get_difficulty(schedule_fn)

    def _to_gas_batch(self, batch):
        """[train_batch, ...] -> [gas, micro_global, ...] sharded arrays."""
        if isinstance(batch, DeviceBatch):
            return batch.tree
        gas = self.gas

        def reshape(x):
            x = np.asarray(x)
            assert x.shape[0] % gas == 0, (
                f"batch dim {x.shape[0]} not divisible by "
                f"gradient_accumulation_steps {gas}")
            return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

        batch = jax.tree_util.tree_map(reshape, batch)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self.plan.batch_sharding(
                x.ndim, has_gas_dim=True, dtype=x.dtype)), batch)

    def put_batch(self, batch) -> "DeviceBatch":
        """Pre-stage a [train_batch, ...] batch on device in the engine's
        gas-sharded layout.  ``train_batch(batch=put_batch(b))`` then skips
        all per-step host work — useful when iterating over device-resident
        data or re-using a batch (benchmarks)."""
        return DeviceBatch(self._to_gas_batch(batch))

    def _lr_device(self) -> jax.Array:
        """Device scalar for the current LR, re-transferred only on change."""
        lr = float(self.get_lr()[0])
        if self._lr_cached_value != lr:
            self._lr_cached_value = lr
            self._lr_cached_dev = jax.device_put(
                np.float32(lr), NamedSharding(self.mesh, P()))
        return self._lr_cached_dev

    def _next_batch(self, data_iter):
        if data_iter is not None:
            return next(data_iter)
        if self._data_iter is None:
            assert self.training_dataloader is not None, (
                "train_batch needs a data_iter or training_data passed to "
                "initialize()")
            self._data_iter = iter(self.training_dataloader)
        return next(self._data_iter)

    # ------------------------------------------------------------------
    # Public API (reference parity)
    # ------------------------------------------------------------------

    def train_batch(self, data_iter: Optional[Iterator] = None,
                    batch: Any = None) -> jax.Array:
        """One full training step: GAS micro-batches fused in one compiled
        program (reference ``PipelineEngine.train_batch`` naming; for the
        plain engine this is forward+backward+step at once)."""
        if batch is None:
            batch = self._next_batch(data_iter)
        if self.curriculum_scheduler is not None:
            batch = self._apply_curriculum(batch)
        breakdown = self.config.wall_clock_breakdown
        if breakdown:
            self.timers("batch_prep").start()
        try:
            gbatch = self._to_gas_batch(batch)
        except Exception:
            if breakdown:
                self.timers("batch_prep").discard()
            raise
        if breakdown:
            self.timers("batch_prep").stop()
        lr = self._lr_device()
        if self._train_step_fn is None and self.nvme_swapper is None:
            self._train_step_fn = (
                self._build_onebit_train_step(gbatch)
                if self._onebit_axes is not None
                else self._build_train_step())
            # for whoever joins a profile's device time to layer kinds
            device_scopes.register(self._train_step_fn.__name__,
                                   self._train_step_fn,
                                   (self.state, gbatch, lr))

        self.tput_timer.start()
        if breakdown:
            self.timers(STEP_GLOBAL_TIMER).start()
        try:
            if self.nvme_swapper is not None:
                self.state, metrics = self._nvme_train_step(gbatch, lr)
            else:
                self.state, metrics = self._train_step_fn(self.state,
                                                          gbatch, lr)
        except CollectiveTimeout as e:
            if breakdown:
                self.timers(STEP_GLOBAL_TIMER).discard()
            self._handle_collective_timeout(e)    # re-raises
        except SwapCorruptionError as e:
            if breakdown:
                self.timers(STEP_GLOBAL_TIMER).discard()
            self._handle_swap_corruption(e)       # re-raises
        except Exception:
            if breakdown:
                self.timers(STEP_GLOBAL_TIMER).discard()
            raise
        if breakdown:
            # one fused XLA program covers fwd+bwd+step; the device-synced
            # bracket is the whole step (fwd/bwd are not separable without
            # deoptimizing — documented divergence from EngineTimers).
            # jit dispatch is async: sync on the result before stopping
            jax.block_until_ready(metrics)
            self.timers(STEP_GLOBAL_TIMER).stop()
        self._last_metrics = metrics
        self.global_steps += 1
        self.micro_steps += self.gas
        self.global_samples += self.config.train_batch_size
        self.lr_scheduler.step()
        self.tput_timer.stop(global_step=True)
        if self._skip_guard is not None:
            # costs one scalar sync per step; built only when
            # resilience.max_consecutive_skips > 0
            self._skip_guard.update(
                bool(jax.device_get(metrics["overflow"])),
                self.global_steps)
        if (self._desync is not None
                and self._desync.should_check(self.global_steps)):
            # cross-rank comparison of replica-identical scalars: a
            # corrupted collective (or diverged host-side stream) raises
            # GradientAnomalyError here instead of training on silently
            m = jax.device_get(metrics)
            self._desync.check({"loss": float(m["loss"]),
                                "grad_norm": float(m["grad_norm"])},
                               self.global_steps)

        if self.global_steps % self.config.steps_per_print == 0:
            m = jax.device_get(metrics)
            log_dist(
                f"step={self.global_steps} loss={float(m['loss']):.4f} "
                f"lr={self.get_lr()[0]:.3e} "
                f"grad_norm={float(m['grad_norm']):.3f} "
                f"loss_scale={float(m['loss_scale']):.0f}", ranks=[0])
            if breakdown:
                # elapsed accumulates across steps_per_print steps; report
                # per-step times like the reference EngineTimers (plus the
                # swap pipeline's stage waits when a swapped tier is live)
                names = ["batch_prep", STEP_GLOBAL_TIMER]
                names += [n for n in ("swap_in_wait", "bucket_update",
                                      "swap_out_wait", "swap_verify")
                          if self.timers.has_timer(n)]
                self.timers.log(names,
                                normalizer=self.config.steps_per_print)
            if (self.config.resilience.comm.straggler_report
                    and self.monitor is not None and self.monitor.enabled):
                # one small allgather per report (opt-in); names the
                # rank every eager collective waits for
                self.monitor.write_comm_health(dist.straggler_report(),
                                               self.global_samples)
            sdc = getattr(self.nvme_swapper, "sdc_counters", None)
            if (sdc is not None and self.monitor is not None
                    and self.monitor.enabled):
                # SDC detection/recovery counters stream alongside the
                # loss: a fleet host with flaky DRAM/storage shows up
                # as a climbing mismatch series, not a silent loss drift
                self.monitor.write_sdc_health(sdc, self.global_samples)
        if self.monitor is not None and self.monitor.enabled:
            m = jax.device_get(metrics)
            self.monitor.write_events([
                ("Train/Samples/train_loss", float(m["loss"]),
                 self.global_samples),
                ("Train/Samples/lr", self.get_lr()[0], self.global_samples),
            ])
        fp = self.config.flops_profiler
        if fp.enabled and self.global_steps == fp.profile_step:
            self._run_flops_profiler(gbatch, lr)
        return metrics["loss"]

    def _run_flops_profiler(self, gbatch, lr) -> None:
        """One-shot step profile at ``flops_profiler.profile_step``
        (reference wires this in ``engine._take_model_step``; here the
        whole fused step is re-traced once and costed from its jaxpr)."""
        from deepspeed_tpu.profiling import FlopsProfiler

        fp = self.config.flops_profiler
        if self._train_step_fn is None:
            # NVMe-offloaded step: no single fused program — cost the
            # fwd+bwd micro step (the dominant FLOPs; the optimizer apply
            # is a host-side bucket stream with no jaxpr)
            gfn = self._nvme_grad_step_fn or self._grad_step_fn
            assert gfn is not None
            mb = jax.tree_util.tree_map(lambda x: x[0], gbatch)
            prof = FlopsProfiler(gfn, ds_engine=self)
            prof.start_profile()
            prof.profile(self.state, mb, self.state.rng,
                         params=self.state.params)
            prof.print_model_profile(profile_step=fp.profile_step,
                                     module_depth=fp.module_depth,
                                     top_modules=fp.top_modules,
                                     detailed=fp.detailed,
                                     output_file=fp.output_file)
            prof.end_profile()
            return
        prof = FlopsProfiler(self._train_step_fn, ds_engine=self)
        prof.start_profile()
        # duration: the step jit donates the state, so it cannot be re-run
        # for measurement; reuse the wall_clock_breakdown bracket when on
        duration = 0.0
        if self.config.wall_clock_breakdown:
            duration = self.timers(STEP_GLOBAL_TIMER).last_interval
        prof.profile(self.state, gbatch, lr, params=self.state.params,
                     duration=duration)
        prof.print_model_profile(profile_step=fp.profile_step,
                                 module_depth=fp.module_depth,
                                 top_modules=fp.top_modules,
                                 detailed=fp.detailed,
                                 output_file=fp.output_file)
        prof.end_profile()

    def eval_batch(self, data_iter: Optional[Iterator] = None,
                   batch: Any = None) -> jax.Array:
        if batch is None:
            batch = self._next_batch(data_iter)
        batch = jax.tree_util.tree_map(
            lambda x: jax.device_put(np.asarray(x),
                                     self.plan.batch_sharding(
                                         np.asarray(x).ndim,
                                         dtype=np.asarray(x).dtype)),
            batch)
        rng = jax.random.fold_in(jax.random.PRNGKey(self.config.seed ^ 0x5EED),
                                 self.global_steps)
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
            device_scopes.register(self._eval_step_fn.__name__,
                                   self._eval_step_fn,
                                   (self.state, batch, rng))
        return self._eval_step_fn(self.state, batch, rng)

    # -- imperative compat ----------------------------------------------

    def forward(self, batch) -> jax.Array:
        """Loss for one micro-batch; stashes it for ``backward``."""
        if self._onebit_axes is not None:
            raise NotImplementedError(
                "the 1-bit compressed optimizer path only supports the "
                "fused train_batch() API (local gradients never leave the "
                "compiled step); use train_batch or a non-1-bit optimizer")
        self._fwd_batch = jax.tree_util.tree_map(
            lambda x: jax.device_put(np.asarray(x),
                                     self.plan.batch_sharding(
                                         np.asarray(x).ndim,
                                         dtype=np.asarray(x).dtype)),
            batch)
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        rng = jax.random.fold_in(self.state.rng, self.micro_steps)
        self._fwd_rng = rng
        return self._eval_step_fn(self.state, self._fwd_batch, rng)

    def backward(self, loss=None) -> None:
        """Accumulate grads for the stashed micro-batch."""
        assert getattr(self, "_fwd_batch", None) is not None, \
            "backward() without forward()"
        if self._grad_step_fn is None:
            self._grad_step_fn = self._build_grad_step()
        _, grads = self._grad_step_fn(self.state, self._fwd_batch,
                                      self._fwd_rng)
        if self._pending_grads is None:
            self._pending_grads = grads
        else:
            self._pending_grads = jax.tree_util.tree_map(
                jnp.add, self._pending_grads, grads)
        self.micro_steps += 1
        self._profile_batch = self._fwd_batch  # kept for flops profiling
        self._fwd_batch = None

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self.gas == 0

    def step(self) -> None:
        """Apply accumulated grads at a GAS boundary (no-op otherwise,
        matching reference engine.step semantics)."""
        if not self.is_gradient_accumulation_boundary():
            return
        assert self._pending_grads is not None, "step() without backward()"
        if self.nvme_swapper is not None:
            lr = jnp.asarray(self.get_lr()[0], jnp.float32)
            fp = self.config.flops_profiler
            if fp.enabled and self.global_steps + 1 == fp.profile_step:
                # fwd+bwd only: the swapped optimizer apply is a host-side
                # leaf loop with no single jaxpr to cost
                self._profile_imperative_step(lr)
            self.state, self._last_metrics = self._nvme_apply_grads(
                self._pending_grads, lr, self.state.rng)
            self._pending_grads = None
            self.global_steps += 1
            self.lr_scheduler.step()
            return
        if self._apply_step_fn is None:
            self._apply_step_fn = self._build_apply_step()
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        fp = self.config.flops_profiler
        if fp.enabled and self.global_steps + 1 == fp.profile_step:
            self._profile_imperative_step(lr)
        self.state, self._last_metrics = self._apply_step_fn(
            self.state, self._pending_grads, lr)
        self._pending_grads = None
        self.global_steps += 1
        self.lr_scheduler.step()
        if self._skip_guard is not None:
            self._skip_guard.update(
                bool(jax.device_get(self._last_metrics["overflow"])),
                self.global_steps)

    def _profile_imperative_step(self, lr) -> None:
        """Flops profile for the imperative fwd/bwd/step path: cost the
        grad fn (fwd+bwd, the dominant FLOPs) and the optimizer apply,
        merged into one report (the fused ``train_batch`` path instead
        profiles its single step program)."""
        from deepspeed_tpu.profiling import FlopsProfiler
        from deepspeed_tpu.profiling.flops_profiler import (_merge,
                                                            profile_fn)

        fp = self.config.flops_profiler
        prof = FlopsProfiler(self._grad_step_fn, ds_engine=self)
        prof.start_profile()
        prof.profile(self.state, self._profile_batch, self._fwd_rng,
                     params=self.state.params)
        if self._apply_step_fn is not None:     # nvme step has no jaxpr
            apply_tree = profile_fn(self._apply_step_fn, self.state,
                                    self._pending_grads, lr)
            _merge(prof._tree, apply_tree)
        prof.print_model_profile(profile_step=fp.profile_step,
                                 module_depth=fp.module_depth,
                                 top_modules=fp.top_modules,
                                 detailed=fp.detailed,
                                 output_file=fp.output_file)
        prof.end_profile()

    # -- checkpointing ----------------------------------------------------

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None,
                        save_latest: bool = True,
                        async_save: Optional[bool] = None) -> str:
        from deepspeed_tpu.checkpoint.engine import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state,
                     save_latest=save_latest, async_save=async_save)

    def offload_states(self, include: Optional[Tuple[str, ...]] = None
                       ) -> None:
        """Move optimizer state (and optionally params) to host memory at
        runtime (reference ``engine.offload_states:3839`` /
        ``zero/offload_states.py``): frees HBM between training phases —
        e.g. while a hybrid engine generates.  ``include``: subset of
        ("optimizer", "params"); default optimizer only.  The next
        train step streams them back in-graph (H2D fetch), or call
        :meth:`reload_states` to move them back eagerly."""
        if self.mesh.devices.flat[0].platform == "cpu":
            logger.warning("offload_states: backend has no host memory "
                           "space; no-op")
            return
        include = include or ("optimizer",)
        from deepspeed_tpu.utils.sharding import memory_space

        to_host = memory_space("pinned_host")

        def host_kind(shardings):
            return jax.tree_util.tree_map(
                lambda s: s.with_memory_kind("pinned_host"), shardings)

        state = self.state
        shardings = self._state_shardings
        if "optimizer" in include:
            host_opt = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, to_host), state.opt_state)
            self._fetch_opt = (
                lambda o, _s=jax.tree_util.tree_map(
                    lambda x: x.sharding, state.opt_state):
                jax.device_put(o, _s))
            state = state.replace(opt_state=host_opt)
            shardings = shardings.replace(
                opt_state=host_kind(shardings.opt_state))
        if "params" in include:
            host_p = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, to_host), state.params)
            self._fetch_params = (
                lambda p, _s=jax.tree_util.tree_map(
                    lambda x: x.sharding, state.params):
                jax.device_put(p, _s))
            state = state.replace(params=host_p)
            shardings = shardings.replace(
                params=host_kind(shardings.params))
        self.state = state
        # the jitted steps bake in_shardings AND the fetch closures; every
        # cached program must rebuild against the host-resident layout
        self._state_shardings = shardings
        self._invalidate_compiled_steps()
        log_dist(f"offload_states: {include} moved to pinned host memory",
                 ranks=[0])

    def reload_states(self) -> None:
        """Inverse of :meth:`offload_states` (reference
        ``engine.reload_states:3871``)."""
        if self.mesh.devices.flat[0].platform == "cpu":
            return
        from deepspeed_tpu.utils.sharding import memory_space

        to_dev = memory_space("device")

        def dev_kind(shardings):
            return jax.tree_util.tree_map(
                lambda s: s.with_memory_kind("device"), shardings)

        self.state = self.state.replace(
            opt_state=jax.tree_util.tree_map(
                lambda x: jax.device_put(x, to_dev), self.state.opt_state),
            params=jax.tree_util.tree_map(
                lambda x: jax.device_put(x, to_dev), self.state.params))
        self._state_shardings = self._state_shardings.replace(
            opt_state=dev_kind(self._state_shardings.opt_state),
            params=dev_kind(self._state_shardings.params))
        self._fetch_opt = lambda o: o
        self._fetch_params = lambda p: p
        self._invalidate_compiled_steps()
        log_dist("reload_states: state back in device memory", ranks=[0])

    def _invalidate_compiled_steps(self) -> None:
        self._train_step_fn = None
        self._eval_step_fn = None
        self._grad_step_fn = None
        self._nvme_grad_step_fn = None
        self._apply_step_fn = None

    def save_16bit_model(self, save_dir: str,
                         output_file: str = "pytorch_model.bin") -> str:
        """Consolidated compute-dtype weights for serving (reference
        ``engine.save_16bit_model``)."""
        from deepspeed_tpu.checkpoint.engine import \
            save_16bit_model as _save16

        return _save16(self, save_dir, output_file)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True):
        from deepspeed_tpu.checkpoint.engine import load_checkpoint as _load

        return _load(self, load_dir, tag=tag,
                     load_optimizer_states=load_optimizer_states,
                     load_lr_scheduler_states=load_lr_scheduler_states)

    def wait_checkpoint(self) -> None:
        """Join an in-flight async checkpoint save (no-op otherwise)."""
        from deepspeed_tpu.checkpoint.engine import wait_checkpoint as _wait

        _wait(self)

    # -- preemption / fault tolerance (resilience/) -----------------------

    def emergency_checkpoint(self, save_dir: str) -> str:
        """Drain any in-flight async save, then take a synchronous
        checkpoint — the last-gasp save a preemption notice triggers.
        A failed in-flight save is logged and superseded (this snapshot
        is strictly newer), never allowed to block the emergency
        write."""
        try:
            self.wait_checkpoint()
        except BaseException as e:
            logger.error(f"emergency checkpoint: in-flight async save "
                         f"failed ({e!r}); writing a fresh synchronous "
                         "checkpoint")
            self._ckpt_saver = None           # drop the poisoned saver
        return self.save_checkpoint(
            save_dir, tag=f"emergency_step{self.global_steps}",
            async_save=False)

    def _handle_collective_timeout(self, e: CollectiveTimeout) -> None:
        """Route a collective timeout through the preemption path: a
        peer is gone or the transport wedged, so this process must stop
        cleanly and let the elastic layer restart the job.  The
        emergency checkpoint is an ATTEMPT — its own collectives may hit
        the same dead peer (the watchdog bounds them too), and a failed
        save must not mask the original timeout."""
        self.comm_timed_out = True
        logger.error(f"collective timeout during training step: {e}")
        save_dir = self._preemption_save_dir
        if save_dir:
            # the postmortem timeline lands NEXT TO the emergency
            # checkpoint (the raise site already dumped to the default
            # flight dir; this copy is the one operators find first)
            from deepspeed_tpu.telemetry import flight

            flight.dump_on_fault("collective_timeout", e, dir=save_dir)
            try:
                path = self.emergency_checkpoint(save_dir)
                logger.error(f"emergency checkpoint committed at {path}; "
                             "aborting for elastic restart")
            except BaseException as ce:
                logger.error(f"emergency checkpoint failed under comm "
                             f"timeout ({ce!r}); aborting without it")
        raise e

    def _handle_swap_corruption(self, e: SwapCorruptionError) -> None:
        """Route persistent silent data corruption in the swap path
        through the preemption machinery: the corrupt swap file is
        already quarantined and the swap state invalidated, so the
        right move is a last-gasp checkpoint (params are intact — the
        corruption was caught BEFORE the update consumed it) and a
        clean abort; the elastic agent then restarts from the newest
        verified checkpoint instead of training on garbage."""
        self.swap_corrupted = True
        logger.error(f"silent data corruption in the NVMe swap path: {e}")
        save_dir = self._preemption_save_dir
        if save_dir:
            from deepspeed_tpu.telemetry import flight

            flight.dump_on_fault("swap_corruption", e, dir=save_dir)
            try:
                path = self.emergency_checkpoint(save_dir)
                logger.error(f"emergency checkpoint committed at {path}; "
                             "aborting for elastic restart")
            except BaseException as ce:
                logger.error(f"emergency checkpoint failed under swap "
                             f"corruption ({ce!r}); aborting without it")
        raise e

    def install_preemption_handler(self, save_dir: str, signals=None,
                                   exit_after: bool = True) -> None:
        """SIGTERM hook (TPU preemption notice): drains the async saver,
        takes an emergency synchronous checkpoint, then re-delivers the
        signal to the previous disposition (``exit_after=False`` returns
        to the interrupted code instead — tests, or jobs that drain
        work themselves).  Call :meth:`uninstall_preemption_handler` to
        restore the prior handlers."""
        import signal as _signal

        signals = tuple(signals or (_signal.SIGTERM,))
        prev = {}

        def _handler(signum, frame):
            self.preempted = True
            logger.error(f"signal {signum}: preemption notice — taking "
                         "emergency checkpoint")
            from deepspeed_tpu.telemetry import flight

            flight.dump_on_fault("sigterm_preemption", dir=save_dir,
                                 extra={"signal": int(signum),
                                        "step": int(self.global_steps)})
            path = self.emergency_checkpoint(save_dir)
            logger.error(f"emergency checkpoint committed at {path}")
            if not exit_after:
                return
            old = prev.get(signum, _signal.SIG_DFL)
            if callable(old):
                old(signum, frame)
            else:
                _signal.signal(signum, old)
                _signal.raise_signal(signum)

        for s in signals:
            prev[s] = _signal.signal(s, _handler)
        self._preemption_prev_handlers = prev
        # collective timeouts reuse this dir for their emergency save
        self._preemption_save_dir = save_dir

    def uninstall_preemption_handler(self) -> None:
        import signal as _signal

        if self._preemption_prev_handlers:
            for s, old in self._preemption_prev_handlers.items():
                _signal.signal(s, old)
            self._preemption_prev_handlers = None

    # -- misc -------------------------------------------------------------

    def get_global_grad_norm(self) -> Optional[float]:
        """Global (pre-clip) gradient norm of the most recent step
        (reference ``engine.py`` ``get_global_grad_norm``)."""
        m = getattr(self, "_last_metrics", None)
        if m is None:
            return None
        return float(jax.device_get(m["grad_norm"]))

    def module_state_dict(self):
        return jax.device_get(self.state.params)

    def train(self, mode: bool = True):  # API parity; no mode flag needed
        return self

    def eval(self):
        return self
