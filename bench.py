"""Benchmark matrix: the BASELINE.md target configs, one JSON line each.

Default (no args) runs config 1 — the driver's headline number — and
prints ONE JSON line ``{"metric", "value", "unit", "vs_baseline"}``.

The full matrix (``--config N``) mirrors BASELINE.md's target list:

1. GPT-2 125M, ZeRO-0 DDP           — headline train MFU (north star 45%)
2. GPT-2 1.3B, ZeRO-2 + fused Adam  — train MFU, bf16
3. Llama-2-7B-class, ZeRO-3         — train MFU (``--size`` to shrink)
4. Long-context Ulysses SP          — attention-heavy train MFU @ 32k seq
5. Mixtral-class MoE + EP           — train MFU (active-params FLOPs)
6. (``--config infer``) KV-cache decode — tokens/s/chip

Configs 2-5 size to a single v5p chip by default; ``--size`` swaps the
model preset (e.g. ``--size gpt2-350m``) and ``--smoke`` shrinks shapes
for CPU runs.  On multi-chip hosts every config shards over all visible
chips (data axis; config 4 prefers the seq axis, 5 the expert axis).

MFU is computed from *device* step time (jax.profiler XPlane events);
wall-clock throughput is reported alongside in ``detail``.  A run that
finds no accelerator is an error unless ``--smoke`` (or
``JAX_PLATFORMS=cpu``) asked for the CPU, and a CPU run prints no device
metric: its lines say ``not measured``.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

# bf16 peak FLOPs per chip by device kind substring
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,  # v5p
    "TPU v6": 918e12,  # trillium
}

NORTH_STAR_MFU = 45.0


def peak_flops(kind: str) -> float:
    """bf16 peak of one chip of ``kind``; a device the table does not
    know is an error, not a default."""
    for k, v in PEAK_FLOPS.items():
        if kind.lower().startswith(k.lower()) or k.lower() in kind.lower():
            return v
    raise KeyError(f"no peak FLOP/s entry for device kind {kind!r} "
                   f"(known: {sorted(PEAK_FLOPS)})")


def _device_seconds_from_trace(trace_dir: str) -> float:
    """Total jit_* device seconds from a profiler trace, averaged over
    the TPU planes so multi-chip hosts aren't overcounted.  Raises when
    the trace is missing, unreadable or holds no device event."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    pdata = ProfileData.from_file(paths[-1])
    total_ns = 0
    n_planes = 0
    for plane in pdata.planes:
        if "TPU" not in plane.name:
            continue
        plane_ns = 0
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("jit_"):
                    plane_ns += ev.duration_ns
        if plane_ns > 0:
            n_planes += 1
            total_ns += plane_ns
    if total_ns == 0:
        raise RuntimeError(f"{paths[-1]} holds no jit_* event on a TPU plane")
    return total_ns / 1e9 / n_planes


def device_seconds_per_call(fn, n: int = 10):
    """(device_seconds, wall_seconds) per fn() call.  Device time comes from
    profiler XPlane events (jit_* entries); wall time brackets only the call
    loop + sync.  On the CPU backend (``--smoke``) there is no device
    plane and device_seconds is ``None``: callers print "not measured"."""
    trace_dir = os.path.join(tempfile.gettempdir(), "dstpu_bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    out = None
    for _ in range(n):
        out = fn()
    jax.device_get(jax.tree_util.tree_map(jnp.sum, out))
    wall = (time.perf_counter() - t0) / n
    jax.profiler.stop_trace()
    if jax.devices()[0].platform == "cpu":
        return None, wall
    return _device_seconds_from_trace(trace_dir) / n, wall


def _measure_train(engine, batch, *, steps, micro_global, seq,
                   flops_per_tok, metric, vs=NORTH_STAR_MFU,
                   extra_detail=None):
    """Shared harness: warm up, time the step, print the JSON line."""
    dev = jax.devices()[0]
    dbatch = engine.put_batch(batch)
    loss = engine.train_batch(batch=dbatch)          # compile
    float(jax.device_get(loss))

    dev_dt, wall_dt = device_seconds_per_call(
        lambda: engine.train_batch(batch=dbatch), n=steps)
    loss = engine.train_batch(batch=dbatch)

    n_chips = len(jax.devices())
    if dev_dt is None:                  # --smoke on the CPU: no device time
        print(json.dumps({
            "metric": metric, "value": None, "unit": "not measured",
            "vs_baseline": 0.0,
            "detail": dict({"wall_step_ms": round(wall_dt * 1e3, 1),
                            "device": dev.device_kind, "n_chips": n_chips,
                            "final_loss": float(jax.device_get(loss))},
                           **(extra_detail or {}))}))
        return
    samples_per_sec = micro_global / dev_dt
    tokens_per_sec = samples_per_sec * seq
    mfu = 100.0 * tokens_per_sec * flops_per_tok / (
        peak_flops(dev.device_kind) * n_chips)
    detail = {
        "samples_per_sec_per_chip": round(samples_per_sec / n_chips, 2),
        "tokens_per_sec": round(tokens_per_sec),
        "device_step_ms": round(dev_dt * 1e3, 1),
        "wall_step_ms": round(wall_dt * 1e3, 1),
        "wall_tokens_per_sec": round(micro_global * seq / wall_dt),
        "device": dev.device_kind,
        "n_chips": n_chips,
        "final_loss": float(jax.device_get(loss)),
    }
    detail.update(extra_detail or {})
    print(json.dumps({
        "metric": metric,
        "value": round(mfu, 2),
        "unit": "% MFU",
        "vs_baseline": round(mfu / vs, 3),
        "detail": detail,
    }))


def _tokens(vocab, batch, seq, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(batch, seq),
                                      dtype=np.int32)}


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

def bench_gpt2_ddp(args) -> None:
    """Config 1 (headline): GPT-2 125M, ZeRO-0 DDP."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.gpt2 import (GPT2LMLoss, count_params,
                                           flops_per_token, get_config)

    on_tpu = not args.smoke
    size = args.size or "gpt2-125m"
    if on_tpu:
        # unrolled blocks (scan_layers=False) let XLA pipeline across layer
        # boundaries — measured 49.5% vs 39.5% MFU on v5e for the 12-block
        # 125M model.  Validated for small models only: larger --size
        # presets keep the scan default (compile time and program size
        # grow with unrolled depth).
        cfg = get_config(size, n_positions=1024,
                         dtype=jnp.bfloat16, remat=False,
                         remat_policy="none",
                         scan_layers=size not in ("gpt2-125m", "gpt2-350m"),
                         use_flash_attention=True)
        # micro=12 measured best on v5e (52.98% vs 52.34 at micro=8,
        # 50.7 at 16 — the r5 sweep)
        micro, seq, steps = 12, 1024, args.steps
    else:
        cfg = get_config("gpt2-125m", n_positions=128, n_embd=256,
                         n_layer=4, n_head=4, dtype=jnp.float32,
                         remat=False)
        micro, seq, steps = 2, 128, 3

    topo = dist.initialize_mesh()
    dp = topo.zero_partition_count()
    ds = {
        "train_batch_size": micro * dp,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": on_tpu},
        "zero_optimization": {"stage": 0},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4,
                                                  "weight_decay": 0.01}},
        "steps_per_print": 1000000,
    }
    batch = _tokens(cfg.vocab_size, micro * dp, seq)
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2LMLoss(cfg), config=ds, topology=topo,
        example_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    _measure_train(
        engine, batch, steps=steps, micro_global=micro * dp, seq=seq,
        flops_per_tok=flops_per_token(cfg, seq),
        metric="gpt2_125m_bf16_train_mfu",
        extra_detail={"params": count_params(engine.state.params)})


def bench_gpt2_zero2_fused(args) -> None:
    """Config 2: GPT-2 1.3B, ZeRO-2, fused (Pallas) Adam, bf16."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.gpt2 import (GPT2LMLoss, count_params,
                                           flops_per_token, get_config)

    on_tpu = not args.smoke
    # 1.3B needs ~18GB of state (bf16 params + fp32 master + moments):
    # ZeRO-2 shards the optimizer over dp, so >=4 chips fit it; a single
    # chip benches the 760M shape (measured: 1.3B OOMs at 23.3G/15.75G)
    default_size = "gpt2-1.3b" if len(jax.devices()) >= 4 else "gpt2-760m"
    size = args.size or (default_size if on_tpu else "gpt2-125m")
    if on_tpu:
        cfg = get_config(size, n_positions=1024, dtype=jnp.bfloat16,
                         remat=True, remat_policy="dots_saveable",
                         scan_layers=True, use_flash_attention=True)
        # micro=6 measured best for the 760M single-chip shape on v5e
        # (53.2% vs 52.9 at micro=4; micro=8 OOMs its fp32-grads step);
        # other sizes (1.3b multi-chip default) keep the validated 4
        micro = 6 if size == "gpt2-760m" else 4
        seq, steps = 1024, args.steps
    else:
        cfg = get_config(size, n_positions=128, n_embd=256, n_layer=4,
                         n_head=4, dtype=jnp.float32, remat=False)
        micro, seq, steps = 2, 128, 3

    topo = dist.initialize_mesh()
    dp = topo.zero_partition_count()
    ds = {
        "train_batch_size": micro * dp,
        "train_micro_batch_size_per_gpu": micro,
        "bf16": {"enabled": on_tpu},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "steps_per_print": 1000000,
    }
    batch = _tokens(cfg.vocab_size, micro * dp, seq)
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2LMLoss(cfg), config=ds, topology=topo,
        example_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    _measure_train(
        engine, batch, steps=steps, micro_global=micro * dp, seq=seq,
        flops_per_tok=flops_per_token(cfg, seq),
        metric=f"{size.replace('-', '_').replace('.', '_')}"
               "_zero2_fused_adam_train_mfu",
        extra_detail={"params": count_params(engine.state.params),
                      "zero_stage": 2})


def bench_llama_zero3(args) -> None:
    """Config 3: Llama-2-7B-class, ZeRO-3 (sharded params + optimizer)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.llama import (LlamaLMLoss, count_params,
                                            flops_per_token, get_config)

    on_tpu = not args.smoke
    # the 7B target (BASELINE.md config 3) needs >=8 chips for its ~98GB
    # of bf16 params + fp32 master state; a single chip benches the
    # TinyLlama-1.1B shape instead
    default_size = "llama2-7b" if len(jax.devices()) >= 8 else "llama-1b"
    size = args.size or (default_size if on_tpu else "tinyllama")
    if on_tpu:
        # unrolled blocks let XLA pipeline across layer boundaries
        # (measured 55.9% vs 43.2% MFU for the 22-layer 1.1B shape on
        # v5e); gated to the 1B default — the 7B/32-layer preset keeps
        # scan for compile time and program size
        cfg = get_config(size, max_position_embeddings=2048,
                         dtype=jnp.bfloat16, remat=True,
                         remat_policy="dots_saveable",
                         scan_layers=size != "llama-1b",
                         use_flash_attention=True)
        micro, seq, steps = 1, 2048, args.steps
    else:
        cfg = get_config(size, dtype=jnp.float32, remat=False)
        micro, seq, steps = 2, 32, 3

    topo = dist.initialize_mesh()
    dp = topo.zero_partition_count()
    # single chip: ZeRO-3 shards nothing, so the fp32 master+moments of
    # the 1.1B model exceed HBM (measured 17.6G/15.75G) — run the
    # documented pure-bf16 mode there (moments in bf16, no fp32 master);
    # >=8 chips run the reference-style bf16-compute/fp32-state scheme
    pure_bf16 = on_tpu and dp < 8
    ds = {
        "train_batch_size": micro * dp,
        "train_micro_batch_size_per_gpu": micro,
        "bf16": {"enabled": on_tpu, "master_weights": not pure_bf16},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 10000},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "steps_per_print": 1000000,
    }
    batch = _tokens(cfg.vocab_size, micro * dp, seq)
    engine, *_ = deepspeed_tpu.initialize(
        model=LlamaLMLoss(cfg), config=ds, topology=topo,
        example_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    _measure_train(
        engine, batch, steps=steps, micro_global=micro * dp, seq=seq,
        flops_per_tok=flops_per_token(cfg, seq),
        metric=f"{size.replace('-', '_')}_zero3_train_mfu",
        extra_detail={"params": count_params(engine.state.params),
                      "zero_stage": 3, "pure_bf16": pure_bf16})


def bench_ulysses_longctx(args) -> None:
    """Config 4: long-context Ulysses SP (all-to-all attention heads)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.llama import (LlamaLMLoss, count_params,
                                            flops_per_token, get_config)

    on_tpu = not args.smoke
    n_dev = len(jax.devices())
    sp = n_dev  # whole mesh on the sequence axis
    if on_tpu:
        # single chip: a ~500M shape + full remat — the 1.1B model's
        # bf16 state + fp32 grads + fp32 CE temporaries exhaust HBM at
        # runtime even with full remat (measured)
        single = n_dev < 8
        size = args.size or ("llama2-7b" if not single else "llama-1b")
        seq = 32768 if not single else 8192
        shrink = dict(hidden_size=1536, intermediate_size=4096,
                      num_hidden_layers=16, num_attention_heads=12,
                      num_key_value_heads=4) \
            if single and args.size is None else {}
        cfg = get_config(size, max_position_embeddings=seq,
                         dtype=jnp.bfloat16, remat=True,
                         remat_policy="full" if single else "dots_saveable",
                         scan_layers=True,
                         use_flash_attention=True,
                         sequence_parallel="ulysses" if sp > 1 else "none",
                         **shrink)
        micro, steps = 1, max(args.steps // 2, 3)
    else:
        size = args.size or "tinyllama"
        seq = 64
        cfg = get_config(size, dtype=jnp.float32, remat=False,
                         max_position_embeddings=seq,
                         sequence_parallel="ulysses" if sp > 1 else "none")
        micro, steps = 1, 3

    topo = dist.initialize_mesh(sp=sp) if sp > 1 else dist.initialize_mesh()
    pure_bf16 = on_tpu and n_dev < 8    # see bench_llama_zero3
    ds = {
        "train_batch_size": micro,
        "train_micro_batch_size_per_gpu": micro,
        "bf16": {"enabled": on_tpu, "master_weights": not pure_bf16},
        "zero_optimization": {"stage": 1 if sp > 1 else 0},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "steps_per_print": 1000000,
    }
    batch = _tokens(cfg.vocab_size, micro, seq)
    engine, *_ = deepspeed_tpu.initialize(
        model=LlamaLMLoss(cfg), config=ds, topology=topo,
        example_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    _measure_train(
        engine, batch, steps=steps, micro_global=micro, seq=seq,
        flops_per_tok=flops_per_token(cfg, seq),
        metric=f"ulysses_seq{seq}_train_mfu",
        extra_detail={"params": count_params(engine.state.params),
                      "seq_parallel": sp, "seqlen": seq})


def bench_moe_ep(args) -> None:
    """Config 5: Mixtral-class MoE, expert parallel + ZeRO.  MFU counts
    ACTIVE params only (top-k routing), the MoE convention."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.mixtral import (MixtralLMLoss, count_params,
                                              flops_per_token, get_config)

    on_tpu = not args.smoke
    n_dev = len(jax.devices())
    if on_tpu:
        # sized to the mesh: ~1B total on >=4 chips, ~0.65B on one chip
        # (bf16 state + fp32 grads of the 1B shape exhaust one chip's HBM).
        # Single chip: micro=12 + unrolled blocks measured best (45.3% vs
        # 24.6% at micro=2/scan) — the MoE optimizer+grads touch ALL
        # expert params each step, so small micro-batches leave MFU
        # memory-bound on optimizer traffic, and the sorted dispatch keeps
        # the dispatch cost linear in tokens where the dense einsum is
        # quadratic.  Multi-chip (EP) keeps scan + the GSPMD einsum path.
        single = n_dev < 4
        # single chip: ~0.78B total sized to HBM (bf16 state + fp32
        # grads+moments), 128-dim heads — the r5 shape sweep measured
        # hidden 1024/Dh=128/8 layers at 57.4% vs 47.7% for the old
        # hidden 768/Dh=64/12 layers at identical micro/gas (Dh=64
        # starves the flash kernel's MXU tiles; wider hidden feeds the
        # expert GEMMs better at the same active-param count)
        dims = (dict(hidden_size=1024, intermediate_size=3584,
                     num_attention_heads=16, num_key_value_heads=8)
                if not single else
                dict(hidden_size=1024, intermediate_size=3584,
                     num_attention_heads=8, num_key_value_heads=4))
        n_layers = 12 if not single else 8
        import os as _os

        if _os.environ.get("DSTPU_MOE_DIMS"):
            assert args.size is None, (
                "DSTPU_MOE_DIMS and --size both set: the preset branch "
                "would silently discard the env dims — pick one")
            parts = _os.environ["DSTPU_MOE_DIMS"].split(",")
            assert len(parts) == 5, (
                "DSTPU_MOE_DIMS=hidden,intermediate,heads,kv_heads,layers")
            h, i_, a, kv, n_layers = map(int, parts)
            dims = dict(hidden_size=h, intermediate_size=i_,
                        num_attention_heads=a, num_key_value_heads=kv)
        cfg = get_config("tinymixtral", vocab_size=32000,
                         num_hidden_layers=n_layers,
                         num_local_experts=8, num_experts_per_tok=2,
                         max_position_embeddings=1024,
                         capacity_factor=1.0,   # reference train default
                         dtype=jnp.bfloat16, remat=True,
                         remat_policy="dots_saveable",
                         scan_layers=not single,
                         use_flash_attention=True, **dims) \
            if args.size is None else get_config(
                args.size, dtype=jnp.bfloat16, remat=True,
                scan_layers=True, use_flash_attention=True)
        # the tuned micro=12 was measured against the default dims only;
        # user --size presets keep the conservative micro
        micro = 4 if not single else (12 if args.size is None else 2)
        # single chip: gas=4 amortizes the optimizer's all-expert-params
        # HBM traffic (gas=1 measured ~1% lower; gas=8 adds nothing at
        # the r5 shape — fwd+bwd dominates once Dh=128 feeds the MXU)
        gas = 4 if single and args.size is None else 1
        micro = int(_os.environ.get("DSTPU_MOE_MICRO", micro))
        gas = int(_os.environ.get("DSTPU_MOE_GAS", gas))
        if _os.environ.get("DSTPU_MOE_REMAT"):
            cfg = dataclasses.replace(
                cfg, remat=_os.environ["DSTPU_MOE_REMAT"] != "none",
                remat_policy=_os.environ["DSTPU_MOE_REMAT"])
        seq, steps = 1024, max(args.steps // (2 if gas > 1 else 1), 3)
    else:
        cfg = get_config("tinymixtral", dtype=jnp.float32, remat=False)
        micro, seq, steps = 2, 32, 3
        gas = 1

    ep = min(n_dev, cfg.num_local_experts)
    topo = dist.initialize_mesh(dp=n_dev // ep, ep=ep) if ep > 1 \
        else dist.initialize_mesh()
    dp = topo.zero_partition_count()
    pure_bf16 = on_tpu and n_dev < 4    # see bench_llama_zero3
    ds = {
        "train_batch_size": micro * max(dp, 1) * gas,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": on_tpu, "master_weights": not pure_bf16},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "steps_per_print": 1000000,
    }
    batch = _tokens(cfg.vocab_size, micro * max(dp, 1) * gas, seq)
    engine, *_ = deepspeed_tpu.initialize(
        model=MixtralLMLoss(cfg), config=ds, topology=topo,
        example_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    _measure_train(
        engine, batch, steps=steps,
        micro_global=micro * max(dp, 1) * gas,
        seq=seq, flops_per_tok=flops_per_token(cfg, seq),
        metric="mixtral_ep_train_mfu",
        extra_detail={"params": count_params(engine.state.params),
                      "experts": cfg.num_local_experts,
                      "gas": gas,
                      "expert_parallel": ep})


def bench_inference(args) -> None:
    """KV-cache decode throughput (tokens/s/chip), greedy sampling."""
    import os

    import deepspeed_tpu

    on_tpu = not args.smoke
    from deepspeed_tpu.models.gpt2 import get_config

    if on_tpu:
        cfg = get_config(args.size or "gpt2-125m", n_positions=1024,
                         dtype=jnp.bfloat16, scan_layers=True, remat=False,
                         use_flash_attention=True, decode=True)
        # bs=64 measured 20.6k vs 19.3k tok/s at bs=32 on v5e (decode
        # tick cost is nearly flat in batch — concurrency is pure win)
        bsz, prompt, new = 64, 128, 128
    else:
        cfg = get_config("gpt2-125m", n_positions=128, n_embd=256,
                         n_layer=4, n_head=4, dtype=jnp.float32,
                         remat=False, decode=True)
        bsz, prompt, new = 2, 16, 8

    from deepspeed_tpu.models.gpt2 import GPT2Model
    engine = deepspeed_tpu.init_inference(
        model=GPT2Model(cfg), max_batch_size=bsz,
        max_out_tokens=prompt + new, rng=jax.random.PRNGKey(0))
    ids = _tokens(cfg.vocab_size, bsz, prompt)["input_ids"]

    jax.block_until_ready(engine.generate(ids, max_new_tokens=new))  # compile
    # device time via profiler, like the train configs; wall reported
    # alongside.
    # The timed loop uses the DEFERRED-HARVEST path (generate_async): call
    # k+1's host work overlaps call k's device work, and the harness's one
    # final sync harvests everything — the serving host-path pipeline's v1
    # treatment.
    engine.host_stats.reset()
    dev_dt, wall_dt = device_seconds_per_call(
        lambda: engine.generate_async(ids, max_new_tokens=new)
        .device_array(), n=3)
    serving_stages = engine.serving_stages()
    n_chips = len(jax.devices())
    if dev_dt is None:                  # --smoke on the CPU: no device time
        print(json.dumps({
            "metric": "gpt2_125m_decode_tokens_per_sec", "value": None,
            "unit": "not measured", "vs_baseline": 0.0,
            "detail": {"batch": bsz, "prompt": prompt, "new_tokens": new,
                       "wall_tokens_per_sec": round(bsz * new / wall_dt, 1),
                       "serving_stages": serving_stages,
                       "device": jax.devices()[0].device_kind}}))
        return
    tps = bsz * new / dev_dt
    # Two floors, both FIXED (VERDICT Weak #5: a floor re-based to the
    # current round's result makes vs_baseline 1.0 by construction and
    # measures nothing).  The ORIGINAL floor (19305.7, the r4 batch-32
    # result this config first regressed against) is the headline
    # vs_baseline; the r5 batch-64 re-measure (20552.8) is reported
    # alongside as vs_baseline_current for the like-for-like batch-64
    # comparison.  Neither may ever move with the round's own result.
    floor_orig = 19305.7                  # r4, batch 32
    floor_batch64 = 20552.8               # r5, batch 64
    print(json.dumps({
        "metric": "gpt2_125m_decode_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps / floor_orig, 3) if on_tpu else 0.0,
        "vs_baseline_orig": round(tps / floor_orig, 3) if on_tpu else 0.0,
        "vs_baseline_current": (round(tps / floor_batch64, 3)
                                if on_tpu else 0.0),
        "detail": {"batch": bsz, "prompt": prompt, "new_tokens": new,
                   "floor_orig_batch32": floor_orig,
                   "floor_current_batch64": floor_batch64,
                   "tokens_per_sec_per_chip": round(tps / n_chips, 1),
                   "wall_tokens_per_sec": round(bsz * new / wall_dt, 1),
                   "wall_vs_device_ratio": round(wall_dt / dev_dt, 2),
                   "device_call_ms": round(dev_dt * 1e3, 1),
                   "serving_stages": serving_stages,
                   # wall time NOT covered by device work — the async
                   # dispatch path never blocks inside the engine, so
                   # the wall/device gap is the authoritative view here
                   "host_bound_fraction": round(
                       max(0.0, 1.0 - dev_dt / wall_dt), 4),
                   "host_cores": os.cpu_count(),
                   "device": jax.devices()[0].device_kind},
    }))


def _ragged_run(model, params, *, max_seqs, max_len, chunk, prompt_lens,
                new, vocab, decode_block=8, topology=None, **eng_kw):
    """One ragged-serving run; returns (gen_tokens, dispatches, wall,
    dev_s, engine)."""
    from deepspeed_tpu.inference.v2.ragged_engine import (
        RaggedInferenceEngineV2)

    eng = RaggedInferenceEngineV2(model, params, max_seqs=max_seqs,
                                  max_seq_len=max_len, prefill_chunk=chunk,
                                  decode_block_size=decode_block,
                                  topology=topology, **eng_kw)
    rng = np.random.default_rng(0)
    for plen in prompt_lens:
        eng.put_request(rng.integers(0, vocab, int(plen), dtype=np.int32),
                        max_new_tokens=new)
    # warm up: compile the SplitFuse tick AND the decode-block program.
    # Long prompts span many SplitFuse ticks, so step until every live
    # slot is past prefill (the first decode block has dispatched), then
    # one more block — otherwise the decode program compiles inside the
    # timed region
    eng.step()
    while eng.has_work() and any(
            s is not None and s.prefill_done < s.ctx_len
            for s in eng.slots):
        eng.step()
    if eng.has_work():
        eng.step()
    eng.sync()          # fold pipelined in-flight warmup tokens first
    warmup_tokens = (sum(len(s.generated) for s in eng.slots
                         if s is not None) +
                     sum(len(r.generated) for r in eng.finished))
    eng.host_stats.reset()          # stage breakdown covers the timed loop

    # device time via profiler, beside the wall clock of the host-driven
    # scheduler
    trace_dir = os.path.join(tempfile.gettempdir(),
                             "dstpu_bench_ragged_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    dispatches = 0
    while eng.has_work():
        eng.step()
        dispatches += 1
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    # None on the CPU (--smoke): no device plane to read
    dev_s = (None if jax.devices()[0].platform == "cpu"
             else _device_seconds_from_trace(trace_dir))
    outs = eng.get_outputs()
    gen_tokens = sum(len(toks) - plen
                     for (_, toks), plen in zip(sorted(outs), prompt_lens))
    gen_tokens -= warmup_tokens           # untimed warmup steps' output
    return gen_tokens, dispatches, wall, dev_s, eng


def _validate_chrome_trace(path):
    """Minimal schema check of a tracer export; returns (ok, n_events).
    The full validator lives in scripts/trace_summarize.py — this keeps
    the bench row honest without importing from scripts/."""
    with open(path) as f:
        doc = json.load(f)
    evs = doc.get("traceEvents")
    ok = isinstance(evs, list) and len(evs) > 0 and all(
        isinstance(ev, dict) and isinstance(ev.get("name"), str)
        and ev.get("ph") in ("X", "i", "M")
        and (ev["ph"] == "M"
             or (isinstance(ev.get("ts"), (int, float))
                 and (ev["ph"] != "X"
                      or isinstance(ev.get("dur"), (int, float)))))
        for ev in evs)
    return ok, len(evs or [])


def bench_ragged(args) -> None:
    """Config ragged: continuous-batching effective throughput — mixed
    prompt lengths share one decode batch (FastGen-style serving, the
    reference's `effective throughput` metric family).  Decode runs in
    on-device multi-tick blocks (K tokens per host dispatch); a second
    run reports quantized serving (fp8 KV pool + int8 weights)."""
    from deepspeed_tpu.models.llama import LlamaModel, get_config

    on_tpu = not args.smoke
    if on_tpu:
        # 128-dim heads: the Pallas ragged paged kernel's supported head
        # dim (H*Dh = hidden, same param count as the 12x64 shape)
        cfg = get_config("llama-1b", hidden_size=768,
                         intermediate_size=2048, num_hidden_layers=12,
                         num_attention_heads=6, num_key_value_heads=2,
                         max_position_embeddings=512,
                         dtype=jnp.bfloat16, scan_layers=False,
                         remat=False, use_flash_attention=False,
                         decode=True)
        # 32 slots matches the static decode loop's batch size (config
        # "infer" bs=32) so the two throughput numbers compare directly;
        # measured 19.4k tok/s vs 9.4k at 8 slots (tick cost is nearly
        # flat in slot count, so concurrency is pure win)
        max_seqs = 32
        max_len, chunk, n_req, new = 512, 256, 2 * max_seqs, 64
    else:
        cfg = get_config("tinyllama", dtype=jnp.float32, remat=False,
                         max_position_embeddings=64, decode=True)
        max_seqs, max_len, chunk, n_req, new = 4, 64, 16, 6, 8

    model = LlamaModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0), np.ones((1, 2), np.int32),
        positions=np.zeros((1, 2), np.int32))["params"]
    rng = np.random.default_rng(0)
    prompt_lens = rng.integers(16 if on_tpu else 4,
                               (max_len - new) if on_tpu else 16,
                               size=n_req)
    run_kw = dict(max_seqs=max_seqs, max_len=max_len, chunk=chunk,
                  prompt_lens=prompt_lens, new=new, vocab=cfg.vocab_size)
    decode_block = 8
    import os

    # fresh metrics registry so the request/stage histograms cover
    # exactly the base run (the nearest-rank cross-check below compares
    # against this engine's tracker, not a process-lifetime blur)
    from deepspeed_tpu.telemetry.metrics import metrics as _registry
    _registry.reset()
    _registry.configure(enabled=True)
    gen_tokens, dispatches, wall, dev_s, base_eng = _ragged_run(
        model, {"params": params}, decode_block=decode_block, **run_kw)
    serving_stages = base_eng.serving_stages()
    # histogram-derived latency percentiles (linear interpolation inside
    # the crossing exponential bucket) next to the tracker's exact
    # nearest-rank values; `agrees` flags the one-bucket-width contract
    # serve_smoke --metrics hard-gates
    hist_latency = {}
    for _mname in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
        _fam = _registry.get(f"dstpu_request_{_mname}")
        if _fam is None:
            continue
        _child = _fam.labels(replica="")
        _entry = {"count": _child.merged()[2]}
        for _q in (50, 99):
            _hq = _child.quantile(_q)
            _nr = serving_stages["requests"].get(f"{_mname}_p{_q}")
            _entry[f"p{_q}"] = round(_hq, 3) if _hq is not None else None
            if _hq is not None and _nr is not None:
                _tol = max(_child.bucket_width_at(_nr),
                           _child.bucket_width_at(_hq)) + 1e-9
                _entry[f"p{_q}_agrees_nearest_rank"] = bool(
                    abs(_hq - _nr) <= _tol)
        hist_latency[_mname] = _entry
    n_chips = len(jax.devices())
    best_s = dev_s if dev_s else wall
    detail = {"requests": int(n_req), "max_seqs": max_seqs,
              "new_tokens_per_req": new, "dispatches": dispatches,
              "generated_tokens": int(gen_tokens),
              "tokens_per_dispatch": round(
                  gen_tokens / max(dispatches, 1), 1),
              "decode_block_size": decode_block,
              "device_s": round(dev_s, 2) if dev_s else None,
              "wall_s": round(wall, 2),
              "wall_tokens_per_sec": round(gen_tokens / wall, 1),
              "wall_vs_device_ratio": (round(wall / dev_s, 2)
                                       if dev_s else None),
              "serving_stages": serving_stages,
              # profiler-measured device seconds against wall when
              # available (authoritative); engine-observed fraction
              # (stage timers) otherwise
              "host_bound_fraction": (
                  round(max(0.0, 1.0 - dev_s / wall), 4) if dev_s
                  else serving_stages["host_bound_fraction"]),
              "host_cores": os.cpu_count(),
              "pipeline": {"enabled": base_eng.pipeline,
                           "async_depth": base_eng.async_depth,
                           "harvest_interval": base_eng.harvest_interval},
              "n_chips": n_chips,
              "device": jax.devices()[0].device_kind}

    # pipeline-off control: the unpipelined host path (fresh metadata
    # upload + one blocking harvest per dispatch) on the SAME workload —
    # the measured before/after for the serving host-path pipeline
    off_t, off_d, off_wall, off_dev, off_eng = _ragged_run(
        model, {"params": params}, decode_block=decode_block,
        pipeline=False, **run_kw)
    off_stages = off_eng.serving_stages()
    detail["pipeline_off"] = {
        "wall_tokens_per_sec": round(off_t / off_wall, 1),
        "tokens_per_sec": round(off_t / (off_dev if off_dev else off_wall),
                                1),
        "dispatches": off_d,
        "host_bound_fraction": off_stages["host_bound_fraction"],
        "serving_stages": off_stages}

    # per-request latency percentiles (the tracker is always on; the
    # base run above is the tracer-OFF control) + tracer overhead: the
    # SAME workload re-run with the unified tracer armed, its Chrome
    # trace exported and schema-checked.  The hard <=5% overhead gate
    # lives in scripts/serve_smoke.py --trace (min-of-3); the bench row
    # records the single-run delta alongside it.
    detail["request_latency"] = dict(serving_stages["requests"])
    detail["request_latency"]["histogram"] = hist_latency
    from deepspeed_tpu import telemetry
    # back-to-back off/on pairs (the base run above warms process-wide
    # caches the later runs inherit — comparing against it would
    # measure process order, not the tracer); min-of-3 each side since
    # smoke walls are a few ms and a single run is noise-dominated
    ctrl_wall = min(_ragged_run(
        model, {"params": params}, decode_block=decode_block,
        **run_kw)[2] for _ in range(5))
    telemetry.configure(enabled=True)
    tr_wall = float("inf")
    for _ in range(5):
        telemetry.trace.clear()
        w = _ragged_run(model, {"params": params},
                        decode_block=decode_block, **run_kw)[2]
        tr_wall = min(tr_wall, w)
    serve_trace_path = "/tmp/dstpu_bench_ragged_serve_trace.json"
    telemetry.trace.export(serve_trace_path)
    telemetry.configure(enabled=False)
    trace_ok, trace_events = _validate_chrome_trace(serve_trace_path)
    detail["tracer"] = {
        "overhead_pct": round((tr_wall - ctrl_wall) / ctrl_wall * 100, 2),
        "wall_s_tracer_on": round(tr_wall, 3),
        "wall_s_tracer_off": round(ctrl_wall, 3),
        "events": trace_events,
        "chrome_trace_valid": trace_ok,
        "export": serve_trace_path}

    # tiered paged-KV store: resident-session capacity beyond HBM.  A
    # pool sized for ~2 resident sessions serves 8 concurrently — the
    # spill tiers park cold sessions (digest-verified page payloads)
    # instead of destroying them, so restore is a page upload rather
    # than a re-prefill.  The tiering-off control runs the SAME
    # oversubscribed workload with destructive eviction; per-step wall
    # latencies give the p50/p99 decode-block cost both ways.
    from deepspeed_tpu.inference.v2.ragged_engine import (
        RaggedInferenceEngineV2)

    t_sessions, t_new, t_page, t_pool = 8, 24, 16, 7
    t_rng = np.random.default_rng(5)
    t_prompts = [t_rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)
                 for _ in range(t_sessions)]
    t_maxlen = min(64, cfg.max_position_embeddings)

    def _tier_serve(tiering):
        eng = RaggedInferenceEngineV2(
            model, {"params": params}, max_seqs=4, max_seq_len=t_maxlen,
            prefill_chunk=16, decode_block_size=4, page_size=t_page,
            num_pages=t_pool, kv_tiering=tiering)
        eng.generate_all(list(t_prompts), max_new_tokens=t_new)  # warmup
        for p in t_prompts:
            eng.put_request(p, max_new_tokens=t_new)
        lats = []
        while eng.has_work():
            t0 = time.perf_counter()
            eng.step()
            lats.append(time.perf_counter() - t0)
            eng.get_outputs()
        return np.asarray(lats), eng

    off_lat, t_off = _tier_serve(None)
    on_lat, t_on = _tier_serve({"host_pages": 64})
    from deepspeed_tpu.inference.paged import pages_for as _pages_for
    hbm_resident = max(1, (t_pool - 1) //
                       _pages_for(12 + t_new, t_page))
    tstats = t_on.tiering.stats()
    restore_ms = round(
        t_on.host_stats.seconds["restore"] * 1e3 /
        max(t_on.restores, 1), 3)
    detail["kv_tiering"] = {
        "sessions": t_sessions,
        "hbm_only_resident_sessions": hbm_resident,
        "resident_sessions": t_sessions - t_on.evictions,
        "resident_capacity_ratio": round(
            (t_sessions - t_on.evictions) / hbm_resident, 2),
        "spills": t_on.spills, "restores": t_on.restores,
        "evictions_tiering_off": t_off.evictions,
        "restore_stall_ms": restore_ms,
        "pages_verified": tstats["pages_verified"],
        "pages_restored": tstats["pages_restored"],
        "step_ms_p50": round(float(np.percentile(on_lat, 50)) * 1e3, 3),
        "step_ms_p99": round(float(np.percentile(on_lat, 99)) * 1e3, 3),
        "tiering_off_step_ms_p50": round(
            float(np.percentile(off_lat, 50)) * 1e3, 3),
        "tiering_off_step_ms_p99": round(
            float(np.percentile(off_lat, 99)) * 1e3, 3),
        "p99_vs_tiering_off": round(
            float(np.percentile(on_lat, 99)) /
            max(float(np.percentile(off_lat, 99)), 1e-9), 3),
        "stage_breakdown": {
            k: v for k, v in tstats.items() if k.endswith("_s")},
    }
    t_on.close()
    t_off.close()

    # million-token context (partial residency): the tiered KV store as
    # virtual memory for attention — the first sink_pages + most recent
    # window_pages stay HBM-resident while the parked middle streams
    # back through the chunked attention scan.  One sequence decodes on
    # a FIXED tiny HBM pool at growing context lengths; the row records
    # tokens/s vs context, the page-in (restore) stall p99 from the
    # dstpu_kv_pagein_stall_ms histogram, and the residency ratio
    # (HBM-resident pages / total KV pages) at each length.
    from deepspeed_tpu.models.llama import LlamaForCausalLM as _CausalLM

    lc_cfg = get_config(
        "tinyllama", vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=1024, dtype=jnp.float32,
        param_dtype=jnp.float32, scan_layers=False, remat=False,
        use_flash_attention=False)
    lc_params = jax.jit(_CausalLM(lc_cfg).init)(
        jax.random.PRNGKey(2), np.zeros((1, 8), np.int32))
    lc_tier = {"host_pages": 512, "long_context": True,
               "sink_pages": 1, "window_pages": 2, "chunk_pages": 2}
    lc_pool, lc_page, lc_new = 8, 16, 32
    lc_resident = (lc_tier["sink_pages"] + lc_tier["window_pages"]
                   + lc_tier["chunk_pages"] + 1)
    lc_rng = np.random.default_rng(7)
    lc_ctxs = (128, 256, 512)

    def _lc_serve(ctx, warm=False):
        prompt = lc_rng.integers(1, 64, size=(ctx - lc_new,),
                                 dtype=np.int32)
        eng = RaggedInferenceEngineV2(
            _CausalLM(lc_cfg), params=lc_params, max_seqs=2,
            max_seq_len=1024, prefill_chunk=16, page_size=lc_page,
            num_pages=lc_pool, decode_block_size=4,
            kv_reserve="on_demand", kv_tiering=dict(lc_tier))
        t0 = time.perf_counter()
        outs = eng.generate_all([prompt], max_new_tokens=lc_new)
        lc_wall = time.perf_counter() - t0
        assert all(len(t) == ctx for t in outs.values())
        st = eng.serving_stages()["kv_tiering"]
        eng.close()
        total_pages = _pages_for(ctx, lc_page)
        return {
            "tokens_per_sec": round(lc_new / max(lc_wall, 1e-9), 1),
            "wall_s": round(lc_wall, 3),
            "pageins": st["pageins"],
            "pagein_pages": st["pagein_pages"],
            "residency_ratio": round(
                min(lc_resident, total_pages) / total_pages, 3),
        }

    _lc_serve(lc_ctxs[0])           # warmup: compiles the scan programs
    lc_by_ctx = {str(c): _lc_serve(c) for c in lc_ctxs}
    _lc_hist = _registry.get("dstpu_kv_pagein_stall_ms")
    _lc_p99 = _lc_hist.quantile(99) if _lc_hist is not None else None
    detail["long_context"] = {
        "hbm_pool_pages": lc_pool,
        "page_size": lc_page,
        "hbm_resident_pages": lc_resident,
        "knobs": {k: lc_tier[k] for k in
                  ("sink_pages", "window_pages", "chunk_pages")},
        "by_context": lc_by_ctx,
        "restore_stall_ms_p50": (
            round(_lc_hist.quantile(50), 3) if _lc_hist else None),
        "restore_stall_ms_p99": (
            round(_lc_p99, 3) if _lc_p99 is not None else None),
        "max_over_hbm_ratio": round(
            _pages_for(lc_ctxs[-1], lc_page) / (lc_pool - 1), 2),
    }

    # cross-request prefix cache: sessions share a common system
    # prompt; the index attaches fully-matched resident KV pages
    # read-only (copy-on-write on divergence) so each admission
    # prefills only its private suffix.  Reuse ratio = shared fraction
    # of the prompt; matches are page-granular, so cached tokens are
    # the page-aligned floor of the shared span.  The cache-off
    # control re-runs the highest-reuse workload with the index
    # disabled — same engine shape, same prompts, full prefill.
    from deepspeed_tpu.telemetry.requests import RequestLatencyTracker

    p_sessions, p_page = 8, 16
    p_total, p_new = (256, 32) if on_tpu else (56, 8)
    p_pool = (4 * _pages_for(p_total + p_new, p_page)
              + p_total // p_page + 2)

    def _pfx_serve(reuse, prefix):
        prng = np.random.default_rng(11)
        n_shared = int(p_total * reuse)
        sys_prompt = prng.integers(0, cfg.vocab_size, n_shared,
                                   dtype=np.int32)
        prompts = [np.concatenate([sys_prompt, prng.integers(
            0, cfg.vocab_size, p_total - n_shared, dtype=np.int32)])
            for _ in range(p_sessions)]
        eng = RaggedInferenceEngineV2(
            model, {"params": params}, max_seqs=4,
            max_seq_len=p_total + p_new, prefill_chunk=16,
            decode_block_size=4, page_size=p_page, num_pages=p_pool,
            prefix_cache=prefix)
        # warmup compiles both program shapes and (cache on) registers
        # the shared prefix — the timed pass sees steady-state serving
        eng.generate_all(list(prompts), max_new_tokens=p_new)
        pc0 = dict(eng.serving_stages().get("prefix_cache") or {})
        eng.request_latency = RequestLatencyTracker()
        for p in prompts:
            eng.put_request(p, max_new_tokens=p_new)
        while eng.has_work():
            eng.step()
            eng.get_outputs()
        rl = eng.request_latency.summary()
        row = {"ttft_ms_p50": rl["ttft_ms_p50"],
               "ttft_ms_p99": rl["ttft_ms_p99"],
               "prefill_computed_tokens": rl["prefill_computed_tokens"],
               "prefill_cached_tokens": rl["prefill_cached_tokens"]}
        pc1 = eng.serving_stages().get("prefix_cache")
        if pc1:
            row.update(
                hit_rate=pc1["hit_rate"],
                hit_requests=(pc1["hit_requests"]
                              - int(pc0.get("hit_requests", 0))),
                cow_copies=(pc1["cow_copies"]
                            - int(pc0.get("cow_copies", 0))))
        eng.close()
        return row

    pfx = {"sessions": p_sessions, "prompt_tokens": p_total,
           "page_size": p_page,
           "reuse": {str(r): _pfx_serve(r, True)
                     for r in (0.0, 0.5, 0.9)},
           "cache_off_control": _pfx_serve(0.9, False)}
    pfx["ttft_p50_speedup_at_0.9"] = round(
        pfx["cache_off_control"]["ttft_ms_p50"] /
        max(pfx["reuse"]["0.9"]["ttft_ms_p50"], 1e-9), 2)
    detail["prefix_cache"] = pfx

    # speculative decoding: ngram (prompt-lookup, no second model), a
    # small random draft model (machinery cost at worst-case ~0
    # acceptance — random weights give the drafter nothing to learn
    # from), and self-draft (draft == target: the draft-quality upper
    # bound, isolating the verify/rollback machinery's ceiling).  The
    # spec-off control is the base run above.  `tokens_per_target_pass`
    # (= 1 + mean accepted length) is the per-weight-read amortization
    # speculation exists to raise.
    base_wall_tps = gen_tokens / wall
    import dataclasses as _dc
    draft_cfg = _dc.replace(
        cfg, num_hidden_layers=max(1, cfg.num_hidden_layers // 4),
        scan_layers=False)
    draft_params = jax.jit(LlamaModel(draft_cfg).init)(
        jax.random.PRNGKey(1), np.ones((1, 2), np.int32),
        positions=np.zeros((1, 2), np.int32))
    spec_runs = {
        "ngram": dict(speculation="ngram"),
        "draft": dict(speculation="draft",
                      draft_model=LlamaModel(draft_cfg),
                      draft_params=draft_params),
        "self_draft": dict(speculation="draft",
                           draft_model=LlamaModel(cfg),
                           draft_params={"params": params}),
    }
    detail["speculation"] = {
        "off_control": {"wall_tokens_per_sec": round(base_wall_tps, 1),
                        "tokens_per_dispatch": round(
                            gen_tokens / max(dispatches, 1), 1)}}
    from deepspeed_tpu.telemetry import profiler as _prof
    for sname, skw in spec_runs.items():
        st_, sd_, swall, sdev, seng = _ragged_run(
            model, {"params": params}, decode_block=decode_block,
            **run_kw, **skw)
        ss = seng.serving_stages()
        brk = dict(ss.get("speculation") or {})
        if brk:
            brk["tokens_per_target_pass"] = round(
                1.0 + brk["mean_accepted_len"], 3)
        # host-vs-device attribution (PR 6's recorded blind spot): the
        # jit closures are named, so the XPlane trace _ragged_run just
        # wrote splits device seconds per program — where does the
        # draft/verify tick actually spend its accelerator time?
        progs = _prof.device_seconds_by_program(
            "/tmp/dstpu_bench_ragged_trace")
        split = {k: (round(v, 4) if isinstance(v, float) else v)
                 for k, v in _prof.split_host_device(
                     swall, sdev if sdev else None).items()}
        split.update({
            "draft_device_s": round(_prof.device_seconds_matching(
                progs, "draft"), 4),
            "verify_device_s": round(_prof.device_seconds_matching(
                progs, "spec_verify"), 4),
            "decode_device_s": round(_prof.device_seconds_matching(
                progs, "ragged_decode_block"), 4),
            "prefill_device_s": round(_prof.device_seconds_matching(
                progs, "ragged_fused_step"), 4)})
        detail["speculation"][sname] = {
            "wall_tokens_per_sec": round(st_ / swall, 1),
            "tokens_per_sec": round(st_ / (sdev if sdev else swall), 1),
            "speedup_vs_off_wall": round((st_ / swall) /
                                         max(base_wall_tps, 1e-9), 3),
            "dispatches": sd_,
            "host_device_split": split,
            "breakdown": brk}

    # decode-block sweep: on-device sampling makes larger K nearly free
    # in device time and divides the host-dispatch count by K
    best_tps = gen_tokens / best_s
    if on_tpu:
        sweep = {}
        for K in (16, 32):
            kt, kd, kwall, kdev, _ = _ragged_run(
                model, {"params": params}, decode_block=K, **run_kw)
            ks = kdev if kdev else kwall
            sweep[K] = {"tokens_per_sec": round(kt / ks, 1),
                        "tokens_per_dispatch": round(kt / max(kd, 1), 1),
                        "wall_tokens_per_sec": round(kt / kwall, 1)}
            if kt / ks > best_tps:
                best_tps = kt / ks
                detail.update(
                    decode_block_size=K, dispatches=kd,
                    generated_tokens=int(kt),
                    tokens_per_dispatch=round(kt / max(kd, 1), 1),
                    device_s=round(kdev, 2) if kdev else None,
                    wall_s=round(kwall, 2),
                    wall_tokens_per_sec=round(kt / kwall, 1))
        detail["decode_block_sweep"] = sweep

    # quantized serving: fp8 KV pool + int8 weights (the memory-bound
    # decode regime where both matter)
    qt, _, qwall, qdev, qeng = _ragged_run(
        model, {"params": params}, kv_cache_dtype="fp8",
        quantize_weights="w8a8", **run_kw)
    detail["kv_fp8_w8a8_tokens_per_sec"] = round(
        qt / (qdev if qdev else qwall), 1)
    detail["kv_fp8_cache_bytes_ratio"] = round(
        qeng.cache_bytes() / max(base_eng.cache_bytes(), 1), 3)

    # quantized KV as a pool format (the kv_quant tentpole): capacity at
    # a FIXED HBM byte budget, spill traffic vs the full-width control,
    # and quality measured (not assumed) — per-tick logit error and
    # greedy divergence under teacher forcing, so the numbers isolate
    # KV quantization from trajectory divergence.
    kq_page = 16
    kq_pps = _pages_for(12 + t_new, kq_page)    # pages per session
    kq_budget = RaggedInferenceEngineV2(
        model, {"params": params}, max_seqs=4, max_seq_len=t_maxlen,
        prefill_chunk=16, decode_block_size=4, page_size=kq_page,
        num_pages=1 + 2 * kq_pps).cache_bytes()  # fp pool, ~2 sessions

    def _kq_capacity(fmt):
        """Serve the 8-session workload on a pool sized by the SAME
        byte budget; resident capacity and evictions tell the story."""
        eng = RaggedInferenceEngineV2(
            model, {"params": params}, max_seqs=4, max_seq_len=t_maxlen,
            prefill_chunk=16, decode_block_size=4, page_size=kq_page,
            kv_pool_bytes=kq_budget, kv_cache_dtype=fmt)
        eng.generate_all(list(t_prompts), max_new_tokens=t_new)
        return {"num_pages": eng.num_pages,
                "resident_sessions": max(1, (eng.num_pages - 1) //
                                         kq_pps),
                "evictions": eng.evictions,
                "pool_bytes": eng.cache_bytes()}

    def _kq_spill(fmt):
        """Tiering-on run: every spilled page carries the pool's
        storage format, so bytes_spilled measures the NVMe/host traffic
        the format saves."""
        eng = RaggedInferenceEngineV2(
            model, {"params": params}, max_seqs=4, max_seq_len=t_maxlen,
            prefill_chunk=16, decode_block_size=4, page_size=kq_page,
            num_pages=1 + 2 * kq_pps, kv_cache_dtype=fmt,
            kv_tiering={"host_pages": 64})
        eng.generate_all(list(t_prompts), max_new_tokens=t_new)
        st = eng.tiering.stats()
        out = {"spills": eng.spills,
               "bytes_spilled": st["bytes_spilled"],
               "pages_verified": st["pages_verified"]}
        eng.close()
        return out

    def _kq_quality(fmt, n_seqs=6, gen=40, prompt_len=8):
        """Teacher-forced lockstep decode: the quantized pool replays
        the fp pool's greedy token stream tick for tick, comparing
        logits at every position."""
        from deepspeed_tpu.inference.common import unroll_scan_params
        qrng = np.random.default_rng(17)
        pp_q = t_maxlen // kq_page
        kq_unroll = bool(getattr(cfg, "scan_layers", False))

        def _mk(pool_fmt):
            pcfg = _dc.replace(
                cfg, decode=True, ragged_decode=False, paged_decode=True,
                max_cache_len=t_maxlen, scan_layers=False,
                kv_page_size=kq_page, kv_num_pages=pp_q + 1,
                tensor_parallel=False, kv_cache_dtype=pool_fmt)
            pmodel = type(model)(pcfg)

            @jax.jit
            def tick(cache, tok, pos):
                # one sequence on contiguous pages 1..pp: flat KV row
                # for position p is page_size + p
                meta = {"kv_lens": (pos + 1)[None].astype(jnp.int32),
                        "page_indices": jnp.arange(
                            1, pp_q + 1, dtype=jnp.int32)[None],
                        "cu_q_lens": jnp.asarray([0, 1], jnp.int32),
                        "num_seqs": jnp.asarray([1], jnp.int32),
                        "new_kv_dest": (kq_page + pos)[None].astype(
                            jnp.int32)}
                p = (unroll_scan_params(params) if kq_unroll
                     else params)
                out, mut = pmodel.apply(
                    {"params": p, "cache": cache}, tok[None, None],
                    positions=pos[None, None], ragged_meta=meta,
                    mutable=["cache"])
                logits = out[0] if isinstance(out, tuple) else out
                return logits[0, 0], mut["cache"]

            meta0 = {"kv_lens": np.zeros((1,), np.int32),
                     "page_indices": np.full((1, pp_q), -1, np.int32),
                     "cu_q_lens": np.zeros((2,), np.int32),
                     "num_seqs": np.zeros((1,), np.int32),
                     "new_kv_dest": np.zeros((1,), np.int32)}
            shapes = jax.eval_shape(lambda: pmodel.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                positions=jnp.zeros((1, 1), jnp.int32),
                ragged_meta=meta0))
            zero = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])
            return tick, zero

        f_tick, f_zero = _mk("none")
        q_tick, q_zero = _mk(fmt)
        max_err, errs, diverged, compared = 0.0, [], 0, 0
        for _ in range(n_seqs):
            prompt = qrng.integers(0, cfg.vocab_size, prompt_len,
                                   dtype=np.int32)
            f_cache, q_cache = f_zero, q_zero
            tok = None
            for p in range(prompt_len + gen - 1):
                t_in = (jnp.asarray(prompt[p], jnp.int32)
                        if p < prompt_len else tok)
                pos = jnp.asarray(p, jnp.int32)
                fl, f_cache = f_tick(f_cache, t_in, pos)
                ql, q_cache = q_tick(q_cache, t_in, pos)
                err = float(jnp.max(jnp.abs(fl - ql)))
                errs.append(err)
                max_err = max(max_err, err)
                if p >= prompt_len - 1:
                    compared += 1
                    diverged += int(int(jnp.argmax(fl)) !=
                                    int(jnp.argmax(ql)))
                    tok = jnp.argmax(fl).astype(jnp.int32)
        return {"logit_max_abs_err": round(max_err, 5),
                "logit_mean_abs_err": round(
                    float(np.mean(errs)), 5),
                "greedy_tokens_compared": compared,
                "greedy_divergence_rate": round(
                    diverged / max(compared, 1), 4)}

    full_cap = _kq_capacity("none")
    full_spill = _kq_spill("none")
    kq = {"hbm_byte_budget": kq_budget, "page_size": kq_page,
          "sessions": t_sessions, "full_width": {
              **full_cap, "spill": full_spill}}
    for fmt in ("int8", "fp8"):
        cap = _kq_capacity(fmt)
        spill = _kq_spill(fmt)
        kq[fmt] = {
            **cap, "spill": spill,
            "resident_sessions_vs_full_width": round(
                cap["resident_sessions"] /
                max(full_cap["resident_sessions"], 1), 2),
            "spill_bytes_vs_full_width": round(
                spill["bytes_spilled"] /
                max(full_spill["bytes_spilled"], 1), 3),
            "quality": _kq_quality(fmt)}
    from deepspeed_tpu.inference.paged import kv_dequant_path
    kq["dequant_path"] = kv_dequant_path(
        cfg.hidden_size // cfg.num_attention_heads)
    detail["kv_quant"] = kq

    if on_tpu:
        # weight-BOUND quantized serving: this config's 0.38 GB model is
        # per-tick-overhead-bound (quantization cannot speed it up — the
        # w8a8 win above is vs the old dequant path), so demonstrate the
        # native-int8-dot capability where decode is actually limited by
        # weight bandwidth: a 1B-class model, same slot count.  FastGen's
        # quantized-serving claims are made in this regime.
        cfg1b = get_config("llama-1b", hidden_size=2048,
                           intermediate_size=5632, num_hidden_layers=22,
                           num_attention_heads=16, num_key_value_heads=4,
                           max_position_embeddings=512,
                           dtype=jnp.bfloat16, scan_layers=False,
                           remat=False, use_flash_attention=False,
                           decode=True)
        model1b = LlamaModel(cfg1b)
        params1b = jax.jit(model1b.init)(
            jax.random.PRNGKey(0), np.ones((1, 2), np.int32),
            positions=np.zeros((1, 2), np.int32))["params"]
        kw1b = dict(run_kw, prompt_lens=prompt_lens[:max_seqs],
                    new=32)
        bt, _, bwall, bdev, _ = _ragged_run(
            model1b, {"params": params1b}, decode_block=16, **kw1b)
        qt1, _, qwall1, qdev1, _ = _ragged_run(
            model1b, {"params": params1b}, decode_block=16,
            quantize_weights="w8a8", **kw1b)
        b_tps = bt / (bdev if bdev else bwall)
        q_tps = qt1 / (qdev1 if qdev1 else qwall1)
        detail["weight_bound_1b"] = {
            "bf16_tokens_per_sec": round(b_tps, 1),
            "w8a8_tokens_per_sec": round(q_tps, 1),
            "speedup": round(q_tps / max(b_tps, 1e-9), 2)}

    # tp=1 vs tp=2 serving (multi-device CPU mesh: the VERDICT-requested
    # comparison; single-chip TPU hosts have no second chip)
    if len(jax.devices()) >= 2 and not on_tpu:
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.comm import comm as _comm

        _comm._state.topology = None
        topo2 = dist.initialize_mesh(dp=1, tp=2,
                                     devices=jax.devices()[:2])
        t2, _, w2, dv2, _ = _ragged_run(model, {"params": params},
                                        topology=topo2, **run_kw)
        detail["tp2_tokens_per_sec"] = round(t2 / (dv2 if dv2 else w2), 1)
        detail["tp1_tokens_per_sec"] = round(gen_tokens / best_s, 1)

    # -- scale-out serving: replicated engines behind the SLO router ----
    # Open-loop request streams against a 2-replica ReplicaSet vs the
    # single-replica control, then a 2x-overload Poisson leg with
    # admission control on (queue caps + burn-rate shedding) and the
    # tracer enabled so residual wall attributes to the named router
    # spans.  On a 1-core host nothing overlaps — the row records that
    # caveat and asserts request conservation + bit-identical greedy
    # outputs instead of a throughput floor.
    from deepspeed_tpu import telemetry as _telemetry
    from deepspeed_tpu.serving import (ReplicaSet, Router,
                                       RouterRejection)
    from deepspeed_tpu.telemetry import SLOSet
    from deepspeed_tpu.telemetry.requests import percentile as _pctl

    so_n = 2 * max_seqs if on_tpu else 10
    so_rng = np.random.default_rng(11)
    so_prompts = [so_rng.integers(0, cfg.vocab_size, int(l),
                                  dtype=np.int32)
                  for l in so_rng.integers(4, max(chunk - 1, 5),
                                           size=so_n)]

    def so_engine(i=0):
        from deepspeed_tpu.inference.v2.ragged_engine import (
            RaggedInferenceEngineV2)
        return RaggedInferenceEngineV2(
            model, {"params": params}, max_seqs=max_seqs,
            max_seq_len=max_len, prefill_chunk=chunk,
            decode_block_size=decode_block)

    def so_run(n_rep, arrivals=None, slo=None, queue_cap=None,
               burn_shed=2.0, burn_defer=1.0):
        """One routed open-loop run.  ``arrivals`` (seconds from start)
        schedules submissions without waiting on responses; None means
        everything arrives at t0 (closed-burst)."""
        rs = ReplicaSet(so_engine, n_rep)
        router = Router(rs, policy="least_tokens", slo=slo,
                        queue_cap=queue_cap, burn_shed=burn_shed,
                        burn_defer=burn_defer)
        outs, rid2i, sub_t, e2e_ms, shed = {}, {}, {}, [], 0
        t0 = time.perf_counter()
        i = 0
        while i < len(so_prompts) or router.outstanding:
            now = time.perf_counter() - t0
            progressed = False
            while i < len(so_prompts) and (arrivals is None or
                                           arrivals[i] <= now):
                try:
                    rid = router.submit(so_prompts[i],
                                        max_new_tokens=new)
                    rid2i[rid] = i
                    sub_t[rid] = time.perf_counter()
                except RouterRejection:
                    shed += 1
                i += 1
                progressed = True
            if router.outstanding:
                router.pump()
                router.join()
                progressed = True
            for rid, toks in router.get_outputs().items():
                e2e_ms.append(
                    (time.perf_counter() - sub_t[rid]) * 1e3)
                outs[rid] = toks
            if not progressed:
                time.sleep(0.0005)     # idle until the next arrival
        wall = time.perf_counter() - t0
        stats = router.stats()
        rs.close()
        return outs, rid2i, sorted(e2e_ms), wall, shed, stats

    # capacity legs accept the whole burst (cap >= the workload);
    # admission only gates the overload leg below
    ctrl_outs, ctrl_map, ctrl_e2e, ctrl_so_wall, _, _ = so_run(
        1, queue_cap=so_n)
    cap_rps = len(ctrl_outs) / ctrl_so_wall
    so_outs, so_map, so_e2e, so_wall, _, so_stats = so_run(
        2, queue_cap=so_n)
    rps2 = len(so_outs) / so_wall

    # request conservation + greedy bit-parity vs the single-replica
    # control (greedy outputs are a pure function of prompt + params,
    # so routing must not change a single token)
    so_ref = {ctrl_map[rid]: toks for rid, toks in ctrl_outs.items()}
    assert sorted(so_map[r] for r in so_outs) == sorted(so_ref), (
        "scale-out run lost requests: "
        f"{len(so_outs)}/{len(so_ref)} finished")
    assert all(np.array_equal(so_outs[rid], so_ref[so_map[rid]])
               for rid in so_outs), (
        "routed greedy outputs diverged from single-replica serving")

    # 2x-overload Poisson leg: arrivals at twice the measured capacity,
    # tight queue caps + burn-rate shedding, tracer on so the wall not
    # covered by engine stages lands in the router.pump span
    so_arrivals = np.cumsum(so_rng.exponential(
        1.0 / (2.0 * cap_rps), size=so_n))
    slo_thr = max(3.0 * (_pctl(so_e2e, 99) or 0.0), 50.0)
    so_slo = SLOSet([f"router_e2e_ms_p99 <= {slo_thr:.1f}"])
    _telemetry.trace.configure(enabled=True)
    _telemetry.trace.clear()
    (ov_outs, ov_map, ov_e2e, ov_wall, ov_shed,
     ov_stats) = so_run(2, arrivals=so_arrivals, slo=so_slo,
                        queue_cap=max_seqs, burn_shed=1.0,
                        burn_defer=float("inf"))
    router_span_s = sum(
        ev.get("dur", 0.0) for ev in _telemetry.trace.snapshot()
        if ev.get("ph") == "X" and ev.get("name") in
        ("router.pump", "router.dispatch")) / 1e6
    _telemetry.trace.configure(enabled=False)
    _telemetry.trace.clear()
    goodput_rps = len(ov_outs) / ov_wall

    multi_device = len(jax.devices()) >= 2
    if multi_device:
        # real overlap available: the replication floor and the
        # admission guarantees are load-bearing
        assert rps2 / max(cap_rps, 1e-9) >= 1.8, (
            f"2-replica requests/s {rps2:.2f} < 1.8x single-replica "
            f"control {cap_rps:.2f}")
        assert (_pctl(ov_e2e, 99) or 0.0) <= slo_thr, (
            "overload leg: accepted-request p99 "
            f"{_pctl(ov_e2e, 99):.1f}ms blew the {slo_thr:.1f}ms SLO "
            "despite admission control")
        assert goodput_rps >= 0.8 * cap_rps, (
            f"overload goodput {goodput_rps:.2f} req/s < 0.8x capacity "
            f"{cap_rps:.2f}")
    detail["scale_out"] = {
        "replicas": 2,
        "policy": "least_tokens",
        "requests": so_n,
        "single_replica_rps": round(cap_rps, 3),
        "two_replica_rps": round(rps2, 3),
        "speedup": round(rps2 / max(cap_rps, 1e-9), 3),
        "e2e_ms_p50": round(_pctl(so_e2e, 50) or 0.0, 1),
        "e2e_ms_p99": round(_pctl(so_e2e, 99) or 0.0, 1),
        "bit_identical_to_single_engine": True,   # asserted above
        "overload": {
            "arrival_rps": round(2.0 * cap_rps, 3),
            "accepted": len(ov_map),
            "shed": ov_shed,
            "finished": len(ov_outs),
            "goodput_rps": round(goodput_rps, 3),
            "goodput_vs_capacity": round(
                goodput_rps / max(cap_rps, 1e-9), 3),
            "accepted_e2e_ms_p99": round(_pctl(ov_e2e, 99) or 0.0, 1),
            "slo_threshold_ms": round(slo_thr, 1),
            "router_span_s": round(router_span_s, 4),
            "rejected_queue_full": ov_stats["rejected_queue_full"],
            "rejected_shed": ov_stats["rejected_shed"],
        },
    }
    if not multi_device:
        # this container exposes ONE host device: replica threads
        # interleave on it, so requests/s cannot scale — the row
        # records the measured numbers with the caveat, and the
        # conservation + bit-parity asserts above carry the gate
        detail["scale_out"]["caveat"] = (
            "single-device host: replica threads share one device, "
            "nothing overlaps; speedup is not meaningful here "
            "(conservation + greedy bit-parity asserted instead)")

    # -- disaggregated serving: prefill split from decode ---------------
    # Bimodal mix (1-in-4 long prefills among short chats) against a
    # 1-prefill + 1-decode role split vs one fused replica.  The split
    # keeps long prefills off the decode replica's step loop, so decode
    # TPOT stops inheriting prefill-induced stalls; finished KV crosses
    # replicas in spill format and every restored page is digest-
    # verified on the receiver.  Conservation, greedy bit-parity and
    # the digest accounting are hard gates on every host; the both-
    # beat-fused tail floors only bind where replicas own real devices.
    dgb_n = so_n
    dgb_rng = np.random.default_rng(13)
    dgb_long_hi = max(chunk + 1, min(2 * chunk, max_len - new - 1))
    dgb_lens = [int(dgb_rng.integers(chunk, dgb_long_hi + 1))
                if i % 4 == 0
                else int(dgb_rng.integers(4, max(chunk - 1, 5)))
                for i in range(dgb_n)]
    dgb_prompts = [dgb_rng.integers(0, cfg.vocab_size, l,
                                    dtype=np.int32) for l in dgb_lens]
    dgb_long = sum(1 for l in dgb_lens if l >= chunk)

    def dgb_engine(i=0):
        # page_size pinned to one prefill chunk: the router's long-
        # prefill threshold (handoff_min_prompt) seeds from the
        # replica page size, and the bimodal mix above straddles chunk
        from deepspeed_tpu.inference.v2.ragged_engine import (
            RaggedInferenceEngineV2)
        return RaggedInferenceEngineV2(
            model, {"params": params}, max_seqs=max_seqs,
            max_seq_len=max_len, prefill_chunk=chunk,
            page_size=chunk,
            num_pages=max_seqs * (max_len // chunk + 1) + 4,
            decode_block_size=decode_block,
            kv_tiering={"host_pages": 16 * max_seqs})

    def dgb_run(n_rep, roles=None):
        rs = ReplicaSet(dgb_engine, n_rep)
        router = Router(rs, policy="least_tokens", queue_cap=dgb_n)
        if roles:
            router.set_roles(roles)
        t0 = time.perf_counter()
        rid2i = {router.submit(p, max_new_tokens=new): i
                 for i, p in enumerate(dgb_prompts)}
        outs = router.drain()
        wall = time.perf_counter() - t0
        res = {
            "outs": {rid2i[r]: t for r, t in outs.items()},
            "wall": wall,
            "stats": router.stats(),
            "recs": [h.engine.request_latency.completed()
                     for h in rs.handles],
            "summ": [h.engine.request_latency.summary()
                     for h in rs.handles],
            "tiering": [dict(h.engine.tiering.counters)
                        for h in rs.handles],
        }
        for h in rs.handles:
            h.engine.audit_kv_sharing()
        rs.close()
        return res

    dgb_fused = dgb_run(1)
    _telemetry.trace.configure(enabled=True)
    _telemetry.trace.clear()
    dgb_split = dgb_run(2, roles={"r0": "prefill", "r1": "decode"})
    dgb_bytes = sum(
        int(ev.get("args", {}).get("bytes", 0))
        for ev in _telemetry.trace.snapshot()
        if ev.get("ph") == "X" and ev.get("name") == "handoff_transfer")
    _telemetry.trace.configure(enabled=False)
    _telemetry.trace.clear()

    assert sorted(dgb_split["outs"]) == sorted(dgb_fused["outs"]), (
        "disagg run lost requests: "
        f"{len(dgb_split['outs'])}/{len(dgb_fused['outs'])} finished")
    assert all(np.array_equal(dgb_split["outs"][i],
                              dgb_fused["outs"][i])
               for i in dgb_fused["outs"]), (
        "disaggregated greedy outputs diverged from fused serving")
    dgb_st = dgb_split["stats"]
    assert (dgb_st["handoff_kv"] == dgb_long
            and dgb_st["handoff_reprefill"] == 0), (
        f"vacuous split: expected {dgb_long} KV handoffs, got "
        f"kv={dgb_st['handoff_kv']} "
        f"reprefill={dgb_st['handoff_reprefill']}")
    dgb_tc = dgb_split["tiering"][1]
    assert (dgb_tc["imports"] == dgb_st["handoff_kv"]
            and dgb_tc["pages_verified"] == dgb_tc["pages_restored"] > 0
            and dgb_tc["quarantined"] == 0), (
        "handoff digest accounting broke: "
        f"imports={dgb_tc['imports']} "
        f"verified={dgb_tc['pages_verified']} "
        f"restored={dgb_tc['pages_restored']} "
        f"quarantined={dgb_tc['quarantined']}")

    # client-meaningful tails: TTFT from whichever replica produced the
    # first token (donor for longs — handoffs==0 on donor records);
    # TPOT from wherever decode steps ran (receiver continuations plus
    # short chats, never donor records, which end at one token)
    dgb_ttft = sorted(r["ttft_ms"] for rr in dgb_split["recs"]
                      for r in rr
                      if r["ttft_ms"] is not None and r["handoffs"] == 0)
    dgb_tpot = sorted(r["tpot_ms"] for rr in dgb_split["recs"]
                      for r in rr if r["tpot_ms"] is not None)
    dgb_f = dgb_fused["summ"][0]
    detail["disagg"] = {
        "replicas": "1 prefill + 1 decode",
        "requests": dgb_n,
        "long_prefills": dgb_long,
        "handoff_kv": dgb_st["handoff_kv"],
        "handoff_reprefill": dgb_st["handoff_reprefill"],
        "handoff_bytes": dgb_bytes,
        "pages_digest_verified": dgb_tc["pages_verified"],
        "fused_wall_s": round(dgb_fused["wall"], 3),
        "split_wall_s": round(dgb_split["wall"], 3),
        "fused_ttft_ms_p50": dgb_f["ttft_ms_p50"],
        "fused_ttft_ms_p99": dgb_f["ttft_ms_p99"],
        "fused_tpot_ms_p99": dgb_f["tpot_ms_p99"],
        "split_ttft_ms_p50": round(_pctl(dgb_ttft, 50) or 0.0, 2),
        "split_ttft_ms_p99": round(_pctl(dgb_ttft, 99) or 0.0, 2),
        "split_tpot_ms_p99": round(_pctl(dgb_tpot, 99) or 0.0, 2),
        "handoff_stall_ms_p50":
            dgb_split["summ"][1]["handoff_stall_ms_p50"],
        "handoff_stall_ms_p99":
            dgb_split["summ"][1]["handoff_stall_ms_p99"],
        "bit_identical_to_fused": True,       # asserted above
    }
    if multi_device:
        # real devices behind each role: the split must beat fused on
        # BOTH tails — TTFT (prefills no longer queue behind decode
        # blocks) and TPOT (decode steps no longer stall on prefills)
        assert (detail["disagg"]["split_ttft_ms_p99"]
                < dgb_f["ttft_ms_p99"]), (
            "disagg TTFT p99 "
            f"{detail['disagg']['split_ttft_ms_p99']}ms did not beat "
            f"fused {dgb_f['ttft_ms_p99']}ms")
        assert (detail["disagg"]["split_tpot_ms_p99"]
                < dgb_f["tpot_ms_p99"]), (
            "disagg TPOT p99 "
            f"{detail['disagg']['split_tpot_ms_p99']}ms did not beat "
            f"fused {dgb_f['tpot_ms_p99']}ms")
    else:
        detail["disagg"]["caveat"] = (
            "single-device host: both roles share one device, prefill "
            "and decode cannot overlap; tail floors not enforced "
            "(conservation, bit-parity and digest accounting asserted "
            "instead)")

    # -- network front door: HTTP/SSE serving at the socket -------------
    # The same 2-replica router behind the asyncio front door, measured
    # where the client sits: socket-level TTFT/TPOT from the load
    # generator at 8/64/200 simultaneous streams, against an in-process
    # control (submit straight into the Router, first-token time from
    # the event stream).  The delta IS the front door's overhead: HTTP
    # parse, SSE framing, the asyncio<->pump-thread hop, and kernel
    # socket buffers.
    from deepspeed_tpu.serving import FrontDoorServer
    from deepspeed_tpu.serving.client import LoadGenerator

    fd_new = 8
    fd_rng = np.random.default_rng(13)

    def fd_prompt_set(n):
        return [fd_rng.integers(0, cfg.vocab_size, int(l),
                                dtype=np.int32)
                for l in fd_rng.integers(4, max(chunk - 1, 5), size=n)]

    def fd_inproc(prompt_list):
        """In-process control: same workload straight into the Router,
        TTFT from the router's harvest-granularity event stream."""
        rs = ReplicaSet(so_engine, 2)
        router = Router(rs, policy="least_tokens",
                        queue_cap=len(prompt_list))
        router.collect_events = True
        sub, first = {}, {}
        t0 = time.perf_counter()
        for q in prompt_list:
            rid = router.submit(q, max_new_tokens=fd_new)
            sub[rid] = time.perf_counter()
        outs = {}
        while router.outstanding:
            router.pump()
            router.join()
            for name, rid, payload in router.poll_events():
                if name == "tokens" and rid not in first:
                    first[rid] = time.perf_counter()
            outs.update(router.get_outputs())
        wall = time.perf_counter() - t0
        rs.close()
        assert len(outs) == len(prompt_list), (
            f"in-process control lost requests: {len(outs)}/"
            f"{len(prompt_list)}")
        ttfts = sorted((first[r] - sub[r]) * 1e3 for r in first)
        return {"requests_per_s": round(len(outs) / wall, 3),
                "ttft_ms_p50": round(_pctl(ttfts, 50) or 0.0, 1),
                "ttft_ms_p99": round(_pctl(ttfts, 99) or 0.0, 1)}

    detail["frontdoor"] = {"replicas": 2, "max_new_tokens": fd_new,
                           "streams": {}}
    for fd_streams in (8, 64, 200):
        fd_prompts = fd_prompt_set(fd_streams)
        rs = ReplicaSet(so_engine, 2)
        router = Router(rs, policy="least_tokens",
                        queue_cap=fd_streams)
        srv = FrontDoorServer(router, port=0).start()
        gen = LoadGenerator(
            srv.host, srv.port,
            lambda i, P=fd_prompts: {"prompt": P[i].tolist(),
                                     "max_new_tokens": fd_new},
            requests=fd_streams, concurrency=fd_streams)
        fd_sum = gen.run()
        srv.close()
        rs.close()
        # conservation at the socket: every stream completes and every
        # generated token arrives exactly once over SSE
        assert fd_sum["completed"] == fd_streams, (
            f"front door lost streams at {fd_streams}-way: "
            f"{fd_sum['completed']}/{fd_streams} ({fd_sum['errors']})")
        assert fd_sum["tokens_streamed"] == fd_streams * fd_new, (
            f"front door dropped tokens at {fd_streams}-way: "
            f"{fd_sum['tokens_streamed']}/{fd_streams * fd_new}")
        detail["frontdoor"]["streams"][str(fd_streams)] = {
            "requests_per_s": fd_sum["requests_per_s"],
            "ttft_ms_p50": fd_sum["ttft_ms_p50"],
            "ttft_ms_p99": fd_sum["ttft_ms_p99"],
            "tpot_ms_p50": fd_sum["tpot_ms_p50"],
            "tpot_ms_p99": fd_sum["tpot_ms_p99"],
            "tokens_streamed": fd_sum["tokens_streamed"],
        }
    detail["frontdoor"]["inprocess_control_8"] = fd_inproc(
        fd_prompt_set(8))
    if not multi_device:
        detail["frontdoor"]["caveat"] = (
            "single-device host: replica threads and the asyncio loop "
            "share one core, so requests/s does not scale with "
            "streams; the row records socket-level latency overhead "
            "vs the in-process control (conservation asserted at "
            "every stream count)")

    # closed-loop autotune: the online controller walks a deliberately
    # mis-tuned engine (harvest=1, depth=1) back toward the hand-tuned
    # base config above; the row records all three throughputs plus the
    # decision trail (scripts/serve_smoke.py --autotune hard-gates
    # convergence/guard/attribution — this is the measured record)
    mis_kw = dict(harvest_interval=1, async_depth=1)
    # the smoke workload is only ~9 host steps — stretch generation so
    # the controller sees enough ticks to run whole probe trials
    at_kw = run_kw if on_tpu else {**run_kw, "new": 40}
    # decode_block=4 keeps a dispatch in (nearly) every host step, so
    # the per-window blocking_gets_per_dispatch signal stays dense
    at_block = decode_block if on_tpu else 4
    at_tok, _, at_wall, _, _ = _ragged_run(
        model, {"params": params}, decode_block=at_block,
        **mis_kw, **at_kw)
    # hand-tuned control on the SAME workload (engine defaults), so the
    # three throughputs in the row are directly comparable
    hd_tok, _, hd_wall, _, _ = _ragged_run(
        model, {"params": params}, decode_block=at_block, **at_kw)
    ctl_cfg = {"interval": 4, "settle": 1, "cooldown": 0,
               "objective": "-blocking_gets_per_dispatch"}
    cv_tok, _, cv_wall, _, cv_eng = _ragged_run(
        model, {"params": params}, decode_block=at_block,
        control=ctl_cfg, **mis_kw, **at_kw)
    ctl = cv_eng._controller
    assert ctl.counts["guard_violations"] == 0, (
        f"oscillation guard violated: {ctl.counts}")
    knob_end = ctl.knobs.snapshot()
    detail["autotune"] = {
        "mis_tuned": dict(mis_kw),
        "mis_tuned_tokens_per_sec": round(at_tok / max(at_wall, 1e-9), 1),
        "hand_tuned_tokens_per_sec": round(hd_tok / max(hd_wall, 1e-9), 1),
        "converged_tokens_per_sec": round(cv_tok / max(cv_wall, 1e-9), 1),
        "decisions": ctl.counts["decisions"],
        "accepts": ctl.counts["accepts"],
        "reverts": ctl.counts["reverts"],
        "freezes": ctl.counts["freezes"],
        "guard_violations": ctl.counts["guard_violations"],
        "knob_end": {k: knob_end[k] for k in sorted(knob_end)},
        "knob_trajectory": [
            {"tick": d["tick"], "knob": d["knob"], "new": d["new"]}
            for d in ctl.decision_log
            if d["action"] in ("accept", "rule")],
    }

    print(json.dumps({
        "metric": "ragged_continuous_batching_tokens_per_sec",
        "value": round(best_tps, 1),
        "unit": "tokens/s",
        # floor = this config's round-4 result (BENCH_MATRIX r4: 19302.3
        # tok/s device) — serving must not regress round over round
        "vs_baseline": round(best_tps / 19302.3, 3) if on_tpu else 0.0,
        "detail": detail,
    }))


def bench_infinity(args) -> None:
    """Config infinity: the beyond-HBM tiers at 7B scale on ONE chip.

    Llama-2-7B (13.5 GB bf16 params, 54 GB fp32 moments — 4x over a
    16 GB chip) takes a full MEASURED train step: params + grads in
    pinned host memory streamed per layer, Adam moments streamed through
    the device in flat host-resident buckets by the host-offload
    optimizer tier (``runtime/swap_tensor.py HostMomentSwapper``; the
    reference capability: ZeRO-Offload 13B on one 32GB V100 at >30
    TFLOPS, docs/_pages/training.md:302).  The row records the measured
    full step, the host-link rooflines that bound it (in-program
    pinned_host<->HBM GB/s), and the NVMe tier's bucketed swap bandwidth
    with the client-link control (eager host<->device copies) beside it;
    on a local TPU host the stream is disk-bound against the io row's
    measured GB/s."""
    import os

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.llama import (LlamaLMLoss, count_params,
                                            flops_per_token, get_config)

    on_tpu = not args.smoke
    if on_tpu:
        size = args.size or "llama2-7b"
        # unrolled layers: XLA streams per-layer host->HBM param copies
        # (scan hoists whole stacked copies — measured 25.3G vs 15.8G)
        cfg = get_config(size, max_position_embeddings=1024,
                         dtype=jnp.bfloat16, remat=True,
                         remat_policy="full", scan_layers=False,
                         use_flash_attention=True)
        # micro=1: larger micros would amortize the per-step host->HBM
        # param stream over more tokens, but XLA keeps ~20 async
        # host-param copy-starts in flight as HLO temps (even with the
        # latency-hiding scheduler off) and micro>=2 OOMs a 16 GB chip.
        # The row instead RECORDS the fwd+bwd host-link bound — at
        # micro=1 the step is already ~3/4 pure transfer, so the
        # TFLOPS number is the link, not the framework (see
        # fwd_bwd_link_fraction in the detail)
        micro = int(os.environ.get("DSTPU_INFINITY_MICRO", "1"))
        seq = int(os.environ.get("DSTPU_INFINITY_SEQ", "1024"))
    else:
        cfg = get_config("tinyllama", dtype=jnp.float32, remat=False,
                         scan_layers=False)
        micro, seq = 2, 32
    nvme_dir = os.environ.get("DSTPU_NVME_DIR", "/tmp/dstpu_nvme")
    topo = dist.initialize_mesh()
    ds = {
        "train_batch_size": micro,
        "train_micro_batch_size_per_gpu": micro,
        "bf16": {"enabled": on_tpu, "master_weights": False},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu", "pin_memory": True},
            "offload_optimizer": {"device": "cpu", "pin_memory": True},
        },
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "steps_per_print": 1000000,
    }
    batch = _tokens(cfg.vocab_size, micro, seq)
    engine, *_ = deepspeed_tpu.initialize(
        model=LlamaLMLoss(cfg), config=ds, topology=topo,
        example_batch=batch, rng=jax.random.PRNGKey(0))
    n_params = count_params(engine.state.params)
    from deepspeed_tpu.runtime.swap_tensor import HostMomentSwapper

    host_tier = isinstance(engine.nvme_swapper, HostMomentSwapper)

    # fwd+bwd alone — reuse the SAME with_gmetrics program the full
    # train step dispatches (a metrics-free variant would cost a second
    # multi-minute 7B compile for two scalar reductions of difference)
    fused_metrics = engine.gas == 1
    if engine._nvme_grad_step_fn is None and engine.nvme_swapper is not None:
        engine._nvme_grad_step_fn = engine._build_grad_step(
            host_grads=engine.offload_param, with_gmetrics=fused_metrics)
    gfn = engine._nvme_grad_step_fn
    if gfn is None:                        # smoke fallback: no swapper
        gfn = engine._grad_step_fn = engine._build_grad_step()
        fused_metrics = False
    mb = jax.tree_util.tree_map(jnp.asarray, batch)
    rngk = jax.random.PRNGKey(1)
    out = gfn(engine.state, mb, rngk)      # compile
    loss, grads = out[0], out[1]
    loss_v = float(jax.device_get(loss))
    jax.block_until_ready(grads)
    times = []
    for _ in range(2 if on_tpu else 1):
        t0 = time.perf_counter()
        out = gfn(engine.state, mb, rngk)
        loss, grads = out[0], out[1]
        jax.block_until_ready((loss, grads))
        times.append(time.perf_counter() - t0)
    fb_s = min(times)
    fwd_bwd_flops_tok = flops_per_token(cfg, seq) * 2.0 / 3.0
    tflops = (fwd_bwd_flops_tok * micro * seq / fb_s) / 1e12
    del grads

    # the MEASURED full train step: fwd+bwd + host-moment optimizer
    # stream (per-bucket programs, moments never leave the accelerator
    # host).  First call compiles the bucket programs.
    losses = [float(jax.device_get(engine.train_batch(batch=batch)))]
    step_times = []
    for _ in range(2 if on_tpu else 1):
        t0 = time.perf_counter()
        losses.append(float(jax.device_get(
            engine.train_batch(batch=batch))))
        step_times.append(time.perf_counter() - t0)
    full_step_s = min(step_times)
    moment_gb = n_params * 8 / 1e9

    detail = {"params": n_params, "seq": seq, "micro": micro,
              "fwd_bwd_step_s": round(fb_s, 2),
              "full_train_step_s": round(full_step_s, 2),
              "full_step_measured": True,
              "optimizer_tier": ("host-moment stream" if host_tier
                                 else "device"),
              "optimizer_step_s": round(full_step_s - fb_s, 2),
              "moment_bytes_total_gb": round(moment_gb, 1),
              "losses": [round(x, 3) for x in losses],
              "initial_loss": round(loss_v, 3),
              "final_loss": round(losses[-1], 3),
              "offload": "param=cpu(host-streamed) grads=cpu "
                         "optimizer=cpu(host-moment buckets)",
              "device": jax.devices()[0].device_kind}

    if on_tpu:
        # host-link rooflines: in-program pinned_host<->HBM copies of a
        # 2 GB block, device time from profiler events.  These BOUND the
        # tiers above: fwd+bwd moves
        # ~2x params h2d + params d2h (grads); the optimizer moves
        # 2x moments each way.
        import sys as _sys

        _sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "scripts"))
        from _prof import profile_device

        from deepspeed_tpu.utils.sharding import memory_space

        d0 = jax.devices()[0]
        hostsh = jax.sharding.SingleDeviceSharding(
            d0, memory_kind="pinned_host")
        devsh = jax.sharding.SingleDeviceSharding(d0, memory_kind="device")
        N = 256 * 1024 * 1024                      # 1 GB fp32
        xh = jax.jit(lambda k: jax.random.normal(k, (N,), jnp.float32),
                     out_shardings=hostsh)(jax.random.PRNGKey(0))
        jax.block_until_ready(xh)
        f_h2d = jax.jit(lambda a: jax.device_put(
            a, memory_space("device")) * 1.000001, out_shardings=devsh)
        yd = f_h2d(xh)
        jax.block_until_ready(yd)
        ms, _ = profile_device(lambda: f_h2d(xh), n=3, tag="h2d")
        h2d_gbps = N * 4 / (ms / 1e3) / 1e9 if ms else 0.0
        f_d2h = jax.jit(lambda a: jax.device_put(
            a * 1.000001, memory_space("pinned_host")),
            out_shardings=hostsh)
        zh = f_d2h(yd)
        jax.block_until_ready(zh)
        ms, _ = profile_device(lambda: f_d2h(yd), n=3, tag="d2h")
        d2h_gbps = N * 4 / (ms / 1e3) / 1e9 if ms else 0.0
        del xh, yd, zh
        param_gb = n_params * 2 / 1e9
        bound_s = 0.0
        if h2d_gbps and d2h_gbps:
            # fwd+bwd: params h2d twice (remat) + grads d2h once;
            # optimizer: moments h2d + d2h + params both ways
            bound_s = (2 * param_gb / h2d_gbps + param_gb / d2h_gbps +
                       (moment_gb + param_gb) / h2d_gbps +
                       (moment_gb + param_gb) / d2h_gbps)
        detail["host_link_h2d_gbps"] = round(h2d_gbps, 2)
        detail["host_link_d2h_gbps"] = round(d2h_gbps, 2)
        detail["link_roofline_step_s"] = round(bound_s, 2)
        detail["link_bound_fraction"] = round(
            bound_s / full_step_s, 2) if full_step_s else None
        if h2d_gbps and d2h_gbps:
            # fwd+bwd alone: params h2d twice (remat recompute) + bf16
            # grads d2h once — the bound the TFLOPS number sits on
            fb_bound = 2 * param_gb / h2d_gbps + param_gb / d2h_gbps
            detail["fwd_bwd_link_bound_s"] = round(fb_bound, 2)
            detail["fwd_bwd_link_fraction"] = round(fb_bound / fb_s, 2)

    # NVMe tier: bucketed swap of the two largest leaves (full-model
    # NVMe streaming through THIS harness is client-link-bound — the
    # control below proves it; the host-moment tier above is the
    # measured full step)
    from deepspeed_tpu.runtime.swap_tensor import NvmeOptimizerSwapper

    flat = jax.tree_util.tree_flatten_with_path(engine.state.params)[0]
    big = sorted(flat, key=lambda kv: -kv[1].size)[:2]
    sub_params = {"/".join(str(getattr(k, "key", k)) for k in kp): v
                  for kp, v in big}
    sub_grads = jax.tree_util.tree_map(
        lambda v: jnp.ones(v.shape, v.dtype), sub_params)
    def measure_swap(verify: bool, reps: int = 3):
        swapper = NvmeOptimizerSwapper(nvme_dir, sub_params,
                                       sdc_verify=verify)
        try:
            swapper.apply(sub_params, sub_grads, lr=1e-4, gscale=1.0)
            nb = sum(v.size * 8 for v in sub_params.values())
            best = float("inf")
            for _ in range(reps):         # best-of: amortize cache noise
                t0 = time.perf_counter()
                swapper.start_prefetch()  # as the engine does, post-bwd
                swapper.apply(sub_params, sub_grads, lr=1e-4, gscale=1.0)
                swapper.drain()           # charge deferred write-back here
                best = min(best, time.perf_counter() - t0)
            stages = dict(swapper.stage_stats)
        finally:
            swapper.close()
        return 2 * nb / best / 1e9, nb, stages

    # verify-off control FIRST (warms the page cache the same way for
    # both), then the verify-on run the row reports — the delta is the
    # measured end-to-end checksum cost on the stream
    gbps_off, _, _ = measure_swap(verify=False)
    stream_gbps, nbytes, stages = measure_swap(verify=True)
    # per-stage pipeline waits: the evidence that the stream is
    # overlap-bound or bandwidth-bound, not an asserted property
    detail["nvme_swap_stages"] = stages
    detail["nvme_swap_gbps"] = round(stream_gbps, 3)
    detail["nvme_swap_gbps_verify_off"] = round(gbps_off, 3)
    # SDC checksum overhead on the moment stream (target <= 5%).  The
    # digests run on a side thread pool, so the cost hides behind the
    # pipeline wherever >= 2 host cores exist; on a 1-core container
    # every pass serializes and this measures the raw 2-extra-memory-
    # passes cost instead (~bytes/9GBps over the stream wall) — read
    # it together with host_cores.  Negative deltas are run-to-run
    # noise, clamped to 0.
    detail["sdc_overhead_pct"] = round(
        max(0.0, (gbps_off - stream_gbps) / gbps_off * 100.0), 2) \
        if gbps_off > 0 else None
    detail["host_cores"] = os.cpu_count()

    # one traced swap step: the swap-path spans (swap_in_wait /
    # bucket_update / swap_out_wait / swap_verify / apply) re-emitted
    # through the unified tracer must export as valid Chrome-trace JSON
    from deepspeed_tpu import telemetry
    telemetry.configure(enabled=True)
    telemetry.trace.clear()
    tr_swapper = NvmeOptimizerSwapper(nvme_dir, sub_params,
                                      sdc_verify=True)
    try:
        tr_swapper.apply(sub_params, sub_grads, lr=1e-4, gscale=1.0)
        tr_swapper.start_prefetch()
        tr_swapper.apply(sub_params, sub_grads, lr=1e-4, gscale=1.0)
        tr_swapper.drain()
    finally:
        tr_swapper.close()
    swap_trace_path = "/tmp/dstpu_infinity_swap_trace.json"
    telemetry.trace.export(swap_trace_path)
    telemetry.configure(enabled=False)
    trace_ok, trace_events = _validate_chrome_trace(swap_trace_path)
    detail["swap_trace"] = {"chrome_trace_valid": trace_ok,
                            "events": trace_events,
                            "export": swap_trace_path}
    if on_tpu:
        # client-link control: eager device_put/device_get of 64 MB —
        # the path every NVMe swap byte takes between host and device
        buf = np.random.default_rng(0).standard_normal(
            16 * 1024 * 1024).astype(np.float32)
        t0 = time.perf_counter()
        db = jax.device_put(buf, jax.devices()[0])
        jax.block_until_ready(db)
        up = buf.nbytes / (time.perf_counter() - t0) / 1e9
        t0 = time.perf_counter()
        _ = np.asarray(db)
        down = buf.nbytes / (time.perf_counter() - t0) / 1e9
        detail["client_link_control_gbps"] = {
            "h2d": round(up, 3), "d2h": round(down, 3)}
        denom = 1.0 / max(up, 1e-9) + 1.0 / max(down, 1e-9)
        detail["nvme_swap_vs_client_link"] = round(
            stream_gbps / (2.0 / denom), 2)

    print(json.dumps({
        "metric": "zero_infinity_7b_single_chip_fwd_bwd_tflops",
        "value": round(tflops, 2),
        "unit": "TFLOPS",
        # reference ZeRO-Offload: 13B on one V100 at >30 TFLOPS
        "vs_baseline": round(tflops / 30.0, 3),
        "detail": detail,
    }))


def bench_io(args) -> None:
    """AIO engine throughput (reference DeepNVMe ds_io numbers: 7/4 GB/s
    read/write without GDS, BASELINE.md).  Sweeps the native engine
    against ``$DSTPU_IO_DIR`` (default /tmp — point it at the NVMe mount
    for authoritative numbers)."""
    import os

    from deepspeed_tpu.io.bench import raw_control, tune

    directory = os.environ.get("DSTPU_IO_DIR", "/tmp")
    size = (64 if args.smoke else 512) << 20
    best = tune(directory, size, loops=1 if args.smoke else 2,
                verbose=False)
    # device-roofline control: O_DIRECT sequential, no ring engine —
    # "the write number IS the disk" must be data, not folklore
    ctrl_r, ctrl_w = raw_control(directory, size)
    print(json.dumps({
        "metric": "aio_read_write_gbps",
        "value": round(best["read_gbps"] + best["write_gbps"], 2),
        "unit": "GB/s (r+w)",
        # reference DeepNVMe without GDS: 7 read + 4 write = 11 combined
        "vs_baseline": round((best["read_gbps"] + best["write_gbps"]) / 11.0,
                             3),
        "detail": {"read_gbps": round(best["read_gbps"], 2),
                   "write_gbps": round(best["write_gbps"], 2),
                   "control_read_gbps": round(ctrl_r, 2),
                   "control_write_gbps": round(ctrl_w, 2),
                   "engine_vs_control_write": round(
                       best["write_gbps"] / ctrl_w, 2) if ctrl_w else None,
                   "dir": directory, "size_mb": size >> 20,
                   "config": best["config"]},
    }))


def bench_leafwise_multiproc(args) -> None:
    """Multi-process LEAFWISE moment-stream rate: two real
    jax.distributed processes (the tests/unit/multiproc fixture worker)
    each swap THEIR ZeRO-3 shard's Adam moments through the per-shard
    leafwise NVMe stream — the path every ``process_count > 1`` job
    runs (the bucketed pipeline is single-process only).  The row is
    the combined cross-rank stream rate; per-rank read/write rates ride
    in ``detail``.  Point ``$DSTPU_IO_DIR`` at the NVMe mount for
    authoritative numbers."""
    import os
    import socket
    import subprocess
    import sys
    import tempfile

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "unit", "multiproc", "worker_train.py")
    scratch = tempfile.mkdtemp(
        prefix="dstpu_leafwise_mp_",
        dir=os.environ.get("DSTPU_IO_DIR", None))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({"DSTPU_COORD": f"127.0.0.1:{port}",
                    "DSTPU_NPROC": "2", "DSTPU_PID": str(pid),
                    "DSTPU_MODE": "nvme", "DSTPU_DIR": scratch,
                    "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    stats = {}
    for p in procs:
        out, _ = p.communicate(timeout=900)
        assert p.returncode == 0, f"leafwise_mp worker failed:\n{out[-3000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                rec = json.loads(line[len("RESULT "):])
                stats[rec["pid"]] = rec["leafwise"]
    shutil.rmtree(scratch, ignore_errors=True)
    assert len(stats) == 2 and all(s is not None for s in stats.values()), \
        stats
    combined = sum(s["stream_gbps"] for s in stats.values())
    print(json.dumps({
        "metric": "nvme_leafwise_multiproc_stream_gbps",
        "value": round(combined, 4),
        "unit": "GB/s (r+w, 2 ranks)",
        # reference DeepNVMe without GDS: 7 read + 4 write = 11 combined
        # (same floor as the io row — each rank's shard stream rides the
        # same AIO engine)
        "vs_baseline": round(combined / 11.0, 4),
        "detail": {f"rank{pid}": {
            "read_gbps": s["read_gbps"], "write_gbps": s["write_gbps"],
            "bytes_read": s["bytes_read"],
            "bytes_written": s["bytes_written"], "wall_s": s["wall_s"]}
            for pid, s in sorted(stats.items())},
    }))


CONFIGS = {
    "1": bench_gpt2_ddp,
    "2": bench_gpt2_zero2_fused,
    "3": bench_llama_zero3,
    "4": bench_ulysses_longctx,
    "5": bench_moe_ep,
    "infer": bench_inference,
    "ragged": bench_ragged,
    "io": bench_io,
    "infinity": bench_infinity,
    "leafwise_mp": bench_leafwise_multiproc,
}


def bench_all(args) -> None:
    """Run EVERY config in a fresh subprocess; write the machine-readable
    matrix to BENCH_MATRIX.json (the committed evidence for all rows —
    regressions in configs 2-5 can't hide behind the headline number)."""
    import datetime
    import os
    import subprocess
    import sys

    records = {}
    failed = []
    for name in ["1", "2", "3", "4", "5", "infer", "ragged", "io",
                 "infinity", "leafwise_mp"]:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--config", name, "--steps", str(args.steps)]
        if args.smoke:
            cmd.append("--smoke")
        print(f"=== bench --config {name}", flush=True)
        # the infinity config streams ~120GB of moments+grads per
        # measured step through host+NVMe tiers: give it headroom
        budget = 3600 if name == "infinity" else 1800
        lines = []
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=budget)
        except subprocess.TimeoutExpired:
            print(f"config {name} timed out", flush=True)
        else:
            lines = [ln for ln in r.stdout.splitlines()
                     if ln.startswith("{")]
            if r.returncode != 0 or not lines:
                print(f"config {name} exited {r.returncode}:\n"
                      f"{r.stderr[-500:]}", flush=True)
                lines = []
        if lines:
            records[name] = json.loads(lines[-1])
            print(lines[-1], flush=True)
        else:
            failed.append(name)
            records[name] = {"metric": f"config_{name}", "value": None,
                             "unit": "FAILED", "vs_baseline": 0.0}
    # device info comes from the children's records — the parent must
    # never touch a jax backend: the chip belongs to one process at a
    # time, and a parent that held it would fail every child's init
    dev_info = next((r.get("detail", {}) for r in records.values()
                     if r.get("detail", {}).get("device")), {})
    out = {
        "generated": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "device": dev_info.get("device", "unknown"),
        "n_chips": dev_info.get("n_chips", 1),
        "smoke": bool(args.smoke),
        "configs": records,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_MATRIX.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")
    if failed:
        raise SystemExit(f"bench --all: config(s) {failed} failed")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="1", choices=sorted(CONFIGS),
                   help="BASELINE.md target config to run")
    p.add_argument("--all", action="store_true",
                   help="run every config (fresh subprocess each) and "
                        "write BENCH_MATRIX.json")
    p.add_argument("--size", default=None,
                   help="model preset override (e.g. gpt2-350m)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes on the CPU: a rehearsal of control "
                        "flow that measures nothing")
    args = p.parse_args()
    if args.all:
        # children probe their own backend; the parent stays off it so
        # it never holds the chip they need
        bench_all(args)
        return
    from deepspeed_tpu.accelerator import require_accelerator
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    if not require_accelerator(cpu_requested=args.smoke):
        args.smoke = True
    enable_compile_cache()
    CONFIGS[args.config](args)


if __name__ == "__main__":
    main()
