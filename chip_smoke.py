#!/usr/bin/env python3
"""Does the system still start on the chip?  The quickest end-to-end proof.

    python chip_smoke.py            # one TPU chip: train, serve, delta rule
    python chip_smoke.py --chips 4  # one 4-chip host: ZeRO-3 and 4 replicas

One process, the entry points a user calls, published widths, random
weights from a fixed seed.  Nothing here is a measurement: the times
printed say how long the smoke took, not how fast the system is.

- *train*: ``deepspeed_tpu.initialize`` + ``engine.train_batch`` on
  GPT-2 medium (``gpt2-350m``, all 24 layers), sequence 1024, bf16 over
  fp32 master weights, AdamW, flash attention.  The loss must be finite
  and fall on a repeated batch, and the compiled step must hold the flash
  kernel, forward and backward.
- *serve*: ``RaggedInferenceEngineV2`` behind ``Router`` and
  ``FrontDoorServer``, spoken to over a loopback socket with
  ``serving/client.py``, at Mistral-7B widths (depth cut, the line says
  to what).  Every request completes, a second pass gives the same
  tokens and compiles nothing, the attention ran through the Pallas
  kernels (bf16 pool and int8 pool), and the engine's logits agree with
  the model's plain ``apply``.
- *delta rule*: the two delta-rule families (``models/olmo_hybrid.py``,
  ``models/solar_open2.py``; two heads at their published head sizes, one
  period of layers) through ``RaggedInferenceEngineV2``: every request
  completes, and a fused tick's short convolution took its one-pass kernel
  (``ops/short_conv.py``), not the twin.
- ``--chips 4`` runs none of them, only what exists across chips: ZeRO-3 over a
  4-device ``data`` mesh against the one-device run of the same job, and
  four replicas behind the router, one per chip, against one replica.

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {...}}`` and the exit code 0 only if every phase
held; anything else raises, exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

SEED = 0


class SmokeFailure(RuntimeError):
    """A phase ran and what came out is wrong."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one smoke run.  :func:`full_plan` is what ``main`` runs;
    the CPU test hands :func:`run` a tiny one with ``on_chip=False``."""
    gpt2: Any                       # models.gpt2.GPT2Config
    train_seq: int
    train_micro: int
    train_steps: int                # after the compile step
    mistral: Any                    # models.mistral.MistralConfig
    mistral_layers_published: int
    serve: Dict[str, int]           # RaggedInferenceEngineV2 sizes
    prompt_lens: Tuple[int, ...]
    new_tokens: int
    quant_prompt_lens: Tuple[int, ...]
    quant_new_tokens: int
    parity_prompt_len: int
    delta_models: Dict[str, Any]    # family -> its model, bf16
    # True: a TPU is attached, so every kernel must take its Pallas route
    # and show up in the compiled programs.  False (tests): the XLA twins.
    on_chip: bool = True


def full_plan() -> Plan:
    import jax.numpy as jnp

    from deepspeed_tpu.models import gpt2, mistral

    published = mistral.get_config("mistral-7b")
    return Plan(
        delta_models=delta_models(),
        gpt2=gpt2.get_config("gpt2-350m", n_positions=1024,
                             use_flash_attention=True),
        train_seq=1024, train_micro=32, train_steps=4,
        # Mistral-7B as published but for depth.  8 of 32 layers: 3.7 GiB
        # of bf16 weights; the chip would hold about three times that
        # beside its KV pools, but every layer is unrolled into the tick
        # and the decode block, and this smoke compiles both twice
        mistral=dataclasses.replace(
            published, num_hidden_layers=8, dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16, scan_layers=False, remat=False,
            use_flash_attention=False),
        mistral_layers_published=published.num_hidden_layers,
        serve=dict(max_seqs=8, max_seq_len=2112, prefill_chunk=256,
                   page_size=64, decode_block_size=8),
        prompt_lens=(512, 640, 768, 1024, 1280, 1536, 1792, 2048),
        new_tokens=64,
        quant_prompt_lens=(512, 768), quant_new_tokens=16,
        parity_prompt_len=48)


def delta_models() -> Dict[str, Any]:
    """One period of each delta-rule family, bf16, two heads of the
    published sizes: 96 / 192 (a head no whole lane tiles) and 128 (a head a
    lane tile)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import olmo_hybrid, solar_open2

    bf16 = dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                scan_layers=False, remat=False, use_flash_attention=False)
    return {
        "olmo_hybrid": olmo_hybrid.OlmoHybridForCausalLM(
            olmo_hybrid.get_config(
                "tinyolmohybrid", linear_key_head_dim=96,
                linear_value_head_dim=192, **bf16)),
        "solar_open2": solar_open2.SolarOpen2ForCausalLM(
            solar_open2.get_config(
                "tinysolar", linear_attn_config=dict(
                    short_conv_kernel_size=4, head_dim=128, num_heads=2,
                    num_kv_heads=None), **bf16))}


# -- train -----------------------------------------------------------------

def _train_engine(plan: Plan, devices: List[Any], zero_stage: Optional[int]):
    """``deepspeed_tpu.initialize`` over ``devices`` on the ``data`` axis;
    returns (engine, repeated batch)."""
    import jax

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.gpt2 import GPT2LMLoss

    topo = dist.initialize_mesh(devices=devices)
    dp = topo.zero_partition_count()
    config = {
        "train_micro_batch_size_per_gpu": plan.train_micro,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},          # fp32 master weights (default)
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "steps_per_print": 1_000_000,
    }
    if zero_stage is not None:
        config["zero_optimization"] = {"stage": zero_stage}
    # every data-parallel rank gets the SAME micro-batch: the global mean
    # loss and gradient then equal the one-device run's, whatever dp is
    micro = np.random.default_rng(SEED).integers(
        0, plan.gpt2.vocab_size, size=(plan.train_micro, plan.train_seq),
        dtype=np.int32)
    batch = {"input_ids": np.tile(micro, (dp, 1))}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMLoss(plan.gpt2), config=config, topology=topo,
        example_batch={"input_ids": micro[:1]},
        rng=jax.random.PRNGKey(SEED))
    return engine, batch


def _train_steps(engine, batch, steps: int) -> Dict[str, Any]:
    """The compile step, then ``steps`` more on the same batch."""
    import jax

    from deepspeed_tpu.telemetry.compiles import count_compiles

    with count_compiles() as compiles:
        t0 = time.perf_counter()
        first = float(jax.block_until_ready(engine.train_batch(batch=batch)))
        first_wall = time.perf_counter() - t0
    losses, walls = [first], []
    with count_compiles() as steady:
        for _ in range(steps):
            t0 = time.perf_counter()
            losses.append(float(jax.block_until_ready(
                engine.train_batch(batch=batch))))
            walls.append(time.perf_counter() - t0)
    check(all(np.isfinite(losses)), f"train loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"train loss did not fall on a repeated batch: {losses}")
    check(steady.count == 0,
          f"{steady.count} compilations after the train compile step")
    return {"losses": [round(x, 4) for x in losses],
            "compile_seconds": round(compiles.seconds, 2),
            "first_step_wall_seconds": round(first_wall, 2),
            "step_wall_seconds": [round(w, 4) for w in walls]}


def _peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats()           # None where not reported (CPU)
    return None if stats is None else int(stats["peak_bytes_in_use"])


def train_phase(plan: Plan) -> Dict[str, Any]:
    import jax

    from deepspeed_tpu.ops import routes

    device = jax.devices()[0]
    engine, batch = _train_engine(plan, [device], zero_stage=None)
    out = {"phase": "train", "model": "gpt2-350m",
           "layers": plan.gpt2.n_layer, "width": plan.gpt2.n_embd,
           "seq": plan.train_seq, "micro_batch": plan.train_micro,
           "zero_stage": int(engine.config.zero_optimization.stage),
           "optimizer": engine.optimizer_name}
    out.update(_train_steps(engine, batch, plan.train_steps))
    taken = routes.taken()
    out["routes"] = {k: taken.get(k) for k in ("flash_attention_fwd",
                                               "flash_attention_bwd")}
    if plan.on_chip:
        check(set(out["routes"].values()) == {"pallas"},
              f"flash attention fell to a reference: {out['routes']}")
        text = engine.lower_train_step(batch).compile().as_text()
        kernels = text.count('custom_call_target="tpu_custom_call"')
        # forward, and the backward's dq and dk/dv kernels
        check(kernels >= 3, f"compiled train step holds {kernels} Pallas "
              "kernels, expected flash forward and backward (>= 3)")
        out["pallas_kernels_in_step"] = kernels
    out["peak_bytes_in_use"] = _peak_bytes(device)
    return out


# -- serve -----------------------------------------------------------------

def _mistral_params(plan: Plan):
    import jax

    from deepspeed_tpu.models.mistral import MistralForCausalLM

    model = MistralForCausalLM(plan.mistral)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED),
                                 np.zeros((1, 8), np.int32))["params"]
    return model, params


def _serve_engine(plan: Plan, model, params, device=None, **kw):
    """One engine; ``device`` makes it a replica slice of that chip."""
    from deepspeed_tpu.inference.v2.ragged_engine import \
        RaggedInferenceEngineV2
    from deepspeed_tpu.parallel.topology import MeshTopology

    topology = None if device is None else MeshTopology(devices=[device])
    return RaggedInferenceEngineV2(model, params=params, topology=topology,
                                   **plan.serve, **kw)


def _prompts(plan: Plan, lens) -> List[List[int]]:
    rng = np.random.default_rng(SEED + 1)
    return [rng.integers(1, plan.mistral.vocab_size, size=(n,)).tolist()
            for n in lens]


def _socket_pass(srv, prompts, new_tokens: int,
                 concurrency: int) -> List[List[int]]:
    """Every prompt once over the loopback socket, ``concurrency`` in
    flight together, odd ones buffered and even ones streamed; the final
    tokens in prompt order."""
    from deepspeed_tpu.serving.client import LoadGenerator

    def payload(i: int) -> Dict[str, Any]:
        return {"prompt": prompts[i], "max_new_tokens": new_tokens,
                "stream": i % 2 == 0}

    gen = LoadGenerator(srv.host, srv.port, payload,
                        requests=len(prompts), concurrency=concurrency)
    gen.run()
    results = sorted(gen.results, key=lambda r: r["i"])
    for r, p in zip(results, prompts):
        check(r["error"] is None and r["final"] is not None,
              f"request {r['i']} did not complete: {r['error']!r} "
              f"(status {r['status']})")
        check(r["final"][:len(p)] == p and
              len(r["final"]) == len(p) + new_tokens,
              f"request {r['i']}: {len(r['final'])} tokens back for a "
              f"{len(p)}-token prompt + {new_tokens} new")
        if r["i"] % 2 == 0:
            check(r["tokens"] == r["final"][len(p):],
                  f"request {r['i']}: streamed tokens differ from final")
    return [r["final"] for r in results]


def _routed_pass(router, prompts, new_tokens: int) -> List[List[int]]:
    rids = [router.submit(np.asarray(p, np.int32),
                          max_new_tokens=new_tokens) for p in prompts]
    outs = router.drain()
    check(sorted(outs) == sorted(rids), "a routed request did not finish")
    return [np.asarray(outs[r]).tolist() for r in rids]


def _logits_parity(plan: Plan, eng, model, prompt: List[int]) -> float:
    """Prefill ``prompt`` and decode one token through the engine's own
    fused-tick program, against the model's plain ``apply`` on the same
    weights; returns the largest logit difference."""
    import jax

    from deepspeed_tpu.inference.common import logits_of

    n, page = len(prompt), eng.page_size
    check(n + 1 <= page, "parity prompt must fit one page")

    def tick(tokens: List[int], start: int) -> np.ndarray:
        k = len(tokens)
        ids = np.zeros((eng.T,), np.int32)
        ids[:k] = tokens
        pos = np.zeros((eng.T,), np.int32)
        pos[:k] = np.arange(start, start + k)
        dest = np.zeros((eng.T,), np.int32)         # rest: trash page
        dest[:k] = page + pos[:k]                   # page 1 of the pool
        kv_lens = np.zeros((eng.max_seqs,), np.int32)
        kv_lens[0] = start + k
        pages = np.full((eng.max_seqs, eng.pages_per_seq), -1, np.int32)
        pages[0, 0] = 1
        cu = np.full((eng.max_seqs + 1,), k, np.int32)
        cu[0] = 0
        rows = np.zeros((eng.max_seqs,), np.int32)
        rows[0] = k - 1
        args = eng.upload_all((
            ids[None], pos[None], kv_lens, pages, cu,
            np.asarray([1], np.int32), dest, rows))
        sel, eng.kv.cache, *_ = eng.fused_step()(eng.params, eng.kv.cache,
                                                 *args)
        return np.asarray(sel[0], np.float32)

    at_last = tick(prompt, 0)
    nxt = int(at_last.argmax())
    at_next = tick([nxt], n)
    ref = np.asarray(logits_of(jax.jit(model.apply)(
        {"params": eng.params}, np.asarray([prompt + [nxt]], np.int32)))[0],
        np.float32)
    check(np.isfinite(at_last).all() and np.isfinite(at_next).all(),
          "engine logits not finite")
    diff = max(float(np.abs(at_last - ref[n - 1]).max()),
               float(np.abs(at_next - ref[n]).max()))
    # bf16 end to end through two different attention programs: a wrong
    # mask or page moves logits by their own magnitude, rounding by ~1%
    bound = 0.05 * float(np.abs(ref[n - 1:]).max()) + 0.05
    check(diff <= bound, f"engine logits differ from plain apply by "
          f"{diff:.4f} (bound {bound:.4f})")
    check(int(ref[n - 1].argmax()) == nxt,
          "engine and plain apply disagree on the greedy token")
    return diff


def serve_phase(plan: Plan) -> Dict[str, Any]:
    import jax

    from deepspeed_tpu.serving import FrontDoorServer, ReplicaSet, Router
    from deepspeed_tpu.telemetry.compiles import count_compiles

    model, params = _mistral_params(plan)
    out: Dict[str, Any] = {
        "phase": "serve", "model": "mistral-7b",
        "layers": plan.mistral.num_hidden_layers,
        "layers_published": plan.mistral_layers_published,
        "width": plan.mistral.hidden_size,
        "heads": [plan.mistral.num_attention_heads,
                  plan.mistral.num_key_value_heads],
        "sliding_window": plan.mistral.sliding_window,
        "requests": len(plan.prompt_lens),
        "prompt_tokens": list(plan.prompt_lens),
        "new_tokens": plan.new_tokens, "transport": "loopback socket"}
    from deepspeed_tpu.ops import paged_decode, paged_prefill

    cfg, page = plan.mistral, plan.serve["page_size"]
    heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
    tiles = paged_decode.tiling(
        kv_heads, page, -(-plan.serve["max_seq_len"] // page),
        cfg.head_dim, cfg.dtype)
    chunk_tiles = tiles and paged_prefill.tiling(
        heads, kv_heads, page, cfg.head_dim, cfg.dtype)
    # the decode blocks take the decode kernel and the fused ticks the
    # chunk kernel wherever their layouts hold
    want = ({"bf16": "pallas-ragged", "int8": "pallas-quant",
             "bf16_decode": (paged_decode.route_name(tiles, kv_heads)
                             if tiles else "pallas-ragged"),
             "bf16_prefill": (paged_prefill.route_name(
                 chunk_tiles, heads, kv_heads, page)
                 if chunk_tiles else "pallas-ragged")}
            if plan.on_chip else
            {"bf16": "xla-dense", "int8": "xla-gather",
             "bf16_decode": "xla-dense", "bf16_prefill": "xla-dense"})
    routes: Dict[str, str] = {}

    # bf16 pool, the path of `examples/serve_llama.py --listen`
    rs = ReplicaSet(lambda i: _serve_engine(plan, model, params), 1)
    try:
        router = Router(rs, policy="least_tokens")
        srv = FrontDoorServer(router, host="127.0.0.1", port=0).start()
        try:
            prompts = _prompts(plan, plan.prompt_lens)
            n = len(prompts)
            t0 = time.perf_counter()
            with count_compiles() as warm:
                together = _socket_pass(srv, prompts, plan.new_tokens, n)
            out["warmup_pass_seconds"] = round(time.perf_counter() - t0, 2)
            out["warmup_compile_seconds"] = round(warm.seconds, 2)
            with count_compiles() as steady:
                t0 = time.perf_counter()
                again = _socket_pass(srv, prompts, plan.new_tokens, n)
                out["second_pass_seconds"] = round(
                    time.perf_counter() - t0, 2)
                # Bit-equality is asked of a FIXED schedule, one request
                # at a time.  On the chip a token decoded inside a fused
                # tick (264 rows) and one decoded by the decode block
                # (8 rows) come from different programs whose bf16
                # matmuls round differently, so with requests in flight
                # together greedy tokens follow the arrival timing.
                alone = _socket_pass(srv, prompts, plan.new_tokens, 1)
                alone_again = _socket_pass(srv, prompts, plan.new_tokens, 1)
        finally:
            srv.close()
        check(alone == alone_again, "greedy output changed between two "
              "passes over the same prompts")
        check(steady.count == 0,
              f"{steady.count} compilations after warm-up")
        out["compiles_after_warmup"] = steady.count
        out["streamed"] = (n + 1) // 2
        out["in_flight_passes_identical"] = together == again
        out["in_flight_identical_to_one_at_a_time"] = together == alone
        eng = rs.handles[0].engine
        kernels = eng.serving_stages()["kernels"]
        routes["bf16"] = kernels["paged_attention"]
        routes["bf16_decode"] = kernels.get(paged_decode.KERNEL)
        routes["bf16_prefill"] = kernels.get(paged_prefill.KERNEL)
        out["logits_max_abs_diff_vs_plain_apply"] = round(_logits_parity(
            plan, eng, model,
            _prompts(plan, (plan.parity_prompt_len,))[0]), 5)
    finally:
        rs.close()

    # int8 pool: a second, short run through the router
    rs = ReplicaSet(lambda i: _serve_engine(plan, model, params,
                                            kv_cache_dtype="int8"), 1)
    try:
        router = Router(rs, policy="least_tokens")
        prompts = _prompts(plan, plan.quant_prompt_lens)
        first_q = _routed_pass(router, prompts, plan.quant_new_tokens)
        with count_compiles() as steady_q:
            second_q = _routed_pass(router, prompts, plan.quant_new_tokens)
        check(first_q == second_q, "int8-pool greedy output changed "
              "between two passes over the same prompts")
        check(steady_q.count == 0, f"{steady_q.count} compilations after "
              "warm-up with the int8 pool")
        stages = rs.handles[0].engine.serving_stages()
        routes["int8"] = stages["kernels"]["paged_attention"]
        check(stages["kv_quant"]["dequant_path"] == routes["int8"],
              "kv_quant block and kernels block name different routes")
    finally:
        rs.close()

    out["routes"] = routes
    check(routes == want, f"attention routes {routes}, expected {want}")
    out["peak_bytes_in_use"] = _peak_bytes(jax.devices()[0])
    return out


# -- delta rule ------------------------------------------------------------

def delta_phase(plan: Plan) -> Dict[str, Any]:
    import jax

    from deepspeed_tpu.inference.v2.ragged_engine import \
        RaggedInferenceEngineV2
    from deepspeed_tpu.ops import routes, short_conv

    want = (f"pallas-rows-{short_conv.ROWS}x{short_conv.LANE_BLOCK}"
            if plan.on_chip else routes.XLA)
    out: Dict[str, Any] = {"phase": "delta_rule", "routes": {},
                           "new_tokens": plan.quant_new_tokens,
                           "prompt_tokens": list(plan.quant_prompt_lens)}
    for name, model in plan.delta_models.items():
        params = jax.jit(model.init)(jax.random.PRNGKey(SEED),
                                     np.zeros((1, 8), np.int32))["params"]
        rng = np.random.default_rng(SEED + 2)
        prompts = [rng.integers(1, model.config.vocab_size, size=(n,))
                   .astype(np.int32) for n in plan.quant_prompt_lens]
        eng = RaggedInferenceEngineV2(model, params=params, **plan.serve)
        try:
            outs = eng.generate_all(prompts,
                                    max_new_tokens=plan.quant_new_tokens)
            check(len(outs) == len(prompts) and all(
                len(t) == len(p) + plan.quant_new_tokens
                for t, p in zip((outs[u] for u in sorted(outs)), prompts)),
                f"{name}: a request did not complete")
            out["routes"][name] = eng.serving_stages()["kernels"].get(
                short_conv.KERNEL)
        finally:
            eng.close()
    check(set(out["routes"].values()) == {want},
          f"short convolution routes {out['routes']}, expected {want}: a "
          "delta-rule model's fused tick took the twin")
    return out


# -- four chips --------------------------------------------------------------

def _bytes_by_device(tree) -> Dict[Any, int]:
    import jax

    held: Dict[Any, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + \
                shard.data.nbytes
    return held


def zero3_phase(plan: Plan) -> Dict[str, Any]:
    """ZeRO-3 over every device on the ``data`` axis at dp x the global
    batch, against the one-device run of the same seed."""
    import jax

    devices = jax.devices()
    dp = len(devices)
    engine, batch = _train_engine(plan, devices[:1], zero_stage=3)
    solo = _train_steps(engine, batch, plan.train_steps)
    solo_bytes = sum(_bytes_by_device(
        (engine.state.params, engine.state.opt_state)).values())
    del engine

    engine, batch = _train_engine(plan, devices, zero_stage=3)
    sharded = _train_steps(engine, batch, plan.train_steps)
    # the repo's own parity tolerance between re-shardings of one job
    # (tests/unit/test_engine.py::test_zero_stages_agree)
    np.testing.assert_allclose(sharded["losses"], solo["losses"],
                               rtol=2e-3, err_msg="ZeRO-3 over "
                               f"{dp} devices vs one device")
    held = _bytes_by_device((engine.state.params, engine.state.opt_state))
    check(set(held) == set(devices),
          f"state on {len(held)} devices, expected {dp}")
    share = [held[d] / solo_bytes for d in devices]
    # small leaves that do not divide stay replicated
    check(all(1 / dp <= s <= 1.15 / dp for s in share),
          f"per-device share of params+optimizer state {share}, "
          f"expected ~1/{dp}")
    text = engine.lower_train_step(batch).compile().as_text()
    # ops by name; the TPU compiler writes a reduce-scatter as a fused
    # "all-reduce-scatter" computation, the CPU's keeps all-reduce + slice
    collectives = {
        "all-gather": text.count(" all-gather(") +
        text.count(" all-gather-start("),
        "reduce-scatter": text.count("reduce-scatter"),
        "all-reduce": text.count(" all-reduce(") +
        text.count(" all-reduce-start(")}
    check(collectives["all-gather"] > 0,
          "ZeRO-3 step compiled without an all-gather")
    check(collectives["reduce-scatter"] > 0 or
          (not plan.on_chip and collectives["all-reduce"] > 0),
          "ZeRO-3 step compiled without a reduce-scatter")
    stats = [d.memory_stats() for d in devices]
    return {"phase": "zero3", "model": "gpt2-350m", "devices": dp,
            "global_batch": plan.train_micro * dp,
            "losses": sharded["losses"], "losses_one_device": solo["losses"],
            "compile_seconds": sharded["compile_seconds"],
            "step_wall_seconds": sharded["step_wall_seconds"],
            "state_share_per_device": [round(s, 4) for s in share],
            "state_bytes_per_device": [held[d] for d in devices],
            "collectives_in_step": collectives,
            "bytes_in_use_per_device": [
                None if s is None else int(s["bytes_in_use"])
                for s in stats]}


def replicas_phase(plan: Plan) -> Dict[str, Any]:
    """One replica per device behind the router, against one replica."""
    import jax

    from deepspeed_tpu.serving import ReplicaSet, Router

    devices = jax.devices()
    model, params = _mistral_params(plan)
    prompts = _prompts(plan, plan.prompt_lens)
    # what round-robin hands each replica.  The one-replica reference
    # serves these shares one after another: on the chip greedy tokens
    # depend on the schedule (see serve_phase), so both sides get the same
    order = list(range(len(prompts)))
    shares = [order[i::len(devices)] for i in range(len(devices))]

    def run(n: int, groups: List[List[int]]):
        rs = ReplicaSet(lambda i: _serve_engine(plan, model, params,
                                                device=devices[i]), n)
        try:
            router = Router(rs, policy="rr")
            outs: List[Any] = [None] * len(prompts)
            for group in groups:
                for i, toks in zip(group, _routed_pass(
                        router, [prompts[i] for i in group],
                        plan.new_tokens)):
                    outs[i] = toks
            stats = router.stats()
            where = []
            for i, h in enumerate(rs):
                on = set(_bytes_by_device((h.engine.params,
                                           h.engine.cache)))
                check(on == {devices[i]}, f"replica {h.name} params/KV "
                      f"pool on {on}, expected {devices[i]}")
                check(stats[f"routed_{h.name}"] > 0,
                      f"replica {h.name} served no request")
                where.append({"replica": h.name, "device": str(devices[i]),
                              "routed": stats[f"routed_{h.name}"]})
            return outs, where
        finally:
            rs.close()

    one, _ = run(1, shares)
    many, where = run(len(devices), [order])
    check(one == many, f"{len(devices)} replicas and one replica gave "
          "different tokens for the same prompts")
    return {"phase": "replicas", "model": "mistral-7b",
            "layers": plan.mistral.num_hidden_layers,
            "replicas": where, "requests": len(prompts),
            "bit_identical_to_one_replica": True}


# -- driver ------------------------------------------------------------------

def run(plan: Plan, chips: int = 1) -> int:
    """Run the phases for ``chips`` devices, print one JSON line each, then
    the cache line and the result line.  A failed phase raises."""
    import jax

    from deepspeed_tpu.telemetry.compiles import count_compiles
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    check(len(devices) == chips,
          f"--chips {chips} but JAX reports {len(devices)} device(s)")
    cache_dir = enable_compile_cache()
    phases = (train_phase, serve_phase, delta_phase) if chips == 1 else \
        (zero3_phase, replicas_phase)
    with count_compiles() as compiles:
        for phase in phases:
            print(json.dumps(phase(plan)), flush=True)
    print(json.dumps({"phase": "compile_cache", "dir": cache_dir,
                      "hits": compiles.cache_hits,
                      "misses": compiles.cache_misses,
                      "compilations": compiles.count,
                      "compile_seconds": round(compiles.seconds, 2)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the paths that exist across chips")
    args = p.parse_args(argv)

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}; the CPU "
              "path is the test suite's (tests/unit/test_chip_smoke.py)",
              file=sys.stderr)
        return 1
    return run(full_plan(), chips=args.chips)


if __name__ == "__main__":
    sys.exit(main())
