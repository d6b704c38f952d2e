"""Training cells: ``deepspeed_tpu.initialize`` + ``engine.train_batch``
over every chip of the cell on the mesh's ``data`` axis, the loop a user
runs: a fresh batch every step, made on the host while the previous step
runs, the loss fetched one step behind.

Set-up (all of it ``setup_s``): the mesh; ``initialize`` (weights made on
the devices from ``--seed``, sharded from the first instant); the
correctness check (every position's loss from the program's model, and
the engine's own forward loss row by row, against the plain float32
reference on the same weights); the compile or cache load of the
step, and the warm-up steps.  The window opens then and closes on
``block_until_ready`` of the last whole step dispatched before ``--seconds``
were over; the rate is taken over all of that time.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark import harness as H


def position_losses_fn(config: Dict[str, Any]):
    """``(lm_params, rows) -> [rows, tokens - 1]`` next-token losses from
    the PROGRAM's model (the family's module at the configuration's
    overrides: its compute type, its kernels, its layer scan), the weights
    cast to the compute type as the engine's own steps cast them.  The
    arithmetic after the logits (``logsumexp - target``, float32) is the
    benchmark's."""
    import jax
    import jax.numpy as jnp

    mcfg = H.build_model_config(config)
    model = H.resolve(config["family"]["module"])(mcfg)

    def fn(lm_params, rows):
        lm_params = jax.tree_util.tree_map(
            lambda x: x.astype(mcfg.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, lm_params)
        logits = model.apply({"params": lm_params}, rows)[:, :-1]
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, rows[:, 1:, None], axis=-1)[..., 0]
        return lse - tgt

    return fn


def _check(cell: H.Cell, engine, seed: int, dp: int) -> Dict[str, Any]:
    """Two comparisons with the plain float32 reference on the engine's
    own weights, over ``rows`` seeded rows of ``tokens`` random tokens:

    per position -- the program's model gives every position's next-token
    loss; each must lie within ``position_loss_abs_tol`` of the
    reference's.  A wrong mask, window, rotation or layer moves single
    positions by the logits' own spread (about 1), many times the bound;
    the MEAN over a row hides all of them (at random weights it is
    ln(vocab) + var/2 for almost any model);

    per row -- the engine's own forward loss (``eval_batch``: its sharding,
    its gathers, its cast, the loss module's target shift), every
    data-parallel rank given the same row, against the mean of the
    reference's positions within ``loss_rel_tol``.  This one is a mean, so
    its bound is set close above the bf16 noise that was measured, and it
    is asked of every row separately."""
    import jax

    spec = cell.config["correct"]
    n_rows = dp * -(-int(spec["rows"]) // dp)   # the batch axis is sharded
    rng = np.random.default_rng([int(seed), 0xC1])
    rows = rng.integers(1, int(cell.config["vocab_size"]),
                        size=(n_rows, int(spec["tokens"])), dtype=np.int32)
    ref = H.plugin("reference", cell.config["family"]["reference"])
    params = engine.state.params
    want = np.stack([ref.token_losses(params, cell.config, r) for r in rows])
    tree = params["params"] if "params" in params else params
    got = np.asarray(jax.jit(position_losses_fn(cell.config))(
        tree[spec["module_scope"]], rows))
    pos_diff = float(np.abs(got - want).max())
    engine_loss = [float(engine.eval_batch(
        batch={"input_ids": np.tile(r[None], (dp, 1))})) for r in rows]
    rel = [abs(g - float(w.mean())) / float(w.mean())
           for g, w in zip(engine_loss, want)]
    return {"ok": bool(np.all(np.isfinite(got)) and
                       np.all(np.isfinite(engine_loss)) and
                       pos_diff <= float(spec["position_loss_abs_tol"]) and
                       max(rel) <= float(spec["loss_rel_tol"])),
            "rows": n_rows, "tokens": int(spec["tokens"]),
            "position_loss_max_abs_diff": pos_diff,
            "position_loss_mean_abs_diff": float(np.abs(got - want).mean()),
            "position_loss_abs_tol": float(spec["position_loss_abs_tol"]),
            "engine_loss": engine_loss,
            "reference_loss": [float(w.mean()) for w in want],
            "loss_max_rel_diff": max(rel),
            "loss_rel_tol": float(spec["loss_rel_tol"])}


def run(cell: H.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, allow_cpu: bool = False) -> str:
    devices = H.require_chips(cell.chips, allow_cpu)
    import jax

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.ops import routes
    from deepspeed_tpu.telemetry.compiles import count_compiles

    from benchmark import flops

    cache_dir = H.enable_compile_cache()
    tcfg = cell.config["train"]
    traffic = cell.traffic
    topo = dist.initialize_mesh(devices=devices)
    dp = topo.zero_partition_count()
    seq, micro = int(tcfg["seq_len"]), int(tcfg["micro_batch_per_chip"])
    rows = micro * dp
    tokens_per_step = rows * seq
    mcfg = H.build_model_config(cell.config)
    loss_module = H.resolve(cell.config["family"]["loss_module"])(mcfg)
    ds_config = dict(tcfg["ds_config"],
                     train_micro_batch_size_per_gpu=micro)
    batches = H.plugin("generators", traffic["generator"]).make(
        traffic, seed, int(cell.config["vocab_size"]), rows, seq)

    with count_compiles() as setup_compiles:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=loss_module, config=ds_config, topology=topo,
            example_batch={"input_ids": batches.batch(0)["input_ids"][:1]},
            rng=H.seed_key(seed))
        check = _check(cell, engine, seed, dp)
        H.log({"phase": "correct_loss", **check})
        step = 0
        for _ in range(int(traffic["warmup_steps"])):
            jax.block_until_ready(
                engine.train_batch(batch=batches.batch(step)))
            step += 1

    sl = traffic["trace_slice"]
    tr = H.TraceSlice(cell.name, seed) if trace else None
    trace_first = int(sl["start_step"])
    trace_last = trace_first + int(sl["steps"]) - 1
    losses: List[float] = []
    pending = None
    nxt = batches.batch(step)
    n = 0
    t0 = H.now()
    setup_s = t0 - t_process
    with count_compiles() as window_compiles:
        while True:
            if tr is not None and n == trace_first:
                if pending is not None:     # a clean edge for the slice
                    losses.append(float(pending))
                    pending = None
                tr.start()
            loss = engine.train_batch(batch=nxt)
            step += 1
            n += 1
            nxt = batches.batch(step)       # while the device runs
            if pending is not None:
                losses.append(float(pending))   # one step behind
            pending = loss
            if tr is not None and n == trace_last + 1:
                losses.append(float(pending))
                pending = None
                tr.stop()
            if H.now() - t0 >= seconds:
                break
        if pending is not None:
            losses.append(float(jax.block_until_ready(pending)))
        t_end = H.now()
    if tr is not None and tr.t_stop is None:
        if tr.t_start is not None:
            tr.stop()
        raise H.BenchmarkError(
            f"--seconds {seconds} ended before the traced steps "
            f"{trace_first}..{trace_last} did")
    window = t_end - t0
    device = H.device_block(devices)
    taken = routes.taken()

    k = max(1, min(5, len(losses) // 2))
    fell = (len(losses) >= 2 and
            float(np.mean(losses[-k:])) < float(np.mean(losses[:k])))
    want = cell.config.get("expected_routes", {})
    routes_ok = allow_cpu or all(taken.get(r) == v for r, v in want.items())
    correct = bool(check["ok"] and np.all(np.isfinite(losses)) and fell
                   and routes_ok and window_compiles.count == 0)
    compared = {
        "position_loss_abs_diff_max": H.compared(
            check["position_loss_max_abs_diff"],
            check["position_loss_abs_tol"]),
        "loss_rel_diff_max": H.compared(check["loss_max_rel_diff"],
                                        check["loss_rel_tol"]),
        "loss_last_less_first": H.compared(
            float(np.mean(losses[-k:])) - float(np.mean(losses[:k]))
            if len(losses) >= 2 else 0.0, 0.0),
        "window_compiles": H.compared(window_compiles.count, 0),
        "routes_as_expected": H.compared(int(routes_ok), 1)}
    rate = n * tokens_per_step / window / cell.chips
    values = {"setup_s": setup_s, "train_tokens_per_s": rate}
    per_token = flops.train_flops_per_token(cell.config, seq)
    line: Dict[str, Any] = {
        "phase": "window", "cell": cell.name, "seed": seed,
        "seconds": seconds, "window_s": window, "steps": n,
        "tokens_per_step": tokens_per_step, "step_s": window / n,
        "loss_first": float(np.mean(losses[:k])),
        "loss_last": float(np.mean(losses[-k:])),
        "zero_stage": int(engine.config.zero_optimization.stage),
        "compiles": {"setup": setup_compiles.count,
                     "setup_seconds": setup_compiles.seconds,
                     "cache_hits": setup_compiles.cache_hits,
                     "cache_misses": setup_compiles.cache_misses,
                     "window": window_compiles.count},
        "compile_cache": cache_dir,
        "routes": {r: taken.get(r) for r in want}, "values": values}
    if not allow_cpu:
        peaks = H.peaks_for(device["kind"])
        line["mfu_pct"] = 100.0 * rate * per_token / peaks["bf16_flops_per_s"]
    H.log(line)
    H.write_out(f"losses-{cell.name}-{seed}.json", {"losses": losses})

    if not trace:
        return H.result_line(correct, n, 0,
                             H.end_to_end_metrics(cell, values), device,
                             check=compared)
    reduced = tr.reduce()
    ctx = {"cell": cell, "trace": reduced,
           "trace_steps": int(sl["steps"]), "seq_len": seq,
           "tokens_per_step": tokens_per_step, "chips": cell.chips,
           "peaks": None if allow_cpu else H.peaks_for(device["kind"])}
    device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    return H.result_line(correct, n, 0, H.layer_metrics(cell, ctx), device,
                         reduced.breakdown(), check=compared)
