"""Serving cells: ``RaggedInferenceEngineV2`` behind ``Router`` and
``FrontDoorServer``, spoken to over a loopback socket by the load generator
in a process of its own.

Set-up (all of it ``setup_s``): weights on the device from ``--seed`` in one
jitted call; engine, replica set, router, server; the correctness requests,
each served alone (which also compiles or loads the fused tick and the
decode block, the only two programs the traffic uses); then the mix's own warm-up
traffic, which runs straight on into the window.  The window starts at
``t0``, a time fixed before the generator is started.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, Optional

import numpy as np

from benchmark import harness as H


def _stages(engine) -> Dict[str, Any]:
    """``serving_stages()`` read from outside the engine's thread: the
    completed-request window can change under the read, so try again."""
    for _ in range(20):
        try:
            return engine.serving_stages()
        except RuntimeError:
            time.sleep(0.001)
    return engine.serving_stages()


def _stage_seconds(st: Dict[str, Any]) -> Dict[str, float]:
    """Host seconds per stage so far (``serving_stages()`` gives
    milliseconds per dispatch), and the dispatches they were spent on."""
    d = max(int(st["dispatches"]), 1)
    out = {s: float(st[f"{s}_ms"]) * d / 1e3
           for s in ("plan", "upload", "dispatch", "harvest")}
    out["dispatches"] = int(st["dispatches"])
    return out


def _spawn_loadgen(plan: Dict[str, Any], name: str) -> subprocess.Popen:
    path = H.write_out(name, plan)
    # -S -E: nothing of the parent's environment or site reaches it; it is
    # standard library only and never touches the chip
    return subprocess.Popen(
        [sys.executable, "-S", "-E", os.path.join(H.HERE, "loadgen.py"),
         path], stdout=subprocess.PIPE)


def _collect(proc: subprocess.Popen, timeout: float) -> Dict[str, Any]:
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise H.BenchmarkError("the load generator did not end in time")
    if proc.returncode != 0:
        raise H.BenchmarkError(f"the load generator exited with "
                               f"{proc.returncode}")
    return json.loads(out)


def _warm_sampling_shapes(mcfg, max_seqs: int) -> None:
    """The fused tick's sampling is eager: the engine picks the rows that
    want a token with ``sel_logits[rows]`` and calls ``sample_logits`` on
    them, so every distinct NUMBER of such rows (1..max_seqs) is a handful
    of tiny programs of its own.  Traffic reaches a new count at any time,
    and nothing may compile inside the window, so all of them are run once
    here, through the same public function on the same shapes.  (That the
    hot loop compiles by batch composition at all is the program's to
    repair: PERF.md, Open questions.)"""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.sampling import sample_logits

    sel = jnp.zeros((max_seqs, mcfg.vocab_size), mcfg.dtype)
    for k in range(1, max_seqs + 1):
        sample_logits(sel[np.asarray(list(range(k)))], None,
                      do_sample=False, temperature=1.0, top_k=0, top_p=1.0)


def _pool_filled(cell: H.Cell, records, engine_records, clock_offset: float,
                 t0: float, seconds: float, num_pages: int
                 ) -> Optional[Dict[str, float]]:
    """How much of the reserved KV pool the window's traffic really held:
    pages (a request's tokens rounded up to whole pages) over the engine's
    admit -> finish spans, sampled every 50 ms, against the usable pages of
    the engine's own pool (``num_pages`` less the null page, as the
    dispatch spans' ``pages_total`` and ``kv_pool_peak_pct`` have it: what
    a token costs is the pool's to know, not ``flops.py``'s).
    ``memory_peak_bytes`` counts the whole reserved pool whatever the
    traffic holds of it."""
    from benchmark.layer_metrics import windows as W

    spans = W.request_spans(records, engine_records, clock_offset)
    eng = cell.config["engine"]
    if spans is None or "kv_pool_bytes" not in eng:
        return None
    page = int(eng["page_size"])
    pages_total = num_pages - 1
    held = [sum(-(-W.kv_tokens_held(s, t0 + k * 0.05) // page)
                for s in spans) for k in range(max(int(seconds / 0.05), 1))]
    return {"pages_total": pages_total,
            "mean_share": float(np.mean(held)) / pages_total,
            "peak_share": float(max(held)) / pages_total,
            "peak_bytes": float(max(held)) * int(eng["kv_pool_bytes"])
            / num_pages}


def _check_alone(cell: H.Cell, srv, params, seed: int) -> Dict[str, Any]:
    """Seeded prompts served alone over the socket, one after the other,
    greedy; every generated token's float32 reference logit must lie within
    the configuration's ``logit_margin`` of the reference maximum, and where
    the configuration gives ``logit_mean_margin`` the MEAN of those gaps
    over all the prompts' tokens within that (a configuration states either
    or both: where one discrete choice decided otherwise in bf16 moves one
    token's logits by more than the lower precision moves most, the widest
    gap does not separate the two and the mean does).  A long prompt crosses
    prefill chunks and pages; a short one is where a wrong mask shows (among
    a dozen keys one key too many moves the logits by units, among 700 by
    under the margin)."""
    from benchmark import loadgen

    spec = cell.config["correct"]
    ref = H.plugin("reference", cell.config["family"]["reference"])
    rng = np.random.default_rng([int(seed), 0xC0])
    gaps = []
    for n in spec["prompt_tokens"]:
        prompt = rng.integers(1, int(cell.config["vocab_size"]),
                              size=int(n)).tolist()
        plan = {"host": srv.host, "port": srv.port, "loop": "open",
                "t0": H.now(), "window_s": 0.0, "drain_s": 1200.0,
                "keep_tokens": True,
                "requests": [{"due": 0.0, "prompt": prompt,
                              "max_new_tokens": int(spec["new_tokens"])}]}
        rec = loadgen.run_plan(plan)["records"][0]
        if not rec["ok"]:
            return {"ok": False, "prompt_tokens": int(n),
                    "error": rec["error"]}
        gaps.append(ref.generated_gaps(params, cell.config, prompt,
                                       rec["generated"]))
    worst = [float(g.max()) for g in gaps]
    mean = float(np.mean(np.concatenate(gaps)))
    limits = {k: float(spec[f"logit_{k}"])
              for k in ("margin", "mean_margin") if f"logit_{k}" in spec}
    if not limits:
        raise H.BenchmarkError("the configuration's ``correct`` states "
                               "neither logit_margin nor logit_mean_margin")
    return {"ok": bool(np.all(np.isfinite(worst)) and
                       max(worst) <= limits.get("margin", np.inf) and
                       mean <= limits.get("mean_margin", np.inf)),
            "prompt_tokens": [int(n) for n in spec["prompt_tokens"]],
            "max_gap": worst, "mean_gap": mean,
            "exact": [int((g == 0).sum()) for g in gaps],
            "tokens": int(spec["new_tokens"]), **limits}


def run(cell: H.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, allow_cpu: bool = False,
        rate_per_s: Optional[float] = None) -> str:
    """Run the cell; returns the last line.  ``allow_cpu`` is for the
    drivers' own CPU tests, ``rate_per_s`` for the knee sweep: function
    arguments, reachable from no command line of ``run.py``."""
    devices = H.require_chips(cell.chips, allow_cpu)
    import jax

    from deepspeed_tpu.inference.v2.ragged_engine import \
        RaggedInferenceEngineV2
    from deepspeed_tpu.serving import FrontDoorServer, ReplicaSet, Router
    from deepspeed_tpu.telemetry.compiles import count_compiles

    cache_dir = H.enable_compile_cache()
    traffic = dict(cell.traffic)
    if rate_per_s is not None:
        traffic["rate_per_s"] = float(rate_per_s)
    mcfg = H.build_model_config(cell.config)
    model = H.resolve(cell.config["family"]["module"])(mcfg)

    timeline = {"imports_and_devices_s": H.now() - t_process}
    with count_compiles() as setup_compiles:
        params = H.init_params(model, cell.config, seed)
        rs = ReplicaSet(lambda i: RaggedInferenceEngineV2(
            model, params=params, **cell.config["engine"]), 1)
        jax.block_until_ready(params)
        timeline["weights_and_engine_s"] = H.now() - t_process
        _warm_sampling_shapes(mcfg, int(cell.config["engine"]["max_seqs"]))
        timeline["sampling_shapes_s"] = H.now() - t_process
    try:
        engine = rs.handles[0].engine
        router = Router(rs, **cell.config.get("router", {}))
        srv = FrontDoorServer(router, host="127.0.0.1", port=0).start()
        try:
            with count_compiles() as check_compiles:
                check = _check_alone(cell, srv, params, seed)
            timeline["correct_alone_s"] = H.now() - t_process
            H.log({"phase": "correct_alone", **check})

            gen = H.plugin("generators", traffic["generator"])
            plan = gen.make(traffic, seed, seconds,
                            int(cell.config["vocab_size"]))
            warmup, drain = plan["warmup_s"], float(traffic["drain_s"])
            t0 = H.now() + warmup + 1.5
            plan.update(host=srv.host, port=srv.port, t0=t0,
                        window_s=float(seconds), drain_s=drain)
            proc = _spawn_loadgen(plan, f"plan-{cell.name}-{seed}.json")
            try:
                H.sleep_until(t0)
                setup_s = t0 - t_process
                before = _stage_seconds(_stages(engine))
                tr = H.TraceSlice(cell.name, seed) if trace else None
                with count_compiles() as window_compiles:
                    if tr is not None:
                        sl = traffic["trace_slice"]
                        start = min(float(sl["start_s"]), seconds * 0.3)
                        H.sleep_until(t0 + start)
                        tr.start()
                        H.sleep_until(tr.t_start + min(
                            float(sl["length_s"]), seconds * 0.4))
                        tr.stop()
                    H.sleep_until(t0 + seconds)
                    after = _stage_seconds(_stages(engine))
                gen_out = _collect(proc, timeout=drain + 60.0)
            finally:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
        finally:
            srv.close()
        records = gen_out["records"]
        stages = _stages(engine)
        engine_records = engine.request_latency.completed()
        routes = dict(stages["kernels"])
        evictions = int(engine.evictions)
        num_pages = int(engine.num_pages)
        device = H.device_block(devices)
    finally:
        rs.close()

    t1 = t0 + seconds
    loop = plan["loop"]
    if loop == "open":
        sample = [r for r in records if t0 <= r["due_t"] < t1]
    else:                       # closed: what completed inside the window
        sample = [r for r in records
                  if r["done_t"] is not None and t0 <= r["done_t"] < t1]
        # a request cut off by the window's end is not a failure of the
        # system; one that ended in an error is
        sample += [r for r in records if r["done_t"] is None and
                   r["error"] != "unfinished_at_drain_end" and
                   t0 <= r["end_t"] < t1]
    failed = [r for r in sample if not r["ok"]]
    end_of_drain = gen_out["ended_t"]

    def ttft(r):                # from DUE; a failure counts as the worst
        return ((r["first_t"] if r["ok"] else end_of_drain) - r["due_t"])

    def tpot(r):
        if not r["ok"]:
            return end_of_drain - r["due_t"]
        return (r["last_t"] - r["first_t"]) / max(r["streamed"] - 1, 1)

    # every ``<what>_p<q>_ms`` a manifest can name: the percentile is data
    values: Dict[str, float] = {"setup_s": setup_s}
    if sample:
        ttfts = [ttft(r) for r in sample]
        tpots = [tpot(r) for r in sample if r["max_new_tokens"] > 1]
        for q in (50, 90, 95, 99):
            values[f"ttft_p{q}_ms"] = 1e3 * H.percentile(ttfts, q)
            values[f"tpot_p{q}_ms"] = 1e3 * H.percentile(tpots, q)
    done = [r for r in sample if r["ok"]]
    tokens_done = sum(r["prompt_tokens"] + r["max_new_tokens"]
                      for r in done if t0 <= r["done_t"] < t1)
    values["total_tokens_per_s"] = tokens_done / seconds

    # the window in fifths: where a pile-up began, if there was one
    fifths = [[r for r in sample
               if int(5 * (r["due_t"] - t0) / seconds) == k]
              for k in range(5)]
    by_fifth = {"requests": [len(f) for f in fifths],
                "failed": [sum(not r["ok"] for r in f) for f in fifths],
                "ttft_p50_ms": [1e3 * H.percentile([ttft(r) for r in f], 50)
                                if f else None for f in fifths]}

    want = cell.config.get("expected_routes", {})
    routes_ok = allow_cpu or all(routes.get(k) == v
                                 for k, v in want.items())
    clock_offset = time.perf_counter() - H.now()
    correct = (check["ok"] and not failed and bool(sample) and routes_ok
               and window_compiles.count == 0)
    compared = {"requests_in_sample_at_least": H.compared(len(sample), 1),
                "failed_requests": H.compared(len(failed), 0),
                "window_compiles": H.compared(window_compiles.count, 0),
                "routes_as_expected": H.compared(int(routes_ok), 1)}
    if "max_gap" in check:
        if "margin" in check:
            compared["logit_gap_max"] = H.compared(max(check["max_gap"]),
                                                   check["margin"])
        if "mean_margin" in check:
            compared["logit_gap_mean"] = H.compared(check["mean_gap"],
                                                    check["mean_margin"])
    else:                       # a correctness request failed outright
        compared["check_requests_ok"] = H.compared(0, 1)
    H.log({"phase": "window", "cell": cell.name, "seed": seed,
           "seconds": seconds, "loop": loop,
           "rate_per_s": traffic.get("rate_per_s"),
           "requests_in_sample": len(sample), "failed": len(failed),
           "by_fifth": by_fifth, "evictions": evictions,
           "kv_pool_filled": _pool_filled(cell, records, engine_records,
                                          clock_offset, t0, seconds,
                                          num_pages),
           "errors": sorted({str(r["error"]) for r in failed}),
           "out_tokens_per_s": sum(r["max_new_tokens"] for r in done)
           / seconds,
           "compiles": {"setup": setup_compiles.count,
                        "check": check_compiles.count,
                        "check_seconds": check_compiles.seconds,
                        "cache_hits": check_compiles.cache_hits,
                        "cache_misses": check_compiles.cache_misses,
                        "window": window_compiles.count},
           "compile_cache": cache_dir, "routes": routes,
           "setup_timeline": timeline, "values": values})
    H.write_out(f"records-{cell.name}-{seed}.json",
                {"t0": t0, "records": records,
                 "engine_records": engine_records})

    if not trace:
        metrics = H.end_to_end_metrics(cell, values)
        return H.result_line(correct, len(sample), len(failed), metrics,
                             device, check=compared)
    reduced = tr.reduce()
    ctx = {"cell": cell, "t0": t0, "seconds": seconds,
           "engine_clock_offset": clock_offset,
           "sample": sample, "records": records,
           "engine_records": engine_records,
           "stages_before": before, "stages_after": after,
           "trace": reduced, "trace_t": (tr.t_start, tr.t_stop),
           "peaks": H.peaks_for(device["kind"]) if not allow_cpu else None}
    metrics = H.layer_metrics(cell, ctx)
    device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    return H.result_line(correct, len(sample), len(failed), metrics, device,
                         reduced.breakdown(), check=compared)
