#!/usr/bin/env python
"""Serving smoke: the pipelined host loop's correctness gate (CI-grade).

The serving analogue of ``scripts/chaos_train.py``: runs the ragged
engine for a few hundred greedy tokens under the pipelined and the
unpipelined host loop and exits NONZERO if

- the pipelined greedy output diverges from the ``pipeline=False``
  reference, or
- the pipelined run's dispatch accounting regresses to per-block syncs.

With ``--kv-tiering`` it additionally gates the tiered paged-KV store:
a deliberately tiny HBM pool forces spill/restore traffic, and the run
exits NONZERO if the tiering-on greedy output diverges from the
tiering-off reference, if no spill actually happened (the gate would
be vacuous), or if any restored page skipped digest verification.

With ``--prefix-cache`` it additionally gates the cross-request prefix
cache: a shared-system-prompt workload must produce greedy output
bit-identical to the cache-off reference, must actually HIT the index
(nonzero hit rate — the gate would be vacuous otherwise), and
``audit_kv_sharing()`` (per-page refcount conservation over slots,
index entries, and spill-holds) must hold after the drain.

With ``--kv-quant`` it additionally gates the quantized paged-KV pool:
the pool must really be quantized (1-byte payload pages plus fp32 scale
rows — the gate is vacuous otherwise, enforced against a full-width
control at <=0.5x the bytes), quantized greedy decode must be
deterministic and bit-identical across tiering on/off (spilled pages
carry the quantized payload, digest-verified), the refcount audit must
hold after the drain, and a teacher-forced lockstep against the
full-width pool must stay inside the measured quality envelope
(per-tick greedy divergence and logit error — quantization is a
bounded approximation, not a different model).

With ``--trace`` it additionally gates the unified tracer: a serving
run with ``DSTPU_TRACE``-style tracing enabled must export a
schema-valid Chrome trace carrying both serving-stage spans and
request lifecycle events, the engine must surface non-None TTFT/TPOT
percentiles, and the tracer-on wall clock must stay within 5% of
tracer-off (min of 3 runs each) — tracing is observability, not a tax.

With ``--metrics`` it additionally gates the metrics/SLO layer: the
registry's Prometheus exposition must parse line-for-line (labels,
cumulative bucket monotonicity, ``+Inf`` bucket == ``_count``), the
histogram-derived TTFT/TPOT p50/p99 must agree with the tracker's
nearest-rank percentiles within one bucket width, tail-based trace
sampling must retain a structurally slow request (3 requests over 2
seats — the queued one's TTFT breaches a calibrated SLO) while
dropping the fast ones, and metrics+sampling-on wall must stay within
5% of all-off (min of 3 runs each).

With ``--elastic`` it additionally gates elastic serving: one replica
grows to two mid-traffic (the newcomer prefix-warmed from the donor),
then the original retires — parked sessions (including one with
SPILLED private KV pages) travel to the survivor in spill format with
the donor's spill-time digests, in-flight requests finish in place —
and the run exits NONZERO if any request is lost or duplicated, if any
greedy output diverges from a static single engine, if the shrink
handed off nothing (vacuous), or if any restored page on the survivor
skipped digest verification.

With ``--chaos`` it additionally gates serving fault tolerance: a
compact seeded campaign over a live socket — one wedged replica (the
liveness watchdog abandons it and the breaker re-dispatches its
streams), one mid-decode replica death (greedy streams replay with
exactly-once tokens on the wire), one failing NVMe device (the KV tier
trips offline and serving degrades host-only) — exiting NONZERO if any
request is lost or duplicated, any survivor output diverges from an
unfaulted reference, any page/tier audit breaks, any fault class
leaves no parseable flight dump, or the watchdog-armed no-fault wall
clock regresses more than 5% over disarmed (min of 3 runs each).

With ``--autotune`` it additionally gates the closed-loop control
plane: a deliberately mis-tuned engine (harvest_interval=1,
async_depth=1) served by the online controller must converge back to
at least the hand-tuned knob settings and within 10% of hand-tuned
throughput, with zero oscillation-guard violations, every knob change
attributable to a named signal in the schema-valid trace export, and
the controller-armed wall clock within 5% of controller-off on the
already-tuned config (min of 3 runs each).

    JAX_PLATFORMS=cpu python scripts/serve_smoke.py [--tokens 250]
    JAX_PLATFORMS=cpu python scripts/serve_smoke.py --kv-tiering
    JAX_PLATFORMS=cpu python scripts/serve_smoke.py --prefix-cache
    JAX_PLATFORMS=cpu python scripts/serve_smoke.py --kv-quant
    JAX_PLATFORMS=cpu python scripts/serve_smoke.py --trace
    JAX_PLATFORMS=cpu python scripts/serve_smoke.py --metrics
    JAX_PLATFORMS=cpu python scripts/serve_smoke.py --elastic
    JAX_PLATFORMS=cpu python scripts/serve_smoke.py --chaos
    JAX_PLATFORMS=cpu python scripts/serve_smoke.py --autotune
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tokens", type=int, default=250,
                   help="max_new_tokens per request (2 requests)")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--kv-tiering", action="store_true",
                   help="also gate the tiered paged-KV store (tiny "
                        "pool, spill/restore parity + verified "
                        "restores)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="also gate the cross-request prefix cache "
                        "(shared-prompt parity vs cache-off, nonzero "
                        "hit rate, refcount-audit conservation)")
    p.add_argument("--kv-quant", action="store_true",
                   help="also gate the quantized paged-KV pool "
                        "(1-byte pages + scales, deterministic, "
                        "tiering parity over quantized bytes, "
                        "teacher-forced quality envelope)")
    p.add_argument("--trace", action="store_true",
                   help="also gate the unified tracer (schema-valid "
                        "Chrome-trace export, request latency "
                        "percentiles, <=5%% tracer-on wall overhead)")
    p.add_argument("--metrics", action="store_true",
                   help="also gate the metrics/SLO layer (exposition "
                        "parses, histogram vs nearest-rank percentile "
                        "agreement, tail sampling keeps the slow "
                        "request, <=5%% metrics-on wall overhead)")
    p.add_argument("--router", action="store_true",
                   help="also gate scale-out serving (2 replicas, "
                        "mixed-priority open-loop workload: greedy "
                        "outputs bit-identical to single-engine, both "
                        "replicas served traffic, admission sheds "
                        "loudly at the queue cap)")
    p.add_argument("--frontdoor", action="store_true",
                   help="also gate the network front door (SSE "
                        "streaming over a real socket bit-identical "
                        "to in-process serving, client disconnect "
                        "reclaims pool pages audit-verified, burned "
                        "deadline is a typed 429, SIGTERM drain "
                        "finishes in-flight streams with zero dropped "
                        "tokens)")
    p.add_argument("--elastic", action="store_true",
                   help="also gate elastic serving (grow 1->2 then "
                        "retire the original under open-loop traffic: "
                        "request conservation, greedy bit-parity vs a "
                        "static single engine, parked sessions handed "
                        "off in spill format and restored "
                        "digest-verified on the survivor)")
    p.add_argument("--disagg", action="store_true",
                   help="also gate disaggregated serving (1 prefill + "
                        "1 decode replica under a bimodal prompt mix: "
                        "request conservation, greedy bit-parity vs "
                        "one fused replica, every handoff "
                        "digest-verified on the receiver, and the "
                        "corrupted-wire leg healing by fold to "
                        "re-prefill)")
    p.add_argument("--chaos", action="store_true",
                   help="also gate serving fault tolerance (one "
                        "replica hang, one mid-stream death, one NVMe "
                        "fault over a live socket: request "
                        "conservation, greedy bit-parity on the "
                        "survivor, clean audits, parseable flight "
                        "dumps, <=5%% watchdog-armed wall overhead)")
    p.add_argument("--autotune", action="store_true",
                   help="also gate the closed-loop control plane "
                        "(mis-tuned engine converges to hand-tuned "
                        "knobs and >=90%% of hand-tuned tok/s, zero "
                        "guard violations, every decision in the "
                        "trace export, <=5%% armed wall overhead)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config

    max_len = args.tokens + 50
    cfg = get_config("tinyllama", vocab_size=64, hidden_size=32,
                     intermediate_size=64, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=2,
                     max_position_embeddings=max(max_len, 128),
                     dtype=jnp.float32, param_dtype=jnp.float32,
                     scan_layers=True, remat=False,
                     use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed),
                                 np.zeros((1, 8), np.int32))

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
               for n in (9, 14)]

    def run(pipeline):
        eng = RaggedInferenceEngineV2(
            LlamaForCausalLM(cfg), params=params, max_seqs=2,
            max_seq_len=max_len, prefill_chunk=16, decode_block_size=8,
            pipeline=pipeline, rng=jax.random.PRNGKey(args.seed))
        outs = eng.generate_all(list(prompts),
                                max_new_tokens=args.tokens)
        return outs, eng

    ref, _ = run(False)
    failures = 0
    outs, eng = run(True)
    ok = sorted(outs) == sorted(ref) and all(
        np.array_equal(outs[u], ref[u]) for u in ref)
    if not ok:
        print("FAIL [pipeline]: greedy output diverged from "
              "pipeline=False")
        failures += 1
    st = eng.host_stats
    if st.blocking_gets >= st.dispatches and st.dispatches > 4:
        print("FAIL [pipeline]: the pipelined run syncs per block "
              f"({st.blocking_gets} gets / {st.dispatches} dispatches)")
        failures += 1
    print(f"[pipeline] ok={ok} blocking_gets={st.blocking_gets} "
          f"dispatches={st.dispatches}")
    if args.kv_tiering:
        # tiny pool: four sequences cannot all stay HBM-resident, so
        # the engine must spill/restore to finish them — and the
        # output must still match the tiering-off run bit-for-bit
        tier_kw = dict(max_seqs=4, page_size=16, num_pages=9,
                       prefill_chunk=16, decode_block_size=4)
        tier_prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
                        for n in (12, 20, 9, 16)]

        def tier_run(tiering):
            eng = RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seq_len=128,
                kv_tiering=tiering, rng=jax.random.PRNGKey(args.seed),
                **tier_kw)
            outs = eng.generate_all(list(tier_prompts),
                                    max_new_tokens=40)
            return outs, eng

        t_ref, _ = tier_run(None)
        t_on, t_eng = tier_run({"host_pages": 64})
        st = t_eng.tiering.stats()
        ok = sorted(t_on) == sorted(t_ref) and all(
            np.array_equal(t_on[u], t_ref[u]) for u in t_ref)
        if not ok:
            print("FAIL [kv-tiering]: tiering-on greedy output diverged "
                  "from tiering-off")
            failures += 1
        if not st["spills"] > 0:
            print("FAIL [kv-tiering]: no spill traffic — the gate ran "
                  f"vacuously ({st})")
            failures += 1
        if st["pages_verified"] != st["pages_restored"]:
            print("FAIL [kv-tiering]: unverified restore: "
                  f"{st['pages_restored']} pages restored, only "
                  f"{st['pages_verified']} digest-verified")
            failures += 1
        print(f"[kv-tiering] ok={ok} spills={st['spills']} "
              f"restores={st['restores']} evictions={t_eng.evictions} "
              f"pages_verified={st['pages_verified']}/"
              f"{st['pages_restored']}")
        t_eng.close()
    if args.prefix_cache:
        # shared-system-prompt workload: 8 sessions over 4 seats share
        # two full pages of system prompt, one repeats another verbatim
        # (full match -> copy-on-write) — later waves must attach the
        # first wave's pages, and greedy output must not move a bit
        pc_kw = dict(max_seqs=4, page_size=16, num_pages=21,
                     prefill_chunk=16, decode_block_size=4,
                     kv_reserve="on_demand")
        sys_prompt = rng.integers(1, 64, size=(32,), dtype=np.int32)
        pc_prompts = [
            np.concatenate([sys_prompt,
                            rng.integers(1, 64, size=(16,),
                                         dtype=np.int32)])
            for _ in range(7)]
        pc_prompts.append(pc_prompts[0].copy())      # full-match/COW

        def pc_run(prefix):
            eng = RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seq_len=128,
                prefix_cache=prefix, rng=jax.random.PRNGKey(args.seed),
                **pc_kw)
            outs = eng.generate_all(list(pc_prompts),
                                    max_new_tokens=24)
            return outs, eng

        p_ref, _ = pc_run(False)
        p_on, p_eng = pc_run(True)
        pc = p_eng.serving_stages()["prefix_cache"]
        ok = sorted(p_on) == sorted(p_ref) and all(
            np.array_equal(p_on[u], p_ref[u]) for u in p_ref)
        if not ok:
            print("FAIL [prefix-cache]: cache-on greedy output diverged "
                  "from cache-off")
            failures += 1
        if not pc["hit_requests"] > 0 or not pc["hit_rate"] > 0:
            print("FAIL [prefix-cache]: zero hit rate — the gate ran "
                  f"vacuously ({pc})")
            failures += 1
        try:
            p_eng.audit_kv_sharing()
        except AssertionError as e:
            print(f"FAIL [prefix-cache]: refcount audit failed: {e}")
            failures += 1
        rl = p_eng.request_latency.summary()
        print(f"[prefix-cache] ok={ok} hit_rate={pc['hit_rate']} "
              f"hit_requests={pc['hit_requests']} "
              f"hit_tokens={pc['hit_tokens']} "
              f"cow_copies={pc['cow_copies']} "
              f"prefill_computed={rl['prefill_computed_tokens']} "
              f"prefill_cached={rl['prefill_cached_tokens']}")
        p_eng.close()
    if args.kv_quant:
        import dataclasses

        from deepspeed_tpu.inference.common import unroll_scan_params

        kq_kw = dict(max_seqs=4, page_size=16, num_pages=9,
                     prefill_chunk=16, decode_block_size=4)
        kq_prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
                      for n in (12, 20, 9, 16)]

        def kq_run(fmt, tiering=None):
            eng = RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seq_len=128,
                kv_cache_dtype=fmt, kv_tiering=tiering,
                rng=jax.random.PRNGKey(args.seed), **kq_kw)
            outs = eng.generate_all(list(kq_prompts), max_new_tokens=40)
            return outs, eng

        q_a, q_eng = kq_run("int8")
        _, f_eng = kq_run("none")
        leaves = jax.tree_util.tree_leaves(q_eng.cache)
        payload = [lf for lf in leaves
                   if np.dtype(lf.dtype).itemsize == 1]
        scales = [lf for lf in leaves
                  if np.dtype(lf.dtype).itemsize != 1]
        if not payload or not scales:
            print("FAIL [kv-quant]: pool is not quantized "
                  f"({len(payload)} payload / {len(scales)} scale "
                  "leaves) — the gate ran vacuously")
            failures += 1
        bytes_ratio = q_eng.cache_bytes() / max(f_eng.cache_bytes(), 1)
        if not bytes_ratio <= 0.5:
            print("FAIL [kv-quant]: quantized pool is "
                  f"{bytes_ratio:.3f}x the full-width pool's bytes — "
                  "expected <=0.5x at the same page count")
            failures += 1
        kq = q_eng.serving_stages().get("kv_quant") or {}
        if kq.get("format") != "int8" or not kq.get(
                "scale_rows_written", 0) > 0:
            print(f"FAIL [kv-quant]: kv_quant stats block missing or "
                  f"unwritten ({kq})")
            failures += 1
        q_b, _ = kq_run("int8")
        det = sorted(q_a) == sorted(q_b) and all(
            np.array_equal(q_a[u], q_b[u]) for u in q_a)
        if not det:
            print("FAIL [kv-quant]: quantized greedy decode is not "
                  "deterministic across identical runs")
            failures += 1
        t_on, qt_eng = kq_run("int8", {"host_pages": 64})
        st = qt_eng.tiering.stats()
        tier_ok = sorted(t_on) == sorted(q_a) and all(
            np.array_equal(t_on[u], q_a[u]) for u in q_a)
        if not tier_ok:
            print("FAIL [kv-quant]: tiering-on quantized output "
                  "diverged — spill/restore must carry the quantized "
                  "payload byte-identically")
            failures += 1
        if not st["spills"] > 0:
            print("FAIL [kv-quant]: no spill traffic under the "
                  f"quantized pool — the tier leg ran vacuously ({st})")
            failures += 1
        if st["pages_verified"] != st["pages_restored"]:
            print("FAIL [kv-quant]: unverified quantized restore: "
                  f"{st['pages_restored']} restored, "
                  f"{st['pages_verified']} verified")
            failures += 1
        try:
            qt_eng.audit_kv_sharing()
        except AssertionError as e:
            print(f"FAIL [kv-quant]: refcount audit failed: {e}")
            failures += 1
        qt_eng.close()

        # teacher-forced lockstep vs the full-width pool: both pools
        # replay the SAME token stream, so per-tick logit error and
        # greedy divergence measure quantization alone (no trajectory
        # compounding).  The envelope is generous against the measured
        # smoke numbers (bench kv_quant: ~2.5% divergence, max err
        # ~0.06 for int8) — this is a broken-kernel tripwire, not a
        # quality benchmark.
        lk_page, lk_len = 16, 64
        pp_q = lk_len // lk_page

        def lk_mk(fmt):
            pcfg = dataclasses.replace(
                cfg, decode=True, ragged_decode=False, paged_decode=True,
                max_cache_len=lk_len, scan_layers=False,
                kv_page_size=lk_page, kv_num_pages=pp_q + 1,
                tensor_parallel=False, kv_cache_dtype=fmt)
            pmodel = LlamaForCausalLM(pcfg)

            @jax.jit
            def tick(cache, tok, pos):
                meta = {"kv_lens": (pos + 1)[None].astype(jnp.int32),
                        "page_indices": jnp.arange(
                            1, pp_q + 1, dtype=jnp.int32)[None],
                        "cu_q_lens": jnp.asarray([0, 1], jnp.int32),
                        "num_seqs": jnp.asarray([1], jnp.int32),
                        "new_kv_dest": (lk_page + pos)[None].astype(
                            jnp.int32)}
                pp = params["params"] if "params" in params else params
                if getattr(cfg, "scan_layers", False):
                    pp = unroll_scan_params(pp)
                out, mut = pmodel.apply(
                    {"params": pp, "cache": cache}, tok[None, None],
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

        f_tick, f_cache = lk_mk("none")
        q_tick, q_cache = lk_mk("int8")
        prompt = rng.integers(1, 64, size=(8,), dtype=np.int32)
        max_err, diverged, compared, tok = 0.0, 0, 0, None
        for pos in range(lk_len - 1):
            t_in = (jnp.asarray(prompt[pos], jnp.int32)
                    if pos < len(prompt) else tok)
            p_in = jnp.asarray(pos, jnp.int32)
            fl, f_cache = f_tick(f_cache, t_in, p_in)
            ql, q_cache = q_tick(q_cache, t_in, p_in)
            max_err = max(max_err, float(jnp.max(jnp.abs(fl - ql))))
            if pos >= len(prompt) - 1:
                compared += 1
                diverged += int(int(jnp.argmax(fl)) !=
                                int(jnp.argmax(ql)))
                tok = jnp.argmax(fl).astype(jnp.int32)
        div_rate = diverged / max(compared, 1)
        if not np.isfinite(max_err) or max_err > 1.0:
            print(f"FAIL [kv-quant]: lockstep logit error {max_err} "
                  "out of envelope (<=1.0) — dequant path is broken, "
                  "not merely approximate")
            failures += 1
        if div_rate > 0.25:
            print(f"FAIL [kv-quant]: teacher-forced greedy divergence "
                  f"{div_rate:.3f} over {compared} ticks exceeds the "
                  "0.25 envelope")
            failures += 1
        print(f"[kv-quant] det={det} tier_ok={tier_ok} "
              f"bytes_ratio={bytes_ratio:.3f} spills={st['spills']} "
              f"verified={st['pages_verified']}/{st['pages_restored']} "
              f"lockstep_max_err={max_err:.4f} "
              f"divergence={div_rate:.3f}/{compared}t")
    if args.trace:
        import tempfile
        import time

        import trace_summarize

        from deepspeed_tpu import telemetry

        def timed(enabled):
            telemetry.configure(enabled=enabled)
            telemetry.trace.clear()
            t0 = time.perf_counter()
            _, eng = run(True)
            return time.perf_counter() - t0, eng

        # the reference run above already warmed the jit caches; min of
        # 3 damps scheduler noise so the 5% gate measures the tracer,
        # not the machine
        off_wall = min(timed(False)[0] for _ in range(3))
        on_wall, t_eng = float("inf"), None
        for _ in range(3):
            w, eng = timed(True)
            if w < on_wall:
                on_wall, t_eng = w, eng
        trace_path = os.path.join(
            tempfile.mkdtemp(prefix="serve_trace_"), "serve_trace.json")
        telemetry.trace.export(trace_path)
        telemetry.configure(enabled=False)
        try:
            events, _ = trace_summarize.load_events(trace_path)
            problems = trace_summarize.validate_events(events)
        except (ValueError, OSError) as e:
            events, problems = [], [str(e)]
        if problems:
            for msg in problems[:5]:
                print(f"FAIL [trace]: malformed trace: {msg}")
            failures += 1
        cats = {ev.get("cat") for ev in events}
        for want in ("serving", "request"):
            if want not in cats:
                print(f"FAIL [trace]: no {want!r}-category events in "
                      f"the export (cats={sorted(c for c in cats if c)})")
                failures += 1
        req = t_eng.serving_stages()["requests"]
        for key in ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50",
                    "queue_wait_ms_p50"):
            if req.get(key) is None:
                print(f"FAIL [trace]: request latency percentile "
                      f"{key} is None ({req})")
                failures += 1
        overhead = (on_wall - off_wall) / off_wall
        if overhead > 0.05:
            print(f"FAIL [trace]: tracer-on wall regressed "
                  f"{overhead * 100:.1f}% (off={off_wall:.3f}s "
                  f"on={on_wall:.3f}s)")
            failures += 1
        print(f"[trace] events={len(events)} overhead="
              f"{overhead * 100:+.1f}% ttft_p50={req.get('ttft_ms_p50')}ms "
              f"tpot_p50={req.get('tpot_ms_p50')}ms exported={trace_path}")
    if args.metrics:
        import re
        import time

        from deepspeed_tpu import telemetry
        from deepspeed_tpu.telemetry import metrics as metrics_mod

        reg = metrics_mod.metrics
        # 3 requests over 2 seats: the queued request's TTFT includes a
        # full generation of queue wait — structurally slow, no sleeps
        m_prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
                     for n in (9, 14, 11)]

        def m_run(**kw):
            eng = RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seqs=2,
                max_seq_len=max_len, prefill_chunk=16,
                decode_block_size=8,
                rng=jax.random.PRNGKey(args.seed), **kw)
            outs = eng.generate_all(list(m_prompts), max_new_tokens=60)
            return outs, eng

        # ---- exposition + percentile agreement (fresh registry) -----
        reg.reset()
        reg.configure(enabled=True)
        _, m_eng = m_run()
        text = reg.export_text()
        line_re = re.compile(
            r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
            r'(\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*\})?'
            r' (\+Inf|-?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?))$')
        lab_re = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
        series = {}
        bad_lines = 0
        for ln in text.splitlines():
            if not ln or ln.startswith("#"):
                continue
            m = line_re.match(ln)
            if m is None:
                bad_lines += 1
                if bad_lines <= 3:
                    print("FAIL [metrics]: unparseable exposition "
                          f"line: {ln!r}")
                continue
            raw = m.group(3)
            series[(m.group(1), m.group(2) or "")] = (
                float("inf") if raw == "+Inf" else float(raw))
        if bad_lines:
            failures += 1
        bucket_runs = {}                # (family, labels-minus-le) -> rows
        count_vals = {}
        for (name, labstr), val in series.items():   # dict = file order
            labs = dict(lab_re.findall(labstr))
            if name.endswith("_bucket") and "le" in labs:
                le_raw = labs.pop("le")
                le = (float("inf") if le_raw == "+Inf" else float(le_raw))
                key = (name[:-len("_bucket")], tuple(sorted(labs.items())))
                bucket_runs.setdefault(key, []).append((le, val))
            elif name.endswith("_count"):
                count_vals[(name[:-len("_count")],
                            tuple(sorted(labs.items())))] = val
        if not bucket_runs or ("dstpu_request_ttft_ms",
                               (("replica", ""),)) not in bucket_runs:
            print("FAIL [metrics]: no request histograms in the "
                  "exposition — the gate ran vacuously "
                  f"({sorted(k[0] for k in bucket_runs)})")
            failures += 1
        for key, rows in sorted(bucket_runs.items()):
            les = [le for le, _v in rows]
            cums = [v for _le, v in rows]
            if les != sorted(les) or les[-1] != float("inf"):
                print(f"FAIL [metrics]: {key[0]}{dict(key[1])} bucket "
                      f"les not ascending-to-+Inf: {les}")
                failures += 1
                continue
            if any(cums[i] > cums[i + 1] for i in range(len(cums) - 1)):
                print(f"FAIL [metrics]: {key[0]}{dict(key[1])} bucket "
                      f"series not cumulative: {cums}")
                failures += 1
            if count_vals.get(key) != cums[-1]:
                print(f"FAIL [metrics]: {key[0]}{dict(key[1])} +Inf "
                      f"bucket {cums[-1]} != _count "
                      f"{count_vals.get(key)}")
                failures += 1
        probs = metrics_mod.validate_metrics_doc(reg.export_json())
        if probs:
            for msg in probs[:5]:
                print(f"FAIL [metrics]: export_json invalid: {msg}")
            failures += 1
        rl = m_eng.request_latency.summary()
        for mname in ("ttft_ms", "tpot_ms"):
            fam = reg.get(f"dstpu_request_{mname}")
            child = fam.labels(replica="") if fam is not None else None
            for q in (50, 99):
                hq = child.quantile(q) if child is not None else None
                nr = rl.get(f"{mname}_p{q}")
                if hq is None or nr is None:
                    print(f"FAIL [metrics]: {mname} p{q} missing "
                          f"(histogram={hq} nearest-rank={nr})")
                    failures += 1
                    continue
                tol = max(child.bucket_width_at(nr),
                          child.bucket_width_at(hq)) + 1e-9
                if abs(hq - nr) > tol:
                    print(f"FAIL [metrics]: {mname} p{q} histogram "
                          f"{hq:.3f} vs nearest-rank {nr:.3f} differ "
                          f"by more than one bucket width ({tol:.3f})")
                    failures += 1

        # ---- tail sampling: calibrated SLO keeps slow, drops fast ----
        comp = m_eng.request_latency.completed()
        ttfts = sorted((c["ttft_ms"], c["uid"]) for c in comp
                       if c["ttft_ms"] is not None)
        slow_ttft, slow_uid = ttfts[-1]
        fast_max = ttfts[-2][0]
        fast_uids = {uid for _t, uid in ttfts[:-1]}
        if not (len(ttfts) == 3 and slow_ttft > 2 * fast_max):
            print("FAIL [metrics]: queued request is not structurally "
                  f"slow (ttfts={ttfts}) — the sampling leg would run "
                  "vacuously")
            failures += 1
        thr = (fast_max * slow_ttft) ** 0.5     # geometric midpoint
        telemetry.trace.clear()
        telemetry.trace.configure(enabled=True, sampling=True,
                                  sample_n=0)
        _, s_eng = m_run(slo=[f"ttft_ms_p99 <= {thr:.6f}"],
                         trace_sample=0)
        st = s_eng.serving_stages()
        ts = st.get("trace_sampling") or {}
        slo_flat = st.get("slo") or {}
        retained = telemetry.trace.retained_snapshot()
        telemetry.trace.configure(enabled=False, sampling=False,
                                  sample_n=0)
        telemetry.trace.clear()
        kept_uids = {ev["args"]["uid"] for ev in retained
                     if ev.get("cat") == "request"
                     and isinstance(ev.get("args"), dict)
                     and "uid" in ev["args"]}
        if slow_uid not in kept_uids:
            print("FAIL [metrics]: breaching slow request "
                  f"uid={slow_uid} not retained (kept={kept_uids}, "
                  f"sampler={ts})")
            failures += 1
        leaked = kept_uids & fast_uids
        if leaked:
            print("FAIL [metrics]: fast requests leaked into the "
                  f"retained ring: {leaked} (sampler={ts})")
            failures += 1
        if not ts.get("promoted_breach", 0) >= 1 or \
                not ts.get("dropped", 0) >= 1:
            print(f"FAIL [metrics]: sampler counters off ({ts}) — "
                  "want >=1 breach promotion and >=1 drop")
            failures += 1
        if not slo_flat.get("ttft_ms_p99_breaches", 0) >= 1:
            print(f"FAIL [metrics]: SLO window saw no breach "
                  f"({slo_flat})")
            failures += 1

        # ---- overhead: metrics + sampling on vs all off --------------
        def m_timed(on):
            reg.configure(enabled=on)
            telemetry.trace.configure(enabled=on, sampling=on,
                                      sample_n=1 if on else 0)
            telemetry.trace.clear()
            t0 = time.perf_counter()
            m_run()
            return time.perf_counter() - t0

        m_off = min(m_timed(False) for _ in range(3))
        m_on = min(m_timed(True) for _ in range(3))
        reg.configure(enabled=True)
        telemetry.trace.configure(enabled=False, sampling=False,
                                  sample_n=0)
        telemetry.trace.clear()
        m_ovh = (m_on - m_off) / m_off
        if m_ovh > 0.05:
            print(f"FAIL [metrics]: metrics+sampling-on wall regressed "
                  f"{m_ovh * 100:.1f}% (off={m_off:.3f}s "
                  f"on={m_on:.3f}s)")
            failures += 1
        print(f"[metrics] series={len(series)} "
              f"histograms={len(bucket_runs)} "
              f"slow_uid={slow_uid} kept={sorted(kept_uids)} "
              f"thr={thr:.1f}ms overhead={m_ovh * 100:+.1f}%")
    if args.router:
        # ---- scale-out serving: router over 2 replicas ---------------
        # greedy outputs are a pure function of (prompt, params), so a
        # routed run must match the single-engine run bit-for-bit no
        # matter how the router spread the requests
        from deepspeed_tpu.serving import (QueueFullRejection,
                                           ReplicaSet, Router)

        r_prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
                     for n in (9, 14, 7, 11, 16, 8, 13, 10)]
        r_new = min(args.tokens, 24)

        def r_engine(i=0):
            return RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seqs=2,
                max_seq_len=max_len, prefill_chunk=16,
                decode_block_size=4, harvest_interval=3,
                rng=jax.random.PRNGKey(args.seed))

        # single-engine reference, same seeds, greedy
        ref_eng = r_engine()
        r_ref = {}
        order = {ref_eng.put_request(p, max_new_tokens=r_new): i
                 for i, p in enumerate(r_prompts)}
        while ref_eng.has_work():
            ref_eng.step()
            for uid, toks in ref_eng.get_outputs():
                r_ref[order[uid]] = toks
        ref_eng.sync()
        for uid, toks in ref_eng.get_outputs():
            r_ref[order[uid]] = toks

        rs = ReplicaSet(r_engine, 2)
        router = Router(rs, policy="least_tokens")
        # mixed-priority open-loop arrivals: everything submitted up
        # front, pumped between submissions (no response waiting)
        rids = {}
        for i, prompt in enumerate(r_prompts):
            rids[router.submit(prompt, priority=i % 2,
                               max_new_tokens=r_new)] = i
            router.pump()
        r_outs = router.drain()
        r_stats = router.stats()

        if sorted(rids[k] for k in r_outs) != sorted(r_ref):
            print(f"FAIL [router]: request conservation broke "
                  f"({len(r_outs)} of {len(r_ref)} finished)")
            failures += 1
        else:
            diverged = [i for rid, i in rids.items()
                        if not np.array_equal(r_outs[rid], r_ref[i])]
            if diverged:
                print(f"FAIL [router]: greedy outputs diverged from "
                      f"single-engine serving for requests {diverged}")
                failures += 1
        # anti-vacuity: under the least-loaded policy with 8 requests
        # over 2 replicas, a replica that served nothing means the
        # router never actually balanced
        if not (r_stats["routed_r0"] > 0 and r_stats["routed_r1"] > 0):
            print(f"FAIL [router]: vacuous run — a replica served zero "
                  f"requests (routed_r0={r_stats['routed_r0']} "
                  f"routed_r1={r_stats['routed_r1']})")
            failures += 1
        # admission must shed loudly at the queue cap: a burst past
        # 2 replicas x cap must raise the typed rejection
        shed_router = Router(rs, policy="least_tokens", queue_cap=2)
        cap_hit = False
        accepted = 0
        try:
            for i in range(8):
                shed_router.submit(r_prompts[i % len(r_prompts)],
                                   max_new_tokens=r_new)
                accepted += 1
        except QueueFullRejection:
            cap_hit = True
        if not cap_hit or accepted != 4:
            print(f"FAIL [router]: admission did not shed at queue cap "
                  f"(accepted {accepted}, expected 4 then "
                  "QueueFullRejection)")
            failures += 1
        shed_router.drain()
        rs.close()
        print(f"[router] requests={len(r_outs)} "
              f"routed_r0={r_stats['routed_r0']} "
              f"routed_r1={r_stats['routed_r1']} "
              f"affinity_hits={r_stats['affinity_hits']} "
              f"cap_shed={cap_hit}")
    if args.frontdoor:
        # ---- network front door: HTTP/SSE over a real socket ---------
        # the server is a transport, not a model: everything that
        # leaves over SSE must be bit-identical to in-process serving,
        # and every way a stream can END early (disconnect, deadline,
        # drain) must leave the engines clean
        import asyncio
        import json as _json
        import signal as _signal
        import time as _time

        from deepspeed_tpu.serving import (FrontDoorServer, ReplicaSet,
                                           Router)
        from deepspeed_tpu.serving import protocol as fd_proto
        from deepspeed_tpu.serving.client import (LoadGenerator,
                                                  sse_generate)

        f_prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
                     for n in (9, 14, 7, 11, 16, 8, 13, 10)]
        f_new = min(args.tokens, 20)

        def f_engine(i=0):
            return RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seqs=2,
                max_seq_len=max_len, prefill_chunk=16,
                decode_block_size=4, harvest_interval=3,
                rng=jax.random.PRNGKey(args.seed))

        def f_reference(prompt_list, new):
            eng = f_engine()
            order = {eng.put_request(q, max_new_tokens=new): i
                     for i, q in enumerate(prompt_list)}
            outs = {}
            while eng.has_work():
                eng.step()
                for uid, toks in eng.get_outputs():
                    outs[order[uid]] = toks
            eng.sync()
            for uid, toks in eng.get_outputs():
                outs[order[uid]] = toks
            eng.close()
            return outs

        f_ref = f_reference(f_prompts, f_new)
        rs = ReplicaSet(f_engine, 2)
        router = Router(rs, policy="least_tokens")
        srv = FrontDoorServer(router, port=0).start()

        # gate 1: SSE streaming bit-parity with in-process serving
        gen = LoadGenerator(
            srv.host, srv.port,
            lambda i: {"prompt": f_prompts[i].tolist(),
                       "max_new_tokens": f_new},
            requests=len(f_prompts), concurrency=4)
        f_sum = gen.run()
        parity_bad = []
        if f_sum["completed"] != len(f_prompts):
            print(f"FAIL [frontdoor]: only {f_sum['completed']} of "
                  f"{len(f_prompts)} streams completed "
                  f"({f_sum['errors']})")
            failures += 1
        else:
            for r in gen.results:
                i = r["i"]
                if (not np.array_equal(r["final"], f_ref[i])
                        or r["tokens"]
                        != list(f_ref[i][len(f_prompts[i]):])):
                    parity_bad.append(i)
            if parity_bad:
                print(f"FAIL [frontdoor]: SSE output diverged from "
                      f"in-process serving for requests {parity_bad}")
                failures += 1
        print(f"[frontdoor] streams={f_sum['completed']} "
              f"ttft_ms_p50={f_sum['ttft_ms_p50']} "
              f"tpot_ms_p50={f_sum['tpot_ms_p50']} parity_ok="
              f"{not parity_bad}")

        def f_quiesce(timeout=20.0):
            t0 = _time.monotonic()
            while _time.monotonic() - t0 < timeout:
                if router.outstanding == 0 and router.queued == 0:
                    _time.sleep(0.1)
                    if router.outstanding == 0:
                        return True
                _time.sleep(0.02)
            return False

        # gate 2: mid-stream client disconnect must cancel the request
        # at the engine and return every pool page, audit-verified
        f_quiesce()
        free0 = [h.engine.allocator.free_pages for h in rs.handles]
        res = asyncio.run(sse_generate(
            srv.host, srv.port,
            {"prompt": f_prompts[0].tolist(), "max_new_tokens": 64},
            abort_after_events=1))
        reclaimed = False
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 20.0:
            if (sum(h.engine.cancels for h in rs.handles) >= 1
                    and router.outstanding == 0
                    and [h.engine.allocator.free_pages
                         for h in rs.handles] == free0):
                reclaimed = True
                break
            _time.sleep(0.05)
        if res["error"] != "client_abort" or not reclaimed:
            print(f"FAIL [frontdoor]: disconnect did not reclaim pool "
                  f"pages (err={res['error']}, free="
                  f"{[h.engine.allocator.free_pages for h in rs.handles]}"
                  f" vs {free0})")
            failures += 1
        f_quiesce()
        try:
            for h in rs.handles:
                h.engine.audit_kv_sharing()
        except AssertionError as e:
            print(f"FAIL [frontdoor]: refcount audit broke after "
                  f"disconnect cancel: {e}")
            failures += 1
        print(f"[frontdoor] disconnect cancel reclaimed={reclaimed} "
              f"engine_cancels="
              f"{sum(h.engine.cancels for h in rs.handles)}")

        # gate 3: a burned deadline is a typed 429 at the front door
        res = asyncio.run(sse_generate(
            srv.host, srv.port,
            {"prompt": f_prompts[0].tolist(), "max_new_tokens": 8,
             "deadline_ms": 0.0}))
        if res["status"] != 429 or res["error"] != "DeadlineRejection":
            print(f"FAIL [frontdoor]: burned deadline returned "
                  f"{res['status']}/{res['error']}, expected "
                  f"429/DeadlineRejection")
            failures += 1
        print(f"[frontdoor] burned deadline -> {res['status']} "
              f"{res['error']}")
        srv.close()
        rs.close()

        # gate 4: SIGTERM drain — new requests 503, the in-flight
        # stream finishes with ZERO dropped tokens (bit-parity incl.)
        d_prompt = f_prompts[1]
        d_ref = f_reference([d_prompt], 24)[0]
        rs2 = ReplicaSet(f_engine, 1)
        router2 = Router(rs2, policy="rr")
        srv2 = FrontDoorServer(router2, port=0).start()
        srv2.install_signal_handlers()

        async def drain_scenario():
            body = _json.dumps({"prompt": d_prompt.tolist(),
                                "max_new_tokens": 24}).encode()
            ra, wa = await asyncio.open_connection(srv2.host, srv2.port)
            wa.write((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n"
                      ).encode() + body)
            await wa.drain()
            await ra.readuntil(b"\r\n\r\n")
            parser = fd_proto.SSEParser()
            events = []
            while not any(e == "tokens" for e, _ in events):
                events += parser.feed(await ra.read(4096))
            os.kill(os.getpid(), _signal.SIGTERM)
            t0 = _time.monotonic()
            while not srv2.draining and _time.monotonic() - t0 < 5.0:
                await asyncio.sleep(0.01)
            rb, wb = await asyncio.open_connection(srv2.host, srv2.port)
            wb.write((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n"
                      ).encode() + body)
            await wb.drain()
            rejected = await rb.read(-1)
            wb.close()
            truncated = False
            while not any(e == "done" for e, _ in events):
                chunk = await ra.read(4096)
                if not chunk:
                    truncated = True
                    break
                events += parser.feed(chunk)
            wa.close()
            return events, rejected, truncated

        d_events, d_rejected, d_trunc = asyncio.run(drain_scenario())
        if (not d_rejected.startswith(b"HTTP/1.1 503")
                or b"Retry-After:" not in d_rejected):
            print(f"FAIL [frontdoor]: draining server did not 503 new "
                  f"work with Retry-After ({d_rejected[:80]!r})")
            failures += 1
        streamed = [t for e, d in d_events if e == "tokens"
                    for t in _json.loads(d)["tokens"]]
        done = next((_json.loads(d) for e, d in d_events if e == "done"),
                    None)
        drain_ok = (not d_trunc and done is not None
                    and np.array_equal(done["tokens"], d_ref)
                    and streamed == list(d_ref[len(d_prompt):]))
        if not drain_ok:
            print(f"FAIL [frontdoor]: drain dropped tokens (truncated="
                  f"{d_trunc}, streamed {len(streamed)} of "
                  f"{len(d_ref) - len(d_prompt)})")
            failures += 1
        if not srv2.wait_drained(30.0):
            print("FAIL [frontdoor]: drain never completed")
            failures += 1
        srv2.close()
        rs2.close()
        print(f"[frontdoor] drain 503={d_rejected[:12]!r} "
              f"inflight_tokens={len(streamed)} zero_dropped={drain_ok}")
    if args.elastic:
        # ---- elastic serving: grow 1->2, then retire the original ----
        # world-size change as a recoverable event: a replica joins a
        # RUNNING router (prefix-warmed from the donor), the original
        # retires mid-traffic (parked sessions travel to the survivor
        # in spill format with the donor's digests; in-flight requests
        # finish in place), and the whole run stays bit-identical to a
        # static single engine
        from deepspeed_tpu.serving import ReplicaSet, Router

        e_rng = np.random.default_rng(args.seed + 5)
        e_prompts = [e_rng.integers(1, 64, size=(n,), dtype=np.int32)
                     for n in (12, 20, 9, 16, 10, 14, 18, 8)]
        e_new = min(args.tokens, 40)

        def e_engine(i=0):
            # pool sized so the first wave cannot stay resident: the
            # engine parks spilled sessions in its waiting queue, which
            # is exactly what the retirement handoff must carry over
            return RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seqs=4,
                max_seq_len=max_len, prefill_chunk=16, page_size=16,
                num_pages=9, decode_block_size=4,
                kv_reserve="on_demand", kv_tiering={"host_pages": 64},
                rng=jax.random.PRNGKey(args.seed))

        ref_eng = e_engine()
        e_ref = {}
        e_order = {ref_eng.put_request(p, max_new_tokens=e_new): i
                   for i, p in enumerate(e_prompts)}
        while ref_eng.has_work():
            ref_eng.step()
            for uid, toks in ref_eng.get_outputs():
                e_ref[e_order[uid]] = toks
        ref_eng.sync()
        for uid, toks in ref_eng.get_outputs():
            e_ref[e_order[uid]] = toks
        ref_eng.close()

        rs = ReplicaSet(e_engine, 1)
        router = Router(rs, policy="least_tokens")
        e_rids = {}
        for i, prompt in enumerate(e_prompts[:4]):
            e_rids[router.submit(prompt, max_new_tokens=e_new)] = i
        # open-loop pumping until pool pressure parks a SPILLED session
        # in the waiting queue (all ops joined before the peek)
        donor_eng = rs[0].engine
        spill_parked = False
        for _ in range(400):
            router.pump()
            router.join()
            if any(r.spilled is not None for r in donor_eng.waiting):
                spill_parked = True
                break
            if not router.outstanding:
                break
        if not spill_parked:
            print("FAIL [elastic]: vacuous run — no spilled session was "
                  "parked on the donor before the shrink")
            failures += 1
        (h2,) = rs.grow(1)
        router.add_replica(h2, warm_from=rs.handles[0])
        for i, prompt in enumerate(e_prompts[4:], start=4):
            e_rids[router.submit(prompt, max_new_tokens=e_new)] = i
        routed_r0 = router.stats()["routed_r0"]
        summary = router.retire_replica("r0")
        rs.shrink("r0")
        e_outs = router.drain()
        e_stats = router.stats()

        if sorted(e_rids[k] for k in e_outs) != sorted(e_ref):
            print(f"FAIL [elastic]: request conservation broke across "
                  f"grow+shrink ({len(e_outs)} of {len(e_ref)} "
                  f"finished)")
            failures += 1
        else:
            diverged = [i for rid, i in e_rids.items()
                        if not np.array_equal(e_outs[rid], e_ref[i])]
            if diverged:
                print(f"FAIL [elastic]: greedy outputs diverged from "
                      f"the static single engine for requests "
                      f"{diverged}")
                failures += 1
        if summary["handed_off"] < 1:
            print("FAIL [elastic]: vacuous shrink — the retired "
                  "replica handed off zero parked sessions")
            failures += 1
        if not (routed_r0 > 0 and e_stats["routed_r1"] > 0):
            print(f"FAIL [elastic]: a replica served zero requests "
                  f"(routed_r0={routed_r0} "
                  f"routed_r1={e_stats['routed_r1']})")
            failures += 1
        tc = rs[0].engine.tiering.counters
        if spill_parked and tc["imports"] < 1:
            print("FAIL [elastic]: the parked spilled session did not "
                  "travel in spill format (survivor imports=0)")
            failures += 1
        if tc["pages_verified"] != tc["pages_restored"]:
            print(f"FAIL [elastic]: restored pages skipped digest "
                  f"verification (verified={tc['pages_verified']} "
                  f"restored={tc['pages_restored']})")
            failures += 1
        rs.close()
        print(f"[elastic] requests={len(e_outs)} "
              f"handed_off={summary['handed_off']} "
              f"moved_pins={summary['moved_pins']} "
              f"routed_r0={routed_r0} "
              f"routed_r1={e_stats['routed_r1']} "
              f"survivor_imports={tc['imports']} "
              f"pages_verified={tc['pages_verified']}")
    if args.disagg:
        # ---- disaggregated serving: split prefill from decode --------
        # replica roles as first-class router state: long prompts land
        # on the prefill replica, run prefill + the first token there,
        # then the finished KV streams to the decode replica in spill
        # format (packed bytes + the donor's digests), where the
        # restore verifies end-to-end; short-chat traffic goes straight
        # to the decode replica.  Greedy outputs must stay bit-exact vs
        # one fused replica, and a corrupted wire payload must be
        # CAUGHT (quarantine + fold to re-prefill), never decoded from.
        from deepspeed_tpu.resilience import faults as dg_faults
        from deepspeed_tpu.serving import ReplicaSet, Router

        dg_rng = np.random.default_rng(args.seed + 6)
        dg_sizes = (24, 5, 40, 7, 33, 6, 20, 9)   # bimodal mix
        dg_prompts = [dg_rng.integers(1, 64, size=(n,), dtype=np.int32)
                      for n in dg_sizes]
        dg_new = min(args.tokens, 12)
        dg_long = sum(1 for p in dg_prompts if p.size >= 16)

        def dg_engine(i=0):
            return RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seqs=4,
                max_seq_len=max_len, prefill_chunk=16, page_size=16,
                num_pages=9, decode_block_size=4,
                kv_reserve="on_demand", kv_tiering={"host_pages": 64},
                rng=jax.random.PRNGKey(args.seed))

        dg_ref_eng = dg_engine()
        dg_ref = {}
        dg_order = {dg_ref_eng.put_request(p, max_new_tokens=dg_new): i
                    for i, p in enumerate(dg_prompts)}
        while dg_ref_eng.has_work():
            dg_ref_eng.step()
            for uid, toks in dg_ref_eng.get_outputs():
                dg_ref[dg_order[uid]] = toks
        dg_ref_eng.sync()
        for uid, toks in dg_ref_eng.get_outputs():
            dg_ref[dg_order[uid]] = toks
        dg_ref_eng.close()

        def dg_run(inject=None):
            rs = ReplicaSet(dg_engine, 2)
            router = Router(rs, policy="least_tokens")
            router.set_roles({"r0": "prefill", "r1": "decode"})
            rids = {router.submit(p, max_new_tokens=dg_new): i
                    for i, p in enumerate(dg_prompts)}
            outs = router.drain()
            stats = router.stats()
            pre, dec = rs.handles[0].engine, rs.handles[1].engine
            pre.audit_kv_sharing()
            dec.audit_kv_sharing()
            res = {"outs": {rids[r]: t for r, t in outs.items()},
                   "stats": stats,
                   "pre_handoffs": pre.handoffs,
                   "dec_tiering": dict(dec.tiering.counters),
                   "handed_off": pre.request_latency.handed_off,
                   "stall_p50": dec.request_latency.summary().get(
                       "handoff_stall_ms_p50")}
            rs.close()
            return res

        clean = dg_run()
        ok_conserve = sorted(clean["outs"]) == sorted(dg_ref)
        if not ok_conserve:
            print(f"FAIL [disagg]: request conservation broke "
                  f"({len(clean['outs'])} of {len(dg_ref)} finished)")
            failures += 1
        else:
            diverged = [i for i in dg_ref
                        if not np.array_equal(clean["outs"][i],
                                              dg_ref[i])]
            if diverged:
                print(f"FAIL [disagg]: greedy outputs diverged from "
                      f"the fused replica for requests {diverged}")
                failures += 1
        st = clean["stats"]
        if not (st["handoffs"] == st["handoff_kv"] == dg_long
                and st["handoff_reprefill"] == 0):
            print(f"FAIL [disagg]: vacuous split — expected {dg_long} "
                  f"KV-path handoffs, got handoffs={st['handoffs']} "
                  f"kv={st['handoff_kv']} "
                  f"reprefill={st['handoff_reprefill']}")
            failures += 1
        tc = clean["dec_tiering"]
        if tc["imports"] != st["handoff_kv"]:
            print(f"FAIL [disagg]: handoff payloads skipped the spill "
                  f"wire format (receiver imports={tc['imports']} != "
                  f"kv handoffs={st['handoff_kv']})")
            failures += 1
        if not (tc["pages_verified"] == tc["pages_restored"] > 0
                and tc["quarantined"] == 0):
            print(f"FAIL [disagg]: restored pages skipped digest "
                  f"verification (verified={tc['pages_verified']} "
                  f"restored={tc['pages_restored']} "
                  f"quarantined={tc['quarantined']})")
            failures += 1
        if clean["handed_off"] != dg_long or not clean["stall_p50"]:
            print(f"FAIL [disagg]: handoff telemetry did not land "
                  f"(donor handed_off={clean['handed_off']}, receiver "
                  f"stall p50={clean['stall_p50']})")
            failures += 1

        # degraded leg: a bitflip on every handoff wire payload — the
        # donor's digests must catch it at restore (quarantine), the
        # session folds to a re-prefill continuation, parity holds
        with dg_faults.FaultInjector(seed=args.seed) as dg_inj:
            dg_inj.bitflip("handoff.import", bits=1, count=100)
            hurt = dg_run(inject=True)
        ok_conserve = sorted(hurt["outs"]) == sorted(dg_ref)
        diverged = ([] if not ok_conserve else
                    [i for i in dg_ref
                     if not np.array_equal(hurt["outs"][i], dg_ref[i])])
        if not ok_conserve or diverged:
            print(f"FAIL [disagg]: corrupted-wire leg broke parity "
                  f"(conserved={ok_conserve}, diverged={diverged})")
            failures += 1
        htc = hurt["dec_tiering"]
        if not (htc["quarantined"] > 0
                and any(s == "handoff.import"
                        for s, _, _ in dg_inj.fired)):
            print(f"FAIL [disagg]: corrupted handoff payload was not "
                  f"quarantined (quarantined={htc['quarantined']}, "
                  f"fired={len(dg_inj.fired)}) — silent SDC risk")
            failures += 1
        print(f"[disagg] requests={len(clean['outs'])} "
              f"handoffs={st['handoffs']} kv={st['handoff_kv']} "
              f"imports={tc['imports']} "
              f"pages_verified={tc['pages_verified']} "
              f"stall_p50_ms={clean['stall_p50']} "
              f"corrupted_quarantined={htc['quarantined']}")
    if args.autotune:
        # ---- closed-loop control plane over a mis-tuned engine -------
        # the controller must walk a deliberately detuned engine back
        # to hand-tuned throughput, with every knob change attributable
        # to a named signal in the trace export and zero oscillation-
        # guard violations
        import tempfile
        import time

        import trace_summarize

        from deepspeed_tpu import telemetry

        MIS = dict(harvest_interval=1, async_depth=1)
        HAND = dict(harvest_interval=4, async_depth=2)
        # deterministic objective: blocking gets per dispatch is a pure
        # function of harvest_interval (~1/h), so the convergence
        # asserts do not ride on wall-clock noise
        CTL = {"interval": 4, "settle": 1, "cooldown": 0,
               "objective": "-blocking_gets_per_dispatch"}
        a_prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
                     for n in (9, 14, 7, 12, 10, 15)]
        a_new = min(args.tokens, 24)

        def a_engine(**kw):
            return RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seqs=2,
                max_seq_len=max_len, prefill_chunk=16,
                decode_block_size=4,
                rng=jax.random.PRNGKey(args.seed), **kw)

        def a_wave(eng):
            t0 = time.perf_counter()
            outs = eng.generate_all(list(a_prompts),
                                    max_new_tokens=a_new)
            wall = time.perf_counter() - t0
            return sum(len(t) for t in outs.values()) / wall

        # hand-tuned steady state: the bar the controller must reach
        # (best of 3 waves; wave 1 pays this shape's jit warmup)
        h_eng = a_engine(**HAND)
        hand_tps = max(a_wave(h_eng) for _ in range(3))

        telemetry.trace.configure(enabled=True)
        telemetry.trace.clear()
        c_eng = a_engine(control=CTL, **MIS)
        wave_tps = [a_wave(c_eng) for _ in range(6)]
        ctl = c_eng._controller
        knob_end = ctl.knobs.snapshot()
        a_path = os.path.join(
            tempfile.mkdtemp(prefix="serve_autotune_"),
            "control_trace.json")
        telemetry.trace.export(a_path)
        telemetry.trace.configure(enabled=False)
        telemetry.trace.clear()

        h_final = int(knob_end["engine.harvest_interval"])
        if not (ctl.counts["decisions"] > 0 and
                ctl.counts["accepts"] > 0 and
                h_final >= HAND["harvest_interval"]):
            print("FAIL [autotune]: controller did not converge off "
                  f"the mis-tuned start (harvest_interval={h_final}, "
                  f"want >={HAND['harvest_interval']}; "
                  f"counts={ctl.counts})")
            failures += 1
        n_tunable = len(ctl.knobs.tunable())
        if ctl.counts["guard_violations"] != 0 or \
                ctl.counts["freezes"] > n_tunable:
            print("FAIL [autotune]: oscillation guard blown "
                  f"(violations={ctl.counts['guard_violations']} "
                  f"freezes={ctl.counts['freezes']} over "
                  f"{n_tunable} tunable knobs)")
            failures += 1
        try:
            a_events, _ = trace_summarize.load_events(a_path)
            a_problems = trace_summarize.validate_events(a_events)
        except (ValueError, OSError) as e:
            a_events, a_problems = [], [str(e)]
        if a_problems:
            for msg in a_problems[:5]:
                print(f"FAIL [autotune]: malformed control trace: "
                      f"{msg}")
            failures += 1
        decs = [ev for ev in a_events
                if ev.get("cat") == "control" and
                ev.get("name") == "control_decision"]
        unattributed = [ev for ev in decs
                        if not (ev.get("args") or {}).get("signal")]
        if len(decs) != len(ctl.decision_log) or unattributed:
            print(f"FAIL [autotune]: decision attribution broke — "
                  f"{len(decs)} trace decisions vs "
                  f"{len(ctl.decision_log)} logged, "
                  f"{len(unattributed)} without a named signal")
            failures += 1
        conv_tps = max(wave_tps[-2:])
        if conv_tps < 0.9 * hand_tps:
            print(f"FAIL [autotune]: converged throughput "
                  f"{conv_tps:.1f} tok/s < 0.9x hand-tuned "
                  f"{hand_tps:.1f} tok/s")
            failures += 1

        # ---- overhead: controller armed vs off on the tuned config ---
        # armed at the production-default cadence (the aggressive
        # probe-every-4-ticks config above is a convergence-test
        # setting); off/on samples interleave so machine noise on this
        # box hits both sides of the min-of-3
        OVH = {"objective": CTL["objective"]}

        def a_timed(armed):
            eng = a_engine(control=OVH if armed else None, **HAND)
            t0 = time.perf_counter()
            eng.generate_all(list(a_prompts), max_new_tokens=a_new)
            return time.perf_counter() - t0

        a_off, a_on = float("inf"), float("inf")
        for _ in range(3):
            a_off = min(a_off, a_timed(False))
            a_on = min(a_on, a_timed(True))
        a_ovh = (a_on - a_off) / a_off
        if a_ovh > 0.05:
            print(f"FAIL [autotune]: controller-armed wall regressed "
                  f"{a_ovh * 100:.1f}% (off={a_off:.3f}s "
                  f"on={a_on:.3f}s)")
            failures += 1
        print(f"[autotune] harvest={MIS['harvest_interval']}->"
              f"{h_final} depth={knob_end['engine.async_depth']} "
              f"decisions={ctl.counts['decisions']} "
              f"accepts={ctl.counts['accepts']} "
              f"freezes={ctl.counts['freezes']} "
              f"tok/s={conv_tps:.1f} vs hand {hand_tps:.1f} "
              f"overhead={a_ovh * 100:+.1f}%")
    if args.chaos:
        # ---- serving fault tolerance: chaos over a live socket -------
        # the compact campaign: one replica hang (watchdog + breaker),
        # one mid-stream death (exception path), one NVMe device
        # failure (degraded tiering) — each over a real socket through
        # the chaos harness's pass assertions (conservation, survivor
        # bit-parity, clean audits, parseable flight dumps) — plus the
        # watchdog-armed no-fault overhead bound
        import tempfile as _tempfile
        import time as _time

        import chaos_serve
        from deepspeed_tpu.serving import ReplicaSet as CReplicaSet
        from deepspeed_tpu.serving import Router as CRouter

        os.environ["DSTPU_FLIGHT_DIR"] = _tempfile.mkdtemp(
            prefix="smoke_chaos_flight_")
        c_prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
                     for n in (9, 14, 7, 11)]
        c_new = min(args.tokens, 16)
        c_wd = 8.0

        def c_engine(i=0):
            return RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params, max_seqs=2,
                max_seq_len=max(max_len, 128), prefill_chunk=16,
                decode_block_size=4, harvest_interval=3,
                rng=jax.random.PRNGKey(args.seed))

        c_nvme = _tempfile.mkdtemp(prefix="smoke_chaos_nvme_")
        c_tier_kw = dict(max_seqs=4, max_seq_len=max(max_len, 128),
                         prefill_chunk=16, page_size=16, num_pages=9,
                         decode_block_size=4, kv_reserve="on_demand")

        def c_tiered(i=0):
            return RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params,
                kv_tiering={"host_pages": 2, "nvme_pages": 16,
                            "nvme_dir": c_nvme,
                            "nvme_fail_threshold": 2},
                rng=jax.random.PRNGKey(args.seed), **c_tier_kw)

        def c_plain(i=0):
            return RaggedInferenceEngineV2(
                LlamaForCausalLM(cfg), params=params,
                rng=jax.random.PRNGKey(args.seed), **c_tier_kw)

        c_tier_prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32)
                          for n in (12, 20, 9, 16, 14, 18)]
        c_ref = chaos_serve.reference(c_engine, c_prompts, c_new)
        c_fail = chaos_serve.hang_pass(c_engine, c_prompts, c_new,
                                       c_ref, args.seed, c_wd)
        c_fail += chaos_serve.serve_pass(
            "step-eio", c_engine, c_prompts, c_new, c_ref,
            lambda inj: inj.io_error("replica.step", after=6, count=1),
            args.seed + 1)[0]
        c_fail += chaos_serve.tier_pass(c_tiered, c_plain,
                                        c_tier_prompts, 40,
                                        args.seed + 3,
                                        only={"kv-degraded"})
        failures += c_fail

        # ---- overhead: watchdog armed vs disarmed, no faults ---------
        # warm drain first so the timed drain measures serving, not
        # compile; off/on samples interleave against machine noise
        def c_timed(wd):
            crs = CReplicaSet(c_engine, 1, watchdog_s=wd)
            crouter = CRouter(crs, policy="rr")
            crouter.submit(c_prompts[0], max_new_tokens=4)
            crouter.drain()
            t0 = _time.perf_counter()
            for q in c_prompts:
                crouter.submit(q, max_new_tokens=c_new)
            crouter.drain()
            w = _time.perf_counter() - t0
            crs.close()
            return w

        c_off, c_on = float("inf"), float("inf")
        for _ in range(3):
            c_off = min(c_off, c_timed(0.0))
            c_on = min(c_on, c_timed(c_wd))
        c_ovh = (c_on - c_off) / c_off
        if c_ovh > 0.05:
            print(f"FAIL [chaos]: watchdog-armed wall regressed "
                  f"{c_ovh * 100:.1f}% (off={c_off:.3f}s "
                  f"on={c_on:.3f}s)")
            failures += 1
        print(f"[chaos] passes_failed={c_fail} watchdog_overhead="
              f"{c_ovh * 100:+.1f}%")
    if failures:
        print(f"serve_smoke: {failures} failure(s)")
        return 1
    print("serve_smoke: the pipelined loop bit-identical to "
          "pipeline=False" +
          (", kv tiering spill/restore exact and verified"
           if args.kv_tiering else "") +
          (", prefix cache exact with nonzero hit rate and clean "
           "refcount audit" if args.prefix_cache else "") +
          (", quantized pool deterministic, tier-exact, inside the "
           "quality envelope" if args.kv_quant else "") +
          (", trace export valid within overhead budget"
           if args.trace else "") +
          (", metrics exposition valid, percentiles agree, tail "
           "sampling selective within overhead budget"
           if args.metrics else "") +
          (", routed serving bit-identical across 2 replicas with "
           "loud queue-cap shedding" if args.router else "") +
          (", front door SSE bit-exact with clean disconnect/deadline/"
           "drain endings" if args.frontdoor else "") +
          (", elastic grow+shrink conserved every request bit-exactly "
           "with digest-verified handoff" if args.elastic else "") +
          (", disaggregated 1P+1D bit-identical to fused with every "
           "handoff digest-verified and the corrupted wire quarantined"
           if args.disagg else "") +
          (", chaos campaign conserved every request through hang/"
           "death/NVMe faults within watchdog overhead budget"
           if args.chaos else "") +
          (", control plane converged the mis-tuned engine with clean "
           "guard and attributable decisions" if args.autotune else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
