"""Serving example: continuous-batching inference (FastGen v2).

Loads a HuggingFace Llama checkpoint if given, otherwise serves random
weights; feeds a stream of variable-length requests through the ragged
engine and prints per-request outputs as slots free up.

    python examples/serve_llama.py [--checkpoint /path/to/hf_dir]

Scale-out serving (``--replicas N``) puts N data-parallel engine
replicas behind the SLO-aware router (``--router-policy`` picks the
load-balancing policy) and prints the router stats after the drain.
Replica ``i`` lives on device ``i`` (modulo the device count): its
weights, KV pool and ticks are committed there through a one-device
``topology``.  On a CPU host, set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before launching
when you want the replica threads to overlap on separate host devices;
without it they interleave on one device (bit-identical results, no
wall-clock overlap).

    python examples/serve_llama.py --replicas 2 --router-policy pressure

Closed-loop control (``--control``) arms the online controller on the
serving host loop; ``--autotune DIR`` first runs the offline knob
sweep and saves a per-host profile the controller seeds from.

    python examples/serve_llama.py --control
    python examples/serve_llama.py --autotune /tmp/dstpu_profiles

Network serving (``--listen HOST:PORT``) puts the engine (or the
routed replica set, with ``--replicas N``) behind the asyncio HTTP
front door: ``POST /v1/generate`` streams tokens over SSE as the
engine harvests them, ``GET /healthz`` and ``GET /metrics`` serve
probes, SIGTERM drains gracefully (503 for new work, in-flight
streams finish).  Port 0 picks a free port.

    python examples/serve_llama.py --listen 127.0.0.1:8071
    python -m deepspeed_tpu.serving.client --port 8071 --requests 32
"""
import argparse

import jax
import numpy as np

from deepspeed_tpu.accelerator import require_accelerator
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.parallel.topology import MeshTopology
from deepspeed_tpu.utils.compile_cache import enable_compile_cache


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--checkpoint", default=None,
                   help="HF checkpoint dir / pytorch_model.bin")
    p.add_argument("--preset", default="tinyllama")
    p.add_argument("--max-seqs", type=int, default=4)
    p.add_argument("--max-seq-len", type=int, default=256)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--no-pipeline", action="store_true",
                   help="disable the serving host-path pipeline "
                        "(per-dispatch blocking harvest)")
    p.add_argument("--harvest-interval", type=int, default=4)
    p.add_argument("--kv-tiering", action="store_true",
                   help="spill cold sequences' KV pages to host RAM "
                        "(and NVMe, with --kv-nvme-pages) instead of "
                        "evicting when the HBM pool fills")
    p.add_argument("--kv-host-pages", type=int, default=256,
                   help="host-RAM tier budget in KV pages")
    p.add_argument("--kv-nvme-pages", type=int, default=0,
                   help="NVMe tier budget in KV pages (0 = host only)")
    p.add_argument("--kv-nvme-dir", default=None,
                   help="directory for NVMe tier page files")
    p.add_argument("--kv-cache-dtype",
                   choices=["none", "int8", "fp8"], default="none",
                   help="store KV pages quantized (1 byte/elem + per-"
                        "row fp32 scales): ~4x the resident sessions "
                        "per HBM byte, attention reads the quantized "
                        "pages directly (no full-pool dequant)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="share identical token prefixes across "
                        "requests: matched KV pages attach read-only "
                        "(copy-on-write on divergence) so repeated "
                        "system prompts skip their prefill")
    p.add_argument("--listen", metavar="HOST:PORT", default=None,
                   help="serve over HTTP/SSE instead of the in-process "
                        "demo loop: POST /v1/generate streams tokens, "
                        "GET /healthz + /metrics serve probes, SIGTERM "
                        "drains gracefully (port 0 = pick a free port)")
    p.add_argument("--replicas", type=int, default=1,
                   help="data-parallel engine replicas behind the "
                        "SLO-aware router (1 = solo engine, no router)")
    p.add_argument("--router-policy",
                   choices=["rr", "least_tokens", "pressure"],
                   default="least_tokens",
                   help="router load-balancing policy for --replicas>1")
    p.add_argument("--control", action="store_true",
                   help="arm the closed-loop controller on the serving "
                        "host loop: adapts harvest/depth/tiering knobs "
                        "from live signals (DSTPU_CONTROL=0 disarms)")
    p.add_argument("--control-profile", default=None,
                   help="host-profile .json or dir that seeds the "
                        "controller's starting knobs (see --autotune)")
    p.add_argument("--autotune", metavar="DIR", default=None,
                   help="offline knob sweep on a short probe workload "
                        "first; saves a per-host profile (fingerprinted "
                        "by cores/device/NVMe) under DIR, then serves "
                        "with the controller seeded from it")
    args = p.parse_args()

    # a missing chip is an error; JAX_PLATFORMS=cpu asks for the CPU
    require_accelerator()
    enable_compile_cache()
    cfg = get_config(args.preset, scan_layers=True, remat=False,
                     use_flash_attention=False,
                     max_position_embeddings=max(
                         args.max_seq_len,
                         get_config(args.preset).max_position_embeddings))
    model = LlamaForCausalLM(cfg)

    params = None
    if args.checkpoint:
        from deepspeed_tpu.module_inject import load_hf_checkpoint

        params = load_hf_checkpoint(model, args.checkpoint)

    tiering = None
    if args.kv_tiering:
        tiering = {"host_pages": args.kv_host_pages,
                   "nvme_pages": args.kv_nvme_pages,
                   "nvme_dir": args.kv_nvme_dir}

    if args.autotune is not None:
        # offline sweep: measure a short probe workload at each knob
        # point, persist the winner keyed by this host's fingerprint
        import time

        from deepspeed_tpu.control import autotune_serving

        probe_rng = np.random.default_rng(1)
        probe = [probe_rng.integers(1, cfg.vocab_size, size=(n,),
                                    dtype=np.int32)
                 for n in (5, 17, 9)]

        def probe_runner(point):
            eng = RaggedInferenceEngineV2(
                model, params=params, max_seqs=args.max_seqs,
                max_seq_len=args.max_seq_len, prefill_chunk=64,
                harvest_interval=int(
                    point.get("engine.harvest_interval", 4)),
                async_depth=int(point.get("engine.async_depth", 2)))
            t0 = time.perf_counter()
            outs = eng.generate_all(list(probe), max_new_tokens=16)
            return sum(t.size for t in outs.values()) / (
                time.perf_counter() - t0)

        prof = autotune_serving(
            probe_runner,
            {"engine.harvest_interval": [1, 2, 4, 8],
             "engine.async_depth": [1, 2, 4]},
            save_to=args.autotune)
        if prof is None:
            raise SystemExit("autotune: every sweep point failed")
        print(f"autotune: host {prof.key} best knobs {prof.knobs} "
              f"({prof.metric_name}={prof.metric:.1f}), profile saved "
              f"under {args.autotune}")
        args.control = True
        if args.control_profile is None:
            args.control_profile = args.autotune

    control = None
    if args.control or args.control_profile:
        control = ({"profile": args.control_profile}
                   if args.control_profile else True)

    def build_engine(replica_idx: int = 0) -> RaggedInferenceEngineV2:
        topology = None
        if args.replicas > 1:
            devices = jax.devices()
            topology = MeshTopology(
                devices=[devices[replica_idx % len(devices)]])
        return RaggedInferenceEngineV2(
            model, params=params, topology=topology,
            max_seqs=args.max_seqs,
            max_seq_len=args.max_seq_len, prefill_chunk=64,
            pipeline=not args.no_pipeline,
            harvest_interval=args.harvest_interval,
            kv_cache_dtype=args.kv_cache_dtype, kv_tiering=tiering,
            prefix_cache=args.prefix_cache, control=control)

    # a burst of variable-length "requests"; with --prefix-cache they
    # share a common system prompt so later admissions hit the index
    rng = np.random.default_rng(0)
    sys_prompt = (rng.integers(1, cfg.vocab_size, size=(64,),
                               dtype=np.int32)
                  if args.prefix_cache else np.zeros((0,), np.int32))
    prompts = [np.concatenate(
        [sys_prompt,
         rng.integers(1, cfg.vocab_size, size=(n,), dtype=np.int32)])
        for n in (5, 17, 9, 30, 12, 7)]

    if args.listen is not None:
        from deepspeed_tpu.serving import (FrontDoorServer, ReplicaSet,
                                           Router)

        host, _, port_s = args.listen.rpartition(":")
        rs = ReplicaSet(build_engine, max(args.replicas, 1))
        router = Router(rs, policy=args.router_policy)
        srv = FrontDoorServer(router, host=host or "127.0.0.1",
                              port=int(port_s or 0)).start()
        srv.install_signal_handlers()   # SIGTERM -> graceful drain
        print(f"front door listening on http://{srv.host}:{srv.port} "
              f"({len(rs.handles)} replica(s), "
              f"policy={args.router_policy})")
        print('  POST /v1/generate  {"prompt": [ids...], '
              '"max_new_tokens": N}  -> SSE token stream')
        print("  GET  /healthz  |  GET /metrics")
        print(f"  load test: python -m deepspeed_tpu.serving.client "
              f"--port {srv.port} --requests 32 --concurrency 8")
        try:
            srv.serve_forever()         # returns once drained
        except KeyboardInterrupt:
            pass
        srv.close()
        s = router.stats()
        print(f"drained: accepted={s['accepted']} "
              f"finished={s['finished']} cancelled={s['cancelled']} "
              f"expired_deadline={s['expired_deadline']}")
        rs.close()
        return

    if args.replicas > 1:
        from deepspeed_tpu.serving import ReplicaSet, Router
        from deepspeed_tpu.telemetry import SLOSet

        rs = ReplicaSet(build_engine, args.replicas)
        router = Router(rs, policy=args.router_policy,
                        slo=SLOSet(["router_e2e_ms_p99 <= 60000"]))
        for prompt in prompts:
            rid = router.submit(prompt,
                                max_new_tokens=args.max_new_tokens)
            print(f"routed request {rid} (prompt {prompt.size} tokens)")
        for rid, tokens in sorted(router.drain().items()):
            print(f"request {rid} done: {tokens.size} tokens -> "
                  f"{tokens[-8:].tolist()}")
        s = router.stats()
        print("router: " +
              " ".join(f"{k}={s[k]}" for k in
                       ("policy", "replicas_alive", "accepted",
                        "finished", "rejected_queue_full",
                        "rejected_shed", "affinity_hits", "rerouted")) +
              " " + " ".join(f"routed_{h.name}={s[f'routed_{h.name}']}"
                             for h in rs))
        for h in rs:
            rl = h.engine.request_latency.summary()
            print(f"  {h.name}: ttft_p50={rl['ttft_ms_p50']}ms "
                  f"router_queue_wait_p50="
                  f"{rl['router_queue_wait_ms_p50']}ms "
                  f"completed={rl['completed']}")
        rs.close()
        return

    engine = build_engine()
    for prompt in prompts:
        uid = engine.put_request(prompt,
                                 max_new_tokens=args.max_new_tokens)
        print(f"queued request {uid} (prompt {prompt.size} tokens)")

    step = 0
    while engine.has_work():
        engine.step()
        step += 1
        for uid, tokens in engine.get_outputs():
            print(f"[step {step}] request {uid} done: "
                  f"{tokens.size} tokens -> {tokens[-8:].tolist()}")
    stages = engine.serving_stages()
    print("serving stages (per dispatch): " +
          " ".join(f"{k}={stages[k]}" for k in
                   ("plan_ms", "upload_ms", "dispatch_ms", "device_ms",
                    "harvest_ms", "host_bound_fraction")))
    ctl = stages.get("control")
    if ctl:
        print("control: " +
              " ".join(f"{k}={ctl[k]}" for k in
                       ("ticks", "decisions", "accepts", "reverts",
                        "freezes", "guard_violations", "objective")) +
              f" knobs={ctl['knobs']}")
    tier = stages.get("kv_tiering")
    if tier:
        print("kv tiering: " +
              " ".join(f"{k}={tier[k]}" for k in
                       ("spills", "restores", "pages_spilled",
                        "pages_restored", "pages_verified", "demotions",
                        "nvme_spills", "prefetch_hits")))
    kq = stages.get("kv_quant")
    if kq:
        print("kv quant: " +
              " ".join(f"{k}={kq[k]}" for k in
                       ("format", "dequant_path", "pool_bytes",
                        "payload_bytes", "scale_bytes",
                        "scale_rows_written")))
    pc = stages.get("prefix_cache")
    if pc:
        rl = engine.request_latency.summary()
        print("prefix cache: " +
              " ".join(f"{k}={pc[k]}" for k in
                       ("hit_rate", "hit_requests", "miss_requests",
                        "hit_tokens", "cow_copies", "entries",
                        "demotions", "revivals")) +
              f" prefill_computed={rl['prefill_computed_tokens']}"
              f" prefill_cached={rl['prefill_cached_tokens']}")
    if tier or pc:
        engine.close()


if __name__ == "__main__":
    main()
