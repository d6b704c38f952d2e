"""The grouped expert matmul alone as a share of its roofline.  Needed time
of one dispatch = the larger of its bytes / HBM bytes per second and its
FLOPs / peak bf16 FLOP/s, both from the program's counts on its
``engine.moe`` span (``moe_rows`` token copies through three matrices of
one expert each; the banks of ``moe_experts_hit`` experts, every row in and
out once: ``flops_moe.py``).  Needed time of the slice = for each of the
two programs, the mean over its spans in the slice x its runs in the slice;
that over the device self time of the kernel's custom calls
(``moe_trace.kernel_seconds``: ``ragged-dot`` in the operation's name).

At 4 to 68 rows an expert the kernel is bound by the banks it reads, so
this is a share of HBM bandwidth in every cell so far."""
from benchmark import flops_moe
from benchmark.layer_metrics import moe_trace

PROGRAMS = {"decode_block": "decode", "fused": "prefill"}


def read(ctx):
    kernel_s = moe_trace.kernel_seconds(ctx)
    if not kernel_s:
        return None
    cfg, peaks, red = ctx["cell"].config, ctx["peaks"], ctx["trace"]
    need = 0.0
    for program, role in PROGRAMS.items():
        rows = moe_trace.spans(ctx, program)
        runs = red.program_runs.get(cfg["programs"][role], 0)
        if not rows or not runs:
            continue
        need += runs * sum(max(
            flops_moe.expert_matmul_bytes(
                cfg, int(r["moe_rows"]), int(r["moe_experts_hit"]))
            / peaks["hbm_bytes_per_s"],
            flops_moe.expert_matmul_flops(cfg, int(r["moe_rows"]))
            / peaks["bf16_flops_per_s"]) for r in rows) / len(rows)
    return 100.0 * need / kernel_s if need else None
