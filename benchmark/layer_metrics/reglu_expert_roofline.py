"""The grouped ReGLU expert matmul alone as a share of its roofline:
``hybrid_expert_roofline``'s reading with ``flops_smallthinker.py``'s counts
(an expert is ``moe_ffn_hidden_size`` wide under this source's keys).  Needed
time of one dispatch = the larger of its bytes / HBM bytes per second and its
FLOPs / peak bf16 FLOP/s, from ``moe_rows`` and ``moe_experts_hit`` on its
``engine.moe`` span; needed time of the slice = for each of the two programs,
the mean over its spans x its runs in the slice; that over the device self
time of the ``ragged-dot...`` custom calls (``moe_trace.kernel_seconds``).
None where the slice has no such span or no such call."""
from benchmark import flops_smallthinker as F
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
            F.expert_matmul_bytes(cfg, int(r["moe_rows"]),
                                  int(r["moe_experts_hit"]))
            / peaks["hbm_bytes_per_s"],
            F.expert_matmul_flops(cfg, int(r["moe_rows"]))
            / peaks["bf16_flops_per_s"]) for r in rows) / len(rows)
    return 100.0 * need / kernel_s if need else None
