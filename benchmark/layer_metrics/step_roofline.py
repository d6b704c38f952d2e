"""The training step's share of its roofline, which is compute: model
FLOPs per step per chip (forward + backward, causal attention, no
recomputation) / peak bf16 FLOP/s, over the device-busy time per step."""
from benchmark import flops
from benchmark.layer_metrics import step_device_ms


def read(ctx):
    ms = step_device_ms.read(ctx)
    if ms is None:
        return None
    need = (flops.train_flops_per_token(ctx["cell"].config, ctx["seq_len"])
            * ctx["tokens_per_step"] / ctx["chips"])
    return 100.0 * (need / ctx["peaks"]["bf16_flops_per_s"]) / (ms / 1e3)
