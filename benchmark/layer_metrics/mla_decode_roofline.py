"""The decode-row kernel of latent attention as a share of its roofline,
which is memory: the latent rows it must read (``flops_mla.
decode_attention_bytes``: 1,152 bytes a live context token a layer, once a
tick whatever the heads) / HBM bytes per second, over the device self time
of ``latent_decode_attention``.  The kernel runs in the decode block (a
block's ``rows``, ``ticks``, ``key_tokens``: the first tick reads
``key_tokens`` rows, every later one a row more a sequence) and for the
one-query rows of a fused tick (``decode_key_tokens``); per program, mean
needed bytes of the dispatches in the slice x its runs in the slice, summed,
over the kernel's time in both.  The pool's rows are stored padded (640 for
576) and a group's last pages are read whole; neither is needed, so the
share cannot pass 100 %."""
from benchmark import flops_mla
from benchmark.layer_metrics import mla_trace


def _key_tokens(program, d):
    if program == "fused":
        return int(d.get("decode_key_tokens", 0))
    ticks = int(d["ticks"])
    return ticks * (int(d["key_tokens"])
                    + int(d["rows"]) * (ticks - 1) / 2.0)


def read(ctx):
    secs = mla_trace.seconds(ctx, (mla_trace.DECODE_KERNEL,))
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = 0.0
    for program in mla_trace.ROLES:
        spans = mla_trace.dispatches(ctx, program)
        runs = mla_trace.runs(ctx, program)
        if spans and runs:
            need += runs * sum(flops_mla.decode_attention_bytes(
                cfg, _key_tokens(program, d)) for d in spans) / len(spans)
    if not need:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / secs
