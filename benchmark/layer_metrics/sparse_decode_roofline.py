"""The chosen-page kernel of block-selecting attention as a share of its
roofline, which is memory: the keys and values of the pages a one-row
query attends (``attend_decode_tokens`` of the ``engine.dispatch`` spans of
both programs: the chosen blocks' keys past ``dense_len``, every earlier
key before it; ``flops_sala.sparse_attention``) / HBM bytes per second,
over the device self time of ``sparse_decode_attention``.  A decode block's
span counts its first tick's positions and each later tick's; the kernel
fetches whole pages and runs a grid step a key step of eight pages, which
is what holds the share down."""
from benchmark import flops_sala
from benchmark.layer_metrics import sala_trace


def read(ctx):
    secs = sala_trace.seconds(ctx, "attn", sala_trace.SPARSE_DECODE)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: sala_trace.bound_s(
        ctx, flops_sala.sparse_attention(
            cfg, int(d.get("attend_decode_tokens", 0)),
            sala_trace.one_row_steps(program, d))))
    return 100.0 * need / secs if need else None
