"""Passes over the layer stack a decoded token took: ``ut_steps`` of each
decode block's ``engine.dispatch`` span weighted by its token steps
(``rows`` x ``ticks``), over the token steps.  ``total_ut_steps`` exactly
while every token runs every pass; the number a per-token early exit would
lower and nothing else may.  Nothing to read where the spans carry no
``ut_steps``."""
from benchmark.layer_metrics import loop_decode_roofline


def read(ctx):
    spans = loop_decode_roofline.blocks(ctx)
    steps = sum(int(b["rows"]) * int(b["ticks"]) for b in spans)
    if not steps:
        return None
    return sum(int(b["ut_steps"]) * int(b["rows"]) * int(b["ticks"])
               for b in spans) / steps
