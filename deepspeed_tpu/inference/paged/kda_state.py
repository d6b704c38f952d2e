"""Kimi-Delta-Attention state: the cache kind of a KDA layer
(``models/solar_open2.py``), which keeps the TWO per-slot leaves a
gated-delta-rule layer keeps (``delta_state.py``) and no K/V, each under
``tree.py:SLOT_STATE`` in a module of its own: the last ``taps - 1`` inputs
of the short convolution before q, k and v (``conv_state.py``'s leaf and
function: ``[max_seqs, 3, 24576]`` bf16 at the published widths, 147,456
bytes a layer a sequence) and a ``d x d`` float32 matrix a head whose rows
decay each by a factor of their own (``ops/kda.py``): ``[max_seqs, d, heads *
d]``, heads along the lanes, 4,194,304 bytes a layer a sequence at 64 heads
of 128 x 128, both dimensions whole lane tiles.

A tick's rows take ``delta_state.py:ragged_gated_delta``'s path to the
letter (tiles of their own for the sequences of two rows or more, the
one-token step for the others, rows ``[T, H * d]`` all the way, scanned
where they lie in a tick whose chunks start on row tiles); what differs is
the rule, and that ``g`` is ``[T, H * d]``, a key channel wide: a row like
q's to the layout, which the scan zeroes past a tile's ``len`` itself."""
from __future__ import annotations

import functools

import jax.numpy as jnp

from deepspeed_tpu.inference.paged.delta_state import (DeltaState,
                                                       _levels_halved,
                                                       ragged_gated_delta)
from deepspeed_tpu.inference.paged.kv_pages import kv_heads_stored
from deepspeed_tpu.inference.paged.tree import state_bytes_per_seq
from deepspeed_tpu.ops import kda


def kda_layers(cfg) -> int:
    """The layers of a config with ``linear_attn_config`` that its
    ``gqa_layers`` does not name (a config without the key has none)."""
    if getattr(cfg, "linear_attn_config", None) is None:
        return 0
    of = int(cfg.num_hidden_layers)
    return of - len({i for i in cfg.gqa_layers if 0 <= i < of})


# ``q, k [T, H, d]``, ``v [T, H, d]``, ``g [T, H * d]`` float32 (the log of a
# token's decay a key channel), ``b [T, H]``, ``pos [T]`` -> ``o [T, H, d]``
ragged_kda = functools.partial(ragged_gated_delta, rule=kda)


class KdaState(DeltaState):
    """The KDA layers of a model whose others keep K/V pages: a
    gated-delta-rule part's refusals and counts, its own names."""

    rule = kda

    def span_attrs(self, cache):
        return {**super(DeltaState, self).span_attrs(cache),
                "kda_layers": self.layers}

    def stages(self, st, cache, cfg, kernels, pages_peak):
        # what a sequence's state costs as stored and as counted (a matrix
        # a head and the convolution's rows, a layer), and the heads a token
        # costs the pool of the model's other layers
        la = cfg.linear_attn_config
        H, d = int(la["num_heads"]), int(la["head_dim"])
        conv_rows = (int(la["short_conv_kernel_size"]) - 1) * 3 * H * d
        counted = self.layers * (H * d * d * 4 + conv_rows
                                 * jnp.dtype(cfg.dtype).itemsize)
        heads = kv_heads_stored(int(cfg.num_key_value_heads))
        return {"kda_state": {
            "state_layers": self.layers,
            "kv_layers": self.of - self.layers,
            "state_bytes_per_seq": state_bytes_per_seq(cache),
            "state_bytes_per_seq_counted": int(counted),
            "state_rows": st.state_rows,
            "kv_heads_stored": heads,
            "kv_bytes_per_token": (self.of - self.layers) * 2 * heads
            * int(cfg.head_dim) * jnp.dtype(cfg.dtype).itemsize,
            **_levels_halved("solve", kda.CHUNK, 2),
            **_levels_halved("pair", kda.CHUNK, 1),
            "routes": dict(kernels)}}
