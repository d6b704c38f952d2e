"""``flops_mla.py`` against a hand count at the published sizes of
``glm-4.7-flash-serve`` (the numbers ISSUE 40 and the configuration file
state), that ``flops.py`` WOULD count this configuration wrong, and the
``mla_*`` readers on hand-built rows and spans: values, ``None`` where
there is nothing to read (a parent's programs carry no latent scopes, its
spans no ``latent_bytes_per_token``), and rooflines that read 100 at their
bounds."""
import types

import pytest

from benchmark import flops, flops_mla
from benchmark import harness as H
from benchmark.layer_metrics import (host_spans, latent_bytes_per_token,
                                     mla_decode_roofline,
                                     mla_prefill_roofline, mla_share_pct,
                                     mla_trace)

READERS = (mla_prefill_roofline, mla_decode_roofline, mla_share_pct,
           latent_bytes_per_token)
MS = 1e6
GLM = H.load_json(H.HERE + "/configs/glm-4.7-flash-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# one fused tick: a chunk of 512 at positions 6,000.. and 7 decode rows
CHUNK_KEYS = sum(range(6001, 6513))
ROW_KEYS = 7 * 12_000
BLOCK = dict(rows=8, ticks=8, key_tokens=8 * 12_000)


def test_flops_mla_against_a_hand_count():
    c = GLM
    assert flops_mla.latent_row(c) == 576
    assert flops_mla.latent_bytes_per_token(c) == 8 * 1152 == 9216
    assert flops_mla.latent_bytes_per_token(c, stored=True) == 10240
    assert 2457 * 64 * 10240 <= c["engine"]["kv_pool_bytes"] < \
        2458 * 64 * 10240
    # ISSUE 40: 2,176 FLOP a query-key pair a head, absorbed
    assert flops_mla.pair_flops(c) == 20 * 2176
    assert flops_mla.chunk_attention_flops(c, 6144) == 8 * 20 * 2176 * 6144
    # ... 267 MFLOP a prompt token a layer at mean context 6,144
    assert round(flops_mla.chunk_attention_flops(c, 6144) / 8 / 1e6) == 267
    assert flops_mla.decode_attention_bytes(c, 100) == 921_600
    # the five matrices: 21,759,232 with the two norms' 1,280 scales
    assert flops_mla.attention_params(c) + 768 + 512 == 21_759_232


def test_the_dense_reader_would_count_it_wrong():
    """Why no ``flops.py`` roofline is listed for this cell: it divides the
    hidden size by the heads and holds K and V per head."""
    assert flops.kv_bytes_per_token(GLM) == 2 * 8 * 20 * 102 * 2 == 65_280
    assert flops.kv_bytes_per_token(GLM) > 7 * \
        flops_mla.latent_bytes_per_token(GLM)


def _rows(chunk_ms, decode_fused_ms, decode_block_ms, latent=True):
    """``scope_trace`` rows of one fused tick and one decode block."""
    fused, block = "ragged_fused_step", "ragged_decode_block"
    row = lambda sub, ms, prog, kind="attn": (      # noqa: E731
        "op", kind, sub, "-", "own", ms / 1e3, prog)
    if not latent:
        return [row("paged_chunk_attention", chunk_ms, fused),
                row("q_proj", 1.0, fused), row(None, 3.0, fused, "mlp")]
    return [row("latent_chunk_attention", chunk_ms, fused),
            row("latent_decode_attention", decode_fused_ms, fused),
            row("latent_decode_attention", decode_block_ms, block),
            row("q_a_proj", 1.0, fused), row("q_b_proj", 2.0, fused),
            row("kv_a_proj_with_mqa", 0.5, fused), row("absorb", 1.5, fused),
            row("o_proj", 2.0, fused), row("kv_a_layernorm", 0.25, fused),
            row(None, 0.75, fused),                 # RoPE, the scatter
            row("moe.experts", 6.0, fused, "moe"),
            row("lm_head", 1.0, block, "head")]


def _ctx(chunk_ms=40.0, decode_fused_ms=1.0, decode_block_ms=8.0,
         latent=True):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    cost = dict(latent_bytes_per_token=10240) if latent else {}
    worker = [ev("replica.op", 1, 399, kind="step"),
              ev("engine.dispatch", 2, 3, program="fused",
                 prefill_tokens=512, prefill_key_tokens=CHUNK_KEYS,
                 decode_key_tokens=ROW_KEYS, pages_in_use=1500,
                 pages_total=2456, **cost),
              ev("engine.dispatch", 60, 61, program="decode_block", **BLOCK,
                 pages_in_use=1600, pages_total=2456, **cost)]
    dev = {"/device:TPU:0": {
        "XLA Modules": [("jit_ragged_fused_step(1)", 0, 60 * MS),
                        ("jit_ragged_decode_block(2)", 60 * MS, 20 * MS)],
        "XLA Ops": [("%a = f32[1] add(f32[1])", 0, 60 * MS),
                    ("%b = f32[1] add(f32[1])", 60 * MS, 20 * MS)]}}
    from benchmark import trace_reduce as TR

    return {"cell": types.SimpleNamespace(name="cell", config=GLM),
            "trace": TR.reduce_planes(dev), "peaks": PEAKS,
            "_host_trace": host_spans.from_planes(dev, {0: worker}),
            "_mla_rows": _rows(chunk_ms, decode_fused_ms, decode_block_ms,
                               latent)}


def _block_bytes():
    return 9216 * 8 * (BLOCK["key_tokens"] + 8 * 3.5)


def test_the_readers_on_a_latent_models_rows_and_spans():
    ctx = _ctx()
    assert mla_prefill_roofline.read(ctx) == pytest.approx(
        100 * 8 * 20 * 2176 * CHUNK_KEYS / 197e12 / 0.040)
    assert mla_decode_roofline.read(ctx) == pytest.approx(
        100 * (9216 * ROW_KEYS + _block_bytes()) / 819e9 / 0.009)
    # projections 5.75, absorption 1.5, kernels 49 of 64 ms in all
    assert mla_share_pct.read(ctx) == pytest.approx(100 * 56.25 / 64.0)
    assert latent_bytes_per_token.read(ctx) == 10240


def test_a_parents_programs_and_spans_give_nothing_to_read():
    ctx = _ctx(latent=False)
    for reader in READERS:
        assert reader.read(ctx) is None
    for key, none in (("_mla_rows", None), ("_host_trace", None)):
        ctx = _ctx()
        ctx[key] = none
        assert mla_prefill_roofline.read(ctx) is None
        assert mla_decode_roofline.read(ctx) is None
    assert mla_trace.seconds(_ctx(), ("no_such_scope",)) is None


def test_the_rooflines_read_100_at_their_bounds():
    chunk_ms = 1e3 * 8 * 20 * 2176 * CHUNK_KEYS / 197e12
    row_ms = 1e3 * 9216 * ROW_KEYS / 819e9
    block_ms = 1e3 * _block_bytes() / 819e9
    at = _ctx(chunk_ms, row_ms, block_ms)
    assert mla_prefill_roofline.read(at) == pytest.approx(100.0)
    assert mla_decode_roofline.read(at) == pytest.approx(100.0)
    slower = _ctx(2 * chunk_ms, 4 * row_ms, 4 * block_ms)
    assert mla_prefill_roofline.read(slower) == pytest.approx(50.0)
    assert mla_decode_roofline.read(slower) == pytest.approx(25.0)
