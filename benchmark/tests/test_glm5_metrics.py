"""``flops_glm5.py`` against a hand count at the published sizes of
``glm-5-serve`` (the numbers ISSUE 69 and the configuration file state),
and the seven new readers on hand-built rows and spans: values, ``None``
where there is nothing to read (a parent's programs carry no such scopes,
its spans none of the counts; Keye's and GLM-4.7's carry half of them), and
rooflines that read 100 at their bounds and never over."""
import types

import pytest

from benchmark import flops_glm5
from benchmark import harness as H
from benchmark.layer_metrics import (dsa_attend_decode_roofline,
                                     dsa_attend_prefill_roofline,
                                     dsa_chosen_share_pct,
                                     dsa_index_decode_roofline,
                                     dsa_index_prefill_roofline,
                                     dsa_pool_bytes_per_token, dsa_share_pct,
                                     glm5_trace, host_spans)

ROOFLINES = (dsa_index_prefill_roofline, dsa_index_decode_roofline,
             dsa_attend_prefill_roofline, dsa_attend_decode_roofline)
READERS = ROOFLINES + (dsa_chosen_share_pct, dsa_share_pct,
                       dsa_pool_bytes_per_token)
MS = 1e6
GLM5 = H.load_json(H.HERE + "/configs/glm-5-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# one fused tick: a chunk of 1,024 at positions 10,000.. and 7 decode rows
# at 12,000; one decode block of 8 rows at 12,000
CHUNK_SEEN = sum(range(10001, 11025))
ROWS_SEEN = 7 * 12001
BLOCK_SEEN = sum(8 * (p + 1) for p in range(12000, 12008))
FUSED = dict(program="fused", prefill_tokens=1024, one_row_seqs=7,
             index_rows=1031, index_visible_tokens=CHUNK_SEEN + ROWS_SEEN,
             index_prefill_tokens=CHUNK_SEEN, index_decode_tokens=ROWS_SEEN,
             sel_rows=1031, sel_key_tokens=1031 * 2048,
             sel_visible_tokens=CHUNK_SEEN + ROWS_SEEN,
             attend_prefill_tokens=1024 * 2048,
             attend_decode_tokens=7 * 2048)
BLOCK = dict(program="decode_block", rows=8, ticks=8, index_rows=64,
             index_visible_tokens=BLOCK_SEEN, index_prefill_tokens=0,
             index_decode_tokens=BLOCK_SEEN, sel_rows=64,
             sel_key_tokens=64 * 2048, sel_visible_tokens=BLOCK_SEEN,
             attend_prefill_tokens=0, attend_decode_tokens=64 * 2048)


def test_flops_glm5_against_a_hand_count():
    c = GLM5
    assert flops_glm5.layers(c) == 6 and flops_glm5.row_width(c) == 576
    assert flops_glm5.index_bytes_per_token(c) == 6 * 128 * 2 == 1536
    assert flops_glm5.pool_bytes_per_token(c) == 6 * (640 + 128) * 2 == 9216
    assert c["engine"]["kv_pool_bytes"] == 2080 * 64 * 9216
    scored = flops_glm5.index_scores(c, 1000, 10, shared_keys=False)
    assert scored["flops"] == 6 * 1000 * 2 * 32 * 128
    assert scored["bytes"] == 6 * (1000 * 256 + 10 * 32 * 128 * 2)
    shared = flops_glm5.index_scores(c, 128_000, 128, shared_keys=True)
    assert shared["bytes"] == 6 * (1000 * 256 + 128 * 32 * 128 * 2)
    att = flops_glm5.chosen_attention(c, 2048, 1, shared_keys=False)
    # 2 x 576 for the score, 2 x 512 for the sum: 2,176 a pair a head
    assert att["flops"] == 6 * 2048 * 64 * 2176
    # ONE row of 1,152 bytes for all 64 heads; the absorbed heads in, the
    # sums out
    assert att["bytes"] == 6 * (2048 * 1152 + 64 * (576 + 512) * 2)
    # a decode row's chosen-row attention is bound by bytes at 109 FLOP a
    # byte of rows against the chip's 240, a prompt chunk's by its products
    assert att["flops"] / 197e12 < att["bytes"] / 819e9
    chunk = flops_glm5.chosen_attention(c, 1024 * 2048, 1024,
                                        shared_keys=True)
    assert chunk["flops"] / 197e12 > chunk["bytes"] / 819e9


def _rows(index_ms, index_row_fused_ms, index_row_block_ms, chunk_ms,
          row_fused_ms, row_block_ms, ours="glm5"):
    fused, block = "ragged_fused_step", "ragged_decode_block"
    row = lambda kind, sub, ms, prog: (             # noqa: E731
        "op", kind, sub, "-", "own", ms / 1e3, prog)
    if ours == "parent":
        return [row("attn", "paged_chunk_attention", chunk_ms, fused),
                row("attn", "select", 1.0, fused),
                row("moe", "moe.route", 1.0, fused)]
    gt = glm5_trace
    chunk, one = (gt.CHUNK_ATTEND, gt.ROW_ATTEND) if ours == "glm5" else (
        "tokensel_chunk_attention", "tokensel_row_attention")
    # the chunks' indexer time in four pieces, the rows' in three
    return [row("attn", gt.INDEXER, 0.1 * index_ms, fused),
            row("attn", "index", 0.1 * index_ms, fused),
            row("attn", "select", 0.1 * index_ms, fused),
            row("attn", "index_select", 0.7 * index_ms, fused),
            row("attn", "index_row", 0.6 * index_row_fused_ms, fused),
            row("attn", "select_row", 0.4 * index_row_fused_ms, fused),
            row("attn", gt.INDEXER, 0.2 * index_row_block_ms, block),
            row("attn", "row_index_scores", 0.4 * index_row_block_ms, block),
            row("attn", "select_row", 0.4 * index_row_block_ms, block),
            row("attn", chunk, chunk_ms, fused),
            row("attn", one, row_fused_ms, fused),
            row("attn", one, row_block_ms, block),
            row("attn", "q_b_proj", 2.0, fused),
            row("attn", "absorb", 1.0, fused),
            row("moe", "moe.experts", 9.0, fused),
            row("moe", "moe.experts", 5.0, block)]


def _ctx(times=(10.0, 3.0, 12.0, 20.0, 1.0, 4.0), ours="glm5"):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    attrs = {"glm5": dict(latent_bytes_per_token=7680,
                          index_bytes_per_token=1536),
             "keye": dict(index_bytes_per_token=768,
                          tokensel_kv_bytes_per_token=12288),
             "parent": {}}[ours]
    fused, block = (dict(FUSED, **attrs), dict(BLOCK, **attrs)) \
        if ours != "parent" else (
            dict(program="fused", prefill_tokens=1024),
            dict(program="decode_block", rows=8, ticks=8))
    worker = [ev("replica.op", 1, 399, kind="step"),
              ev("engine.dispatch", 2, 3, **fused),
              ev("engine.dispatch", 60, 61, **block)]
    dev = {"/device:TPU:0": {
        "XLA Modules": [("jit_ragged_fused_step(1)", 0, 60 * MS),
                        ("jit_ragged_decode_block(2)", 60 * MS, 20 * MS)],
        "XLA Ops": [("%a = f32[1] add(f32[1])", 0, 60 * MS),
                    ("%b = f32[1] add(f32[1])", 60 * MS, 20 * MS)]}}
    from benchmark import trace_reduce as TR

    return {"cell": types.SimpleNamespace(name="cell", config=GLM5),
            "trace": TR.reduce_planes(dev), "peaks": PEAKS,
            "_host_trace": host_spans.from_planes(dev, {0: worker}),
            "_mla_rows": _rows(*times, ours=ours)}


def _bounds_ms():
    c = GLM5
    index_ms = 1e3 * flops_glm5.index_scores(
        c, CHUNK_SEEN, 1024, shared_keys=True)["flops"] / 197e12
    row = lambda seen, n: 1e3 * flops_glm5.index_scores(  # noqa: E731
        c, seen, n, shared_keys=False)["bytes"] / 819e9
    chunk_ms = 1e3 * flops_glm5.chosen_attention(
        c, 1024 * 2048, 1024, shared_keys=True)["flops"] / 197e12
    att = lambda pairs, n: 1e3 * flops_glm5.chosen_attention(  # noqa: E731
        c, pairs, n, shared_keys=False)["bytes"] / 819e9
    return (index_ms, row(ROWS_SEEN, 7), row(BLOCK_SEEN, 64), chunk_ms,
            att(7 * 2048, 7), att(64 * 2048, 64))


def test_the_readers_on_the_models_rows_and_spans(capsys):
    ctx = _ctx()
    b = _bounds_ms()
    assert dsa_index_prefill_roofline.read(ctx) == pytest.approx(
        100 * b[0] / 10.0)
    assert dsa_index_decode_roofline.read(ctx) == pytest.approx(
        100 * (b[1] + b[2]) / 15.0)
    assert dsa_attend_prefill_roofline.read(ctx) == pytest.approx(
        100 * b[3] / 20.0)
    assert dsa_attend_decode_roofline.read(ctx) == pytest.approx(
        100 * (b[4] + b[5]) / 5.0)
    # 2,048 of 10,000-12,000 visible keys
    assert dsa_chosen_share_pct.read(ctx) == pytest.approx(
        100 * (1031 + 64) * 2048 / (CHUNK_SEEN + ROWS_SEEN + BLOCK_SEEN))
    assert 17 < dsa_chosen_share_pct.read(ctx) < 20
    # of the FUSED TICK's time: its indexer, choice and both attentions
    assert dsa_share_pct.read(ctx) == pytest.approx(
        100 * (10.0 + 3.0 + 20.0 + 1.0) / (34.0 + 2.0 + 1.0 + 9.0))
    assert dsa_pool_bytes_per_token.read(ctx) == 9216
    assert "keye_scopes" in capsys.readouterr().out


@pytest.mark.parametrize("ours", ["parent", "keye"])
def test_other_programs_and_spans_give_nothing_to_read(ours):
    """The parent's programs carry none of the scopes and its spans none of
    the counts; Keye's carry the indexer's and the counts but not the
    chosen-LATENT names, and no latent bytes."""
    ctx = _ctx(ours=ours)
    for reader in READERS:
        assert reader.read(ctx) is None, reader.__name__
    # without the trace's rows nothing reads; without the host's spans the
    # share of the tick's device time still does (it counts no span)
    for key, still in (("_mla_rows", ()), ("_host_trace", (dsa_share_pct,))):
        ctx = _ctx()
        ctx[key] = None
        for reader in READERS:
            if key == "_mla_rows" and reader is dsa_pool_bytes_per_token:
                continue                    # the spans' own count
            assert (reader.read(ctx) is None) == (reader not in still), (
                key, reader.__name__)


def test_the_rooflines_read_100_at_their_bounds_and_never_over():
    b = _bounds_ms()
    at = _ctx(b)
    for reader in ROOFLINES:
        assert reader.read(at) == pytest.approx(100.0)
    slower = _ctx(tuple(2 * x for x in b))
    for reader in ROOFLINES:
        assert reader.read(slower) == pytest.approx(50.0)
    # what the equations need is the LEAST any form could do: a masked
    # dense pass over every visible pair, at the chip's peak, reads the
    # chosen share of them
    dense_ms = b[3] * CHUNK_SEEN / (1024 * 2048)
    masked = _ctx((b[0], b[1], b[2], dense_ms, b[4], b[5]))
    assert dsa_attend_prefill_roofline.read(masked) == pytest.approx(
        100 * 1024 * 2048 / CHUNK_SEEN)
