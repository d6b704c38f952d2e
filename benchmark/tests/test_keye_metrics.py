"""``flops_keye.py`` against a hand count at the published sizes of
``keye-vl2-30b-a3b-serve`` (the numbers ISSUE 47 and the configuration file
state), and the new readers on hand-built rows and spans: values, ``None``
where there is nothing to read (a parent's programs carry no such scopes,
its spans none of the counts), and rooflines that read 100 at their
bounds."""
import types

import pytest

from benchmark import flops_keye
from benchmark import harness as H
from benchmark.layer_metrics import (host_spans, index_bytes_per_token,
                                     index_decode_roofline,
                                     index_prefill_roofline, keye_trace,
                                     tokensel_decode_roofline,
                                     tokensel_prefill_roofline,
                                     tokensel_selected_share_pct,
                                     tokensel_share_pct)

ROOFLINES = (index_prefill_roofline, index_decode_roofline,
             tokensel_prefill_roofline, tokensel_decode_roofline)
READERS = ROOFLINES + (tokensel_selected_share_pct, tokensel_share_pct,
                       index_bytes_per_token)
MS = 1e6
KEYE = H.load_json(H.HERE + "/configs/keye-vl2-30b-a3b-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# one fused tick: a chunk of 1,024 at positions 20,000.. and 7 decode rows
# at 24,000; one decode block of 8 rows at 24,000
CHUNK_SEEN = sum(range(20001, 21025))
ROWS_SEEN = 7 * 24001
BLOCK_SEEN = sum(8 * (p + 1) for p in range(24000, 24008))
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


def test_flops_keye_against_a_hand_count():
    c = KEYE
    assert flops_keye.layers(c) == 6
    assert flops_keye.index_bytes_per_token(c) == 6 * 64 * 2 == 768
    assert flops_keye.pool_bytes_per_token(c) == 6 * 2176 == 13_056
    assert c["engine"]["kv_pool_bytes"] == 4160 * 64 * 13_056
    scored = flops_keye.index_scores(c, 1000, 10, shared_keys=False)
    assert scored["flops"] == 6 * 1000 * 2 * 16 * 64
    assert scored["bytes"] == 6 * (1000 * 128 + 10 * 16 * 64 * 2)
    shared = flops_keye.index_scores(c, 128_000, 128, shared_keys=True)
    assert shared["bytes"] == 6 * (1000 * 128 + 128 * 16 * 64 * 2)
    att = flops_keye.chosen_attention(c, 2048, 1, shared_keys=False)
    assert att["flops"] == 6 * 2048 * 32 * 4 * 128
    assert att["bytes"] == 6 * (2048 * 2048 + 32 * 128 * 2 * 2)


def _rows(index_ms, index_row_fused_ms, index_row_block_ms, chunk_ms,
          row_fused_ms, row_block_ms, ours=True):
    fused, block = "ragged_fused_step", "ragged_decode_block"
    row = lambda kind, sub, ms, prog: (             # noqa: E731
        "op", kind, sub, "-", "own", ms / 1e3, prog)
    if not ours:
        return [row("attn", "paged_chunk_attention", chunk_ms, fused),
                row("attn", "select", 1.0, fused),
                row("moe", "moe.route", 1.0, fused)]
    kt = keye_trace
    # the chunks' indexer time in five pieces, the rows' in three
    return [row("attn", kt.INDEXER, 0.1 * index_ms, fused),
            row("attn", "index", 0.1 * index_ms, fused),
            row("attn", "index_scores", 0.3 * index_ms, fused),
            row("attn", "select", 0.1 * index_ms, fused),
            row("attn", "index_select", 0.4 * index_ms, fused),
            row("attn", "index_row", 0.6 * index_row_fused_ms, fused),
            row("attn", "select_row", 0.4 * index_row_fused_ms, fused),
            row("attn", kt.INDEXER, 0.2 * index_row_block_ms, block),
            row("attn", "index_row", 0.4 * index_row_block_ms, block),
            row("attn", "select_row", 0.4 * index_row_block_ms, block),
            row("attn", kt.CHUNK_ATTEND, chunk_ms, fused),
            row("attn", kt.ROW_ATTEND, row_fused_ms, fused),
            row("attn", kt.ROW_ATTEND, row_block_ms, block),
            row("attn", "q_proj", 2.0, fused),
            row("moe", "moe.experts", 9.0, fused)]


def _ctx(times=(10.0, 3.0, 12.0, 20.0, 1.0, 4.0), ours=True):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    attrs = dict(index_bytes_per_token=768, tokensel_kv_bytes_per_token=12288)
    fused, block = (dict(FUSED, **attrs), dict(BLOCK, **attrs)) if ours \
        else (dict(program="fused", prefill_tokens=1024),
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

    return {"cell": types.SimpleNamespace(name="cell", config=KEYE),
            "trace": TR.reduce_planes(dev), "peaks": PEAKS,
            "_host_trace": host_spans.from_planes(dev, {0: worker}),
            "_mla_rows": _rows(*times, ours=ours)}


def _bounds_ms():
    c = KEYE
    index_ms = 1e3 * flops_keye.index_scores(
        c, CHUNK_SEEN, 1024, shared_keys=True)["flops"] / 197e12
    row = lambda seen, n: 1e3 * flops_keye.index_scores(  # noqa: E731
        c, seen, n, shared_keys=False)["bytes"] / 819e9
    chunk_ms = 1e3 * flops_keye.chosen_attention(
        c, 1024 * 2048, 1024, shared_keys=True)["flops"] / 197e12
    att = lambda pairs, n: 1e3 * flops_keye.chosen_attention(  # noqa: E731
        c, pairs, n, shared_keys=False)["bytes"] / 819e9
    return (index_ms, row(ROWS_SEEN, 7), row(BLOCK_SEEN, 64), chunk_ms,
            att(7 * 2048, 7), att(64 * 2048, 64))


def test_the_readers_on_the_models_rows_and_spans(capsys):
    ctx = _ctx()
    b = _bounds_ms()
    assert index_prefill_roofline.read(ctx) == pytest.approx(
        100 * b[0] / 10.0)
    assert index_decode_roofline.read(ctx) == pytest.approx(
        100 * (b[1] + b[2]) / 15.0)
    assert tokensel_prefill_roofline.read(ctx) == pytest.approx(
        100 * b[3] / 20.0)
    assert tokensel_decode_roofline.read(ctx) == pytest.approx(
        100 * (b[4] + b[5]) / 5.0)
    # 2,048 of 20,000-24,000 visible keys
    assert 8 < tokensel_selected_share_pct.read(ctx) < 11
    assert tokensel_share_pct.read(ctx) == pytest.approx(
        100 * 50.0 / (50.0 + 2.0 + 9.0))
    assert index_bytes_per_token.read(ctx) == 768
    assert "keye_scopes" in capsys.readouterr().out


def test_a_parents_programs_and_spans_give_nothing_to_read():
    ctx = _ctx(ours=False)
    for reader in READERS:
        assert reader.read(ctx) is None
    for key in ("_mla_rows", "_host_trace"):
        ctx = _ctx()
        ctx[key] = None
        for reader in ROOFLINES:
            assert reader.read(ctx) is None


def test_the_rooflines_read_100_at_their_bounds():
    b = _bounds_ms()
    at = _ctx(b)
    for reader in ROOFLINES:
        assert reader.read(at) == pytest.approx(100.0)
    slower = _ctx(tuple(2 * x for x in b))
    for reader in ROOFLINES:
        assert reader.read(slower) == pytest.approx(50.0)
