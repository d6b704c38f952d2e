"""``flops_sala.py`` against a hand count at the published sizes of
``minicpm-sala-serve`` (the numbers ISSUE 43 and the configuration file
state), and the new readers on hand-built rows and spans: values, ``None``
where there is nothing to read (a parent's programs carry no such kernels,
its spans none of the counts), and rooflines that read 100 at their
bounds."""
import types

import pytest

from benchmark import flops_sala
from benchmark import harness as H
from benchmark.layer_metrics import (host_spans, linear_decode_roofline,
                                     linear_scan_roofline, sala_trace,
                                     sparse_decode_roofline,
                                     sparse_prefill_roofline,
                                     sparse_selected_share_pct,
                                     state_bytes_per_seq)

READERS = (linear_scan_roofline, linear_decode_roofline,
           sparse_prefill_roofline, sparse_decode_roofline,
           sparse_selected_share_pct, state_bytes_per_seq)
MS = 1e6
SALA = H.load_json(H.HERE + "/configs/minicpm-sala-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# one fused tick: a chunk of 1,024 at positions 9,000.. and 7 decode rows
# at 12,000; one decode block of 8 rows at 12,000
CHUNK_PAIRS = 1024 * 4096 - sum(63 - p % 64 for p in range(9000, 10024))
ROW_PAIRS = 7 * (4096 - (63 - 12000 % 64))
BLOCK_PAIRS = sum(8 * (4096 - (63 - p % 64)) for p in range(12000, 12008))
FUSED = dict(program="fused", prefill_tokens=1024, one_row_seqs=7,
             attend_prefill_tokens=CHUNK_PAIRS,
             attend_decode_tokens=ROW_PAIRS, sparse_rows=1031,
             sparse_key_tokens=CHUNK_PAIRS + ROW_PAIRS,
             sparse_visible_tokens=sum(range(9001, 10025)) + 7 * 12001)
BLOCK = dict(program="decode_block", rows=8, ticks=8,
             attend_decode_tokens=BLOCK_PAIRS, sparse_rows=64,
             sparse_key_tokens=BLOCK_PAIRS,
             sparse_visible_tokens=sum(8 * (p + 1)
                                       for p in range(12000, 12008)))


def test_flops_sala_against_a_hand_count():
    c = SALA
    assert (flops_sala.linear_layers(c), flops_sala.sparse_layers(c)) == (9, 3)
    assert flops_sala.state_bytes_per_seq(c) == 9 * 2 * 2 ** 20 == 18_874_368
    # K and V 3 KiB a token, compressed keys 4 x 2 x 512 B a page of 64
    assert flops_sala.pool_bytes_per_token(c) == 3072 + 192
    assert c["engine"]["kv_pool_bytes"] == 2457 * 64 * 3264
    scan = flops_sala.linear_scan(c, 1024)
    assert scan["flops"] == 9 * 1024 * 32 * (4 * 128 * 128 + 4 * 128 * 128)
    assert scan["bytes"] == 9 * 1024 * 32 * 128 * 4 * 2
    step = flops_sala.linear_decode(c, 8)
    assert step["bytes"] == 9 * 8 * 2 * 2 ** 21            # 2 x 2 MiB a row
    att = flops_sala.sparse_attention(c, 4096, 1)
    assert att["flops"] == 3 * 4096 * 32 * 4 * 128
    assert att["bytes"] == 3 * (4096 * 2 * 2 * 128 * 2 + 32 * 128 * 2 * 2)


def _rows(scan_ms, step_fused_ms, step_block_ms, chunk_ms, dec_fused_ms,
          dec_block_ms, ours=True):
    fused, block = "ragged_fused_step", "ragged_decode_block"
    row = lambda kind, sub, ms, prog: (             # noqa: E731
        "op", kind, sub, "-", "own", ms / 1e3, prog)
    if not ours:
        return [row("attn", "paged_chunk_attention", chunk_ms, fused),
                row("conv", "in_proj", 1.0, fused)]
    return [row("linear", sala_trace.SCAN, scan_ms, fused),
            row("linear", sala_trace.STEP, step_fused_ms, fused),
            row("linear", sala_trace.STEP, step_block_ms, block),
            row("attn", sala_trace.SPARSE_CHUNK, chunk_ms, fused),
            row("attn", sala_trace.SPARSE_DECODE, dec_fused_ms, fused),
            row("attn", sala_trace.SPARSE_DECODE, dec_block_ms, block),
            row("linear", "q_proj", 2.0, fused),
            row("attn", "select", 1.0, fused),
            row("mlp", None, 9.0, fused)]


def _ctx(times=(4.0, 1.0, 8.0, 20.0, 1.0, 4.0), ours=True):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    state = dict(state_rows=8, state_layers=9, state_bytes=18_874_368)
    fused, block = (dict(FUSED, **state), dict(BLOCK, **state)) if ours \
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

    return {"cell": types.SimpleNamespace(name="cell", config=SALA),
            "trace": TR.reduce_planes(dev), "peaks": PEAKS,
            "_host_trace": host_spans.from_planes(dev, {0: worker}),
            "_mla_rows": _rows(*times, ours=ours)}


def _bounds_ms():
    c = SALA
    scan = flops_sala.linear_scan(c, 1024)
    scan_ms = 1e3 * max(scan["flops"] / 197e12, scan["bytes"] / 819e9)
    step = lambda n: 1e3 * flops_sala.linear_decode(    # noqa: E731
        c, n)["bytes"] / 819e9
    chunk_ms = 1e3 * flops_sala.sparse_attention(
        c, CHUNK_PAIRS, 0)["flops"] / 197e12
    dec = lambda pairs, n: 1e3 * flops_sala.sparse_attention(  # noqa: E731
        c, pairs, n)["bytes"] / 819e9
    return (scan_ms, step(7), step(64), chunk_ms, dec(ROW_PAIRS, 7),
            dec(BLOCK_PAIRS, 64))


def test_the_readers_on_the_models_rows_and_spans(capsys):
    ctx = _ctx()
    b = _bounds_ms()
    assert linear_scan_roofline.read(ctx) == pytest.approx(100 * b[0] / 4.0)
    assert linear_decode_roofline.read(ctx) == pytest.approx(
        100 * (b[1] + b[2]) / 9.0)
    assert sparse_prefill_roofline.read(ctx) == pytest.approx(
        100 * b[3] / 20.0)
    assert sparse_decode_roofline.read(ctx) == pytest.approx(
        100 * (b[4] + b[5]) / 5.0)
    share = sparse_selected_share_pct.read(ctx)
    assert 30 < share < 46              # 4,096 of 9,000-12,000 visible keys
    assert state_bytes_per_seq.read(ctx) == 18_874_368
    assert "sala_scopes" in capsys.readouterr().out


def test_a_parents_programs_and_spans_give_nothing_to_read():
    ctx = _ctx(ours=False)
    for reader in READERS:
        assert reader.read(ctx) is None
    for key in ("_mla_rows", "_host_trace"):
        ctx = _ctx()
        ctx[key] = None
        for reader in READERS[:4]:
            assert reader.read(ctx) is None


def test_the_rooflines_read_100_at_their_bounds():
    b = _bounds_ms()
    at = _ctx(b)
    for reader in READERS[:4]:
        assert reader.read(at) == pytest.approx(100.0)
    slower = _ctx(tuple(2 * x for x in b))
    for reader in READERS[:4]:
        assert reader.read(slower) == pytest.approx(50.0)
