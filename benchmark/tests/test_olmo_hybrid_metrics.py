"""``flops_olmo_hybrid.py`` against a hand count at the published sizes of
``olmo-hybrid-7b-serve`` (the numbers ISSUE 50 and the configuration file
state), and the three new readers on hand-built rows and spans: values,
``None`` where there is nothing to read (a parent's programs carry no such
kernels; a cell of another configuration reports none of them), and
rooflines that read 100 at their bounds."""
import types

import pytest

from benchmark import flops_olmo_hybrid as fo
from benchmark import harness as H
from benchmark.layer_metrics import (delta_decode_roofline,
                                     delta_scan_roofline, delta_share_pct,
                                     host_spans, scope_linear_pct,
                                     state_bytes_per_seq)

READERS = (delta_scan_roofline, delta_decode_roofline, delta_share_pct)
MS = 1e6
OLMO = H.load_json(H.HERE + "/configs/olmo-hybrid-7b-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE = 9 * (2_211_840 + 69_120)
# one fused tick: a chunk of 1,024 and 7 decode rows; one decode block of 8
# rows and 8 ticks
FUSED = dict(program="fused", prefill_tokens=1024, one_row_seqs=7,
             state_rows=8, state_layers=9, delta_layers=9, state_bytes=STATE)
BLOCK = dict(program="decode_block", rows=8, ticks=8, state_rows=8,
             state_layers=9, delta_layers=9, state_bytes=STATE)


def test_flops_olmo_hybrid_against_a_hand_count():
    c = OLMO
    assert (fo.delta_layers(c), fo.attention_layers(c)) == (9, 3)
    assert fo.state_bytes_per_seq(c) == STATE == 20_528_640
    assert fo.pool_bytes_per_token(c) == 3 * 2 * 32 * 128 * 2 == 49_152
    assert fo.pool_bytes_per_token(c, heads_stored=30) == 46_080
    assert c["engine"]["kv_pool_bytes"] == 2065 * 64 * 49_152
    scan = fo.delta_scan(c, 1024, chunk=64)
    a_token_a_head = (4 * 64 * 96 + 2 * 64 * (96 + 192) + 2 * 64 * 192
                      + 2 * 64 * 64 / 3 + 6 * 96 * 192)
    assert scan["flops"] == pytest.approx(9 * 1024 * 30 * a_token_a_head)
    # ISSUE 50: 0.054 GFLOP a prompt token over the nine layers
    assert scan["flops"] / 1024 == pytest.approx(0.054e9, rel=0.03)
    assert scan["bytes"] == 9 * 1024 * 30 * ((2 * 96 + 2 * 192) * 2 + 8)
    step = fo.delta_decode(c, 8)
    assert step["bytes"] == 9 * 8 * 2 * 2_211_840
    assert step["flops"] == 9 * 8 * 30 * 7 * 96 * 192


def _rows(scan_ms, step_fused_ms, step_block_ms, ours=True):
    fused, block = "ragged_fused_step", "ragged_decode_block"
    row = lambda kind, sub, ms, prog: (             # noqa: E731
        "op", kind, sub, "-", "own", ms / 1e3, prog)
    rest = [row("linear", "q_proj", 2.0, fused), row("mlp", None, 9.0, fused),
            row("attn", "paged_chunk_attention", 3.0, fused)]
    if not ours:
        # another model's mixer under kind ``linear``
        return rest + [row("linear", "linear_chunk_scan", 4.0, fused)]
    return rest + [row("linear", delta_scan_roofline.SCAN, scan_ms, fused),
                   row("linear", delta_decode_roofline.STEP, step_fused_ms,
                       fused),
                   row("linear", delta_decode_roofline.STEP, step_block_ms,
                       block)]


def _ctx(times=(10.0, 1.0, 8.0), ours=True, config=OLMO):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    fused, block = (FUSED, BLOCK) if ours else (
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

    return {"cell": types.SimpleNamespace(name="cell", config=config),
            "trace": TR.reduce_planes(dev), "peaks": PEAKS,
            "_host_trace": host_spans.from_planes(dev, {0: worker}),
            "_mla_rows": _rows(*times, ours=ours)}


def _bounds_ms():
    scan = fo.delta_scan(OLMO, 1024)
    scan_ms = 1e3 * max(scan["flops"] / 197e12, scan["bytes"] / 819e9)
    step = lambda n: 1e3 * fo.delta_decode(OLMO, n)["bytes"] / 819e9  # noqa
    return scan_ms, step(7), step(64)


def test_the_readers_on_the_models_rows_and_spans():
    ctx = _ctx()
    b = _bounds_ms()
    assert delta_scan_roofline.read(ctx) == pytest.approx(100 * b[0] / 10.0)
    assert delta_decode_roofline.read(ctx) == pytest.approx(
        100 * (b[1] + b[2]) / 9.0)
    assert delta_share_pct.read(ctx) == pytest.approx(
        100 * 19.0 / (19.0 + 14.0))
    # the two generic readers on this model's spans
    assert state_bytes_per_seq.read(ctx) == STATE
    assert scope_linear_pct.__doc__ and delta_share_pct.__doc__


def test_a_program_without_the_kernels_gives_nothing_to_read():
    """The parent's programs, and a cell of another configuration whose
    mixer is under kind ``linear`` too, report none of the three."""
    sala = H.load_json(H.HERE + "/configs/minicpm-sala-serve.json")
    for ctx in (_ctx(ours=False), _ctx(ours=False, config=sala)):
        for reader in READERS:
            assert reader.read(ctx) is None
    # no joined rows: nothing for any; no spans: no count of needed work
    for key, readers in (("_mla_rows", READERS), ("_host_trace", READERS[:2])):
        ctx = _ctx()
        ctx[key] = None
        for reader in readers:
            assert reader.read(ctx) is None


def test_the_rooflines_read_100_at_their_bounds():
    b = _bounds_ms()
    at = _ctx(b)
    for reader in READERS[:2]:
        assert reader.read(at) == pytest.approx(100.0)
    slower = _ctx(tuple(2 * x for x in b))
    for reader in READERS[:2]:
        assert reader.read(slower) == pytest.approx(50.0)
