"""``flops_solar.py`` against a hand count at the published sizes of
``solar-open2-250b-serve`` (the numbers ISSUE 56 and the configuration file
state), and the four new readers on hand-built rows and spans: values,
``None`` where there is nothing to read (a parent's programs carry no such
kernels and no such counts; a cell of another configuration reports none of
them), and rooflines that read 100 at their bounds."""
import types

import pytest

from benchmark import flops_hybrid
from benchmark import flops_solar as fs
from benchmark import harness as H
from benchmark.layer_metrics import (expert_rows_held_share_pct, host_spans,
                                     hybrid_load_max_over_mean,
                                     kda_decode_roofline, kda_scan_roofline,
                                     kda_share_pct, state_bytes_per_seq)

READERS = (kda_scan_roofline, kda_decode_roofline, kda_share_pct)
MS = 1e6
SOLAR = H.load_json(H.HERE + "/configs/solar-open2-250b-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE = 3 * (4_194_304 + 147_456)
# one fused tick: a chunk of 1,024 and 7 decode rows; one decode block of 8
# rows and 8 ticks
FUSED = dict(program="fused", prefill_tokens=1024, one_row_seqs=7,
             state_rows=8, state_layers=3, kda_layers=3, state_bytes=STATE)
BLOCK = dict(program="decode_block", rows=8, ticks=8, state_rows=8,
             state_layers=3, kda_layers=3, state_bytes=STATE)
# the expert layers' counts: a tick of 1,031 live rows routes 8 copies a
# layer, an eighth of them to the 40 experts held
MOE_FUSED = dict(program="fused", ticks=1, moe_rows=4 * 1031, moe_visits=160,
                 moe_experts_hit=4 * 40, moe_load_max=52,
                 moe_rows_routed=4 * 8 * 1031, moe_experts_held=4 * 40)
MOE_BLOCK = dict(program="decode_block", ticks=8, moe_rows=4 * 70,
                 moe_experts_hit=4 * 8 * 7, moe_load_max=3, moe_visits=224,
                 moe_rows_routed=4 * 8 * 64, moe_experts_held=4 * 8 * 40)


def test_flops_solar_against_a_hand_count():
    c = SOLAR
    assert (fs.kda_layers(c), fs.attention_layers(c)) == (3, 1)
    assert fs.state_bytes_per_seq(c) == STATE == 13_025_280
    assert fs.pool_bytes_per_token(c) == 2 * 8 * 128 * 2 == 4096
    assert c["engine"]["kv_pool_bytes"] == 2065 * 64 * 4096 == 541_327_360
    scan = fs.kda_scan(c, 1024, chunk=64)
    a_token_a_head = (6 * 64 * 128 + 2 * 64 * 64 / 3 + 6 * 128 * 128)
    assert scan["flops"] == pytest.approx(3 * 1024 * 64 * a_token_a_head)
    # q, k, v, o in bf16, g at 128 a head in float32, b
    assert scan["bytes"] == 3 * 1024 * 64 * (4 * 128 * 2 + 128 * 4 + 4)
    # the bytes bound it: g alone is a third of them
    assert scan["bytes"] / 819e9 > 2 * scan["flops"] / 197e12
    step = fs.kda_decode(c, 8)
    assert step["bytes"] == 3 * 8 * 2 * 4_194_304
    assert step["flops"] == 3 * 8 * 64 * 7 * 128 * 128
    # the shared expert readers read this file through GLM's two names
    assert flops_hybrid.expert_layers(c) == 4
    assert flops_hybrid.expert_params(c) == 15_728_640


def _rows(scan_ms, step_fused_ms, step_block_ms, ours=True):
    fused, block = "ragged_fused_step", "ragged_decode_block"
    row = lambda kind, sub, ms, prog: (             # noqa: E731
        "op", kind, sub, "-", "own", ms / 1e3, prog)
    rest = [row("linear", "q_proj", 2.0, fused), row("moe", None, 9.0, fused),
            row("attn", "paged_chunk_attention", 3.0, fused)]
    if not ours:
        # another model's mixer under kind ``linear``
        return rest + [row("linear", "delta_chunk_scan", 4.0, fused)]
    return rest + [row("linear", kda_scan_roofline.SCAN, scan_ms, fused),
                   row("linear", kda_decode_roofline.STEP, step_fused_ms,
                       fused),
                   row("linear", kda_decode_roofline.STEP, step_block_ms,
                       block)]


def _ctx(times=(10.0, 1.0, 8.0), ours=True, config=SOLAR):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    fused, block, moe_f, moe_b = (FUSED, BLOCK, MOE_FUSED, MOE_BLOCK)
    if not ours:
        fused = dict(program="fused", prefill_tokens=1024)
        block = dict(program="decode_block", rows=8, ticks=8)
        drop = ("moe_rows_routed", "moe_experts_held")
        moe_f, moe_b = ({k: v for k, v in m.items() if k not in drop}
                        for m in (MOE_FUSED, MOE_BLOCK))
    worker = [ev("replica.op", 1, 399, kind="step"),
              ev("engine.dispatch", 2, 3, **fused),
              ev("engine.moe", 3, 3.1, **moe_f),
              ev("engine.dispatch", 60, 61, **block),
              ev("engine.moe", 61, 61.1, **moe_b)]
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
    scan = fs.kda_scan(SOLAR, 1024)
    scan_ms = 1e3 * max(scan["flops"] / 197e12, scan["bytes"] / 819e9)
    step = lambda n: 1e3 * fs.kda_decode(SOLAR, n)["bytes"] / 819e9  # noqa
    return scan_ms, step(7), step(64)


def test_the_readers_on_the_models_rows_and_spans():
    ctx = _ctx()
    b = _bounds_ms()
    assert kda_scan_roofline.read(ctx) == pytest.approx(100 * b[0] / 10.0)
    assert kda_decode_roofline.read(ctx) == pytest.approx(
        100 * (b[1] + b[2]) / 9.0)
    assert kda_share_pct.read(ctx) == pytest.approx(
        100 * 19.0 / (19.0 + 14.0))
    assert expert_rows_held_share_pct.read(ctx) == pytest.approx(
        100 * (4 * 1031 + 4 * 70) / (4 * 8 * 1031 + 4 * 8 * 64))
    # two generic readers on this model's spans: the state a sequence, and
    # the load over the HELD experts (num_experts 40, four expert layers)
    assert state_bytes_per_seq.read(ctx) == STATE
    assert hybrid_load_max_over_mean.read(ctx) == pytest.approx(
        (52 * 160 / (4 * 1031) + 3 * 160 * 8 / (4 * 70)) / 2)


def test_a_program_without_the_kernels_gives_nothing_to_read():
    """The parent's programs, and a cell of another configuration whose
    mixer is under kind ``linear`` too, report none of the four."""
    olmo = H.load_json(H.HERE + "/configs/olmo-hybrid-7b-serve.json")
    for ctx in (_ctx(ours=False), _ctx(ours=False, config=olmo)):
        for reader in READERS + (expert_rows_held_share_pct,):
            assert reader.read(ctx) is None
    # no joined rows: nothing for any; no spans: no count of needed work
    for key, readers in (("_mla_rows", READERS), ("_host_trace", READERS[:2]
                                                  + (expert_rows_held_share_pct,))):
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
