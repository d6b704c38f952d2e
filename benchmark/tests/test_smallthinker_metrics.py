"""``flops_smallthinker.py`` against a hand count at the published sizes of
``smallthinker-21b-a3b-serve`` (the numbers ISSUE 64 and the configuration
file state), and the three new readers on hand-built rows and spans: values,
``None`` where there is nothing to read (a program without the scopes or the
counts), rooflines that read 100 at their bounds, and the accepted readers
that read this configuration's spans through the names they know."""
import types

import pytest

from benchmark import flops_smallthinker as fs
from benchmark import harness as H
from benchmark.layer_metrics import (attn_window_share_pct,
                                     gqa4_decode_roofline,
                                     gqa4_prefill_roofline, host_spans,
                                     kv_pool_peak_pct, reglu_expert_roofline,
                                     trinity_trace, window_pages_saved_pct,
                                     window_pool_peak_pct)

MS = 1e6
CFG = H.load_json(H.HERE + "/configs/smallthinker-21b-a3b-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
W, F = fs.WINDOW, fs.FULL
# one fused tick: a chunk of 1,024 rows from position 3,000 on and 31 decode
# rows at 5,000; one decode block of 32 rows and 8 ticks from 5,000 on
CHUNK_FULL = sum(range(3_001, 4_025))
FUSED = dict(program="fused", prefill_tokens=1024,
             prefill_key_tokens=CHUNK_FULL, decode_key_tokens=31 * 5_001,
             window_prefill_key_tokens=CHUNK_FULL,
             window_decode_key_tokens=31 * 4096, window_layers=6,
             window_pages_total=2624, window_pages_held=2000,
             window_pages_released=3, full_pages_held=2400,
             pages_in_use=2400, pages_total=4128)
BLOCK = dict(program="decode_block", rows=32, ticks=8,
             key_tokens=32 * 5_001, window_prefill_key_tokens=0,
             window_decode_key_tokens=256 * 4096, window_layers=6,
             window_pages_total=2624, window_pages_held=2050,
             window_pages_released=2, full_pages_held=2530,
             pages_in_use=2530, pages_total=4128)
MOE_FUSED = dict(program="fused", ticks=1, moe_rows=8 * 6 * 1055,
                 moe_experts_hit=8 * 64)
MOE_BLOCK = dict(program="decode_block", ticks=8, moe_rows=8 * 8 * 6 * 32,
                 moe_experts_hit=8 * 8 * 61)


def test_flops_smallthinker_against_a_hand_count():
    c = CFG
    assert (fs.layers(c, W), fs.layers(c, F)) == (6, 2)
    assert fs.pool_bytes_per_token(c, F) == 2 * 2 * 4 * 128 * 2 == 4096
    assert fs.pool_bytes_per_token(c, W) == 6 * 2048
    eng = c["engine"]
    assert eng["kv_pool_bytes"] == 4129 * 64 * 4096 == 1_082_392_576
    assert eng["window_pool_bytes"] == 2625 * 64 * 6 * 2048 == 2_064_384_000
    assert fs.expert_params(c) == 3 * 2560 * 768 == 5_898_240
    assert fs.expert_matmul_flops(c, 10) == 2 * 5_898_240 * 10
    assert fs.expert_matmul_bytes(c, 10, 3) == 2 * (3 * 5_898_240
                                                    + 2 * 2560 * 10)
    # ISSUE 64's decode tick: 32 rows, 61 of 64 experts hit a layer, is
    # 5.8 GB of banks, memory-bound by far
    tick = fs.expert_matmul_bytes(c, 8 * 6 * 32, 8 * 61)
    assert 5.7e9 < tick < 5.8e9
    assert tick / 819e9 > 50 * fs.expert_matmul_flops(c, 8 * 6 * 32) / 197e12
    pre = fs.key_tokens(FUSED, "prefill")
    assert pre == {F: CHUNK_FULL, W: CHUNK_FULL}
    work = fs.attention(c, pre, rows=1024)
    assert work["flops"] == 4 * 28 * 128 * 8 * CHUNK_FULL
    assert work["bytes"] == 2 * 8 * 1024 * 28 * 128 * 2
    dec = fs.key_tokens(BLOCK, "decode")
    assert dec == {F: 8 * 32 * 5_001 + 32 * 8 * 7 / 2, W: 256 * 4096}
    step = fs.attention(c, dec)
    assert step["bytes"] == (2 * dec[F] + 6 * dec[W]) * 2 * 4 * 128 * 2
    assert step["bytes"] / 819e9 > step["flops"] / 197e12      # memory
    # at a context of 5,000 the window pool is read for two thirds of it
    assert 0.6 < 6 * dec[W] / (2 * dec[F] + 6 * dec[W]) < 0.72


def _rows(chunk_ms, decode_fused_ms, decode_block_ms, ours=True):
    fused, block = "ragged_fused_step", "ragged_decode_block"
    row = lambda kind, sub, ms, prog: (             # noqa: E731
        "op", kind, sub, "-", "own", ms / 1e3, prog)
    rest = [row("moe", "moe.experts", 9.0, fused),
            row("moe", "moe.route", 1.0, fused),
            row("attn", "input_layernorm", 1.0, fused)]
    if not ours:
        return rest + [row("attn", "paged_chunk_attention", 4.0, fused)]
    kern = lambda kind, name: f"{kind}/{name}"      # noqa: E731
    return rest + [
        row("attn", "attn_window", 2.0, fused),
        row("attn", kern("attn_window", trinity_trace.CHUNK_KERNEL),
            0.7 * chunk_ms, fused),
        row("attn", kern("attn_full", trinity_trace.CHUNK_KERNEL),
            0.3 * chunk_ms, fused),
        row("attn", kern("attn_window", trinity_trace.DECODE_KERNEL),
            decode_fused_ms, fused),
        row("attn", kern("attn_full", trinity_trace.DECODE_KERNEL),
            decode_block_ms, block)]


def _ctx(times=(10.0, 1.0, 4.0), ours=True, gmm_ms=(40.0, 16.0)):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    fused, block = FUSED, BLOCK
    if not ours:
        keep = ("program", "prefill_tokens", "prefill_key_tokens",
                "decode_key_tokens", "rows", "ticks", "key_tokens",
                "pages_in_use", "pages_total")
        fused, block = ({k: v for k, v in d.items() if k in keep}
                        for d in (FUSED, BLOCK))
    worker = [ev("replica.op", 1, 399, kind="step"),
              ev("engine.dispatch", 2, 3, **fused),
              ev("engine.moe", 3, 3.1, **MOE_FUSED),
              ev("engine.dispatch", 60, 61, **block),
              ev("engine.moe", 61, 61.1, **MOE_BLOCK)]
    dev = {"/device:TPU:0": {
        "XLA Modules": [("jit_ragged_fused_step(1)", 0, 60 * MS),
                        ("jit_ragged_decode_block(2)", 60 * MS, 20 * MS)],
        "XLA Ops": [("%a = f32[1] add(f32[1])", 0, 60 * MS),
                    ("%b = f32[1] add(f32[1])", 60 * MS, 20 * MS)]}}
    from benchmark import trace_reduce as TR

    return {"cell": types.SimpleNamespace(name="cell", config=CFG),
            "trace": TR.reduce_planes(dev), "peaks": PEAKS,
            "_host_trace": host_spans.from_planes(dev, {0: worker}),
            "_mla_rows": _rows(*times, ours=ours),
            "_moe_device_ops": [
                ("%ragged-dot.1 = bf16[8,8] custom-call(", gmm_ms[0] / 1e3),
                ("%ragged-dot.2 = bf16[8,8] custom-call(", gmm_ms[1] / 1e3),
                ("%fusion.3 = bf16[8,8] fusion(", 1.0)]}


def _bound_ms(work):
    return 1e3 * max(work["flops"] / 197e12, work["bytes"] / 819e9)


def _bounds_ms():
    chunk = _bound_ms(fs.attention(CFG, fs.key_tokens(FUSED, "prefill"),
                                   rows=1024))
    one = _bound_ms(fs.attention(CFG, fs.key_tokens(FUSED, "decode")))
    block = _bound_ms(fs.attention(CFG, fs.key_tokens(BLOCK, "decode")))
    gmm = sum(1e3 * max(
        fs.expert_matmul_bytes(CFG, m["moe_rows"], m["moe_experts_hit"])
        / 819e9, fs.expert_matmul_flops(CFG, m["moe_rows"]) / 197e12)
        for m in (MOE_FUSED, MOE_BLOCK))
    return chunk, one, block, gmm


def test_the_readers_on_the_models_rows_and_spans():
    ctx = _ctx()
    chunk, one, block, gmm = _bounds_ms()
    assert gqa4_prefill_roofline.read(ctx) == pytest.approx(
        100 * chunk / 10.0)
    assert gqa4_decode_roofline.read(ctx) == pytest.approx(
        100 * (one + block) / 5.0)
    assert reglu_expert_roofline.read(ctx) == pytest.approx(100 * gmm / 56.0)
    # the accepted readers on this model's rows and spans
    total = 11.0 + 2.0 + 10.0 + 1.0 + 4.0
    assert attn_window_share_pct.read(ctx) == pytest.approx(
        100 * (2.0 + 7.0 + 1.0) / total)
    assert window_pool_peak_pct.read(ctx) == pytest.approx(100 * 2050 / 2624)
    assert window_pages_saved_pct.read(ctx) == pytest.approx(
        100 * (1 - (2000 + 2050) / (2400 + 2530)))
    assert kv_pool_peak_pct.read(ctx) == pytest.approx(100 * 2530 / 4128)


def test_a_program_without_the_scopes_gives_nothing_to_read():
    ctx = _ctx(ours=False)
    assert gqa4_prefill_roofline.read(ctx) is None
    assert gqa4_decode_roofline.read(ctx) is None
    for key, readers in (
            ("_mla_rows", (gqa4_prefill_roofline, gqa4_decode_roofline)),
            ("_host_trace", (gqa4_prefill_roofline, gqa4_decode_roofline,
                             reglu_expert_roofline))):
        ctx = _ctx()
        ctx[key] = None
        for reader in readers:
            assert reader.read(ctx) is None
    ctx = _ctx()
    ctx["_moe_device_ops"] = [("%fusion.3 = bf16[8,8] fusion(", 1.0)]
    assert reglu_expert_roofline.read(ctx) is None


def test_the_rooflines_read_100_at_their_bounds():
    chunk, one, block, gmm = _bounds_ms()
    at = _ctx((chunk, one, block), gmm_ms=(0.75 * gmm, 0.25 * gmm))
    assert gqa4_prefill_roofline.read(at) == pytest.approx(100.0)
    assert gqa4_decode_roofline.read(at) == pytest.approx(100.0)
    assert reglu_expert_roofline.read(at) == pytest.approx(100.0)
    slower = _ctx((2 * chunk, 2 * one, 2 * block), gmm_ms=(gmm, gmm))
    assert gqa4_prefill_roofline.read(slower) == pytest.approx(50.0)
    assert gqa4_decode_roofline.read(slower) == pytest.approx(50.0)
    assert reglu_expert_roofline.read(slower) == pytest.approx(50.0)
