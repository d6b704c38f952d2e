"""``flops_trinity.py`` against a hand count at the published sizes of
``trinity-large-serve`` (the numbers ISSUE 59 and the configuration file
state), and the six new readers on hand-built rows and spans: values,
``None`` where there is nothing to read (a parent's programs carry no such
scopes and no such counts; a cell of another configuration reports none of
them), rooflines that read 100 at their bounds, and the shared readers that
read this configuration's file through the names they know."""
import types

import pytest

from benchmark import flops_hybrid
from benchmark import flops_trinity as ft
from benchmark import harness as H
from benchmark.layer_metrics import (attn_full_share_pct,
                                     attn_window_share_pct,
                                     expert_rows_held_share_pct, host_spans,
                                     hybrid_load_max_over_mean,
                                     kv_pool_peak_pct, mixed_decode_roofline,
                                     mixed_prefill_roofline, trinity_trace,
                                     window_pages_saved_pct,
                                     window_pool_peak_pct)

TRACE = (attn_window_share_pct, attn_full_share_pct, mixed_prefill_roofline,
         mixed_decode_roofline)
POOL = (window_pool_peak_pct, window_pages_saved_pct)
MS = 1e6
TRINITY = H.load_json(H.HERE + "/configs/trinity-large-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
W, F = ft.WINDOW, ft.FULL
# one fused tick: a chunk of 1,024 rows from position 20,000 on and 7 decode
# rows at 30,000; one decode block of 8 rows and 8 ticks from 30,000 on
CHUNK_FULL = sum(range(20_001, 21_025))
FUSED = dict(program="fused", prefill_tokens=1024,
             prefill_key_tokens=CHUNK_FULL, decode_key_tokens=7 * 30_001,
             window_prefill_key_tokens=1024 * 4096,
             window_decode_key_tokens=7 * 4096, window_layers=4,
             window_pages_total=656, window_pages_held=500,
             window_pages_released=16, full_pages_held=2900,
             pages_in_use=2900, pages_total=4104)
BLOCK = dict(program="decode_block", rows=8, ticks=8, key_tokens=8 * 30_001,
             window_prefill_key_tokens=0,
             window_decode_key_tokens=64 * 4096, window_layers=4,
             window_pages_total=656, window_pages_held=528,
             window_pages_released=0, full_pages_held=3800,
             pages_in_use=3800, pages_total=4104)
MOE_FUSED = dict(program="fused", ticks=1, moe_rows=4 * 520, moe_visits=140,
                 moe_experts_hit=4 * 32, moe_load_max=40,
                 moe_rows_routed=4 * 4 * 1031, moe_experts_held=4 * 32)


def test_flops_trinity_against_a_hand_count():
    c = TRINITY
    assert (ft.layers(c, W), ft.layers(c, F)) == (4, 1)
    assert ft.pool_bytes_per_token(c, F) == 2 * 8 * 128 * 2 == 4096
    assert ft.pool_bytes_per_token(c, W) == 4 * 4096
    assert ft.pages_bound(c) == 82
    eng = c["engine"]
    assert eng["kv_pool_bytes"] == 4105 * 64 * 4096 == 1_076_101_120
    assert eng["window_pool_bytes"] == 657 * 64 * 4 * 4096 == 688_914_432
    pre = ft.key_tokens(FUSED, "prefill")
    assert pre == {F: CHUNK_FULL, W: 1024 * 4096}
    work = ft.attention(c, pre, rows=1024)
    assert work["flops"] == 4 * 48 * 128 * (CHUNK_FULL + 4 * 1024 * 4096)
    assert work["bytes"] == 2 * 5 * 1024 * 48 * 128 * 2
    # the window layers, four of five, need under half of the pairs here
    assert 4 * 1024 * 4096 < CHUNK_FULL
    assert work["flops"] / 197e12 > 10 * work["bytes"] / 819e9   # compute
    dec = ft.key_tokens(BLOCK, "decode")
    assert dec == {F: 8 * 8 * 30_001 + 8 * 8 * 7 / 2, W: 64 * 4096}
    assert ft.key_tokens(BLOCK, "prefill") == {F: 0.0, W: 0.0}
    step = ft.attention(c, dec)
    assert step["bytes"] == (dec[F] + 4 * dec[W]) * 2 * 8 * 128 * 2
    assert step["bytes"] / 819e9 > step["flops"] / 197e12      # memory
    # the shared expert readers read this file through the source's names
    assert flops_hybrid.expert_layers(c) == 4
    assert flops_hybrid.expert_params(c) == 28_311_552
    assert flops_hybrid.dense_layers(c) == 1


def _rows(chunk_ms, decode_fused_ms, decode_block_ms, ours=True):
    fused, block = "ragged_fused_step", "ragged_decode_block"
    row = lambda kind, sub, ms, prog: (             # noqa: E731
        "op", kind, sub, "-", "own", ms / 1e3, prog)
    rest = [row("moe", "moe.experts", 9.0, fused), row("mlp", None, 3.0,
                                                       fused),
            row("attn", "input_layernorm", 1.0, fused)]
    if not ours:
        # another model's attention: no scope says which kind of layer
        return rest + [row("attn", "paged_chunk_attention", 4.0, fused),
                       row("attn", "q_proj", 2.0, fused)]
    kern = lambda kind, name: f"{kind}/{name}"      # noqa: E731
    return rest + [
        row("attn", "attn_window", 2.0, fused),
        row("attn", "attn_full", 0.5, fused),
        row("attn", kern("attn_window", trinity_trace.CHUNK_KERNEL),
            0.6 * chunk_ms, fused),
        row("attn", kern("attn_full", trinity_trace.CHUNK_KERNEL),
            0.4 * chunk_ms, fused),
        row("attn", kern("attn_window", trinity_trace.DECODE_KERNEL),
            decode_fused_ms, fused),
        row("attn", kern("attn_full", trinity_trace.DECODE_KERNEL),
            decode_block_ms, block)]


def _ctx(times=(10.0, 1.0, 4.0), ours=True, config=TRINITY):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    fused, block, moe = FUSED, BLOCK, MOE_FUSED
    if not ours:
        keep = ("program", "prefill_tokens", "prefill_key_tokens",
                "decode_key_tokens", "rows", "ticks", "key_tokens",
                "pages_in_use", "pages_total")
        fused, block = ({k: v for k, v in d.items() if k in keep}
                        for d in (FUSED, BLOCK))
    worker = [ev("replica.op", 1, 399, kind="step"),
              ev("engine.dispatch", 2, 3, **fused),
              ev("engine.moe", 3, 3.1, **moe),
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
    bound = lambda w: 1e3 * max(w["flops"] / 197e12,        # noqa: E731
                                w["bytes"] / 819e9)
    chunk = bound(ft.attention(TRINITY, ft.key_tokens(FUSED, "prefill"),
                               rows=1024))
    one = bound(ft.attention(TRINITY, ft.key_tokens(FUSED, "decode")))
    block = bound(ft.attention(TRINITY, ft.key_tokens(BLOCK, "decode")))
    return chunk, one, block


def test_the_readers_on_the_models_rows_and_spans():
    ctx = _ctx()
    chunk, one, block = _bounds_ms()
    total = 13.0 + 2.5 + 10.0 + 1.0 + 4.0
    assert attn_window_share_pct.read(ctx) == pytest.approx(
        100 * (2.0 + 6.0 + 1.0) / total)
    assert attn_full_share_pct.read(ctx) == pytest.approx(
        100 * (0.5 + 4.0 + 4.0) / total)
    assert mixed_prefill_roofline.read(ctx) == pytest.approx(
        100 * chunk / 10.0)
    assert mixed_decode_roofline.read(ctx) == pytest.approx(
        100 * (one + block) / 5.0)
    assert window_pool_peak_pct.read(ctx) == pytest.approx(100 * 528 / 656)
    assert window_pages_saved_pct.read(ctx) == pytest.approx(
        100 * (1 - (500 + 528) / (2900 + 3800)))
    # the shared readers on this model's spans: the FIRST pool's peak, the
    # load over the 32 HELD experts of four expert layers, the held share
    assert kv_pool_peak_pct.read(ctx) == pytest.approx(100 * 3800 / 4104)
    assert hybrid_load_max_over_mean.read(ctx) == pytest.approx(
        40 * 4 * 32 / (4 * 520))
    assert expert_rows_held_share_pct.read(ctx) == pytest.approx(
        100 * 520 / (4 * 1031))


def test_a_program_without_the_scopes_gives_nothing_to_read():
    """The parent's programs, and a cell of another configuration whose
    attention runs the same two kernels, report none of the six."""
    mistral = H.load_json(H.HERE + "/configs/mistral-7b-serve.json")
    for ctx in (_ctx(ours=False), _ctx(ours=False, config=mistral)):
        for reader in TRACE + POOL:
            assert reader.read(ctx) is None
    # no joined rows: nothing for the four; no spans: nothing for the two,
    # and no count of needed work for the rooflines
    for key, readers in (("_mla_rows", TRACE),
                         ("_host_trace", POOL + TRACE[2:])):
        ctx = _ctx()
        ctx[key] = None
        for reader in readers:
            assert reader.read(ctx) is None


def test_the_rooflines_read_100_at_their_bounds():
    chunk, one, block = _bounds_ms()
    at = _ctx((chunk, one, block))
    assert mixed_prefill_roofline.read(at) == pytest.approx(100.0)
    assert mixed_decode_roofline.read(at) == pytest.approx(100.0)
    slower = _ctx((2 * chunk, 2 * one, 2 * block))
    assert mixed_prefill_roofline.read(slower) == pytest.approx(50.0)
    assert mixed_decode_roofline.read(slower) == pytest.approx(50.0)
