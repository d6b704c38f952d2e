"""``flops_hybrid.py`` against a hand count at the published sizes of
``lfm2-24b-a2b-serve`` (the numbers ISSUE 35 and the configuration file
state), that the readers it replaces WOULD count this configuration wrong,
and the ``.lfm2`` readers on a hand-built trace: values, ``None`` where
there is nothing to read (a parent's spans carry no ``state_layers``, no
``bias_swaps``), and a roofline that reads 100 at the bandwidth bound."""
import types

import pytest

from benchmark import flops, flops_hybrid, flops_moe
from benchmark import harness as H
from benchmark.layer_metrics import (bias_swap_pct, host_spans,
                                     hybrid_decode_roofline,
                                     hybrid_expert_roofline,
                                     hybrid_expert_share_pct,
                                     hybrid_load_max_over_mean,
                                     kv_pool_peak_pct, state_rows_per_tick)

READERS = (hybrid_decode_roofline, hybrid_expert_roofline,
           hybrid_expert_share_pct, hybrid_load_max_over_mean,
           bias_swap_pct, state_rows_per_tick)
MS = 1e6
LFM2 = H.load_json(H.HERE + "/configs/lfm2-24b-a2b-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
EXPERT = 3 * 2048 * 1536                    # 9,437,184 parameters
FIXED = 2 * (8 * 4 * 2048 * 2048 + 2 * 10_485_760 + 2 * 72_351_744
             + 65536 * 2048) + 4 * 8 * 2048 * 64


def test_flops_hybrid_against_a_hand_count():
    c = LFM2
    assert (flops_hybrid.conv_layers(c), flops_hybrid.attention_layers(c),
            flops_hybrid.dense_layers(c), flops_hybrid.expert_layers(c)
            ) == (8, 2, 2, 8)
    # ISSUE 35, Tentpole 5: 16.78 M, 10.49 M, 72.35 M, 9.437 M, 134.2 M
    assert flops_hybrid.conv_mixer_params(c) == 16_777_216
    assert flops_hybrid.attention_mixer_params(c) == 10_485_760
    assert flops_hybrid.dense_mlp_params(c) == 72_351_744
    assert flops_hybrid.expert_params(c) == EXPERT
    assert flops_hybrid.head_params(c) == 134_217_728
    # every matrix of the stage: 5,267 M parameters, 10.53 GB in bf16
    assert flops_hybrid.stage_params(c) == 5_266_997_248
    # 8 KiB a cached token (two K/V layers of 8 heads stored at 128), a
    # page of 64 is 512 KiB, 1280 of them the pool's 640 MiB
    assert flops_hybrid.kv_bytes_per_token(c) == 8192
    assert 1280 * 64 * 8192 == c["engine"]["kv_pool_bytes"]
    # 64 KiB of state a sequence, read and written once a step
    assert flops_hybrid.state_bytes_per_row(c) == 2 * 65536
    # mixers 0.31 GB, dense MLPs 0.29, head 0.27, routers (float32) 0.004
    assert flops_hybrid.fixed_step_bytes(c) == FIXED == 872_415_232
    # a step of 32 rows at 1,500 context tokens each that hits 55 of 64
    # experts in each of 8 layers: 8.3 GB of banks of 9.6 GB
    step = flops_hybrid.decode_step_bytes(c, 8 * 55, 32 * 1500, 32)
    assert step == FIXED + 2 * EXPERT * 440 + 8192 * 48000 + 131072 * 32
    assert 0.85 < 2 * EXPERT * 440 / step < 0.88
    assert flops_hybrid.expert_matmul_flops(c, 1) == 2 * EXPERT
    assert flops_hybrid.expert_matmul_bytes(c, 1, 1) == 2 * EXPERT + 8192


def test_the_readers_of_one_width_and_one_kind_would_count_it_wrong():
    """Why the ``.lfm2`` roofline entries are a family of their own:
    ``flops.py`` reads ``intermediate_size`` as every layer's MLP and every
    layer as holding K/V at the true head width; ``flops_moe.py`` reads it
    as one expert's width."""
    c = LFM2
    assert flops.kv_bytes_per_token(c) == 2 * 10 * 8 * 64 * 2 != \
        flops_hybrid.kv_bytes_per_token(c)
    assert flops_moe.expert_params(c) == 3 * 2048 * 11776 != \
        flops_hybrid.expert_params(c)
    assert flops.weight_bytes(c) != flops_hybrid.fixed_step_bytes(c)


def _device(block_ms=100.0):
    line = lambda name, op, shapes: (  # noqa: E731
        f"%{name} = {shapes[0]} {op}({', '.join(shapes[1:])})")
    ops = [
        (line("ragged-dot-gmm.3", "custom-call",
              ["bf16[2176,1536]", "bf16[2176,2048]", "bf16[64,2048,1536]"]),
         0, 12),
        (line("ragged_paged_attention_kernel.5", "custom-call",
              ["bf16[544,32,128]", "bf16[1280,64,16,128]"]), 12, 8),
        (line("ragged-dot-gmm.4", "custom-call",
              ["bf16[128,1536]", "bf16[128,2048]", "bf16[64,2048,1536]"]),
         40, 0.7 * block_ms),
        (line("paged_decode_attention.7", "custom-call",
              ["bf16[32,32,128]", "bf16[1280,64,16,128]"]),
         40 + 0.7 * block_ms, 0.3 * block_ms)]
    return {"/device:TPU:0": {
        "XLA Modules": [("jit_ragged_fused_step(1)", 0, 20 * MS),
                        ("jit_ragged_decode_block(2)", 40 * MS,
                         block_ms * MS)],
        "XLA Ops": [(n, a * MS, d * MS) for n, a, d in ops]}}


def _worker(state=True, rows=32, keys=32 * 1500):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    st = lambda n: dict(state_rows=n, state_layers=8) if state else {}
    sw = lambda n: dict(bias_swaps=n) if state else {}
    return [ev("replica.op", 1, 399, kind="step"),
            ev("engine.dispatch", 2, 3, program="fused", prefill_tokens=512,
               prefill_key_tokens=512 * 513 // 2, decode_key_tokens=0,
               pages_in_use=400, pages_total=1279, **st(1)),
            ev("engine.moe", 21, 21.01, program="fused", ticks=1,
               moe_rows=8 * 4 * 512, moe_experts_hit=8 * 64,
               moe_load_max=60, **sw(8 * 100)),
            ev("engine.dispatch", 35, 36, program="decode_block", rows=rows,
               ticks=8, key_tokens=keys, pages_in_use=800, pages_total=1279,
               **st(rows)),
            ev("engine.moe", 399, 399.01, program="decode_block", ticks=8,
               moe_rows=8 * 8 * 4 * rows, moe_experts_hit=8 * 8 * 55,
               moe_load_max=9, **sw(8 * 8 * 6))]


def _ctx(block_ms=100.0, **kw):
    from benchmark import trace_reduce as TR

    dev = _device(block_ms)
    ops = [(n, t / 1e9) for n, t in TR.self_times(
        dev["/device:TPU:0"]["XLA Ops"])]
    return {"cell": types.SimpleNamespace(name="cell", config=LFM2),
            "trace": TR.reduce_planes(dev), "_moe_device_ops": ops,
            "_host_trace": host_spans.from_planes(dev, {0: _worker(**kw)}),
            "_module_runs": [[
                (0.0, 0.01, "jit_ragged_decode_block"),
                (1.0, 0.02, "jit_ragged_fused_step"),
                (2.0, block_ms / 1e3, "jit_ragged_decode_block"),
                (3.0, block_ms / 1e3, "jit_ragged_decode_block"),
                (4.0, 0.03, "jit_ragged_decode_block")]],
            "peaks": PEAKS}


def _block_bytes(rows=32, keys=32 * 1500):
    return 8 * (FIXED + 2 * EXPERT * 8 * 55 + 131072 * rows
                + 8192 * (keys + rows * 3.5))


def test_the_readers_on_a_hybrid_models_spans():
    ctx = _ctx()
    assert hybrid_decode_roofline.read(ctx) == pytest.approx(
        100 * _block_bytes() / 819e9 / 0.100)
    # the kernel 12 + 70 ms of 12 + 8 + 100 busy
    assert hybrid_expert_share_pct.read(ctx) == pytest.approx(
        100 * 82 / 120)
    fused = max((2 * EXPERT * 512 + 2 * 2 * 2048 * 16384) / 819e9,
                2 * EXPERT * 16384 / 197e12)
    block = (2 * EXPERT * 3520 + 2 * 2 * 2048 * 8192) / 819e9
    assert hybrid_expert_roofline.read(ctx) == pytest.approx(
        100 * (fused + block) / 0.082)
    # 8 EXPERT layers, not 10: 60 rows against 32 a bank, 9 against 2
    assert hybrid_load_max_over_mean.read(ctx) == pytest.approx(
        (60 / 32 + 9 / 2) / 2)
    # 800 + 384 tokens' sets changed of 4096 + 2048 routings
    assert bias_swap_pct.read(ctx) == pytest.approx(100 * 1184 / 6144)
    assert state_rows_per_tick.read(ctx) == pytest.approx((8 + 256) / 2)
    assert kv_pool_peak_pct.read(ctx) == pytest.approx(100 * 800 / 1279)


def _shared_of(ctx, shards):
    """``ctx`` with its ``engine.moe`` spans as a chip that holds one of
    ``shards`` shares of each router's experts writes them: ``moe_rows`` is
    what the held banks saw, ``moe_rows_routed`` every copy the router made,
    ``bias_swaps`` (live tokens over the whole router) as it was."""
    events = [(n, a, d, dict(st, moe_rows=st["moe_rows"] // shards,
                             moe_rows_routed=st["moe_rows"])
               if n == "engine.moe" else st) for n, a, d, st in _worker()]
    return dict(ctx, _host_trace=host_spans.from_planes(_device(),
                                                        {0: events}))


def _bias_swap_pct_until_pr_68(ctx):
    from benchmark.layer_metrics import moe_trace

    spans = [r for r in moe_trace.spans(ctx) if "bias_swaps" in r]
    return 100.0 * sum(int(r["bias_swaps"]) for r in spans) / (
        sum(int(r["moe_rows"]) for r in spans) / 4)


@pytest.mark.parametrize("shards,old_over_new", [(None, 1), (1, 1), (8, 8)])
def test_bias_swap_pct_divides_by_the_routings_the_router_made(
        shards, old_over_new):
    """A span without ``moe_rows_routed`` (LFM2, GLM: every expert held)
    reads what it read to the last digit; one of a chip that holds a share
    of its router's experts reads the same share of the same routings,
    where the form until PR 68 divided by the held rows and read ``shards``
    times it."""
    ctx = _ctx() if shards is None else _shared_of(_ctx(), shards)
    share = 100 * 1184 / 6144
    if shards is None:
        assert bias_swap_pct.read(ctx) == _bias_swap_pct_until_pr_68(ctx)
    assert bias_swap_pct.read(ctx) == pytest.approx(share)
    # the old form on one chip of eight: a share over 100
    assert _bias_swap_pct_until_pr_68(ctx) == pytest.approx(
        old_over_new * share)
    assert 0 < share < 100 < 8 * share


def test_a_parents_spans_give_nothing_to_read():
    ctx = _ctx(state=False)
    for reader in (hybrid_decode_roofline, bias_swap_pct,
                   state_rows_per_tick):
        assert reader.read(ctx) is None
    ctx["_host_trace"] = host_spans.from_planes(
        _device(), {0: [e for e in _worker() if e[0] != "engine.moe"]})
    for reader in (hybrid_decode_roofline, hybrid_expert_roofline,
                   hybrid_expert_share_pct, hybrid_load_max_over_mean,
                   bias_swap_pct):
        assert reader.read(ctx) is None
    ctx["_host_trace"] = None
    for reader in READERS:
        assert reader.read(ctx) is None


@pytest.mark.parametrize("rows,keys", [(1, 1), (32, 32 * 1500),
                                       (32, 32 * 4000)])
def test_the_roofline_stays_under_100_at_the_bandwidth_bound(rows, keys):
    ms = 1e3 * _block_bytes(rows, keys) / 819e9
    share = lambda t: hybrid_decode_roofline.read(  # noqa: E731
        _ctx(block_ms=t, rows=rows, keys=keys))
    assert share(ms) == pytest.approx(100.0, rel=1e-6)
    assert share(1.25 * ms) == pytest.approx(80.0, rel=1e-6)
