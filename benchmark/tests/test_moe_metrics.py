"""``flops_moe.py`` against a hand count at the published widths, and the
three ``moe_*`` readers on a hand-built trace: spans of a decode block and a
fused tick beside device operations named as the chip names them."""
import types

import pytest

from benchmark import flops_moe
from benchmark import harness as H
from benchmark.layer_metrics import (host_spans, moe_decode_roofline,
                                     moe_expert_roofline,
                                     moe_load_max_over_mean, moe_trace)

MS = 1e6
OLMOE = H.load_json(H.HERE + "/configs/olmoe-1b-7b-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_flops_moe_against_a_hand_count():
    c = OLMOE
    assert flops_moe.expert_params(c) == 3 * 2048 * 1024 == 6_291_456
    # attention 4 x 2048^2 = 16.8 M and the router 2048 x 64 a layer, 8
    # layers; the head 50304 x 2048
    assert flops_moe.dense_params(c) == \
        8 * (4 * 2048 * 2048 + 2048 * 64) + 50304 * 2048 == 238_288_896
    # a decode step that hits every expert of every layer: 6.44 GB of banks
    # + 0.48 GB dense, and 64 KiB a context token
    full = flops_moe.decode_step_bytes(c, 8 * 64, 0)
    assert full == 2 * (238_288_896 + 512 * 6_291_456) == 6_919_028_736
    assert flops_moe.decode_step_bytes(c, 8 * 64, 1000) - full == \
        1000 * 65536
    # one token copy through one expert: 12.6 MFLOP; one expert's bank
    # 12.6 MB, a row in and out 8 KiB
    assert flops_moe.expert_matmul_flops(c, 1) == 12_582_912
    assert flops_moe.expert_matmul_bytes(c, 1, 1) == 12_582_912 + 8192
    assert flops_moe.expert_matmul_bytes(c, 10, 0) == 81_920


def _device():
    """One chip, 0-100 ms.  A fused tick 0-30 and a decode block 40-100,
    each with its grouped matmul, its sort/gather/combine operations
    (shapes with the routed copies 4352 and 256 in them), attention and a
    head that are no part of the expert layer."""
    line = lambda name, op, shapes: (  # noqa: E731
        f"%{name} = {shapes[0]} {op}({', '.join(shapes[1:])})")
    ops = [
        (line("fusion.1", "fusion", ["bf16[544,2048]", "bf16[2048,2048]"]),
         0, 6),
        (line("ragged-dot-none.3", "custom-call",
              ["bf16[4352,1024]", "bf16[4352,2048]", "bf16[64,2048,1024]"]),
         6, 12),
        (line("sort.5", "sort", ["s32[4352]", "s32[4352]"]), 18, 2),
        (line("fusion.7", "fusion", ["f32[544,64]", "f32[544,2048]",
                                     "f32[2048,64]"]), 20, 1),
        (line("fusion.9", "fusion", ["bf16[544,50304]", "bf16[2048,50304]"]),
         21, 9),
        (line("ragged-dot-none.4", "custom-call",
              ["bf16[256,1024]", "bf16[256,2048]", "bf16[64,2048,1024]"]),
         40, 40),
        (line("ragged-dot-metadata.2", "custom-call", ["s32[65]", "s32[64]"]),
         80, 1),
        (line("gather.11", "gather", ["bf16[256,2048]", "bf16[32,2048]",
                                      "s32[256]"]), 81, 4),
        (line("ragged_paged_attention_kernel.13", "custom-call",
              ["bf16[32,16,128]", "s32[32,64]", "bf16[1280,64,32,128]"]),
         85, 15),
    ]
    return {"/device:TPU:0": {
        "XLA Modules": [("jit_ragged_fused_step(1)", 0, 30 * MS),
                        ("jit_ragged_decode_block(2)", 40 * MS, 60 * MS)],
        "XLA Ops": [(n, a * MS, d * MS) for n, a, d in ops]}}


def _worker():
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    return [ev("replica.op", 1, 99, kind="step"),
            ev("engine.dispatch", 2, 3, program="fused", prefill_tokens=512,
               prefill_key_tokens=512 * 513 // 2, decode_key_tokens=0),
            ev("engine.moe", 31, 31.01, program="fused", ticks=1,
               moe_rows=8 * 8 * 512, moe_experts_hit=8 * 64,
               moe_load_max=90),
            ev("engine.dispatch", 35, 36, program="decode_block", rows=32,
               ticks=8, key_tokens=32 * 2000),
            ev("engine.moe", 99, 99.01, program="decode_block", ticks=8,
               moe_rows=8 * 8 * 8 * 32, moe_experts_hit=8 * 8 * 63,
               moe_load_max=12)]


@pytest.fixture
def ctx():
    from benchmark import trace_reduce as TR

    dev = _device()
    ht = host_spans.from_planes(dev, {0: _worker()})
    red = TR.reduce_planes(dev)
    ops = [(n, t / 1e9) for n, t in TR.self_times(
        dev["/device:TPU:0"]["XLA Ops"])]
    return {"cell": types.SimpleNamespace(name="cell", config=OLMOE),
            "trace": red, "_host_trace": ht, "_moe_device_ops": ops,
            "peaks": PEAKS}


def test_the_grouped_matmul_is_told_by_its_name(ctx):
    # the two matmuls 12 + 40 ms and the metadata call 1 ms; the sort, the
    # router fusion and the gather carry the routed copies as a dimension
    # and are scope_moe_pct's to read, not this name's
    assert moe_trace.kernel_seconds(ctx) == pytest.approx(0.053)


def test_decode_roofline_counts_experts_hit_not_the_bank(ctx):
    # 8 ticks: dense 0.477 GB each, 8 x 8 x 63 expert banks over the block
    # (one expert of 64 idle in every layer and tick), and 64 KiB x
    # (8 x 64000 + 32 x 28) context tokens; over 60 ms (a made-up time: the
    # arithmetic is what is tested)
    need = (8 * 2 * 238_288_896 + 4032 * 12_582_912
            + 65536 * (8 * 64000 + 32 * 28))
    assert moe_decode_roofline.read(ctx) == pytest.approx(
        100 * need / 819e9 / 0.060)


def test_expert_roofline_is_bytes_bound_at_these_rows(ctx):
    fused = max((512 * 12_582_912 + 2 * 2 * 2048 * 32768) / 819e9,
                32768 * 12_582_912 / 197e12)
    block = (4032 * 12_582_912 + 2 * 2 * 2048 * 16384) / 819e9
    assert fused == (512 * 12_582_912 + 2 * 2 * 2048 * 32768) / 819e9
    assert moe_expert_roofline.read(ctx) == pytest.approx(
        100 * (fused + block) / 0.053)


def test_load_max_over_mean(ctx):
    # fused: 90 rows on the fullest of 8 x 64 experts holding 32768 copies
    # (64 each); the block: 12 against 4
    assert moe_load_max_over_mean.read(ctx) == pytest.approx(
        (90 / 64 + 12 / 4) / 2)


def test_a_program_without_the_spans_reports_nothing(ctx):
    ctx["_host_trace"] = host_spans.from_planes(
        _device(), {0: [e for e in _worker() if e[0] != "engine.moe"]})
    for reader in (moe_decode_roofline, moe_expert_roofline,
                   moe_load_max_over_mean):
        assert reader.read(ctx) is None
