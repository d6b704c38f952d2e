"""``flops_loop.py`` against a hand count at the published widths, and the
four ``.ouro`` readers on a hand-built trace: values, ``None`` where there
is nothing to read (a parent's spans, which carry neither ``ut_steps`` nor
page counts), and a roofline that stays under 100 whatever the counts."""
import types

import pytest

from benchmark import flops_loop
from benchmark import harness as H
from benchmark.layer_metrics import (host_spans, kv_pool_peak_pct,
                                     loop_decode_roofline,
                                     loop_decode_step_ms,
                                     loop_steps_per_token)

READERS = (loop_decode_roofline, loop_decode_step_ms, kv_pool_peak_pct,
           loop_steps_per_token)

MS = 1e6
OURO = H.load_json(H.HERE + "/configs/ouro-2.6b-serve.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LOOP = dict(ut_steps=4, kv_slots=192, pages_total=84)


def test_flops_loop_against_a_hand_count():
    c = OURO
    assert flops_loop.block_matmul_params(c) == \
        4 * 2048 * 2048 + 3 * 2048 * 5632 == 51_380_224
    assert flops_loop.head_params(c) == 49152 * 2048 == 100_663_296
    assert flops_loop.kv_slots(c) == 192
    # 8 KiB a token a slot, 1.5 MiB a token, 96 MiB a 64-token page
    assert flops_loop.kv_bytes_per_token(c) == 8192 * 192 == 1_572_864
    # a decode step with no context: four passes over 4.93 GB + the head
    assert flops_loop.decode_step_bytes(c, 0) == \
        2 * (4 * 48 * 51_380_224 + 100_663_296) == 19_931_332_608
    assert flops_loop.decode_step_bytes(c, 3500) - 19_931_332_608 == \
        3500 * 1_572_864
    # a prompt token: 19.9 GFLOP
    assert flops_loop.forward_flops_per_token(c) == 19_931_332_608.0


def _device(block_ms):
    return {"/device:TPU:0": {
        "XLA Modules": [("jit_ragged_fused_step(1)", 0, 30 * MS),
                        ("jit_ragged_decode_block(2)", 40 * MS,
                         block_ms * MS)],
        "XLA Ops": [("%fusion.1 = bf16[16,2048] fusion(bf16[2048,2048])",
                     0, 30 * MS),
                    ("%paged_decode_attention.3 = bf16[16,16,128] "
                     "custom-call(bf16[340,64,32,128])", 40 * MS,
                     block_ms * MS)]}}


def _worker(**loop):
    ev = lambda n, a, b, **st: (n, a * MS, (b - a) * MS, st)  # noqa: E731
    return [ev("replica.op", 1, 399, kind="step"),
            ev("engine.dispatch", 2, 3, program="fused", prefill_tokens=200,
               prefill_key_tokens=200 * 201 // 2, decode_key_tokens=0,
               **dict(loop, pages_in_use=40) if loop else {}),
            ev("engine.dispatch", 35, 36, program="decode_block", rows=12,
               ticks=8, key_tokens=3500,
               **dict(loop, pages_in_use=63) if loop else {})]


def _ctx(block_ms=300.0, **loop):
    """The slice cuts a block at each end (the profiler records the part
    it saw) and holds three whole ones."""
    from benchmark import trace_reduce as TR

    dev = _device(block_ms)
    runs = [0.09 * block_ms, 1.01 * block_ms, 0.99 * block_ms,
            0.8 * block_ms]
    return {"cell": types.SimpleNamespace(name="cell", config=OURO),
            "trace": TR.reduce_planes(dev),
            "_host_trace": host_spans.from_planes(dev, {0: _worker(**loop)}),
            "_module_runs": [
                [(0.0, runs[0] / 1e3, "jit_ragged_decode_block"),
                 (1.0, 0.03, "jit_ragged_fused_step")] + [
                    (2.0 + k, ms / 1e3, "jit_ragged_decode_block")
                    for k, ms in enumerate(runs[1:])]],
            "peaks": PEAKS}


def test_the_four_readers_on_a_looped_models_spans():
    ctx = _ctx(**LOOP)
    # 8 ticks x 19.93 GB + 1.5 MiB x (8 x 3500 + 12 x 28) context tokens,
    # over a WHOLE block's 300 ms (a made-up time: the arithmetic is
    # tested): the two whole runs, not the mean of all four, 217
    need = 8 * 19_931_332_608 + 1_572_864 * (8 * 3500 + 12 * 28)
    assert loop_decode_roofline.read(ctx) == pytest.approx(
        100 * need / 819e9 / 0.300)
    assert loop_decode_step_ms.read(ctx) == pytest.approx(300.0 / 8)
    assert kv_pool_peak_pct.read(ctx) == pytest.approx(100 * 63 / 84)
    assert loop_steps_per_token.read(ctx) == 4.0


def test_a_parents_spans_give_nothing_to_read():
    ctx = _ctx()
    for reader in READERS:
        assert reader.read(ctx) is None
    ctx["_host_trace"] = None
    for reader in READERS:
        assert reader.read(ctx) is None
    # a slice too short to hold a whole block times nothing
    short = _ctx(**LOOP)
    short["_module_runs"] = [short["_module_runs"][0][:2]]
    assert loop_decode_roofline.read(short) is None
    assert loop_decode_step_ms.read(short) is None


def test_bytes_and_time_are_of_the_same_whole_blocks():
    """Four dispatch spans, the first and the last with other counts: the
    share is the middle two's bytes over the whole runs' time."""
    ctx = _ctx(**LOOP)
    worker = _worker(**LOOP)
    block = worker[-1]
    more = [(block[0], (35 + 50 * k) * MS, block[2],
             dict(block[3], key_tokens=keys))
            for k, keys in enumerate((100, 3500, 3500, 5000))]
    ctx["_host_trace"] = host_spans.from_planes(
        _device(300.0), {0: worker[:2] + more})
    need = 8 * 19_931_332_608 + 1_572_864 * (8 * 3500 + 12 * 28)
    assert loop_decode_roofline.read(ctx) == pytest.approx(
        100 * need / 819e9 / 0.300)


@pytest.mark.parametrize("rows,keys", [(1, 1), (12, 3500), (16, 16 * 1023)])
def test_the_roofline_stays_under_100_at_the_bandwidth_bound(rows, keys):
    """A block that took exactly its bytes over the peak bandwidth reads
    100; any real block is slower.  Nothing is counted twice: at every
    count the share of a block as slow as the roofline is 100, not more."""
    need = 8 * 19_931_332_608 + 1_572_864 * (8 * keys + rows * 28)
    ms = 1e3 * need / 819e9

    def share(block_ms):
        ctx = _ctx(block_ms=block_ms, **LOOP)
        for e in ctx["_host_trace"].spans("engine.dispatch"):
            if e[3]["program"] == "decode_block":
                e[3].update(rows=rows, key_tokens=keys)
        return loop_decode_roofline.read(ctx)

    assert share(ms) == pytest.approx(100.0, rel=1e-6)
    assert share(1.25 * ms) == pytest.approx(80.0, rel=1e-6)
