"""The grouped expert matmul (``ops/grouped_matmul.py``): the Pallas kernel
under the interpreter against its twin ``jax.lax.ragged_dot``, and the
selection between the two.

Bit equality of the two at ``tk == K`` is the chip's statement (PERF.md,
PR 29: the v5e's MXU sums both the same way).  The CPU's two float32
summation orders leave the last bf16 bit of about one result in 10^4
open, so here operands whose products and sums are exact in float32 are
held to the bit, and normal ones to one bf16 rounding.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.comm as dist
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import GROUPED_MATMUL
from deepspeed_tpu.ops import grouped_matmul as gm
from deepspeed_tpu.ops import routes

# one bf16 rounding of the result, beside what two float32 summation
# orders leave of a result near zero
ULP, NEAR_ZERO = 2.0 ** -7, 1e-5


def _spread(total, groups, seed):
    """``total`` rows over ``groups`` groups, none empty."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, total), groups - 1,
                              replace=False))
    return np.diff(np.concatenate([[0], cuts, [total]])).tolist()


# (rows, K, N, group sizes, tiles or None for the rule's)
CASES = {
    # empty groups first, last and between full ones; all rows live
    "empty-groups": (256, 256, 128, [0, 100, 0, 0, 56, 1, 99, 0], None),
    # a fused tick's skew: one expert takes nearly every row (PR 28 saw
    # 413 of 440), and its run straddles three row tiles
    "one-group-nearly-all": (440, 128, 256,
                             [3, 0, 413, 5, 0, 7, 2, 10], None),
    # padding rows of a tick join no group: the tail is dead
    "dead-rows": (256, 128, 128, [20, 0, 31, 7, 0, 0, 60, 2], None),
    # a tile of rows with nothing live in it at all
    "dead-tile": (384, 128, 128, [5, 0, 100, 0], None),
    "rows-not-a-multiple-of-tm": (300, 256, 384, _spread(300, 8, 0), None),
    "fewer-rows-than-a-tile": (16, 128, 128, [4, 0, 9, 3], None),
    "many-small-groups": (256, 128, 256, _spread(256, 64, 1), None),
    # K split: partial sums meet in the accumulator in another order
    "tk-under-K": (300, 512, 256, _spread(290, 8, 2), (128, 128, 128)),
    "tk-under-K-tn-under-N": (256, 384, 384, [0, 250, 6, 0], (128, 128, 128)),
    # every group starts 64 rows into a row tile: the second visit of each
    # multiplies a bank that is resident while the next one comes
    "all-groups-straddle": (640, 128, 256, [64, 128, 128, 128, 128, 64],
                            None),
    # more visits of one bank than there are slots, between groups that
    # share their tile with it
    "five-tiles-between-threes": (640, 128, 128, [3, 3, 600, 3, 3], None),
    # more empty groups in a run than slots: the sequence of bank tiles
    # skips them
    "empty-before-between-after": (384, 128, 256,
                                   [0, 0, 0, 0, 130, 0, 0, 0, 0, 0, 120, 1,
                                    0, 0, 0, 0], None),
    "last-group-ends-short-of-R": (512, 128, 128, [100, 0, 150, 30], None),
    # keye's prefill tick cut in K and N: 128 groups of 64.5 rows at the
    # mean, two passes over n
    "keye-cut": (8256, 128, 256, _spread(8256, 128, 3), (128, 128, 128)),
    # one group hit: its tile of the second pass is the next of the first's
    "one-group-two-passes": (256, 128, 256, [0, 200, 0, 0], (128, 128, 128)),
}


def _operands(case, data, dtype):
    R, K, N, sizes, tiling = CASES[case]
    kr, kb = jax.random.split(jax.random.PRNGKey(len(case)))
    E = len(sizes)
    if data == "exact":
        # small integers and eighths: every product and every sum of K of
        # them is exact in float32, whatever the order
        rows = jax.random.randint(kr, (R, K), -4, 5).astype(dtype)
        banks = (jax.random.randint(kb, (E, K, N), -8, 9) / 8).astype(dtype)
    else:
        rows = jax.random.normal(kr, (R, K), jnp.float32).astype(dtype)
        banks = (jax.random.normal(kb, (E, K, N), jnp.float32)
                 * K ** -0.5).astype(dtype)
    return rows, banks, jnp.asarray(sizes, jnp.int32), tiling


@pytest.mark.parametrize("data", ["exact", "normal"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_ragged_dot(case, data):
    rows, banks, sizes, tiling = _operands(case, data, jnp.bfloat16)
    want = jax.lax.ragged_dot(rows, banks, sizes)
    if tiling is None:
        got = gm.grouped_matmul(rows, banks, sizes, interpret=True)
        assert routes.taken()[gm.KERNEL].endswith("-interpret")
    else:
        assert tiling[1:] != rows.shape[1:] + banks.shape[2:]
        got = gm._gmm(rows, banks, sizes, tiling=tiling, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    live = int(sizes.sum())             # rows past it are nobody's
    got = np.asarray(got[:live], np.float32)
    want = np.asarray(want[:live], np.float32)
    if data == "exact":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=ULP, atol=NEAR_ZERO)


@pytest.mark.parametrize("skew", [0.05, 0.5, 5.0])
def test_group_metadata_is_upstreams(skew):
    """Against megablox ``make_group_metadata`` over the visits the grid
    makes: random sizes from a few huge groups to an even spread, with
    and without dead rows."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata

    rng = np.random.default_rng(int(skew * 100))
    for _ in range(10):
        E, tm = int(rng.choice([4, 8, 64])), int(rng.choice([8, 128]))
        rows = int(rng.integers(1, 40)) * tm
        live = rows if rng.random() < 0.3 else int(rng.integers(0, rows + 1))
        sizes = jnp.asarray(rng.multinomial(
            live, rng.dirichlet(np.full(E, skew))).astype(np.int32))
        # each under one jit: a draw's shapes are its own, and op by op
        # every one of them would compile some hundred small programs
        (off, gid, mid, *_), visits = jax.jit(
            gm.group_metadata, static_argnums=(1, 2))(sizes, rows, tm)
        (off_, gid_, mid_), visits_ = jax.jit(functools.partial(
            make_group_metadata, m=rows, tm=tm, num_nonzero_groups=E,
            visit_empty_groups=False))(
                group_sizes=sizes, start_group=jnp.int32(0))
        v = int(visits)
        assert v == int(visits_) and gid.shape == gid_.shape
        np.testing.assert_array_equal(off, off_)
        np.testing.assert_array_equal(gid[:v], gid_[:v])
        np.testing.assert_array_equal(mid[:v], mid_[:v])


@pytest.mark.parametrize("case", list(CASES))
def test_the_bank_sequence_is_the_non_empty_groups_in_order(case):
    """What ``group_metadata`` gives beside upstream's three, and
    ``visits``, against a plain loop over the groups."""
    rows, _, _, sizes, tiling = CASES[case]
    tm = gm.TM if tiling is None else tiling[0]
    padded = -(-rows // tm) * tm
    (_, gid, _, hit_groups, hit), visits = gm.group_metadata(
        jnp.asarray(sizes, jnp.int32), padded, tm)
    want_hit, want_visits, start = [], 0, 0
    for g, size in enumerate(sizes):
        if size:
            want_hit.append(g)
            want_visits += (start + size - 1) // tm - start // tm + 1
        start += size
    assert hit.shape == (1,) and int(hit[0]) == len(want_hit)
    assert hit_groups.shape == (len(sizes),)
    assert hit_groups[:len(want_hit)].tolist() == want_hit
    assert int(visits) == want_visits
    if tm == gm.TM:
        assert int(gm.visits(jnp.asarray(sizes, jnp.int32))) == want_visits
    # a visit's group is new exactly where the kernel takes its next tile
    gid = gid[:want_visits].tolist()
    firsts = [v for v in range(want_visits) if v == 0 or gid[v] != gid[v - 1]]
    assert [gid[v] for v in firsts] == want_hit


def test_float32_operands():
    rows, banks, sizes, _ = _operands("empty-groups", "normal", jnp.float32)
    np.testing.assert_allclose(
        gm.grouped_matmul(rows, banks, sizes, interpret=True),
        jax.lax.ragged_dot(rows, banks, sizes), atol=1e-5)


def _layer(hidden, inter, activation="swiglu", **kw):
    return MoE(hidden_size=hidden, num_experts=8, intermediate_size=inter,
               k=2, activation=activation, dtype=jnp.float32,
               expert_parallel=False, normalize_weights=False, **kw)


@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_grouped_layer_through_the_kernel_matches_the_einsum_path(
        monkeypatch, activation):
    """``MoE(dispatch_impl="grouped")`` with the kernel in place of its
    twin, against the dense one-hot oracle that drops nothing, with an
    expert no token takes and rows that are no token."""
    monkeypatch.setattr(moe_layer, "grouped_matmul", functools.partial(
        gm.grouped_matmul, interpret=True))
    monkeypatch.setattr(routes, "_taken", {})
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 128))
    oracle = _layer(128, 256, activation, dispatch_impl="einsum",
                    drop_tokens=False)
    params = oracle.init(jax.random.PRNGKey(1), x)
    # expert 3 is never among the two largest
    gate = params["params"]["gate"].at[:, 3].set(0.0)
    x = x.at[..., 0].set(4.0)
    gate = gate.at[0].set(jnp.where(jnp.arange(8) == 3, -50.0, gate[0]))
    params = {"params": {**params["params"], "gate": gate}}
    want, _ = oracle.apply(params, x)
    grouped = _layer(128, 256, activation, dispatch_impl="grouped")
    (got, _), stats = grouped.apply(params, x, mutable=["moe_stats"])
    assert routes.taken()[gm.KERNEL] == \
        "gmm-128x128x256-interpret+gmm-128x256x128-interpret"
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(stats["moe_stats"]["counts"][0][0]) == 2 * 18
    # dead rows: zeros out, and no live row moves
    live = jnp.arange(9) < 4
    y, _ = grouped.apply(params, x[:1], live=live[None])
    np.testing.assert_allclose(y[0, :4], want[0, :4], atol=2e-5)
    assert not np.asarray(y[0, 4:]).any()


# (backend is a TPU, devices in the installed topology or None, hidden)
SELECTION = {
    "cpu": (False, None, 128, "ragged-dot-xla", "ragged-dot"),
    "tpu-no-topology": (True, None, 128, "ragged-dot-mosaic",
                        "gmm-128x128x256+gmm-128x256x128"),
    "tpu-one-device-topology": (True, 1, 128, "ragged-dot-mosaic",
                                "gmm-128x128x256+gmm-128x256x128"),
    "tpu-two-device-mesh": (True, 2, 128, "ragged-dot-mosaic", "ragged-dot"),
    # hidden 96: the lane width divides neither K of the first two banks
    # nor N of the third
    "tpu-tiles-do-not-divide": (True, None, 96, "ragged-dot-mosaic",
                                "ragged-dot"),
}


@pytest.mark.parametrize("case", list(SELECTION))
def test_selection_is_recorded_under_both_keys(monkeypatch, devices, case):
    """The route is decided while the layer traces, from the backend, the
    installed topology and the banks' shapes; nothing runs here."""
    on_tpu, mesh_devices, hidden, mosaic_or_xla, kernel = SELECTION[case]
    monkeypatch.setattr(routes, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(routes, "_taken", {})
    if mesh_devices:
        dist.initialize_mesh(dp=1, tp=mesh_devices,
                             devices=devices[:mesh_devices])
    layer = _layer(hidden, 256, dispatch_impl="grouped")
    x = jax.ShapeDtypeStruct((1, 12, hidden), jnp.float32)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    out, _ = jax.eval_shape(layer.apply, params, x)
    assert out.shape == x.shape
    assert routes.taken() == {GROUPED_MATMUL: mosaic_or_xla,
                              gm.KERNEL: kernel}


def test_tile_rule():
    """K whole wherever the bank tile fits beside the rows; the published
    widths of the three expert families that serve through it."""
    assert gm.tiles(2048, 1024, 2) == (128, 2048, 1024)     # OLMoE
    assert gm.tiles(1024, 2048, 2) == (128, 1024, 2048)
    assert gm.tiles(4096, 14336, 2) == (128, 4096, 512)     # Mixtral-8x7B
    assert gm.tiles(14336, 4096, 2) == (128, 7168, 256)
    assert gm.tiles(2048, 1408, 2) == (128, 2048, 1408)     # Qwen1.5-MoE
    assert gm.tiles(2048, 1024, 4) == (128, 2048, 512)      # float32
    assert gm.tiles(2048, 1000, 2) is None
    for k, n, itemsize in ((2048, 1024, 2), (14336, 4096, 2),
                           (4096, 14336, 4)):
        tm, tk, tn = gm.tiles(k, n, itemsize)
        assert k % tk == 0 and n % tn == 0
        assert gm._vmem_bytes(tm, tk, tn, itemsize, slots=2) <= gm._TILE_BUDGET
