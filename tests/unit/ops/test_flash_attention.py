"""Flash attention kernel tests (reference: tests/unit/ops kernel tests).

Runs the blockwise-XLA path natively on CPU and the Pallas kernel in
interpreter mode, both against the naive O(S^2) reference.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import routes
from deepspeed_tpu.ops.flash_attention import (flash_attention,
                                               mha_reference,
                                               _blockwise_fwd,
                                               _diag_rows,
                                               _flash_bwd_pallas,
                                               _flash_fwd_pallas)
from tests.unit.ops import flash_whole_tile_kernels as whole_tile


def _make_qkv(rng, B=2, H=4, Hkv=None, S=128, D=32, dtype=jnp.float32):
    Hkv = Hkv or H
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_reference(causal):
    rng = np.random.default_rng(0)
    q, k, v = _make_qkv(rng, S=96, D=16)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_blockwise_uneven_blocks():
    rng = np.random.default_rng(1)
    q, k, v = _make_qkv(rng, S=80, D=16)  # 80 not divisible by 32
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_gqa():
    rng = np.random.default_rng(2)
    q, k, v = _make_qkv(rng, H=8, Hkv=2, S=64, D=16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_cross_attention_lengths():
    rng = np.random.default_rng(3)
    B, H, D = 1, 2, 16
    q = jnp.asarray(rng.normal(size=(B, H, 48, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, 96, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, 96, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_causal_cross_attention_decode_alignment():
    """Bottom-right-aligned causal: a 1-token query over a 64-token KV cache
    (decode step) must attend to ALL keys, and gradients must match."""
    rng = np.random.default_rng(30)
    B, H, D, Sk = 1, 2, 16, 64
    k = jnp.asarray(rng.normal(size=(B, H, Sk, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, Sk, D)), jnp.float32)
    for Sq in (1, 16, 48):
        q = jnp.asarray(rng.normal(size=(B, H, Sq, D)), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        out_i = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(out_i), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(mha_reference(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    rng = np.random.default_rng(4)
    q, k, v = _make_qkv(rng, B=1, H=2, S=64, D=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=32,
                                       block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_gqa_gradients():
    rng = np.random.default_rng(5)
    q, k, v = _make_qkv(rng, B=1, H=4, Hkv=2, S=32, D=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_pallas_kernel_interpret_mode():
    """The TPU kernel itself, run through the Pallas interpreter on CPU."""
    rng = np.random.default_rng(6)
    q, k, v = _make_qkv(rng, B=1, H=2, S=128, D=32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pallas_kernel_interpret_gqa_noncausal():
    rng = np.random.default_rng(7)
    q, k, v = _make_qkv(rng, B=1, H=4, Hkv=2, S=128, D=32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_lse_matches_logsumexp():
    rng = np.random.default_rng(8)
    q, k, v = _make_qkv(rng, B=1, H=1, S=64, D=16)
    _, lse = _blockwise_fwd(q, k, v, sm_scale=0.25, causal=False,
                            block_q=32, block_k=32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
    ref_lse = jax.scipy.special.logsumexp(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_bwd_interpret(causal):
    """The Pallas dq / dkv kernels, via the interpreter on CPU."""
    rng = np.random.default_rng(9)
    q, k, v = _make_qkv(rng, B=1, H=2, S=96, D=32)  # 96: uneven vs block 64

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=64,
                                       block_k=64, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_pallas_bwd_interpret_gqa():
    rng = np.random.default_rng(10)
    q, k, v = _make_qkv(rng, B=1, H=4, Hkv=2, S=64, D=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32,
                                       block_k=32, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_fully_masked_rows_zero_output():
    """Causal with Sk < S: query rows with zero valid keys must output 0
    (not a uniform average of masked values) and carry zero gradient."""
    rng = np.random.default_rng(11)
    B, H, D, Sq, Sk = 1, 2, 16, 8, 4
    q = jnp.asarray(rng.normal(size=(B, H, Sq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, Sk, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, Sk, D)), jnp.float32)
    # causal_offset = Sk - Sq = -4: rows 0-3 see no keys
    for interp in (False, True):
        out = flash_attention(q, k, v, causal=True, block_q=4, block_k=4,
                              interpret=interp)
        np.testing.assert_allclose(np.asarray(out[:, :, :4]), 0.0, atol=1e-6)
        g = jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=4, block_k=4,
            interpret=interp) ** 2))(q)
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g[:, :, :4]), 0.0, atol=1e-6)


@pytest.mark.parametrize("mesh_kw", [dict(dp=8), dict(dp=2, tp=2, ep=2)],
                         ids=["dp8", "dp2-ep2-tp2"])
def test_pallas_kernel_partitions_manually_under_a_mesh(devices, mesh_kw):
    """GSPMD cannot split a Mosaic kernel, so under an installed
    multi-device mesh the Pallas route runs in a shard_map: batch over the
    data axes, heads over ``tensor``.  Same values and gradients as the
    unsharded reference, from inside a jit whose inputs are sharded."""
    import deepspeed_tpu.comm as dist
    from jax.sharding import NamedSharding, PartitionSpec as P

    topo = dist.initialize_mesh(**mesh_kw)
    q, k, v = _make_qkv(np.random.default_rng(5), B=8, H=4, Hkv=2, S=16,
                        D=8)
    batch_sharded = NamedSharding(
        topo.mesh, P(("data", "expert"), None, None, None))
    qs, ks, vs = (jax.device_put(x, batch_sharded) for x in (q, k, v))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    flash = functools.partial(flash_attention, causal=True, block_q=8,
                              block_k=8, interpret=True)
    text = jax.jit(flash).lower(qs, ks, vs).as_text()
    assert "shard_map" in text or "manual" in text, (
        "the Pallas route must lower inside a manual region")
    got, got_grads = jax.jit(jax.value_and_grad(loss(flash), (0, 1, 2)))(
        qs, ks, vs)
    want, want_grads = jax.value_and_grad(
        loss(functools.partial(mha_reference, causal=True)), (0, 1, 2))(
        q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=5e-4, rtol=5e-4)


# (H, Hkv, Sq, Sk, D, causal, block, sb): sb the rows of a row group where a
# diagonal tile is worked by row groups, 0 where the call keeps the
# whole-tile code
ROW_GROUP_CASES = [
    pytest.param((2, 2, 512, 512, 128, True, 256, 128), id="d128-mha"),
    pytest.param((4, 1, 512, 512, 128, True, 256, 128), id="d128-gqa4"),
    pytest.param((2, 2, 512, 512, 64, True, 256, 128), id="d64-mha"),
    pytest.param((4, 1, 512, 512, 64, True, 256, 128), id="d64-gqa4"),
    # one tile, all of it diagonal, two row groups of 256: the rows of 512
    # tokens that the training cell's check of its outputs runs
    pytest.param((4, 1, 512, 512, 64, True, 1024, 256), id="one-tile"),
    pytest.param((2, 1, 256, 512, 64, True, 256, 128), id="offset-a-block"),
    pytest.param((2, 1, 256, 384, 64, True, 256, 0), id="offset-no-multiple"),
    pytest.param((2, 1, 300, 300, 64, True, 256, 0), id="padded-tail"),
    pytest.param((2, 1, 512, 512, 64, False, 256, 0), id="not-causal"),
    pytest.param((2, 1, 96, 96, 32, True, 32, 0), id="blocks-of-32"),
    pytest.param((2, 1, 256, 256, 64, True, 128, 0), id="one-lane-tile"),
    # half a block of 192 is no whole lane tiles: Mosaic cannot slice there
    pytest.param((2, 1, 384, 384, 64, True, 192, 0), id="half-unaligned"),
]


@pytest.mark.parametrize("case", ROW_GROUP_CASES)
def test_row_groups_engage_by_shape(case):
    """Where a causal call's tiles sit square on the diagonal the three
    kernels work them by row groups: forward, dq, dk and dv against the
    naive reference.  Every other call takes the whole-tile code it took
    before: the same bits as the kernels kept in
    ``flash_whole_tile_kernels.py``."""
    H, Hkv, Sq, Sk, D, causal, block, sb = case
    assert _diag_rows(causal, min(block, Sq), min(block, Sk), Sq, Sk) == sb
    rng = np.random.default_rng(60)
    q, do = (jnp.asarray(rng.normal(size=(1, H, Sq, D)), jnp.float32)
             for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(1, Hkv, Sk, D)), jnp.float32)
            for _ in range(2))
    kw = dict(sm_scale=D ** -0.5, causal=causal, block_q=block,
              block_k=block, interpret=True)
    out, lse = _flash_fwd_pallas(q, k, v, **kw)
    dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, do, **kw)
    if not sb:
        want_out, want_lse = whole_tile._flash_fwd_pallas(q, k, v, **kw)
        want = whole_tile._flash_bwd_pallas(q, k, v, want_out, want_lse, do,
                                            **kw)
        for got, ref in zip((out, lse, dq, dk, dv),
                            (want_out, want_lse, *want)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        return
    ref, vjp = jax.vjp(functools.partial(mha_reference, causal=causal),
                       q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # the kernels return dk and dv a q head; the caller sums a GQA group
    dk, dv = (g.reshape(1, Hkv, H // Hkv, Sk, D).sum(axis=2)
              for g in (dk, dv))
    for got, ref in zip((dq, dk, dv), vjp(do)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-4, rtol=5e-4)


# (Sq, Sk, block) -> what the causal call records: live tiles / grid steps
# and the rows of a row group
@pytest.mark.parametrize("shape,want", [
    ((512, 512, 128), "10/10-diag0"),
    ((1024, 1024, 256), "10/10-diag128"),
    ((1024, 1024, 512), "3/3-diag256"),
    ((512, 512, 1024), "1/1-diag256"),
    ((256, 768, 256), "3/3-diag128"),
    ((96, 96, 32), "6/6-diag0"),
    # rows 0-3 reach no key: their q block keeps one step and no live tile
    ((8, 4, 4), "1/2-diag0"),
])
def test_causal_calls_record_their_live_tiles(monkeypatch, shape, want):
    """``flash_attention_causal`` names, a distinct call shape, the tiles
    at and under the diagonal, the grid steps taken for them (no step for
    a tile above it) and the row group of the diagonal tiles; the forward
    and the backward of one shape record once, a call that is not causal
    records nothing."""
    monkeypatch.setattr(routes, "_taken", {})
    Sq, Sk, block = shape
    rng = np.random.default_rng(61)
    q = jnp.asarray(rng.normal(size=(1, 1, Sq, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 1, Sk, 8)), jnp.float32)
            for _ in range(2))
    attend = functools.partial(flash_attention, block_q=block, block_k=block,
                               interpret=True)
    attend(q, k, v, causal=False)
    assert "flash_attention_causal" not in routes.taken()
    jax.grad(lambda q: jnp.sum(attend(q, k, v, causal=True)))(q)
    assert routes.taken()["flash_attention_causal"] == want + "-interpret"
    assert routes.taken()["flash_attention_fwd"] == "pallas-interpret"
    assert routes.taken()["flash_attention_bwd"] == "pallas-interpret"
