"""Sequence-parallelism tests (reference:
tests/unit/sequence_parallelism/test_ulysses.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.ops.flash_attention import mha_reference
from deepspeed_tpu.sequence import (ring_attention, ulysses_attention,
                                    vocab_sequence_parallel_cross_entropy)


def _qkv(rng, B=2, H=4, Hkv=None, S=64, D=16):
    Hkv = Hkv or H
    return (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32))


@pytest.fixture
def sp_mesh(devices):
    return dist.initialize_mesh(dp=2, sp=4)


def _shard_seq(topo, x):
    return jax.device_put(x, NamedSharding(topo.mesh,
                                           P("data", None, "seq", None)))


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(sp_mesh, causal):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng)
    qs, ks, vs = (_shard_seq(sp_mesh, t) for t in (q, k, v))
    out = jax.jit(lambda q, k, v: ulysses_attention(
        q, k, v, mesh=sp_mesh.mesh, causal=causal))(qs, ks, vs)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("uneven_kv", ["replicate", "once"])
def test_ulysses_gqa_kv_expansion(sp_mesh, uneven_kv):
    """Hkv=2 < sp=4, both GQA layouts: "replicate" expands kv to the
    query head count BEFORE the all-to-all (round-5 behavior, the
    parity reference); "once" ships each kv head through the a2a once
    and expands after the scatter (kv-head-rate wire bytes) — same
    math, both must match the dense reference."""
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, H=8, Hkv=2)
    qs, ks, vs = (_shard_seq(sp_mesh, t) for t in (q, k, v))
    out = jax.jit(lambda q, k, v: ulysses_attention(
        q, k, v, mesh=sp_mesh.mesh, uneven_kv=uneven_kv))(qs, ks, vs)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_ulysses_uneven_paths_bit_match(sp_mesh):
    """The send-once layout is a pure comm optimization: its output
    matches the replicating layout to float equality on the same
    shards."""
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, H=8, Hkv=2)
    qs, ks, vs = (_shard_seq(sp_mesh, t) for t in (q, k, v))
    a = jax.jit(lambda q, k, v: ulysses_attention(
        q, k, v, mesh=sp_mesh.mesh, uneven_kv="replicate"))(qs, ks, vs)
    b = jax.jit(lambda q, k, v: ulysses_attention(
        q, k, v, mesh=sp_mesh.mesh, uneven_kv="once"))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                               rtol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_reference(sp_mesh, causal):
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng)
    qs, ks, vs = (_shard_seq(sp_mesh, t) for t in (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=sp_mesh.mesh, causal=causal))(qs, ks, vs)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_ring_gqa(sp_mesh):
    """Ring keeps K/V at Hkv heads through the hops; output matches MHA."""
    rng = np.random.default_rng(21)
    q, k, v = _qkv(rng, H=8, Hkv=2)
    qs, ks, vs = (_shard_seq(sp_mesh, t) for t in (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=sp_mesh.mesh, causal=True))(qs, ks, vs)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_ring_gradients_match(sp_mesh):
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, B=1, H=2, S=32, D=8)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=sp_mesh.mesh,
                                      causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4)


def test_ulysses_gradients_match(sp_mesh):
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, B=1, H=4, S=32, D=8)

    def loss_uly(q, k, v):
        return jnp.sum(ulysses_attention(q, k, v, mesh=sp_mesh.mesh,
                                         causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4)


def test_vocab_seq_parallel_cross_entropy(devices):
    topo = dist.initialize_mesh(dp=1, sp=4, tp=2)
    rng = np.random.default_rng(5)
    B, S, V = 2, 16, 64
    logits = jnp.asarray(rng.normal(size=(B, S, V)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    ls = jax.device_put(logits, NamedSharding(topo.mesh,
                                              P(None, "seq", "tensor")))
    ts = jax.device_put(targets, NamedSharding(topo.mesh, P(None, "seq")))
    loss = jax.jit(lambda l, t: vocab_sequence_parallel_cross_entropy(
        l, t, mesh=topo.mesh))(ls, ts)
    ref_logp = jax.nn.log_softmax(logits, axis=-1)
    ref = -jnp.mean(jnp.take_along_axis(ref_logp, targets[..., None],
                                        axis=-1))
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)


@pytest.mark.parametrize("backend", ["ulysses", "ring"])
def test_llama_trains_with_sequence_parallel(devices, backend):
    from deepspeed_tpu.models.llama import LlamaLMLoss, get_config

    topo = dist.initialize_mesh(dp=2, sp=4)
    cfg = get_config("tinyllama", dtype=jnp.float32, param_dtype=jnp.float32,
                     remat=False, use_flash_attention=False,
                     sequence_parallel=backend)
    ds_config = {
        "train_batch_size": 8,
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3,
                                                  "fused": False}},
        "steps_per_print": 10000,
    }
    rng = np.random.default_rng(6)
    batch = {"input_ids": rng.integers(0, 256, size=(8, 32), dtype=np.int32)}
    engine, *_ = deepspeed_tpu.initialize(
        model=LlamaLMLoss(cfg), config=ds_config, topology=topo,
        example_batch=batch, rng=jax.random.PRNGKey(0))
    losses = [float(jax.device_get(engine.train_batch(batch=batch)))
              for _ in range(4)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_sp_loss_matches_dp_loss(devices):
    """Same model/seed: sp=4 x dp=2 loss == pure dp=8 loss (first step)."""
    from deepspeed_tpu.models.llama import LlamaLMLoss, get_config

    rng = np.random.default_rng(7)
    batch = {"input_ids": rng.integers(0, 256, size=(8, 32), dtype=np.int32)}
    results = {}
    for name, (kw, sp_mode) in {
        "dp": (dict(dp=8), "none"),
        "sp": (dict(dp=2, sp=4), "ulysses"),
    }.items():
        topo = dist.initialize_mesh(**kw)
        cfg = get_config("tinyllama", dtype=jnp.float32,
                         param_dtype=jnp.float32, remat=False,
                         use_flash_attention=False, sequence_parallel=sp_mode)
        engine, *_ = deepspeed_tpu.initialize(
            model=LlamaLMLoss(cfg),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-3, "fused": False}},
                    "steps_per_print": 10000},
            topology=topo, example_batch=batch, rng=jax.random.PRNGKey(3))
        results[name] = [float(jax.device_get(
            engine.train_batch(batch=batch))) for _ in range(3)]
    np.testing.assert_allclose(results["dp"], results["sp"], rtol=2e-4)


class TestUlyssesCommBytes:
    """Uneven-head Ulysses a2a: the byte accounting (the mesh parity tests
    are above)."""

    def test_uneven_kv_bytes_at_kv_head_rate(self):
        from deepspeed_tpu.sequence import ulysses_comm_bytes

        plan = ulysses_comm_bytes((2, 8, 64, 16), (2, 2, 64, 16), sp=4)
        # replicate ships H/sp=2 kv-head-pairs/device; once ships the
        # single kv head each query block consumes
        assert plan["kv_bytes_once"] < plan["kv_bytes_replicate"]
        assert plan["kv_once_ratio"] == 0.5
        assert plan["total_once"] < plan["total_replicate"]

    def test_even_heads_unchanged(self):
        from deepspeed_tpu.sequence import ulysses_comm_bytes

        plan = ulysses_comm_bytes((2, 8, 64, 16), (2, 4, 64, 16), sp=4)
        assert "kv_bytes_even" in plan

    def test_uneven_plan_covers_every_query_head(self):
        from deepspeed_tpu.sequence.layer import _uneven_kv_plan

        for H, Hkv, sp in [(8, 2, 4), (16, 2, 8), (12, 3, 4),
                           (8, 2, 8)]:
            idx, lmap, m = _uneven_kv_plan(H, Hkv, sp)
            g, Hl = H // Hkv, H // sp
            assert idx.shape == (sp * m,)
            for r in range(sp):
                dev_heads = idx[r * m:(r + 1) * m]
                for j in range(Hl):
                    want = (r * Hl + j) // g
                    assert dev_heads[lmap[r, j]] == want, (H, Hkv, sp, r,
                                                          j)
