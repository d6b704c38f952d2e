"""MoE tests (reference: tests/unit/moe/test_moe.py + gating semantics of
deepspeed/moe/sharded_moe.py top1gating/top2gating)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import (capacity, moe_combine,
                                           moe_dispatch, top2gating,
                                           topkgating)
from deepspeed_tpu.moe.layer import MoE


def test_top1_routes_to_argmax():
    logits = jnp.asarray([[0.1, 2.0, 0.3],
                          [3.0, 0.2, 0.1],
                          [0.1, 0.2, 4.0]], jnp.float32)
    gr = topkgating(logits, k=1, capacity_factor=3.0)
    routed = np.argmax(np.asarray(gr.combine).sum(axis=2), axis=1)
    np.testing.assert_array_equal(routed, [1, 0, 2])


def test_top2_weights_sum_to_one():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    gr = top2gating(logits, capacity_factor=4.0)  # big capacity: no drops
    w = np.asarray(gr.combine).sum(axis=(1, 2))
    np.testing.assert_allclose(w, 1.0, atol=1e-5)


def test_capacity_drop_renormalizes_survivor():
    """Reference top2gating semantics: when a token's second choice is
    capacity-dropped, the surviving first choice absorbs the FULL weight
    (gates renormalized post-drop, sharded_moe.py:290)."""
    # 4 tokens, all first-choice expert 0, distinct second choices.
    # C = ceil(2*1.0*4/4) = 2: expert 0 keeps tokens 0,1 and drops 2,3;
    # every second choice fits.
    logits = jnp.asarray([[5.0, 2.0, -5.0, -5.0],
                          [5.0, -5.0, 2.0, -5.0],
                          [5.0, -5.0, -5.0, 2.0],
                          [5.0, 2.0, -5.0, -5.0]], jnp.float32)
    gr = topkgating(logits, k=2, capacity_factor=1.0, min_capacity=1)
    w = np.asarray(gr.combine).sum(axis=(1, 2))
    # tokens 0,1: both choices kept -> weight 1. tokens 2,3: only the
    # second choice survives -> renormalized to 1 (NOT g2/(g1+g2))
    np.testing.assert_allclose(w, 1.0, atol=1e-5)
    # and tokens 2,3 route only to their surviving second choice
    per_expert = np.asarray(gr.combine).sum(axis=2)  # [G, E]
    assert per_expert[2, 0] == 0 and per_expert[3, 0] == 0
    assert per_expert[2, 3] > 0.99 and per_expert[3, 1] > 0.99


def test_full_drop_gives_zero_output():
    """A token whose every choice is dropped contributes nothing (and must
    not NaN via the eps-clamped denominator)."""
    logits = jnp.asarray([[5.0, -9.0], [5.0, -9.0], [5.0, -9.0]], jnp.float32)
    gr = topkgating(logits, k=1, capacity_factor=0.4, min_capacity=1)
    # C = max(ceil(0.4 * 3 / 2), 1) = 1: only token 0 fits on expert 0
    w = np.asarray(gr.combine).sum(axis=(1, 2))
    assert w[0] > 0.99
    np.testing.assert_allclose(w[1:], 0.0, atol=1e-6)
    assert np.isfinite(np.asarray(gr.l_aux))


def test_aux_loss_uniform_is_one():
    """Perfectly uniform routing gives l_aux == 1 (switch-transformer
    normalization, reference top1gating l_aux)."""
    G, E = 64, 8
    logits = jnp.tile(jnp.eye(E, dtype=jnp.float32) * 0.0, (G // E, 1))
    gr = topkgating(logits, k=1, capacity_factor=8.0)
    np.testing.assert_allclose(float(gr.l_aux), 1.0, atol=0.05)


def test_dispatch_combine_roundtrip():
    """With capacity for everyone and k=1, combine(dispatch(x)) scales each
    token by its gate weight."""
    rng = np.random.default_rng(1)
    G, E, M = 8, 4, 16
    x = jnp.asarray(rng.normal(size=(G, M)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(G, E)), jnp.float32)
    gr = topkgating(logits, k=1, capacity_factor=float(E))
    y = moe_combine(moe_dispatch(x, gr.dispatch.astype(x.dtype)), gr.combine)
    w = np.asarray(gr.combine).sum(axis=(1, 2), keepdims=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) * w[:, None],
                               atol=1e-5, rtol=1e-5)


def test_moe_single_expert_equals_dense_mlp():
    """E=1, k=1, ample capacity: the MoE layer must equal the plain SwiGLU
    MLP with the same weights (EP==dense parity, reference test_moe)."""
    rng = np.random.default_rng(2)
    B, S, M, I = 2, 8, 16, 32
    x = jnp.asarray(rng.normal(size=(B, S, M)), jnp.float32)
    moe = MoE(hidden_size=M, num_experts=1, intermediate_size=I, k=1,
              capacity_factor=2.0, dtype=jnp.float32,
              param_dtype=jnp.float32, expert_parallel=False)
    params = moe.init(jax.random.PRNGKey(0), x)
    y, l_aux = moe.apply(params, x)

    p = params["params"]
    w1, w2, w3 = (np.asarray(p["w1"])[0], np.asarray(p["w2"])[0],
                  np.asarray(p["w3"])[0])
    xs = np.asarray(x).reshape(-1, M)
    h = xs @ w1
    ref = ((h / (1 + np.exp(-h))) * (xs @ w3)) @ w2
    np.testing.assert_allclose(np.asarray(y).reshape(-1, M), ref,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(float(l_aux), 1.0, atol=1e-5)  # E=1: me*ce*E


def test_moe_grads_flow_to_experts_and_gate():
    rng = np.random.default_rng(3)
    B, S, M, I, E = 2, 8, 16, 32, 4
    x = jnp.asarray(rng.normal(size=(B, S, M)), jnp.float32)
    moe = MoE(hidden_size=M, num_experts=E, intermediate_size=I, k=2,
              capacity_factor=2.0, dtype=jnp.float32,
              param_dtype=jnp.float32, expert_parallel=False)
    params = moe.init(jax.random.PRNGKey(0), x)

    def loss(p):
        y, aux = moe.apply(p, x)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)["params"]
    for name in ("gate", "w1", "w2", "w3"):
        assert float(jnp.sum(jnp.abs(g[name]))) > 0, f"zero grad for {name}"


@pytest.mark.slow
def test_mixtral_tiny_trains(devices):
    """End-to-end: tiny Mixtral under the engine on dp=2 x ep=4 mesh with
    ZeRO-1 — BASELINE.md config #5 shape (EP + ZeRO)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.mixtral import MixtralLMLoss, get_config

    topo = dist.initialize_mesh(dp=2, ep=4)
    cfg = get_config("tinymixtral", dtype=jnp.float32,
                     param_dtype=jnp.float32, scan_layers=True, remat=False,
                     use_flash_attention=False)
    ds_config = {
        "train_batch_size": 16,
        "gradient_accumulation_steps": 1,
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "steps_per_print": 1000,
    }
    rng = np.random.default_rng(4)
    batch = {"input_ids": rng.integers(0, 256, size=(16, 16),
                                       dtype=np.int32)}
    engine, *_ = deepspeed_tpu.initialize(
        model=MixtralLMLoss(cfg), config=ds_config, topology=topo,
        example_batch={"input_ids": batch["input_ids"][:2]},
        rng=jax.random.PRNGKey(0))
    # expert params must actually live on the expert axis
    w1_sharding = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda x: x.sharding,
                               engine.state.params))
    losses = [float(jax.device_get(engine.train_batch(batch=batch)))
              for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_capacity_formula():
    assert capacity(num_tokens=64, num_experts=8, capacity_factor=1.0,
                    min_capacity=4) == 8
    assert capacity(num_tokens=64, num_experts=8, capacity_factor=1.0,
                    min_capacity=4, k=2) == 16
    assert capacity(num_tokens=8, num_experts=8, capacity_factor=1.0,
                    min_capacity=4) == 4


# -- the dropless grouped path (serving) -----------------------------------

def _pair(activation, normalize, impl_kw):
    return MoE(hidden_size=32, num_experts=8, intermediate_size=16, k=2,
               activation=activation, dtype=jnp.float32,
               expert_parallel=False, normalize_weights=normalize,
               **impl_kw)


@pytest.mark.parametrize("normalize", [False, True],
                         ids=["raw-gates", "renormalized"])
@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_grouped_matches_the_einsum_path_that_drops_nothing(activation,
                                                            normalize):
    """Forward of the grouped (``ragged_dot``) path against the dense
    one-hot oracle with ``drop_tokens=False``, with one expert that no
    token takes (an empty group between full ones)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 32))
    oracle = _pair(activation, normalize,
                   dict(dispatch_impl="einsum", drop_tokens=False))
    params = oracle.init(jax.random.PRNGKey(1), x)
    # expert 3 is never among the two largest
    gate = params["params"]["gate"].at[:, 3].set(0.0)
    x = x.at[..., 0].set(4.0)
    gate = gate.at[0].set(jnp.where(jnp.arange(8) == 3, -50.0, gate[0]))
    params = {"params": {**params["params"], "gate": gate}}
    want, aux_want = oracle.apply(params, x)
    grouped = _pair(activation, normalize, dict(dispatch_impl="grouped"))
    (got, aux), stats = grouped.apply(params, x, mutable=["moe_stats"])
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(aux, aux_want, rtol=1e-6)
    rows, hit, load, visits = (
        int(v) for v in stats["moe_stats"]["counts"][0])
    assert rows == 2 * 18 and hit <= 7 and load >= 36 / 7
    # 36 rows lie in one row tile of the grouped matmul: a visit a group
    assert visits == hit


def test_grouped_dead_rows_take_no_expert_row():
    """Rows that are no token (padding of a tick, inactive decode slots)
    come back as zeros, count in no counter and change no live row."""
    layer = _pair("swiglu", False, dict(dispatch_impl="grouped"))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 32))
    params = layer.init(jax.random.PRNGKey(3), x)
    live = jnp.arange(12) < 5
    (y, _), stats = layer.apply(params, x, live=live[None],
                                mutable=["moe_stats"])
    (y5, _), _ = layer.apply(params, x[:, :5], mutable=["moe_stats"])
    np.testing.assert_allclose(y[0, :5], y5[0], atol=1e-6)
    assert not np.asarray(y[0, 5:]).any()
    assert int(stats["moe_stats"]["counts"][0][0]) == 2 * 5
    # nobody asked for the counts: nothing is returned, nothing at init
    assert "moe_stats" not in params
    assert isinstance(layer.apply(params, x), tuple)


def test_serving_configs_are_dropless():
    """``decode`` or ``paged_decode`` on the config is what selects the
    grouped path, for every expert family; training keeps its own."""
    import dataclasses

    from deepspeed_tpu.models import mixtral, olmoe, qwen2_moe

    for mod, preset in ((mixtral, "tinymixtral"),
                        (qwen2_moe, "tinyqwen2moe"), (olmoe, "tinyolmoe")):
        cfg = mod.get_config(preset)
        assert mixtral.moe_layer(cfg, "m").dispatch_impl == "auto"
        for flag in ("decode", "paged_decode"):
            serving = dataclasses.replace(cfg, **{flag: True})
            assert mixtral.moe_layer(serving, "m").dispatch_impl == "grouped"
