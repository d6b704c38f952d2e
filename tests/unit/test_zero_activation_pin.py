"""ZeRO's fourth layout: activations pinned to the plan's batch sharding
(``runtime/zero.py:pin_activation``), so that a stage-3 layer gathers its
weights to the local batch and reduce-scatters their gradients instead of
running tensor-parallel over the ``data`` axis behind all-to-alls."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.models.llama import LlamaLMLoss, LlamaMLP, get_config
from deepspeed_tpu.runtime import zero
from deepspeed_tpu.utils.compat import shard_map

DP = 4
MICRO = 2
SEQ = 32


def _llama(**kw):
    return get_config("tinyllama", scan_layers=True, remat=True,
                      remat_policy="full", use_flash_attention=False, **kw)


def _engine(stage, gas, dtype):
    topo = dist.initialize_mesh(dp=DP, devices=jax.devices()[:DP])
    rows = MICRO * DP * gas
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 256, size=(rows, SEQ), dtype=np.int32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaLMLoss(_llama(dtype=dtype)), topology=topo,
        config={
            "train_batch_size": rows,
            "gradient_accumulation_steps": gas,
            "steps_per_print": 100,
            "bf16": {"enabled": dtype == jnp.bfloat16},
            # plain SGD at lr 1 and no clipping: the step's update IS the
            # gradient, so p - p' reads every gradient leaf of the fused step
            "optimizer": {"type": "SGD", "params": {"lr": 1.0}},
            "zero_optimization": {"stage": stage,
                                  "stage3_param_persistence_threshold": 64},
        },
        example_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    return engine, batch


def _step(engine, batch):
    """(compiled text, loss, gradient leaves) of one fused step."""
    text = engine.lower_train_step(batch).compile().as_text()
    before = jax.tree_util.tree_map(np.asarray, engine.state.params)
    loss = float(engine.train_batch(batch=batch))
    grads = jax.tree_util.tree_map(lambda p, q: p - np.asarray(q), before,
                                   engine.state.params)
    return text, loss, grads


def _activation_gathers(text, global_batch):
    """all-gathers of the compiled step whose result leads with the global
    batch and the sequence: an activation made whole on every device."""
    shaped = re.compile(r"= \(?\w+\[%d,%d[,\]]" % (global_batch, SEQ))
    return [line for line in text.splitlines()
            if re.search(r" all-gather(-start)?\(", line)
            and shaped.search(line)]


# relative L2 error allowed a gradient leaf: in bf16 (8 bits of mantissa,
# partial sums in another order) the two steps differ by 1.5-1.7 %, in
# float32 by 3e-6; a partial sum left unreduced is off by 75 %
TOLERANCE = {jnp.bfloat16: 2 ** -5, jnp.float32: 1e-4}


@pytest.mark.parametrize("stage,gas,dtype", [
    (1, 1, jnp.bfloat16), (1, 2, jnp.bfloat16), (3, 1, jnp.bfloat16),
    (3, 2, jnp.bfloat16), (3, 2, jnp.float32)])
def test_step_keeps_activations_on_their_batch_shard(stage, gas, dtype,
                                                     devices, monkeypatch):
    """At this size too the unpinned stage-3 step runs the MLP's backward
    tensor-parallel over ``data`` (5 all-to-alls, 3 gathered activations
    on the CPU's partitioner); the pinned one may not."""
    engine, batch = _engine(stage, gas, dtype)
    text, loss, grads = _step(engine, batch)
    assert engine.plan.activation_pins > 0
    assert f"{engine.plan.activation_pins} activation pins" in \
        engine.plan.describe(engine.state.params, engine.base_specs)
    assert " all-to-all(" not in text
    assert not _activation_gathers(text, MICRO * DP)

    # the control: the same step with the pin made the identity
    monkeypatch.setattr(zero, "pin_activation", lambda x: x)
    control, cbatch = _engine(stage, gas, dtype)
    _, closs, cgrads = _step(control, cbatch)
    assert control.plan.activation_pins == 0
    np.testing.assert_allclose(loss, closs, rtol=TOLERANCE[dtype] / 8)
    for (path, g), c in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(cgrads)):
        assert np.linalg.norm(c) > 0, path
        assert np.linalg.norm(g - c) <= \
            TOLERANCE[dtype] * np.linalg.norm(c), path


def _is_returned(x):
    return zero.pin_activation(x) is x


@pytest.mark.parametrize("case", ["no_topology", "one_device", "manual",
                                  "tensor_only", "ragged_batch"])
def test_pin_returns_its_argument_where_nothing_is_to_pin(case, devices):
    x = jnp.ones((4, 8, 16))
    if case == "no_topology":
        assert dist.peek_topology() is None
        assert _is_returned(x)
    elif case == "one_device":
        dist.initialize_mesh(dp=1, devices=jax.devices()[:1])
        assert _is_returned(x)
    elif case == "tensor_only":
        dist.initialize_mesh(dp=1, tp=2, devices=jax.devices()[:2])
        assert _is_returned(x)
    elif case == "ragged_batch":
        dist.initialize_mesh(dp=DP, devices=jax.devices()[:DP])
        assert _is_returned(jnp.ones((3, 8, 16)))
        assert not _is_returned(x)          # the same mesh does pin 4 rows
    else:
        topo = dist.initialize_mesh(dp=DP, devices=jax.devices()[:DP])
        seen = []

        def body(v):
            seen.append(_is_returned(v))
            return v

        shard_map(body, mesh=topo.mesh, in_specs=P("data"),
                  out_specs=P("data"), check_vma=False)(x)
        assert seen == [True]


def test_mlp_program_on_one_device_is_the_unpinned_one(monkeypatch):
    """The serving programs trace ``LlamaMLP`` with no multi-device
    topology: their jaxpr, and with it their compile-cache key, is the
    identity control's."""
    cfg = _llama()
    mlp = LlamaMLP(cfg)
    x = jnp.ones((2, SEQ, cfg.hidden_size), cfg.dtype)
    params = mlp.init(jax.random.PRNGKey(0), x)

    def jaxpr():
        return str(jax.make_jaxpr(mlp.apply)(params, x))

    dist.initialize_mesh(dp=1, devices=jax.devices()[:1])
    pinned = jaxpr()
    monkeypatch.setattr(zero, "pin_activation", lambda x: x)
    assert pinned == jaxpr()
    assert "sharding_constraint" not in pinned
