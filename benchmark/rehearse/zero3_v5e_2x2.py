#!/usr/bin/env python3
"""Compile the ZeRO-3 training step of a training configuration for a
DESCRIBED v5e 2x2 host, without a chip: what the TPU compiler needs on each
device (``memory_analysis()``) and which collectives it put in the step.

    JAX_PLATFORMS=cpu python benchmark/rehearse/zero3_v5e_2x2.py [config] [micro_batch]

A script, never imported by a test: it describes the topology at top level
(on-chip-measurement guide, section 2).  Nothing runs on a described
device, so while the engine is built every jitted call is answered with
the shapes and shardings it would return (``eval_shape``) and every
``device_put`` with the shape it was given; the step itself is then lowered
by the engine's own ``lower_train_step`` and compiled by the TPU compiler.
A compile that passes is not a chip run.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import flax  # noqa: F401  (libraries keep the real jax.jit: imported
import jax
import jax.numpy as jnp
import numpy as np
import optax  # noqa: F401   before it is replaced below)
from jax.experimental import topologies

from benchmark import harness as H

topo_desc = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
name = sys.argv[1] if len(sys.argv) > 1 else "mistral-7b-zero3"
config = H.load_json(os.path.join(H.HERE, "configs", name + ".json"))
tcfg = config["train"]
micro = int(sys.argv[2]) if len(sys.argv) > 2 else \
    int(tcfg["micro_batch_per_chip"])

# -- nothing may execute: answer with shapes ---------------------------------
_real_jit, _real_put = jax.jit, jax.device_put


def _struct(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                sharding=sharding)


def _shape_only_jit(fn=None, **kw):
    if fn is None:
        return lambda f: _shape_only_jit(f, **kw)
    jitted = _real_jit(fn, **kw)

    class Shapes:
        lower = staticmethod(jitted.lower)
        eval_shape = staticmethod(jitted.eval_shape)

        def __call__(self, *a, **k):
            return jitted.eval_shape(*a, **k)

    return Shapes()


def _shape_only_put(x, device=None, **kw):
    if device is None:
        return x
    if jax.tree_util.treedef_is_leaf(jax.tree_util.tree_structure(device)):
        return jax.tree_util.tree_map(lambda a: _struct(a, device), x)
    return jax.tree_util.tree_map(_struct, x, device)


jax.jit, jax.device_put = _shape_only_jit, _shape_only_put

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.ops import routes

routes.on_tpu = lambda: True        # the kernels' TPU branch, as on the chip

topo = dist.initialize_mesh(devices=list(topo_desc.devices))
dp = topo.zero_partition_count()
seq = int(tcfg["seq_len"])
mcfg = H.build_model_config(config)
module = H.resolve(config["family"]["loss_module"])(mcfg)
ds_config = dict(tcfg["ds_config"], train_micro_batch_size_per_gpu=micro)
gib = 2.0 ** 30


def total_gib(mem) -> float:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib



batch = {"input_ids": np.zeros((micro * dp, seq), np.int32)}
engine, _, _, _ = deepspeed_tpu.initialize(
    model=module, config=ds_config, topology=topo,
    example_batch={"input_ids": batch["input_ids"][:1]},
    rng=jax.random.PRNGKey(0))
jax.jit = _real_jit
compiled = engine.lower_train_step(batch).compile()
text = compiled.as_text()
mem = compiled.memory_analysis()
print({"config": name, "layers": mcfg.num_hidden_layers, "devices": dp,
       "micro_batch_per_chip": micro, "seq_len": seq,
       "argument_gib": mem.argument_size_in_bytes / gib,
       "output_gib": mem.output_size_in_bytes / gib,
       "alias_gib": mem.alias_size_in_bytes / gib,
       "temp_gib": mem.temp_size_in_bytes / gib,
       "total_gib": total_gib(mem),
       "pallas_kernels": text.count('custom_call_target="tpu_custom_call"'),
       "all_gather": text.count(" all-gather(") +
       text.count(" all-gather-start("),
       "reduce_scatter": text.count("reduce-scatter"),
       "all_reduce": text.count(" all-reduce(") +
       text.count(" all-reduce-start(")})

# the correctness check's own program (benchmark/drivers/train.py): every
# position's loss from the program's model on the engine's sharded weights.
# It runs beside the resident state, so its arguments count the weights
# only and the optimizer's moments come on top.
from benchmark.drivers import train as train_driver

spec = config["correct"]
params = engine.state.params
tree = params["params"] if "params" in params else params
n_rows = dp * -(-int(spec["rows"]) // dp)
check = jax.jit(train_driver.position_losses_fn(config)).lower(
    tree[spec["module_scope"]],
    jax.ShapeDtypeStruct((n_rows, int(spec["tokens"])), jnp.int32)).compile()
cmem = check.memory_analysis()
print({"program": "position_losses", "rows": n_rows,
       "tokens": int(spec["tokens"]),
       "argument_gib": cmem.argument_size_in_bytes / gib,
       "temp_gib": cmem.temp_size_in_bytes / gib,
       "total_gib": total_gib(cmem),
       "pallas_kernels":
       check.as_text().count('custom_call_target="tpu_custom_call"')})
