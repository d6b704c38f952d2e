"""Sharding-constraint helper shared by layers that need explicit GSPMD
placement (MoE dispatch, pipeline state)."""
from __future__ import annotations

import jax


def maybe_constrain(x: jax.Array, spec) -> jax.Array:
    """``with_sharding_constraint`` against the installed topology's mesh;
    no-op when no topology is installed (meshless unit tests) or when the
    mesh lacks one of the spec's axes."""
    import deepspeed_tpu.comm as dist

    topo = dist.peek_topology()
    if topo is None:
        return x
    axes = {a for e in spec if e is not None
            for a in ((e,) if isinstance(e, str) else e)}
    if not axes.issubset(set(topo.mesh.axis_names)):
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(topo.mesh, P(*spec)))


def memory_space(kind: str):
    """``jax.device_put`` target for crossing memory KINDS (host↔device
    streaming): the ``jax.memory.Space`` enum."""
    return (jax.memory.Space.Device if kind == "device"
            else jax.memory.Space.Host)
