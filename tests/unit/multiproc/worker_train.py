"""Worker for the two-process jax.distributed tests (launched by
test_multiprocess.py — reference tests/unit/common.py:129 DistributedExec
spawns real worker processes the same way).

Env: DSTPU_COORD (host:port), DSTPU_NPROC, DSTPU_PID, DSTPU_MODE
(train | nvme), DSTPU_DIR (scratch).
Prints machine-readable lines: ``RESULT <json>``.
"""
import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from deepspeed_tpu.resilience.retry import retriable  # noqa: E402

# The old-gloo transport intermittently fails the rendezvous/first
# connect (EnforceNotMet preamble.length) — a transient, so the
# distributed bootstrap rides the resilience backoff decorator instead
# of flaking the whole worker.
retriable(attempts=4, base_s=0.5, cap_s=4.0,
          retry_on=(RuntimeError, OSError))(jax.distributed.initialize)(
    coordinator_address=os.environ["DSTPU_COORD"],
    num_processes=int(os.environ["DSTPU_NPROC"]),
    process_id=int(os.environ["DSTPU_PID"]))

import flax.linen as nn            # noqa: E402
import jax.numpy as jnp            # noqa: E402
import numpy as np                 # noqa: E402

import deepspeed_tpu               # noqa: E402
import deepspeed_tpu.comm as dist  # noqa: E402
from deepspeed_tpu.resilience import distributed as rdist  # noqa: E402

# per-rank fault plumbing (DSTPU_FAULT_SPEC / DSTPU_FAULT_RANK): no-op
# unless the launching test armed it
rdist.install_injector_from_env()


class TinyNet(nn.Module):
    @nn.compact
    def __call__(self, batch):
        h = nn.Dense(32)(batch["x"])
        out = nn.Dense(1)(nn.relu(h))
        return jnp.mean((out - batch["y"]) ** 2)


def data(step, n=16):
    rng = np.random.default_rng(500 + step)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    return {"x": x, "y": np.sum(x, axis=1, keepdims=True) * 0.1}


def main():
    mode = os.environ.get("DSTPU_MODE", "train")
    scratch = os.environ["DSTPU_DIR"]
    assert jax.process_count() == int(os.environ["DSTPU_NPROC"])
    assert jax.device_count() == 8, jax.device_count()
    assert len(jax.local_devices()) == 4

    ds = {"train_batch_size": 16,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
          "zero_optimization": {"stage": 3},
          "steps_per_print": 1000000}
    if mode == "nvme":
        ds["zero_optimization"]["offload_optimizer"] = {
            "device": "nvme",
            "nvme_path": os.path.join(scratch, "swap")}
        os.makedirs(os.path.join(scratch, "swap"), exist_ok=True)

    topo = dist.initialize_mesh(dp=8)
    eng, *_ = deepspeed_tpu.initialize(
        model=TinyNet(), config=ds, topology=topo,
        example_batch=jax.tree_util.tree_map(lambda a: a[:1], data(0)),
        rng=jax.random.PRNGKey(0))
    if mode == "nvme":
        assert eng.nvme_swapper is not None, "nvme swap refused"

    # one fixed batch: losses must fall monotonically-ish (the parity
    # asserts need a deterministic signal, not fresh noise per step)
    losses = []
    for s in range(3):
        losses.append(float(jax.device_get(eng.train_batch(batch=data(0)))))
    # per-shard leafwise moment-stream rate: multi-process jobs run the
    # leafwise NVMe stream (each rank swaps its own partition) — report
    # this rank's measured read/write rate (the bench-matrix
    # leafwise_mp row aggregates it)
    leafwise = (dict(eng.nvme_swapper.stage_stats)
                if mode == "nvme" and eng.nvme_swapper is not None else None)
    ckpt = os.path.join(scratch, "ckpt")
    eng.save_checkpoint(ckpt, tag="t", async_save=False)

    # fresh engine in the SAME processes resumes from the cross-process
    # sharded checkpoint and continues identically
    eng2, *_ = deepspeed_tpu.initialize(
        model=TinyNet(), config=ds, topology=topo,
        example_batch=jax.tree_util.tree_map(lambda a: a[:1], data(0)),
        rng=jax.random.PRNGKey(7))
    tag, _ = eng2.load_checkpoint(ckpt, tag="t")
    assert tag is not None, "resume failed"
    l_resume = float(jax.device_get(eng2.train_batch(batch=data(3))))
    l_orig = float(jax.device_get(eng.train_batch(batch=data(3))))

    print("RESULT " + json.dumps({
        "pid": jax.process_index(),
        "losses": losses,
        "l_orig": l_orig,
        "l_resume": l_resume,
        "leafwise": leafwise,
    }), flush=True)


if __name__ == "__main__":
    main()
