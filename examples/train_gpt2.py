"""Minimal end-to-end training example: GPT-2 with ZeRO-3 + bf16.

Run (single host; the mesh spans every visible chip):

    python examples/train_gpt2.py --steps 50

On the 8-device CPU test mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_gpt2.py --steps 5 --preset tiny
"""
import argparse

import jax
import numpy as np

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.accelerator import require_accelerator
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMLoss, get_config
from deepspeed_tpu.utils.compile_cache import enable_compile_cache


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="gpt2-125m")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--micro", type=int, default=4)
    p.add_argument("--zero-stage", type=int, default=3)
    p.add_argument("--save", default=None, help="checkpoint dir")
    args = p.parse_args()

    # a missing chip is an error; JAX_PLATFORMS=cpu asks for the CPU
    on_tpu = require_accelerator()
    enable_compile_cache()
    topo = dist.initialize_mesh()            # all chips on the data axis
    dp = topo.zero_partition_count()

    if args.preset == "tiny":
        cfg = GPT2Config(vocab_size=256, n_positions=args.seq, n_embd=64,
                         n_layer=2, n_head=2, dropout=0.0,
                         scan_layers=True, remat=False)
    else:
        cfg = get_config(args.preset, n_positions=args.seq,
                         scan_layers=True, use_flash_attention=on_tpu)

    ds_config = {
        "train_micro_batch_size_per_gpu": args.micro,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": on_tpu},
        "zero_optimization": {"stage": args.zero_stage},
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.01}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_num_steps": 10}},
        "gradient_clipping": 1.0,
        "steps_per_print": 10,
    }

    rng = np.random.default_rng(0)

    def batch():
        return {"input_ids": rng.integers(
            0, cfg.vocab_size, size=(args.micro * dp, args.seq),
            dtype=np.int32)}

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMLoss(cfg), config=ds_config, topology=topo,
        example_batch=batch(), rng=jax.random.PRNGKey(0))

    for step in range(args.steps):
        engine.train_batch(batch=batch())

    if args.save:
        tag = engine.save_checkpoint(args.save)
        print(f"checkpoint saved: {tag}")


if __name__ == "__main__":
    main()
