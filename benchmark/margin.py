#!/usr/bin/env python3
"""The second reading a serving configuration's ``logit_margin`` (or
``logit_mean_margin``: the driver compares what the file states) is set
from: what the check says of a model that computes in the nearest precision
BELOW the one the configuration states.  Part of no check; run by hand on
the chip when a configuration with such a reference is added or its margin
is questioned:

    python benchmark/margin.py --config olmoe-1b-7b-serve [--seeds 1,2,3]

For each seed: the configuration's weights from the seed (as the driver
makes them), the check's two seeded prompts, and for each prompt the GREEDY
tokens of the plain reference computed with every matmul operand rounded to
e4m3 (``precision="float8"`` of the family's reference; bf16 is what the
configurations state).  Those tokens are then judged as the driver judges
the system's: each one's float32 reference logit against the float32
reference maximum at its position.  A margin is tight enough only if such a
model comes out NOT correct; the line printed per seed says so.

No engine, no traffic: the reference alone, on the chip because at the
published widths the CPU would take an hour.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", default="1,2,3")
    p.add_argument("--precision", default="float8")
    args = p.parse_args(argv)

    import numpy as np

    from benchmark import harness as H

    config = H.load_json(os.path.join(H.HERE, "configs",
                                      args.config + ".json"))
    H.require_chips(1)
    ref = H.plugin("reference", config["family"]["reference"])
    model = H.resolve(config["family"]["module"])(
        H.build_model_config(config))
    spec = config["correct"]
    for seed in (int(s) for s in args.seeds.split(",")):
        params = H.init_params(model, config, seed)
        rng = np.random.default_rng([seed, 0xC0])     # the driver's prompts
        limits = {k: float(spec[k])
                  for k in ("logit_margin", "logit_mean_margin") if k in spec}
        worst, over, every = [], [], []
        for n in spec["prompt_tokens"]:
            prompt = rng.integers(1, int(config["vocab_size"]),
                                  size=int(n)).tolist()
            seq = list(prompt)
            for _ in range(int(spec["new_tokens"])):
                seq.append(int(np.argmax(np.asarray(ref.logits(
                    params, config, seq, last=1,
                    precision=args.precision))[0])))
            gaps = ref.generated_gaps(params, config, prompt,
                                      seq[len(prompt):])
            worst.append(float(gaps.max()))
            over.append(int((gaps > limits.get("logit_margin",
                                               np.inf)).sum()))
            every.extend(gaps.tolist())
        mean = float(np.mean(every))
        print(json.dumps({
            "config": args.config, "seed": seed,
            "precision": args.precision,
            "prompt_tokens": spec["prompt_tokens"], "max_gap": worst,
            "mean_gap": mean, "tokens_over_margin": over,
            "of": int(spec["new_tokens"]), **limits,
            "correct": (max(worst) <= limits.get("logit_margin", np.inf)
                        and mean <= limits.get("logit_mean_margin",
                                               np.inf))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
