"""Batched serving example: the continuous engine over a queue of requests,
each slot refilled as its request finishes.

    PYTHONPATH=src python examples/serve_batched.py --arch yi-6b --requests 6
"""
import argparse

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.serve import Request, serve
from repro.models.model import Model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    reqs = [Request(i, list(rng.integers(0, cfg.vocab, args.prompt_len)),
                    args.max_new) for i in range(args.requests)]
    stats = serve(model, params, reqs, slots=args.slots,
                  cap=args.prompt_len + args.max_new + 2)
    for r in reqs[:3]:
        print(f"req {r.rid}: prompt {r.prompt[:6]}... -> {r.out}")
    print(f"{stats['tokens']} tokens, {stats['tok_per_s']:.1f} tok/s, "
          f"{stats['engine_steps']} engine steps")


if __name__ == "__main__":
    main()
