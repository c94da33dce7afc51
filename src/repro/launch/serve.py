"""Serving driver: batched generation with the KV-cache engine.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --batch 4 --prompt-len 64 --max-new 16
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import get_config
from repro.models import api
from repro.serving.engine import Engine
from repro.utils.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--requests", type=int, default=3,
                    help="number of batched request rounds")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, reduced=not args.full)
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = Engine(cfg, params, max_new=args.max_new)

    rng = np.random.default_rng(args.seed)
    for r in range(args.requests):
        prompts = rng.integers(0, cfg.vocab_size,
                               size=(args.batch, args.prompt_len),
                               dtype=np.int32)
        out = engine.generate(prompts)
        print(f"round {r}: in {prompts.shape} -> out {out.shape}, "
              f"sample tail: {out[0, -8:].tolist()}")
    print(f"steady-state throughput: {engine.throughput():.1f} tok/s "
          f"(prefills={engine.stats['prefill_calls']}, "
          f"decode_steps={engine.stats['decode_steps']}, "
          f"compile {engine.stats['compile_wall']:.2f}s excluded)")


if __name__ == "__main__":
    main()
