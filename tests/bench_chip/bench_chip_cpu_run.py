"""One run of a reduced cell on the CPU, in a fresh process, optionally
with a fault planted under the timed path.  Prints the result line.

    python tests/bench_chip/bench_chip_cpu_run.py [--fault NAME] \
        [--trace 1] [--control fp8] [--config kimi-k2-reduced]

``--config`` serves a test-only configuration of ``extra_families/``
(whose family module is found there) in place of the reduced InternLM2.

The run forks its shard; a process that has already started JAX's
runtime must not fork one, so tests start this script instead of calling
the runner in their own process.
"""
import time

T_START = time.perf_counter()

import argparse                                           # noqa: E402
import json                                               # noqa: E402
import sys                                                # noqa: E402
from pathlib import Path                                  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np                                        # noqa: E402

from benchmarks.chip import families, runner, spec        # noqa: E402

SMALL = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128,
             vocab_size=256)
SEED = 2**31 + 17
# between the bf16 program's widest gap at this size (0.012) and the
# float8 control's (0.136)
LIMIT = 0.06


EXTRA = Path(__file__).resolve().parent / "extra_families"


def small_cell(trace: bool, config=None) -> spec.Cell:
    if config is None:
        c = spec.config("internlm2-1.8b")
        c.update(SMALL)
    else:
        c = json.loads((EXTRA / f"{config}.json").read_text())
    c["serve"] = {"max_batch": 8, "max_batch_delay_ms": 20.0,
                  "max_new_cap": 16, "prompt_buckets": [16, 32]}
    t = {"generator": "open", "rate_per_s": 3.0, "burst": 4,
         "prompt_len": {"values": [16, 32], "p": [0.5, 0.5]},
         "max_new": {"values": [4, 12], "p": [0.5, 0.5]},
         "check": {"sample": 32, "logit_gap": LIMIT}}
    bench = spec.benchmark()
    return spec.Cell("small", 1, c, t, bench["end_to_end"],
                     bench["per_layer"] if trace else [])


def alter_tokens(engine):
    """Every decoded token is changed where it is produced."""
    decode = engine.decode_batch
    engine.decode_batch = lambda state: (decode(state) + 1) % 256


def keep_state(engine):
    """A decode step leaves the cache as it found it."""
    decode = engine.decode_batch

    def step(state):
        cache = state.cache
        out = decode(state)
        state.cache = cache
        return out
    engine.decode_batch = step


def wrong_rows(engine):
    """Compaction gathers the neighbouring rows."""
    gather = engine.gather_rows
    engine.gather_rows = lambda state, rows: gather(
        state, [(r + 1) % state.padded_b for r in rows])


def swap_requests(engine):
    """Each row continues its neighbour's prompt, so results reach the
    wrong requests."""
    import jax
    import jax.numpy as jnp
    prefill = engine.prefill_batch

    def roll(tokens, **kw):
        first, state = prefill(tokens, **kw)
        state.cache = jax.tree.map(lambda x: jnp.roll(x, 1, axis=1),
                                   state.cache)
        state.cur = jnp.roll(state.cur, 1, axis=0)
        return np.roll(first, 1), state
    engine.prefill_batch = roll


FAULTS = {f.__name__: f for f in (alter_tokens, keep_state, wrong_rows,
                                  swap_requests)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", choices=["fp8"])
    ap.add_argument("--config", choices=["kimi-k2-reduced"])
    args = ap.parse_args()
    if args.config:
        # the shard is forked from here and finds the family as this does
        families.__path__.append(str(EXTRA))
    result = runner.run("small", SEED, 2.0, bool(args.trace),
                        t_start=T_START, platform="cpu",
                        cell=small_cell(bool(args.trace), args.config),
                        hook=FAULTS.get(args.fault), control=args.control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
