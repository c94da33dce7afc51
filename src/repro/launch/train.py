"""Training driver: any assigned arch, checkpoint/restart, metrics.

Runs on whatever mesh is ambient -- the CPU host mesh for examples/smoke
and the production meshes on a real pod (same code path as the dry-run's
train program).  Demonstrates the fault-tolerance loop: async checkpoints
every --ckpt-every steps, `--resume` restores the newest valid checkpoint
and the deterministic step-keyed data stream realigns automatically.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ShardingConfig, TrainConfig, get_config
from repro.data.loader import PrefetchLoader
from repro.data.tokens import make_batch
from repro.launch import steps
from repro.utils.compile_cache import use_compile_cache


def train(arch: str, *, reduced: bool = True, steps_total: int = 50,
          batch: int = 8, seq: int = 128, lr: float = 3e-4,
          ckpt_dir: str = None, ckpt_every: int = 20, resume: bool = False,
          microbatches: int = 1, log_every: int = 10, seed: int = 0,
          stop_after: int = None, print_fn=print):
    """stop_after: interrupt the run after this step (fault-injection /
    resume tests) without changing the LR schedule, which is always derived
    from steps_total."""
    cfg = get_config(arch, reduced=reduced)
    tc = TrainConfig(lr=lr, warmup_steps=max(steps_total // 20, 1),
                     total_steps=steps_total, seed=seed)
    sc = ShardingConfig(microbatches=microbatches)

    state = steps.init_state(cfg, jax.random.PRNGKey(seed))
    start_step = 0
    manager = None
    if ckpt_dir:
        manager = CheckpointManager(ckpt_dir)
        if resume:
            s, restored = manager.restore(state)
            if s is not None:
                state, start_step = restored, s
                print_fn(f"resumed from checkpoint step {s}")

    step_fn = jax.jit(steps.make_train_step(cfg, tc, sc),
                      donate_argnums=(0,))

    def batch_fn(step):
        return make_batch(cfg, "train", batch, seq, step=step, seed=seed)

    loader = PrefetchLoader(batch_fn, start_step=start_step)
    losses = []
    stop_at = min(steps_total, stop_after) if stop_after else steps_total
    t0 = time.perf_counter()
    try:
        for step, host_batch in loader:
            if step >= stop_at:
                break
            jbatch = jax.tree.map(jax.numpy.asarray, host_batch)
            state, metrics = step_fn(state, jbatch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % log_every == 0 or step == steps_total - 1:
                dt = time.perf_counter() - t0
                print_fn(f"step {step:5d} loss {loss:8.4f} "
                         f"ce {float(metrics['ce']):8.4f} "
                         f"gnorm {float(metrics['grad_norm']):7.3f} "
                         f"lr {float(metrics['lr']):.2e} "
                         f"({dt:.1f}s)")
            if manager and ckpt_every and step and step % ckpt_every == 0:
                manager.save(step, state)
    finally:
        loader.close()
        if manager:
            manager.wait()
    if manager:
        manager.save(stop_at, state, blocking=True)
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="full published config (default: reduced)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    _, losses = train(args.arch, reduced=not args.full,
                      steps_total=args.steps, batch=args.batch, seq=args.seq,
                      lr=args.lr, microbatches=args.microbatches,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      resume=args.resume, seed=args.seed)
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    main()
