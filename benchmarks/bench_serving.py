"""Inference shard throughput: batched continuous serving vs. unbatched
one-Engine-call-per-task dispatch, through the same broker fabric.

Both arms fork a real shard process (``repro.serving.shard``) against a
proc-backend broker and drive N queued requests through it with the
``InferenceClient``; the only difference is ``ServeSpec.max_batch`` --
32 (pad-bounded micro-batches + continuous decode) vs. 1 (every request
is its own prefill + decode loop, the pre-shard dispatch pattern).  The
reported ``inference_tasks_per_sec`` therefore isolates exactly what the
subsystem claims: micro-batching amortizes the per-call engine overhead
(dispatch, launch, weight traffic) across the batch, on top of an
identical exactly-once transport.

The engine is the reduced reference model, built *inside* the shard
child (this parent process never imports jax).  A warmup wave per arm
pays the jit compiles before the clock starts, so the rows report warm
steady-state -- the same honesty rule as ``Engine.throughput()``.
"""
from __future__ import annotations

import time

PROMPT_BUCKETS = (16,)
MAX_NEW = 8


def _spec(max_batch: int):
    from repro.serving.shard import ServeSpec, default_engine_factory
    return ServeSpec(engine_factory=default_engine_factory(max_new=64),
                     max_batch=max_batch, prompt_buckets=PROMPT_BUCKETS,
                     max_batch_delay_ms=5.0, max_new_cap=64,
                     default_max_new=MAX_NEW)


def _prompts(n: int):
    # ragged lengths within one bucket: realistic padding, one prompt
    # executable shape per batch bucket
    return [[(i % 251) + 1] * (8 + i % 9) for i in range(n)]


def _run_arm(max_batch: int, n: int, timeout: float):
    """One shard, one client, N queued requests; returns tasks/sec."""
    from repro.core.queues import ColmenaQueues
    from repro.serving.shard import (InferenceClient, send_shard_stop,
                                     start_inference_shard, wait_for_exit)
    spec = _spec(max_batch)
    q = ColmenaQueues([], backend="proc", lease_timeout=60.0,
                      serve_spec=spec)
    proc = None
    try:
        proc = start_inference_shard(q.transport.address, spec,
                                     lease_timeout=60.0,
                                     identity=f"infer@bench:b{max_batch}")
        client = InferenceClient(q)
        # warmup: pays engine build + jit compile for every batch bucket
        # this arm can see (the prompt bucket and cache reserve are
        # constant here, so the executable key varies only by batch).
        # Ascending pow2 waves: even when arrival raggedness splits a
        # wave into partial batches, every piece's bucket is a size an
        # earlier wave already compiled
        b = 1
        while True:
            client.infer(_prompts(b), max_new=MAX_NEW, timeout=timeout)
            if b >= max_batch:
                break
            b = min(b * 2, max_batch)
        t0 = time.perf_counter()
        res = client.infer(_prompts(n), max_new=MAX_NEW, timeout=timeout)
        wall = time.perf_counter() - t0
        bad = [r for r in res if not r.success]
        assert not bad, bad[0].error
        return n / wall, wall
    finally:
        try:
            send_shard_stop(q.transport, spec.topic)
        except (ConnectionError, OSError):
            pass
        if proc is not None:
            # the next arm's shard needs the device this one holds
            wait_for_exit(proc)
        q.shutdown()


def run(n: int = 1000, timeout: float = 1200.0):
    """The acceptance configuration: N=1,000 queued requests, batched
    (max_batch=32) vs. unbatched (max_batch=1), expect >= 3x."""
    rows = []
    batched, wall_b = _run_arm(32, n, timeout=timeout)
    rows.append(("inference_tasks_per_sec[batched]", batched,
                 f"N={n}, max_batch=32, wall {wall_b:.1f}s"))
    unbatched, wall_u = _run_arm(1, n, timeout=timeout)
    rows.append(("inference_tasks_per_sec[unbatched]", unbatched,
                 f"N={n}, max_batch=1, wall {wall_u:.1f}s"))
    rows.append(("inference_batching_speedup", batched / unbatched,
                 "batched / unbatched, expect >= 3x"))
    return rows


def run_quick(n: int = 128, timeout: float = 600.0):
    """CI smoke subset: same two arms and row names at a size a shared
    runner finishes in minutes.  The speedup gate still applies -- the
    amortization claim does not need N=1,000 to show up."""
    return run(n=n, timeout=timeout)


def main(argv=None) -> int:
    import argparse
    import json

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("-N", type=int, default=None,
                   help="queued requests per arm (default: 128 quick,"
                        " 1000 full)")
    p.add_argument("--quick", action="store_true",
                   help="CI smoke size (N=128)")
    p.add_argument("--json", default="", metavar="PATH",
                   help="also write rows as JSON (name -> {value, note})")
    p.add_argument("--min-speedup", type=float, default=0.0, metavar="X",
                   help="fail (exit 1) if batched/unbatched < X")
    args = p.parse_args(argv)
    fn = run_quick if args.quick else run
    rows = fn(**({} if args.N is None else {"n": args.N}))
    for name, val, extra in rows:
        print(f"{name},{val:.2f},{extra}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({name: {"value": val, "note": extra}
                       for name, val, extra in rows}, f, indent=2)
    if args.min_speedup:
        speedup = next(v for name, v, _ in rows
                       if name == "inference_batching_speedup")
        if speedup < args.min_speedup:
            print(f"FAIL: batching speedup {speedup:.2f}x below the "
                  f"{args.min_speedup:.1f}x acceptance bound")
            return 1
        print(f"OK: batching speedup {speedup:.2f}x >= "
              f"{args.min_speedup:.1f}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
