#!/usr/bin/env python3
"""Bring-up check: serve the full published internlm2-1.8b on one TPU
through the fabric's normal entry points, and check what comes back.

    python3 chip_smoke.py [--seed N]       # one chip
    python3 chip_smoke.py --chips 4        # the replica path only

One chip, in three phases:

1. serve: a proc-backend ``ColmenaQueues`` and one inference shard
   (``start_inference_shard``) whose engine is the full-width config
   (24 layers, d_model 2048, 16/8 heads, vocab 92544) with random
   weights from ``--seed``.  The shard runs with ``JAX_PLATFORMS=tpu``,
   so a chip that fails to initialise raises instead of falling back to
   the CPU.  Two rounds of the same 8 prompts (exactly the 128-token
   bucket, ``max_new`` 16): the first pays the shard's engine build and
   compiles, the second is warm.  Every result must succeed, hold 16
   in-vocab tokens and name a TPU as the device that computed it, and
   the rounds must agree.
2. the shard is stopped (``send_shard_stop``) and waited for.  Only then
   does this process initialise JAX and take the chip.
3. reference: the same prompts, as one unpadded batch, through the same
   bf16 prefill in-process must give the served first tokens, and its
   logits must agree with a float32 reference forward
   (``repro.models.reference``, matmul precision "highest") within
   ``LOGIT_TOL`` times the largest reference logit.

``--chips 4`` runs only the replica path and its comparison: the same
requests through four shards of one host behind one broker (each shard
confined to its own chip by ``chip_env``) and through one shard.  It
asserts four distinct chips and identical results.  Every micro-batch
holds one request there, so both runs execute the same batch-1 programs.

The last line of stdout is ``{"ok": true, "device": {...}}``; any
failure exits non-zero before printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "internlm2-1.8b"
N_PROMPTS = 8
PROMPT_LEN = 128
MAX_NEW = 16
#: bound on max |bf16 prefill logit - float32 reference logit| relative
#: to max |reference logit|.  On the CPU the bf16 error at full width
#: grows from 0.4% (1 layer) to 0.7% (4 layers) of that maximum.
LOGIT_TOL = 0.05
SERVE_TIMEOUT = 900.0                      # seconds, per round
LEASE_TIMEOUT = 120.0                      # covers a shard's compiles


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def make_prompts(vocab: int, seed: int) -> list:
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (N_PROMPTS, PROMPT_LEN)).tolist()


def serve_spec(reduced: bool, seed: int, max_batch: int):
    from repro.serving.shard import ServeSpec, default_engine_factory
    return ServeSpec(
        engine_factory=default_engine_factory(ARCH, reduced=reduced,
                                              seed=seed, max_new=MAX_NEW),
        max_batch=max_batch, prompt_buckets=(PROMPT_LEN,),
        # a round's requests form one micro-batch: flush on full only
        max_batch_delay_ms=10_000.0, max_new_cap=MAX_NEW)


def _check_parent_jax_free() -> None:
    # JAX reads JAX_PLATFORMS when it is imported: a shard forked from a
    # parent that imported it would ignore the env it is given
    _check("jax" not in sys.modules,
           "this process imported jax before forking its shards")


def serve_round(client, prompts: list, procs: list) -> list:
    """``InferenceClient`` submit + gather, failing as soon as a shard
    process has died instead of waiting out the timeout."""
    ids = client.submit(prompts, max_new=MAX_NEW)
    t_end = time.monotonic() + SERVE_TIMEOUT
    while True:
        try:
            return client.gather(ids, timeout=2.0)
        except TimeoutError:
            dead = [f"{p.name} (exit {p.exitcode})" for p in procs
                    if not p.is_alive()]
            _check(not dead, f"inference shard died: {', '.join(dead)}")
            if time.monotonic() > t_end:
                raise


def check_results(results: list, vocab: int, platform: str) -> list:
    """Every result succeeded with MAX_NEW in-vocab tokens, computed on
    ``platform``; returns the token lists."""
    out = []
    for i, r in enumerate(results):
        _check(r.success, f"request {i} failed in the shard: {r.error}")
        toks = list(r.value)
        _check(len(toks) == MAX_NEW,
               f"request {i}: {len(toks)} tokens, expected {MAX_NEW}")
        _check(all(0 <= t < vocab for t in toks),
               f"request {i}: token outside the vocab of {vocab}: {toks}")
        _check(r.device is not None
               and r.device["platform"] == platform,
               f"request {i} was served on {r.device}, not on {platform}")
        out.append(toks)
    return out


def serve_one_shard(prompts: list, *, reduced: bool, seed: int,
                    platform: str) -> tuple:
    """Phases 1 and 2: returns (token lists, the shard's device)."""
    from repro.configs.base import get_config
    from repro.core.queues import ColmenaQueues
    from repro.serving.shard import (InferenceClient, send_shard_stop,
                                     start_inference_shard, wait_for_exit)
    vocab = get_config(ARCH, reduced=reduced).vocab_size
    spec = serve_spec(reduced, seed, max_batch=N_PROMPTS)
    queues = ColmenaQueues([], backend="proc", lease_timeout=LEASE_TIMEOUT,
                           serve_spec=spec)
    proc = None
    try:
        _check_parent_jax_free()
        t0 = time.perf_counter()
        proc = start_inference_shard(
            queues.transport.address, spec, lease_timeout=LEASE_TIMEOUT,
            identity="infer@smoke:0", env={"JAX_PLATFORMS": platform})
        client = InferenceClient(queues)
        first = serve_round(client, prompts, [proc])
        t1 = time.perf_counter()
        warm = serve_round(client, prompts, [proc])
        t2 = time.perf_counter()
        toks = check_results(first, vocab, platform)
        _check(check_results(warm, vocab, platform) == toks,
               "the warm round served other tokens than the first")
        device = first[0].device
        print(f"shard device: {device}")
        print(f"shard first round: {t1 - t0:.3f} s wall (fork, engine"
              f" build, compiles, {N_PROMPTS} requests)")
        print(f"shard warm round: {t2 - t1:.3f} s wall,"
              f" {N_PROMPTS * MAX_NEW} tokens ({N_PROMPTS} x {MAX_NEW})")
        send_shard_stop(queues.transport, spec.topic)
        wait_for_exit(proc)
        return toks, device
    finally:
        if proc is not None and proc.is_alive():
            wait_for_exit(proc, timeout=0.0)
        queues.shutdown()


def check_against_reference(prompts: list, served: list, *, reduced: bool,
                            seed: int) -> None:
    """Phase 3, in this process: the served first tokens from the same
    bf16 prefill, and that prefill's logits against the float32
    reference."""
    import jax
    import numpy as np
    from repro.models import api, reference
    from repro.serving.shard import default_engine_factory

    t0 = time.perf_counter()
    engine = default_engine_factory(ARCH, reduced=reduced, seed=seed,
                                    max_new=MAX_NEW)()
    jax.block_until_ready(engine.params)
    t1 = time.perf_counter()
    cfg, params = engine.cfg, engine.params
    tokens = np.asarray(prompts, np.int32)           # one unpadded batch
    prefill = jax.jit(lambda p, t: api.prefill(p, cfg, {"tokens": t})[0])
    compiled = prefill.lower(params, tokens).compile()
    t2 = time.perf_counter()
    logits = np.asarray(compiled(params, tokens), np.float32)
    print(f"engine build: {t1 - t0:.3f} s; bf16 prefill compile:"
          f" {t2 - t1:.3f} s (B={N_PROMPTS}, S={PROMPT_LEN})")
    first = logits.argmax(-1).tolist()
    _check(first == [t[0] for t in served],
           f"served first tokens {[t[0] for t in served]} differ from the"
           f" in-process bf16 prefill's {first}")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: reference.last_logits(p, cfg, t))(
            params, tokens)
        ref = np.asarray(ref)
    err = float(np.max(np.abs(logits - ref)))
    scale = float(np.max(np.abs(ref)))
    print(f"max logit error vs float32 reference: {err:.6f}"
          f" (max |ref logit| {scale:.6f}, relative {err / scale:.6f},"
          f" tolerance {LOGIT_TOL})")
    _check(np.isfinite(logits).all() and np.isfinite(ref).all(),
           "non-finite logits")
    _check(err <= LOGIT_TOL * scale,
           f"bf16 prefill logits differ from the float32 reference by"
           f" {err:.4f} > {LOGIT_TOL} x {scale:.4f}")


def serve_replicas(prompts: list, n_shards: int, *, reduced: bool,
                   seed: int, platform: str) -> tuple:
    """One host, ``n_shards`` inference shards behind its broker, two
    rounds of the prompts.  Returns (token lists of the second round,
    the distinct chips that served any request)."""
    from repro.configs.base import get_config
    from repro.core.cluster import ClusterLauncher, ClusterSpec, HostSpec
    from repro.serving.shard import InferenceClient
    vocab = get_config(ARCH, reduced=reduced).vocab_size
    spec = ClusterSpec(
        [HostSpec("h0", inference_shards=n_shards, thinker=True,
                  env={"JAX_PLATFORMS": platform})],
        lease_timeout=LEASE_TIMEOUT)
    sspec = serve_spec(reduced, seed, max_batch=1)
    _check_parent_jax_free()
    t0 = time.perf_counter()
    with ClusterLauncher(spec, serve_spec=sspec) as lc:
        queues = lc.connect(serve_spec=sspec)
        try:
            client = InferenceClient(queues)
            # the first round warms every shard; the second finds them
            # all parked on the topic, so each one takes requests
            rounds = [serve_round(client, prompts, []) for _ in range(2)]
        finally:
            queues.shutdown()
    toks = [check_results(r, vocab, platform) for r in rounds]
    _check(toks[0] == toks[1], f"{n_shards} shard(s): the two rounds"
           " served different tokens")
    chips = {json.dumps(r.device, sort_keys=True) for r in sum(rounds, [])}
    print(f"{n_shards} shard(s): {time.perf_counter() - t0:.3f} s wall for"
          f" 2 x {N_PROMPTS} requests; chips: {sorted(chips)}")
    return toks[1], chips


def device_line(platform: str) -> dict:
    """Takes the chip for this process, once every shard has exited."""
    import jax
    from repro.utils.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_platforms", platform)
    devs = jax.devices()
    _check(devs[0].platform == platform,
           f"JAX reports {devs[0].platform}, not {platform}")
    return {"ok": True, "device": {"platform": devs[0].platform,
                                   "kind": devs[0].device_kind,
                                   "count": len(devs)}}


def run(*, chips: int, seed: int, reduced: bool = False,
        platform: str = "tpu") -> dict:
    """The whole check; ``reduced``/``platform`` let a CPU rehearsal
    drive the same code at a small size."""
    from repro.configs.base import get_config
    prompts = make_prompts(get_config(ARCH, reduced=reduced).vocab_size,
                           seed)
    if chips == 1:
        served, device = serve_one_shard(prompts, reduced=reduced, seed=seed,
                                         platform=platform)
        line = device_line(platform)
        _check(line["device"]["kind"] == device["kind"],
               f"served on {device['kind']}, but this process sees"
               f" {line['device']['kind']}")
        check_against_reference(prompts, served, reduced=reduced, seed=seed)
        return line
    many, chips_many = serve_replicas(prompts, chips, reduced=reduced,
                                      seed=seed, platform=platform)
    one, _ = serve_replicas(prompts, 1, reduced=reduced, seed=seed,
                            platform=platform)
    _check(many == one, f"{chips} shards served other tokens than 1 shard")
    _check(len(chips_many) == chips,
           f"{chips} shards ran on {len(chips_many)} distinct chip(s)")
    line = device_line(platform)
    _check(line["device"]["count"] == chips,
           f"{line['device']['count']} devices, expected {chips}")
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    line = run(chips=args.chips, seed=args.seed)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
