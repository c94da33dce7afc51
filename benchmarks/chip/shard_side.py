"""What runs inside the inference shard: the engine factory that
``ServeSpec.engine_factory`` calls there, and a monitor thread.

The factory applies the configuration file's sizes to the program's
config, makes the weights on the device from the seed in one jitted
call, builds the program's ``Engine`` and warms exactly the shapes the
cell's traffic can produce, through the engine's own calls.  It then
writes ``ready-<pid>.json`` into the run's control directory.

The monitor waits for the parent's ``window.json``; in a traced run it
takes a ``jax.profiler`` trace of the window's last ``trace_s`` seconds
from inside the shard and stops it there (the shard ends with
``os._exit``, so nothing at exit would).  Once the parent writes
``closed.json`` it records the compiles that fell inside the window, the
compaction gathers the window ran and the chip's peak memory in
``shard-<pid>.json``.

Fabric modules import no jax, so the shard's first jax import is here,
after its environment (``JAX_PLATFORMS``, ``chip_env``) is in place.
"""
from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path

from benchmarks.chip import families
from benchmarks.chip import weights as W

ANNOTATE = "bench."


def write_json(path: Path, obj: dict) -> None:
    """Written whole or not at all: the other side polls for it."""
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(obj))
    os.replace(tmp, path)


def wait_file(path: Path, poll: float = 0.01):
    while not path.exists():
        time.sleep(poll)
    return json.loads(path.read_text())


def program_config(c: dict):
    """The program's config for the configuration file's architecture,
    with every size as the file states it: the family's."""
    return families.of(c).program_config(c)


def make_params(cfg, c: dict, seed: int):
    """The weights, in the program's tree, made on the device in one
    jitted call.  Fails loudly where the program's tree is not the one
    ``weights`` makes."""
    import jax
    import jax.numpy as jnp
    from repro.models import api
    want = {p: ((layers,) if layers else ()) + s
            for p, (s, _, layers) in W.leaves(c).items()}
    have = {p: tuple(a.shape)
            for p, a in W.flatten(api.abstract_params(cfg)).items()}
    if have != want:
        raise ValueError(f"the program's parameter tree {have} is not the"
                         f" one the benchmark makes {want}")
    dtype = jnp.dtype(c["dtype"])
    return jax.jit(lambda k: W.as_tree(W.all_leaves(k, c, dtype)))(
        W.seed_key(seed))


def pow2_at_most(n: int, cap: int) -> int:
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


def warm_shapes(serve: dict, traffic: dict) -> list:
    """(batch, prompt bucket, reserve, decodes) for every micro-batch the
    traffic can make: batches are powers of two up to ``max_batch``, and
    a micro-batch reserves its bucket plus the power of two that covers
    the largest ``max_new`` among its rows."""
    batches, b = [], 1
    while b < serve["max_batch"]:
        batches.append(b)
        b <<= 1
    batches.append(serve["max_batch"])
    buckets = sorted(serve["prompt_buckets"])
    used = sorted({min(x for x in buckets if x >= s)
                   for s in traffic["prompt_len"]["values"]})
    news = sorted(set(traffic["max_new"]["values"]))
    return [(B, S, S + pow2_at_most(m, serve["max_new_cap"]), m > 1)
            for S in used for m in news for B in batches]


def warm(engine, shapes: list, vocab: int) -> int:
    """Drive every shape once through the engine's own calls: prefill
    (with its cache growth), a decode step, and the compaction gathers
    down to each smaller batch.  Returns the number of calls."""
    import numpy as np
    calls = 0
    batches = sorted({B for B, *_ in shapes})
    for B, S, R, decodes in shapes:
        tokens = (np.arange(B * S, dtype=np.int32).reshape(B, S) * 7919
                  ) % vocab
        _, state = engine.prefill_batch(tokens, reserve=R)
        calls += 1
        if not decodes:
            continue
        engine.decode_batch(state)
        calls += 1
        for b in batches:
            if b < B:
                engine.gather_rows(state, list(range(b)))
                calls += 1
    return calls


class CompileLog:
    """Compiles and persistent-cache reads, with their times."""

    def __init__(self):
        self.events = []

    def __call__(self, name, seconds, **kw):
        if "compile" in name or "cache_retrieval" in name:
            self.events.append((time.perf_counter(), name, float(seconds)))

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] <= t1]


def count_gathers(engine, times: list) -> None:
    """Appends the time of every compaction gather to ``times``."""
    gather = engine.gather_rows

    def gather_rows(state, rows):
        times.append(time.perf_counter())
        return gather(state, rows)

    engine.gather_rows = gather_rows


def real_rows(tokens) -> int:
    """Requests in a padded prompt matrix: its distinct rows that are
    not all padding (rows past the requests copy one of them)."""
    return len({tuple(r) for r in tokens.tolist() if any(r)})


def annotate(engine) -> None:
    """Host annotations around the engine's calls, named with their
    shapes, so the trace reduction can tie device time to each call.
    ``B`` is the batch the device runs, ``N`` the requests in it: those
    a group was prefilled or last compacted with (a row that finished
    without a compaction counts until the next)."""
    import jax
    prefill, decode, gather = (engine.prefill_batch, engine.decode_batch,
                               engine.gather_rows)
    rows = {}                                    # id(state) -> requests

    def prefill_batch(tokens, *, reserve=None, frames=None):
        B, S = tokens.shape
        N = real_rows(tokens)
        with jax.profiler.TraceAnnotation(
                f"{ANNOTATE}prefill:B={B}:N={N}:S={S}:R={reserve}"):
            first, state = prefill(tokens, reserve=reserve, frames=frames)
        rows[id(state)] = N
        return first, state

    def decode_batch(state):
        N = rows[id(state)]
        with jax.profiler.TraceAnnotation(
                f"{ANNOTATE}decode:B={state.padded_b}:N={N}"
                f":R={state.reserve}:P={state.pos}"):
            return decode(state)

    def gather_rows(state, idx):
        with jax.profiler.TraceAnnotation(
                f"{ANNOTATE}gather:B={state.padded_b}:to={len(idx)}"):
            out = gather(state, idx)
        rows.pop(id(state), None)
        rows[id(out)] = len(set(idx))
        return out

    engine.prefill_batch = prefill_batch
    engine.decode_batch = decode_batch
    engine.gather_rows = gather_rows


def monitor(ctl: Path, trace: bool, log: CompileLog, device,
            gathers: list) -> None:
    import jax
    w = wait_file(ctl / "window.json")
    pid = os.getpid()
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        time.sleep(max(w["trace_from"] - time.perf_counter(), 0.0))
        jax.profiler.start_trace(str(ctl / f"profile-{pid}"),
                                 profiler_options=opts)
        with jax.profiler.TraceAnnotation(f"{ANNOTATE}traced"):
            time.sleep(max(w["trace_to"] - time.perf_counter(), 0.0))
        jax.profiler.stop_trace()
    closed = wait_file(ctl / "closed.json")
    stats = device.memory_stats() or {}
    write_json(ctl / f"shard-{pid}.json", {
        "compiles_in_window": [e[1:] for e in log.between(w["t0"],
                                                           closed["t"])],
        "gathers_in_window": sum(w["t0"] <= t <= w["t_end"]
                                 for t in gathers),
        "memory_peak_bytes": stats.get("peak_bytes_in_use"),
    })


def factory(c: dict, traffic: dict, seed: int, ctl: str, *, trace: bool,
            platform: str, hook=None):
    """The ``ServeSpec.engine_factory`` of one run.  ``hook(engine)``
    (tests only) may replace the engine's calls after the warm-up."""
    def build():
        t0 = time.perf_counter()
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(log)
        device = jax.devices()[0]
        if device.platform != platform:
            raise RuntimeError(f"the shard found {device.platform}, not"
                               f" {platform}")
        from repro.serving.engine import Engine
        t1 = time.perf_counter()
        cfg = program_config(c)
        params = make_params(cfg, c, seed)
        jax.block_until_ready(params)
        t2 = time.perf_counter()
        engine = Engine(cfg, params, max_new=c["serve"]["max_new_cap"])
        calls = warm(engine, warm_shapes(c["serve"], traffic),
                     cfg.vocab_size)
        t3 = time.perf_counter()
        if hook is not None:
            hook(engine)
        gathers: list = []
        count_gathers(engine, gathers)
        if trace:
            annotate(engine)
        threading.Thread(target=monitor, args=(Path(ctl), trace, log,
                                               device, gathers),
                         daemon=True, name="bench-monitor").start()
        write_json(Path(ctl) / f"ready-{os.getpid()}.json", {
            "jax_init_s": t1 - t0, "weights_s": t2 - t1, "warm_s": t3 - t2,
            "warm_calls": calls, "compiles": len(log.events),
            "device": engine.device_info()})
        return engine
    return build
