"""Paper Fig. 5: median per-task lifecycle component times, with and
without the Value Server, for SynApp {T, D=0, I=1MB, O=0, N=8}."""
from __future__ import annotations

from repro.apps.synapp import SynConfig, run_synapp

COMPONENTS = ("serialize_request", "request_queue_transit",
              "serialize_result", "result_queue_transit",
              "deserialize_result", "proxy_put")


def _d0_rows(T: int, N: int):
    """True zero-length tasks with small inputs: measures the dispatch
    floor of the fabric itself (polling loops would show up here).  The
    backend dimension tracks the cross-process transport overhead
    trajectory: "local" is thread workers on in-process queues, "proc"
    is the paper's topology (broker-backed socket queues + worker OS
    processes).  Shared by the full run and the CI quick subset so the
    row names the bench-smoke gate matches on can never drift between
    them."""
    rows = []
    for backend in ("local", "proc"):
        res = run_synapp(SynConfig(T=T, D=0.0, I=1 << 10, O=0, N=N,
                                   use_value_server=False, backend=backend))
        suffix = "" if backend == "local" else f"[{backend}]"
        rows.append((f"d0_per_task_wall{suffix}",
                     res["per_task_wall"] * 1e6, f"n={res['n_results']}"))
        rows.append((f"d0_total_overhead{suffix}",
                     res["total_overhead_median"] * 1e6,
                     f"median lifecycle overhead at D=0, {backend} backend"))
    return rows


def run(T: int = 200, I: int = 1 << 20, N: int = 8, D: float = 0.005):
    """D is near-zero (paper: zero-length tasks) but non-zero so the
    single-CPU consumer thread keeps up and queue *waiting* (a container
    artifact) does not mask the serialization/transfer components."""
    rows = []
    for use_vs in (False, True):
        res = run_synapp(SynConfig(T=T, D=D, I=I, O=0, N=N,
                                   use_value_server=use_vs))
        tag = "vs" if use_vs else "novs"
        for comp in COMPONENTS:
            if comp in res["medians"]:
                rows.append((f"fig5_{tag}_{comp}",
                             res["medians"][comp] * 1e6, ""))
        rows.append((f"fig5_{tag}_total_overhead",
                     res["total_overhead_median"] * 1e6,
                     f"n={res['n_results']}"))
    # the paper's claim: VS reduces serialization+communication for 1MB
    novs = [r for r in rows if r[0] == "fig5_novs_total_overhead"][0][1]
    vs = [r for r in rows if r[0] == "fig5_vs_total_overhead"][0][1]
    rows.append(("fig5_vs_improvement_pct", 100.0 * (novs - vs) / novs,
                 "expect >0 at 1MB"))
    rows.extend(_d0_rows(T, N))
    rows.extend(_direct_rows(T, N))
    rows.extend(_trace_rows(T, N))
    # proc-backend 1MB row alongside the fig5 numbers: what crossing real
    # process boundaries (and the sharded VS) costs at the paper's I=1MB
    for use_vs in (False, True):
        res = run_synapp(SynConfig(T=T, D=D, I=I, O=0, N=N,
                                   use_value_server=use_vs, backend="proc"))
        tag = "vs" if use_vs else "novs"
        rows.append((f"fig5_{tag}_total_overhead[proc]",
                     res["total_overhead_median"] * 1e6,
                     f"n={res['n_results']}"))
    rows.extend(run_checkpoint_bench())
    rows.extend(run_device_array_bench())
    return rows


def _direct_rows(T: int, N: int, reps: int = 3):
    """Cluster D=0 with the Thinker homed away from the pools: this used
    to measure a per-frame relay hop (old bound: <=2x the single-broker
    floor).  With the direct-path data plane there is no hop any more --
    after a one-time ``endpoints`` discovery every submission and result
    dials the topic's home broker directly -- so remote placement should
    cost nothing.  The floor arm is the SAME 2-host fabric with the
    Thinker co-homed with its topic (every data-plane frame at one
    broker): same TCP sockets, same launcher, same process census --
    the only variable is the Thinker's placement, i.e. exactly what the
    direct path changed.  (Comparing against ``d0_per_task_wall[proc]``
    instead would smuggle in the unix-socket-vs-TCP-loopback tax of the
    single-host backend, which no data-plane design can remove.)  The
    ratio row is the CI acceptance gate (``--max-cluster-direct-ratio``,
    bound 1.1x): arms are interleaved and best-of-``reps`` so a load
    burst on a shared CI runner degrades both instead of poisoning
    whichever one it landed on."""
    floor_cfg = SynConfig(T=T, D=0.0, I=1 << 10, O=0, N=N,
                          use_value_server=False, cluster_hosts=2,
                          cluster_thinker_remote=False)
    direct_cfg = SynConfig(T=T, D=0.0, I=1 << 10, O=0, N=N,
                           use_value_server=False, cluster_hosts=2,
                           cluster_thinker_remote=True)
    floor_us = direct_us = None
    n_results = 0
    for _ in range(reps):
        f = run_synapp(floor_cfg)["per_task_wall"] * 1e6
        res = run_synapp(direct_cfg)
        d = res["per_task_wall"] * 1e6
        n_results = res["n_results"]
        floor_us = f if floor_us is None else min(floor_us, f)
        direct_us = d if direct_us is None else min(direct_us, d)
    return [("cluster_d0_direct_per_task_wall", direct_us,
             f"n={n_results}, best of {reps}, remote Thinker; co-homed "
             f"floor={floor_us:.0f}us on the same fabric"),
            ("cluster_d0_direct_ratio", direct_us / floor_us,
             "remote-Thinker wall / co-homed single-broker floor, same "
             f"2-host fabric (interleaved, best of {reps} each); "
             "acceptance <=1.1x")]


def _trace_rows(T: int, N: int, reps: int = 3):
    """What the tracing plane costs when it is ON: the same D=0
    proc-backend dispatch-floor config, one arm untraced, one arm at
    the *default* sampling rate (the shipped knob -- this ratio is the
    CI acceptance gate, ``--max-trace-overhead-ratio``, bound 1.05x),
    and one informational arm at ``trace_sample=1.0`` (every task
    emits its full span set through every hop -- the worst case, kept
    visible so a hot-path regression in the tracer shows up even when
    sampling hides it from the gate).  Arms are interleaved and
    best-of-``reps`` like the cluster ratio.  The obs env is scrubbed
    before the off arm because ``run_synapp`` exports it process-wide
    for the fabric's forked children."""
    import os
    import shutil
    import tempfile

    from repro import observability as obs
    from repro.observability import trace as obs_trace

    base = dict(T=T, D=0.0, I=1 << 10, O=0, N=N,
                use_value_server=False, backend="proc")
    off_us = dflt_us = full_us = None
    n_results = 0
    sink_root = tempfile.mkdtemp(prefix="repro-bench-obs-")

    def scrub():
        os.environ.pop(obs.ENV_DIR, None)
        os.environ.pop(obs.ENV_SAMPLE, None)
        obs_trace._T._pid = -1              # tracer re-reads the env

    try:
        for rep in range(reps):
            scrub()
            off = run_synapp(SynConfig(**base))["per_task_wall"] * 1e6
            scrub()
            res = run_synapp(SynConfig(
                **base, trace_sample=obs.DEFAULT_SAMPLE,
                trace_dir=f"{sink_root}/dflt{rep}"))
            dflt = res["per_task_wall"] * 1e6
            n_results = res["n_results"]
            scrub()
            full = run_synapp(SynConfig(
                **base, trace_sample=1.0,
                trace_dir=f"{sink_root}/full{rep}"))["per_task_wall"] * 1e6
            off_us = off if off_us is None else min(off_us, off)
            dflt_us = dflt if dflt_us is None else min(dflt_us, dflt)
            full_us = full if full_us is None else min(full_us, full)
    finally:
        scrub()
        shutil.rmtree(sink_root, ignore_errors=True)
    return [("d0_traced_per_task_wall[proc]", dflt_us,
             f"n={n_results}, default sampling "
             f"({obs.DEFAULT_SAMPLE:g}), best of {reps}; untraced "
             f"floor={off_us:.0f}us interleaved"),
            ("d0_trace_overhead_ratio", dflt_us / off_us,
             "default-sampling D=0 proc wall / untraced wall "
             f"(interleaved, best of {reps} each); acceptance <=1.05x"),
            ("d0_trace_overhead_ratio[full]", full_us / off_us,
             "trace_sample=1.0 wall / untraced wall -- informational "
             "worst case, not gated")]


def run_device_array_bench(mib: int = 8, reps: int = 5):
    """The zero-copy device-array lane: put/get roundtrip of a multi-MB
    array through a real shard process, typed ndcodec path vs a
    codec-off client (the old pickle path -- the formats self-describe,
    so both clients read the same shard).  The arms are interleaved and
    each takes its best of ``reps`` (after a warmup pass), so load
    drift degrades both equally instead of poisoning one."""
    import time

    import numpy as np

    from repro.core.transport.shards import ShardedValueServer

    nbytes = mib << 20

    def roundtrip(client):
        t0 = time.perf_counter()
        key = client.put(arr, sync=True)
        out = client.get(key)
        dt = time.perf_counter() - t0
        assert np.asarray(out).nbytes == nbytes
        client.delete(key)
        return dt * 1e3

    # fork the shard before this process touches the device: a forked
    # child inherits a runtime it cannot use
    vs = ShardedValueServer(1)
    try:
        import jax.numpy as jnp
        arr = jnp.arange(mib << 18, dtype=jnp.float32)     # mib MiB
        plain = ShardedValueServer.connect([a for _, a in vs._members],
                                           array_codec=False)
        roundtrip(vs), roundtrip(plain)            # warmup both arms
        t_codec = t_pickle = None
        for _ in range(reps):
            tc, tp = roundtrip(vs), roundtrip(plain)
            t_codec = tc if t_codec is None else min(t_codec, tc)
            t_pickle = tp if t_pickle is None else min(t_pickle, tp)
    finally:
        vs.shutdown()
    note = f"{mib}MiB jax array, best of {reps}"
    return [("vs_device_array_roundtrip_ms", t_codec, note),
            ("vs_device_array_roundtrip_pickle_ms", t_pickle, note),
            ("vs_device_array_codec_speedup", t_pickle / t_codec,
             "pickle-path roundtrip / typed-codec roundtrip; expect >1")]


def run_checkpoint_bench(n_envs: int = 500, env_bytes: int = 2048):
    """Cost of the exactly-once machinery's checkpoint path: snapshot +
    restore of a broker holding ``n_envs`` queued envelopes (the price a
    campaign pays per ``--checkpoint-every`` interval)."""
    import time

    from repro.core.transport import Envelope, make_transport
    from repro.utils.timing import now as tnow

    t = make_transport("proc")
    try:
        ch = t.channel("bench", "requests")
        payload = b"\0" * env_bytes
        for i in range(n_envs):
            ch.put(Envelope(tnow(), payload, {"task_id": str(i)}))
        t0 = time.perf_counter()
        snap = t.snapshot()
        t_snap = time.perf_counter() - t0
        t2 = make_transport("proc")
        try:
            t0 = time.perf_counter()
            t2.restore(snap)
            t_restore = time.perf_counter() - t0
        finally:
            t2.close()
    finally:
        t.close()
    note = f"{n_envs}x{env_bytes}B queued, {len(snap)}B snapshot"
    return [("ckpt_snapshot_ms", t_snap * 1e3, note),
            ("ckpt_restore_ms", t_restore * 1e3, note)]


def run_quick(T: int = 100, N: int = 8):
    """The CI smoke subset: the D=0 dispatch-floor rows on both
    backends, the direct-path cluster ratio and the trace-overhead
    ratio (the rows the bench-smoke gates bound -- a ratio of two
    interleaved walls is far less machine-sensitive than any
    absolute-ms floor), and the device-array roundtrip.  The fig5 /
    checkpoint sweeps still need a quiet machine and stay in the
    full run."""
    rows = _d0_rows(T, N)
    rows.extend(_direct_rows(T, N))
    rows.extend(_trace_rows(T, N))
    rows.extend(run_device_array_bench())
    return rows


def main(argv=None) -> int:
    """CLI for the CI bench-smoke job: run (optionally just the quick
    D=0 subset), write the rows as JSON, and fail when the local-backend
    dispatch floor exceeds the acceptance bound -- the first automated
    guard on the perf trajectory."""
    import argparse
    import json

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("-T", type=int, default=None,
                   help="tasks per config (default: 100 quick, 200 full --"
                        " the full default must track run()'s so bare"
                        " invocations stay comparable across PRs)")
    p.add_argument("--quick", action="store_true",
                   help="only the D=0 rows on both backends")
    p.add_argument("--json", default="", metavar="PATH",
                   help="also write rows as JSON (name -> {value_us, note})")
    p.add_argument("--max-d0-local-ms", type=float, default=0.0,
                   metavar="MS",
                   help="fail (exit 1) if d0_per_task_wall exceeds this")
    p.add_argument("--max-cluster-direct-ratio", type=float, default=0.0,
                   metavar="X",
                   help="fail (exit 1) if cluster_d0_direct_ratio (the "
                        "direct-path cluster wall over the single-broker "
                        "proc floor, same run) exceeds this")
    p.add_argument("--max-trace-overhead-ratio", type=float, default=0.0,
                   metavar="X",
                   help="fail (exit 1) if d0_trace_overhead_ratio (the "
                        "fully-traced D=0 proc wall over the untraced "
                        "wall, interleaved) exceeds this")
    args = p.parse_args(argv)
    if args.quick:
        rows = run_quick(**({} if args.T is None else {"T": args.T}))
    else:
        rows = run(**({} if args.T is None else {"T": args.T}))
    for name, val, extra in rows:
        print(f"{name},{val:.1f},{extra}")
    if args.json:
        # neutral "value": most rows are microseconds, but full runs
        # include e.g. fig5_vs_improvement_pct -- a unit-bearing key
        # would mislabel those for artifact consumers
        with open(args.json, "w") as f:
            json.dump({name: {"value": val, "note": extra}
                       for name, val, extra in rows}, f, indent=2)
    if args.max_d0_local_ms:
        d0_us = next(v for n, v, _ in rows if n == "d0_per_task_wall")
        bound_us = args.max_d0_local_ms * 1e3
        if d0_us > bound_us:
            print(f"FAIL: d0_per_task_wall {d0_us:.0f}us exceeds the "
                  f"{args.max_d0_local_ms:.1f}ms acceptance bound")
            return 1
        print(f"OK: d0_per_task_wall {d0_us:.0f}us within "
              f"{args.max_d0_local_ms:.1f}ms")
    if args.max_cluster_direct_ratio:
        ratio = next(v for n, v, _ in rows
                     if n == "cluster_d0_direct_ratio")
        if ratio > args.max_cluster_direct_ratio:
            print(f"FAIL: cluster_d0_direct_ratio {ratio:.2f}x exceeds "
                  f"the {args.max_cluster_direct_ratio:.2f}x acceptance "
                  "bound (direct path should sit on the single-broker "
                  "floor)")
            return 1
        print(f"OK: cluster_d0_direct_ratio {ratio:.2f}x within "
              f"{args.max_cluster_direct_ratio:.2f}x")
    if args.max_trace_overhead_ratio:
        ratio = next(v for n, v, _ in rows
                     if n == "d0_trace_overhead_ratio")
        if ratio > args.max_trace_overhead_ratio:
            print(f"FAIL: d0_trace_overhead_ratio {ratio:.2f}x exceeds "
                  f"the {args.max_trace_overhead_ratio:.2f}x acceptance "
                  "bound (full-sampling tracing should stay in the "
                  "dispatch-floor noise)")
            return 1
        print(f"OK: d0_trace_overhead_ratio {ratio:.2f}x within "
              f"{args.max_trace_overhead_ratio:.2f}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
