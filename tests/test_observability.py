"""The tracing + metrics plane: sampling, sink round trips, clock-offset
chains, the Chrome-trace export, the Fig.-5 decomposition acceptance
check, meta namespacing, the bounded RateMeter, the span-name-registry
fabriclint pass, and chaos trace continuity (a SIGKILLed attempt leaves
an evidenced sub-trace; the winning attempt alone completes)."""
import json
import os
import signal
import textwrap
import threading
import time

import pytest

from repro import observability as obs
from repro.core import (ColmenaQueues, ProcessPoolTaskServer, TaskServer,
                        message as msg)
from repro.core.transport import Envelope
from repro.observability import metrics as obs_metrics
from repro.observability import trace as obs_trace
from repro.observability.report import (check_decomposition,
                                        decomposition_table, global_offsets,
                                        read_sinks, summarize_metrics,
                                        to_chrome)
from repro.utils.timing import RateMeter, now


@pytest.fixture
def obs_env(tmp_path, monkeypatch):
    """Point the (per-process, env-configured) tracer singleton at a
    fresh sink dir and reset it afterwards so other tests stay
    untraced."""
    monkeypatch.setenv(obs.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(obs.ENV_SAMPLE, "1.0")
    monkeypatch.delenv(obs.ENV_HOST, raising=False)
    obs_trace._T._pid = -1                  # force a re-read of the env
    obs_metrics.reset()
    yield tmp_path
    obs_trace._T._pid = -1                  # next use re-reads restored env
    obs_metrics.reset()


# ---------------------------------------------------------------------------
# RateMeter: bounded sliding window (the unbounded-events fix)
# ---------------------------------------------------------------------------

def test_rate_meter_window_is_bounded():
    m = RateMeter(window_events=16)
    for i in range(1000):
        m.add_busy(0.001)
    # cumulative totals cover the whole campaign ...
    assert m.count == 1000
    assert m.busy == pytest.approx(1.0)
    # ... but the per-event record is capped at the window
    assert len(m.events) == 16


def test_rate_meter_recent_rate():
    m = RateMeter(window_events=64)
    assert m.recent_rate() == 0.0           # no rate from a single event
    m.add_busy(0.0)
    assert m.recent_rate() == 0.0
    for _ in range(9):
        m.add_busy(0.0)
    # 10 events: rate = 9 / (t_last - t_first), positive and finite
    r = m.recent_rate()
    assert r > 0.0
    m2 = RateMeter(window_events=4)
    for _ in range(100):
        m2.add_busy(0.0)
    # the window's rate only looks at the retained 4 events
    assert m2.recent_rate() > 0.0
    assert len(m2.events) == 4


def test_rate_meter_utilization():
    m = RateMeter()
    m.add_busy(0.5)
    m.add_busy(0.5)
    u = m.utilization(capacity=2.0)
    assert 0.0 < u
    assert m.busy == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# meta namespacing: only meta["timers"] reaches Timer.intervals
# ---------------------------------------------------------------------------

def test_unknown_meta_key_never_lands_in_task_timer():
    """The grafting regression, closed structurally: a new top-level
    meta key (bookkeeping) must never be misrecorded as a lifecycle
    interval -- only the namespaced "timers" sub-dict is grafted."""
    q = ColmenaQueues(["t"])
    try:
        task = msg.Task(topic="t", method="m", args=(1,))
        data = msg.timed_serialize(task, task.timer, "serialize_request")
        env = Envelope(now(), data,
                       {"timers": {"serialize_request": 0.25},
                        "task_id": task.task_id,
                        "some_future_flag": 123,    # bookkeeping, not a timer
                        "redelivered": 2})
        decoded = q._decode_task(env)
        assert "some_future_flag" not in decoded.timer.intervals
        assert "redelivered" not in decoded.timer.intervals
        assert "task_id" not in decoded.timer.intervals
        assert decoded.timer.intervals["serialize_request"] >= 0.25
        # delivery-side trace context rides as attributes, not intervals
        assert decoded.attempt == 2
        assert decoded.trace is False
    finally:
        q.shutdown()


def test_unknown_meta_key_never_lands_in_result_timer():
    q = ColmenaQueues(["t"])
    try:
        q.send_task(0, method="m", topic="t")     # active-count balance
        result = msg.Result(task_id="tid-x", topic="t", method="m",
                            success=True, value=7)
        data = msg.serialize(result)
        env = Envelope(now(), data,
                       {"timers": {"serialize_result": 0.125},
                        "output_size": 4, "rogue": "nope"})
        decoded = q._decode_result(env)
        assert "rogue" not in decoded.timer.intervals
        assert "output_size" not in decoded.timer.intervals
        assert decoded.timer.intervals["serialize_result"] == 0.125
        assert decoded.output_size == 4
    finally:
        q.shutdown()


# ---------------------------------------------------------------------------
# tracer: sampling, addr forms, sink round trip
# ---------------------------------------------------------------------------

def test_sampling_deterministic_and_extremes(obs_env):
    assert obs.enabled()
    assert obs.sample_rate() == 1.0
    assert obs.sampled("any-task-at-rate-one")
    obs_trace._T.sample = 0.0
    assert not obs.sampled("any-task-at-rate-zero")
    obs_trace._T.sample = 0.5
    picks = {f"task-{i}": obs.sampled(f"task-{i}") for i in range(400)}
    assert any(picks.values()) and not all(picks.values())
    # deterministic: every hop hashing the same id gets the same verdict
    for tid, verdict in picks.items():
        assert obs.sampled(tid) == verdict


def test_disabled_tracer_is_inert(tmp_path, monkeypatch):
    monkeypatch.delenv(obs.ENV_DIR, raising=False)
    obs_trace._T._pid = -1
    try:
        assert not obs.enabled()
        assert not obs.sampled("anything")
        # span/instant/emit_timers are no-ops: nothing written anywhere
        obs.span("t", "execute", 0.0, 1.0)
        obs.instant("t", "task_started")
        obs.emit_timers("t", {"execute": 1.0})
        obs.flush_metrics(force=True)
        assert list(tmp_path.iterdir()) == []
    finally:
        obs_trace._T._pid = -1


def test_addr_str_canonical_forms():
    assert obs.addr_str(("unix", "/tmp/b.sock")) == "/tmp/b.sock"
    assert obs.addr_str(("127.0.0.1", 5123)) == "127.0.0.1:5123"
    assert obs.addr_str("/tmp/plain.sock") == "/tmp/plain.sock"
    assert obs.addr_str(b"/tmp/bytes.sock") == "/tmp/bytes.sock"


def test_sink_round_trip(obs_env):
    obs.configure(role="tester", host="hX", addr="brk:1", ref="",
                  offset=0.0)
    obs.span("tid1", "execute", 1.0, 2.0, attempt=1, worker="w0")
    obs.instant("tid1", "task_started", attempt=1)
    obs.emit_timers("tid1", {"execute": 1.0})
    obs.counter("tasks_completed").inc(3)
    obs.gauge("worker_busy_frac").set(0.5)
    obs.observe("infer_queue_delay", 0.002)
    obs.flush_metrics(force=True)
    procs, spans, timers, metrics = read_sinks(obs_env)
    (proc,) = [p for p in procs if p["role"] == "tester"]
    assert proc["host"] == "hX" and proc["addr"] == "brk:1"
    execute = [s for s in spans if s["name"] == "execute"]
    assert len(execute) == 1
    # annotated with the emitting proc's identity for clock alignment
    assert execute[0]["host"] == "hX" and execute[0]["role"] == "tester"
    assert execute[0]["attempt"] == 1
    assert execute[0]["args"]["worker"] == "w0"
    instants = [s for s in spans if s["kind"] == "instant"]
    assert len(instants) == 1 and instants[0]["name"] == "task_started"
    assert timers[0]["intervals"] == {"execute": 1.0}
    summary = summarize_metrics(metrics)
    assert summary["counters"]["tasks_completed"] == 3
    assert summary["gauges"]["worker_busy_frac"] == [0.5]


def test_read_sinks_skips_truncated_final_line(obs_env):
    head = json.dumps({"kind": "proc", "host": "h", "role": "r", "pid": 9,
                       "addr": "", "ref": "", "offset": 0.0, "t": 0.0})
    good = json.dumps({"kind": "span", "trace": "t", "name": "execute",
                       "t0": 0.0, "t1": 1.0})
    # a writer SIGKILLed mid-write leaves exactly one torn final line
    (obs_env / "spans-h-r-9.jsonl").write_text(
        head + "\n" + good + '\n{"kind": "span", "trace": "t2", "na')
    procs, spans, _, _ = read_sinks(obs_env)
    assert len(procs) == 1
    assert [s["trace"] for s in spans] == ["t"]


def test_metrics_registry_snapshot():
    obs_metrics.reset()
    try:
        obs.counter("redeliveries").inc()
        obs.counter("redeliveries").inc(4)
        obs.gauge("queue_depth").set(17)
        obs.observe("batch_occupancy", 0.75)
        obs.observe("batch_occupancy", 0.5)
        snap = obs.metrics_snapshot()
        assert snap["counters"]["redeliveries"] == 5
        assert snap["gauges"]["queue_depth"] == 17.0
        h = snap["histos"]["batch_occupancy"]
        assert h["count"] == 2 and h["sum"] == pytest.approx(1.25)
        assert sum(h["buckets"].values()) == 2
    finally:
        obs_metrics.reset()


# ---------------------------------------------------------------------------
# report: offset chains, Chrome export, decomposition check
# ---------------------------------------------------------------------------

def test_global_offsets_compose_along_ref_chain():
    procs = [
        {"host": "h0", "role": "broker", "pid": 1, "addr": "A",
         "ref": "", "offset": 0.0},                 # coordinator = root
        {"host": "h1", "role": "broker", "pid": 2, "addr": "B",
         "ref": "A", "offset": 1.0},                # member -> coordinator
        {"host": "h1", "role": "worker", "pid": 3, "addr": "",
         "ref": "B", "offset": 0.5},                # worker -> member
    ]
    offs = global_offsets(procs)
    assert offs[("h0", "broker", 1)] == 0.0
    assert offs[("h1", "broker", 2)] == 1.0
    assert offs[("h1", "worker", 3)] == pytest.approx(1.5)


def test_to_chrome_event_structure():
    procs = [{"host": "h0", "role": "worker", "pid": 7, "addr": "",
              "ref": "", "offset": 0.0}]
    spans = [{"kind": "span", "trace": "t1", "name": "execute",
              "t0": 10.0, "t1": 10.5, "host": "h0", "role": "worker",
              "pid": 7, "attempt": 1, "args": {"worker": "w"}},
             {"kind": "instant", "trace": "t1", "name": "task_started",
              "t": 10.0, "host": "h0", "role": "worker", "pid": 7}]
    doc = to_chrome(procs, spans)
    json.dumps(doc)                         # must be valid JSON end to end
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "h0/worker/7"
    (x,) = [e for e in events if e.get("ph") == "X"]
    assert x["name"] == "execute"
    assert x["dur"] == pytest.approx(0.5e6)     # microseconds
    assert x["ts"] >= 0.0                       # t_zero-normalized
    assert x["args"]["attempt"] == 1 and x["args"]["worker"] == "w"
    (i,) = [e for e in events if e.get("ph") == "i"]
    assert i["name"] == "task_started"


def test_check_decomposition_pass_and_fail():
    spans = [
        {"kind": "span", "trace": "ok", "name": "execute",
         "t0": 0.0, "t1": 0.050},
        {"kind": "span", "trace": "ok", "name": "serialize_request",
         "t0": 0.0, "t1": 0.010},
        # a non-mirrored span must not count toward the sum
        {"kind": "span", "trace": "ok", "name": "queue_wait",
         "t0": 0.0, "t1": 9.0},
        {"kind": "span", "trace": "drifted", "name": "execute",
         "t0": 0.0, "t1": 0.030},           # timer says 0.050: 40% drift
    ]
    timers = [
        {"kind": "timers", "trace": "ok",
         "intervals": {"execute": 0.050, "serialize_request": 0.010,
                       "proxy_put": 5.0}},  # non-mirrored interval ignored
        {"kind": "timers", "trace": "drifted",
         "intervals": {"execute": 0.050}},
        {"kind": "timers", "trace": "tiny",
         "intervals": {"execute": 0.001}},  # under 10ms: skipped as noise
    ]
    checked, failed, worst = check_decomposition(spans, timers,
                                                 max_drift=0.1)
    assert checked == 2
    assert failed == 1
    assert worst == pytest.approx(0.4)
    checked, failed, _ = check_decomposition(spans, timers, max_drift=0.5)
    assert checked == 2 and failed == 0


def test_decomposition_table_rows():
    spans = [{"kind": "span", "trace": "t", "name": "execute",
              "t0": 0.0, "t1": 0.5},
             {"kind": "span", "trace": "t", "name": "execute",
              "t0": 0.0, "t1": 1.5},
             {"kind": "instant", "trace": "t", "name": "task_started",
              "t": 0.0}]
    rows = decomposition_table(spans)
    assert [r[0] for r in rows] == ["execute"]  # instants excluded
    name, n, med, p90, tot = rows[0]
    assert n == 2 and tot == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# traced campaign end to end (local backend, single process)
# ---------------------------------------------------------------------------

def test_traced_local_campaign_decomposition(obs_env):
    q = ColmenaQueues(["t"], trace=1.0, trace_dir=str(obs_env))
    assert q.trace_dir == str(obs_env)
    server = TaskServer(q, workers_per_topic=2)
    server.register(lambda x: time.sleep(0.02) or x * 2, name="t")
    try:
        with server:
            for i in range(6):
                q.send_task(i, method="t", topic="t")
            got = []
            while len(got) < 6:
                r = q.get_result("t", timeout=20)
                assert r is not None and r.success
                got.append(r)
    finally:
        q.shutdown()
    procs, spans, timers, metrics = read_sinks(obs_env)
    assert len(timers) == 6                 # one Timer record per task
    # every task's span sum agrees with its envelope Timer totals
    checked, failed, worst = check_decomposition(spans, timers, 0.1)
    assert checked == 6, f"only {checked} tasks checkable"
    assert failed == 0, f"worst drift {worst:.1%}"
    by_name = {r[0] for r in decomposition_table(spans)}
    assert {"submit", "serialize_request", "queue_wait",
            "request_queue_transit", "execute", "serialize_result",
            "publish_result", "result_queue_transit",
            "deserialize_result"} <= by_name


def test_trace_off_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv(obs.ENV_DIR, raising=False)
    monkeypatch.delenv(obs.ENV_SAMPLE, raising=False)
    obs_trace._T._pid = -1
    q = ColmenaQueues(["t"])
    try:
        assert q.trace_dir == ""
        server = TaskServer(q, workers_per_topic=2)
        with server:
            q.send_task(1, method="t", topic="t")
            server.register(lambda x: x, name="t")
            q.send_task(2, method="t", topic="t")
            r = q.get_result("t", timeout=20)
            assert r is not None
    finally:
        q.shutdown()
        obs_trace._T._pid = -1
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------------
# fabriclint: the span-name-registry pass
# ---------------------------------------------------------------------------

def _lint(tmp_path, source):
    from repro.analysis import fabriclint
    f = tmp_path / "mod_under_lint.py"
    f.write_text(textwrap.dedent(source))
    return fabriclint.run([f], passes=["span-name-registry"])


def test_span_name_registry_declared_names_pass(tmp_path):
    findings = _lint(tmp_path, """\
        from repro import observability as obs

        def f(tid):
            obs.span(tid, "execute", 0.0, 1.0)
            obs.instant(tid, "task_started")
            obs.counter("tasks_completed").inc()
            obs.gauge("queue_depth").set(3)
            obs.observe("infer_queue_delay", 0.01)
    """)
    assert findings == []


def test_span_name_registry_flags_undeclared_name(tmp_path):
    findings = _lint(tmp_path, """\
        from repro import observability as obs

        def f(tid):
            obs.span(tid, "execuet", 0.0, 1.0)
            obs.counter("tasks_compelted").inc()
    """)
    assert len(findings) == 2
    assert all(f.pass_name == "span-name-registry" for f in findings)
    assert "execuet" in findings[0].message
    assert "names.py" in findings[0].message


def test_span_name_registry_flags_dynamic_name(tmp_path):
    findings = _lint(tmp_path, """\
        from repro import observability as obs

        def f(tid, which):
            obs.span(tid, "stage_" + which, 0.0, 1.0)
    """)
    assert len(findings) == 1
    assert "non-literal" in findings[0].message


def test_span_name_registry_checks_region_names(tmp_path):
    findings = _lint(tmp_path, """\
        from repro import observability as obs

        def f(which):
            with obs.region("serve.step", B=1, N=1, P=8):
                pass
            with obs.region("serve.stpe"):
                pass
            with obs.region("serve." + which):
                pass
    """)
    assert len(findings) == 2
    assert all(f.pass_name == "span-name-registry" for f in findings)
    assert "serve.stpe" in findings[0].message
    assert "REGION_NAMES" in findings[0].message
    assert "non-literal" in findings[1].message


def test_span_name_registry_ignores_other_receivers(tmp_path):
    # Timer.span and arbitrary .counter attributes are not obs calls
    findings = _lint(tmp_path, """\
        class Timer:
            def span(self, name, a, b):
                pass

        def f(timer, db):
            timer.span("not_a_span_name", "m0", "m1")
            db.counter("whatever").inc()
    """)
    assert findings == []


def test_fabric_instrumentation_is_registry_clean():
    """The live instrumentation in core/** and serving/** must satisfy
    its own lint pass (the satellite's enforcement, self-applied)."""
    from repro.analysis import fabriclint
    findings = fabriclint.run(list(fabriclint.DEFAULT_TARGETS),
                              passes=["span-name-registry"])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_clock_ops_are_registered_idempotent():
    from repro.analysis.idempotent_ops import IDEMPOTENT_OPS
    assert "clock_sync" in IDEMPOTENT_OPS
    assert "stats_scrape" in IDEMPOTENT_OPS


# ---------------------------------------------------------------------------
# live scrape: the stats_scrape broker op
# ---------------------------------------------------------------------------

def test_stats_scrape_reports_depth_and_inflight(obs_env):
    from repro.observability.monitor import scrape_address
    q = ColmenaQueues(["t"], backend="proc", trace=1.0,
                      trace_dir=str(obs_env))
    try:
        for i in range(3):
            q.send_task(i, method="t", topic="t")
        stats = scrape_address(q.transport.address)
        assert stats["queue_depth"]["t/requests"] == 3
        assert stats["inflight_leases"]["t/requests"] == 0
        assert "metrics" in stats and "pid" in stats
        # lease one batch WITHOUT acking: depth drops, inflight rises
        envs = q._topics["t"].requests.get_batch(2, timeout=5)
        assert len(envs) == 2
        stats = scrape_address(q.transport.address)
        assert stats["queue_depth"]["t/requests"] == 1
        assert stats["inflight_leases"]["t/requests"] == 2
        # a full drain-and-ack releases the leases again
        q._topics["t"].requests.ack(flush=True)
        stats = scrape_address(q.transport.address)
        assert stats["inflight_leases"]["t/requests"] == 0
    finally:
        q.shutdown()


def test_clock_sync_roundtrip_small_offset(obs_env):
    q = ColmenaQueues(["t"], backend="proc")
    try:
        offset = obs.calibrate(q.transport.clock_sync)
        # same machine, same CLOCK_MONOTONIC: the offset is bounded by
        # the roundtrip (generous slack for a loaded CI box)
        assert abs(offset) < 0.5
    finally:
        q.shutdown()


# ---------------------------------------------------------------------------
# chaos: trace continuity across SIGKILL (proc backend)
# ---------------------------------------------------------------------------

def _pid_of(identity):
    return int(identity.rsplit("/pid", 1)[1])


@pytest.mark.slow
def test_worker_sigkill_leaves_two_attempt_subtraces(obs_env):
    """Kill a worker mid-execute: the dead attempt's sub-trace ends at
    its ``task_started`` instant (flushed to the O_APPEND sink within
    one flusher period of execute starting), the redelivery runs as
    attempt 1, and exactly one attempt publishes."""
    q = ColmenaQueues(["t"], backend="proc", lease_timeout=1.0,
                      trace=1.0, trace_dir=str(obs_env))
    pool = ProcessPoolTaskServer(q, workers_per_topic=2)

    def slow(x):
        time.sleep(0.6)
        return (os.getpid(), x)

    pool.register(slow, name="t")
    try:
        with pool:
            tid = q.send_task(7, method="t", topic="t")
            deadline = time.time() + 10
            while not pool.task_history.get(tid) and time.time() < deadline:
                time.sleep(0.01)
            history = pool.task_history.get(tid)
            assert history, "task never started"
            victim = _pid_of(history[0])
            # the contract: crash evidence survives for any execution
            # longer than one flush period.  Give the victim's flusher
            # two periods to land task_started, then kill mid-execute
            # (the task sleeps 0.6s) with the lease still unacked.
            time.sleep(2.5 * obs_trace.FLUSH_SECONDS)
            os.kill(victim, signal.SIGKILL)
            r = q.get_result("t", timeout=30)
            assert r is not None and r.success
            assert r.value[0] != victim
            assert q.get_result("t", timeout=1.5) is None
    finally:
        q.shutdown()
    _, spans, timers, _ = read_sinks(obs_env)
    mine = [s for s in spans if s.get("trace") == tid]
    assert mine, "no spans for the traced task"
    started = [s for s in mine if s["name"] == "task_started"]
    attempts = {s.get("attempt", 0) for s in started}
    # one sub-trace per delivery attempt: the killed original (0) and
    # the lease-expiry redelivery (1).  A loaded box may expire the
    # lease again mid-retry and add further attempts; 0 and 1 are the
    # guaranteed floor.
    assert {0, 1} <= attempts, f"attempt instants: {sorted(attempts)}"
    # the SIGKILLed attempt 0 never closed its execute span: every
    # completing execute belongs to a redelivery
    execs = [s for s in mine
             if s["name"] == "execute" and s["kind"] == "span"]
    assert execs and all(e.get("attempt", 0) >= 1 for e in execs)
    # exactly one publish won the first-completion claim fabric-wide
    pubs = [s for s in mine if s["name"] == "publish_result"]
    claimed = [p for p in pubs if (p.get("args") or {}).get("claimed")]
    assert len(claimed) == 1, f"{len(claimed)} claimed of {len(pubs)}"
    # the consumer-side Timer record exists for the winning attempt
    assert [t for t in timers if t["trace"] == tid]


@pytest.mark.slow
def test_shard_sigkill_leaves_no_orphan_traces(obs_env):
    """Kill an inference shard mid-campaign with tracing on: every
    sampled request's trace still reaches a result (redelivery to the
    replacement shard), so no trace dangles without completion spans."""
    from repro.serving.shard import (InferenceClient, ServeSpec,
                                     start_inference_shard)
    from tests.test_serving_shard import _slow_stub_factory

    spec = ServeSpec(engine_factory=_slow_stub_factory, max_batch=4,
                     prompt_buckets=(8,), max_batch_delay_ms=5.0)
    q = ColmenaQueues([], backend="proc", lease_timeout=1.0,
                      serve_spec=spec, trace=1.0, trace_dir=str(obs_env))
    procs = []
    try:
        procs.append(start_inference_shard(
            q.transport.address, spec, lease_timeout=1.0,
            identity="infer@chaos:0"))
        client = InferenceClient(q)
        tids = client.submit([[i + 1, i + 2] for i in range(12)],
                             max_new=6)
        got: dict = {}
        deadline = time.time() + 30
        while not got and time.time() < deadline:
            for r in q.get_results(spec.topic, max_n=64, timeout=0.5):
                got.setdefault(r.task_id, []).append(r)
        assert got, "shard produced nothing before the kill"
        assert len(got) < 12, "campaign finished before the kill"
        os.kill(procs[0].pid, signal.SIGKILL)
        procs[0].join(timeout=5)
        procs.append(start_inference_shard(
            q.transport.address, spec, lease_timeout=1.0,
            identity="infer@chaos:1"))
        deadline = time.time() + 60
        while len(got) < 12 and time.time() < deadline:
            for r in q.get_results(spec.topic, max_n=64, timeout=0.5):
                got.setdefault(r.task_id, []).append(r)
        assert sorted(got) == sorted(tids)
        assert not {t: len(rs) for t, rs in got.items() if len(rs) > 1}
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=3)
        q.shutdown()
    _, spans, _, _ = read_sinks(obs_env)
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s.get("trace"), []).append(s)
    # every request trace completed: a result_queue_transit span exists
    # (the thinker decoded exactly one result per id), so nothing is
    # orphaned at the dead shard's in-flight point
    for tid in tids:
        names = {s["name"] for s in by_trace.get(tid, [])}
        assert "result_queue_transit" in names, (
            f"trace {tid} dangles with only {sorted(names)}")
        # exactly one claimed retirement fabric-wide per id
        claimed = [s for s in by_trace[tid]
                   if s["name"] == "retire"
                   and (s.get("args") or {}).get("claimed")]
        assert len(claimed) <= 1
    # no spans for ids the campaign never issued (stop markers etc. are
    # untraced control traffic)
    assert set(by_trace) <= set(tids)
