"""Multi-host cluster fabric: spec/partition agreement, broker
federation (cross-broker routing, leases and claims through the relay,
bundled snapshots), topology-aware straggler placement, the launcher's
simulated hosts, and kill-one-host chaos."""
import os
import signal
import threading
import time

import pytest

from repro.core import ColmenaQueues, ProcessPoolTaskServer
from repro.core.cluster import ClusterLauncher, ClusterSpec, HostSpec
from repro.core.cluster.spec import resolve_home
from repro.core.process_pool import dispatch_topic, host_of
from repro.core.transport import Envelope
from repro.core.transport.proc import ProcTransport
from repro.utils.timing import now


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------

def test_spec_validation_and_partition():
    spec = ClusterSpec([
        HostSpec("h0", pools={"simulate": 2}, thinker=True),
        HostSpec("h1", pools={"simulate": 2, "train": 1}),
        HostSpec("h2", broker=False, pools={"train": 1}),
    ])
    assert spec.broker_hosts == ["h0", "h1"]
    assert spec.coordinator == "h0"
    assert spec.thinker_host == "h0"
    # topic homed with its first broker-running pool host
    part = spec.partition()
    assert part == {"simulate": "h0", "train": "h1"}
    # pool channels home at their host's broker; a brokerless host's
    # channels land deterministically on some member
    assert resolve_home(dispatch_topic("h1", "simulate"), part,
                        spec.broker_hosts) == "h1"
    assert resolve_home(dispatch_topic("h2", "train"), part,
                        spec.broker_hosts) in spec.broker_hosts
    assert spec.pool_hosts("train") == ["h1", "h2"]
    with pytest.raises(ValueError, match="duplicate"):
        ClusterSpec([HostSpec("a"), HostSpec("a")])
    with pytest.raises(ValueError, match="broker"):
        ClusterSpec([HostSpec("a", broker=False)])
    with pytest.raises(ValueError, match="without brokers"):
        ClusterSpec([HostSpec("a"), HostSpec("b", broker=False)],
                    partition={"t": "b"})
    with pytest.raises(ValueError, match="host name"):
        ClusterSpec([HostSpec("a/b")])
    # explicit overrides win
    spec2 = ClusterSpec([HostSpec("h0", pools={"t": 1}), HostSpec("h1")],
                        partition={"t": "h1"})
    assert spec2.partition()["t"] == "h1"


# ---------------------------------------------------------------------------
# federation (broker-only launchers: the relay layer in isolation)
# ---------------------------------------------------------------------------

@pytest.fixture
def federation():
    """Two federated brokers; topic "t" homed at h1, so every h0-client
    frame for it crosses the relay."""
    spec = ClusterSpec([HostSpec("h0"), HostSpec("h1")],
                       partition={"t": "h1"}, lease_timeout=0.5)
    lc = ClusterLauncher(spec).start()
    transports = []

    def dial(host):
        t = ProcTransport(address=lc.address_of(host), lease_timeout=0.5)
        transports.append(t)
        return t

    yield lc, dial
    lc.stop()


def test_cross_broker_routing_roundtrip(federation):
    lc, dial = federation
    t0, t1 = dial("h0"), dial("h1")
    ch0 = t0.channel("t", "requests")
    ch0.put(Envelope(now(), b"payload", {"task_id": "a"}))  # relayed
    # both members see the same queue (h1 owns it; h0 relays the len)
    assert len(ch0) == 1
    assert len(t1.channel("t", "requests")) == 1
    env = ch0.get(timeout=2)            # leased dequeue through the relay
    assert env is not None and env.data == b"payload"
    assert env.meta["task_id"] == "a"
    ch0.ack(flush=True)                 # ack routes home by topic
    time.sleep(0.7)                     # well past lease_timeout
    assert ch0.get(timeout=0.3) is None  # acked: never redelivered


def test_lease_expiry_redelivers_through_relay(federation):
    lc, dial = federation
    ch = dial("h0").channel("t", "requests")
    ch.put(Envelope(now(), b"x", {"task_id": "b"}))
    got = []
    th = threading.Thread(target=lambda: got.extend(
        ch.get_batch(1, timeout=2)))
    th.start()
    th.join()                           # thread dies holding the lease
    assert len(got) == 1
    env = ch.get(timeout=3)             # expiry runs at the home broker
    assert env is not None and env.meta["redelivered"] == 1
    ch.ack(flush=True)


def test_put_claim_dedups_across_members(federation):
    lc, dial = federation
    ch0 = dial("h0").channel("t", "results")
    ch1 = dial("h1").channel("t", "results")
    # two publishers racing through *different* local brokers arbitrate
    # at the topic's home
    assert ch0.put(Envelope(now(), b"win", {}), claim="tid-1") is True
    assert ch1.put(Envelope(now(), b"lose", {}), claim="tid-1") is False
    assert len(ch0) == 1
    assert ch0.get(timeout=1).data == b"win"
    ch0.ack(flush=True)


def test_federated_snapshot_restore_bundle(federation):
    lc, dial = federation
    t0 = dial("h0")
    reqs = t0.channel("t", "requests")          # homed h1
    local = t0.channel("elsewhere", "requests")  # hashed somewhere
    for i in range(3):
        reqs.put(Envelope(now(), b"task%d" % i, {"task_id": str(i)}))
    local.put(Envelope(now(), b"other", {"task_id": "z"}))
    t0.channel("t", "results").put(Envelope(now(), b"done", {}),
                                   claim="done-id")
    snap = t0.snapshot()

    spec2 = ClusterSpec([HostSpec("h0"), HostSpec("h1")],
                        partition={"t": "h1"}, lease_timeout=0.5)
    with ClusterLauncher(spec2).start() as lc2:
        t2 = ProcTransport(address=lc2.address_of("h0"), lease_timeout=0.5)
        t2.restore(snap)
        # identical federation state -> identical bundle bytes
        assert t2.snapshot() == snap
        assert len(t2.channel("t", "requests")) == 3
        assert len(t2.channel("elsewhere", "requests")) == 1
        assert len(t2.channel("t", "results")) == 1
        # the claim window restored at the topic's home still dedups
        assert t2.channel("t", "results").put(
            Envelope(now(), b"dup", {}), claim="done-id") is False
        t2.client.close()


# ---------------------------------------------------------------------------
# topology-aware straggler placement (two pools, one shared broker)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cross_host_backup_lands_on_other_host():
    queues = ColmenaQueues(["t"], backend="proc", lease_timeout=5.0)

    def task(x):
        time.sleep(x)
        return os.getpid()

    pools = []
    try:
        for host in ("hA", "hB"):
            pool = ProcessPoolTaskServer(
                queues, workers_per_topic=1, host=host,
                backup_hosts={"t": [h for h in ("hA", "hB") if h != host]},
                straggler_factor=3.0, straggler_min_history=1)
            pool.register(task, name="t")
            pools.append(pool)
        for pool in pools:
            pool.start()
        # warm the runtime history of BOTH hosts (whichever host holds
        # the slow task needs history for its monitor to fire)
        warm = 0
        deadline = time.time() + 20
        while (any(not p._runtimes.get("t") for p in pools)
               and time.time() < deadline):
            queues.send_task(0.0, method="t", topic="t")
            warm += 1
            queues.get_result("t", timeout=10)
        assert all(p._runtimes.get("t") for p in pools), "warmup starved"
        tid = queues.send_task(1.2, method="t", topic="t")
        r = queues.get_result("t", timeout=30)
        assert r is not None and r.success
        # exactly one pool (the origin's) fired a backup...
        firing = [p for p in pools if tid in p.backup_targets]
        assert len(firing) == 1, "straggler backup never fired"
        origin_pool = firing[0]
        target = origin_pool.backup_targets[tid]
        # ...and placed it on the other host
        assert target != origin_pool.host
        # the backup demonstrably *started* on the other host
        other_pool = next(p for p in pools if p is not origin_pool)
        hist_dl = time.time() + 10
        while (not other_pool.task_history.get(tid)
               and time.time() < hist_dl):
            time.sleep(0.05)
        backup_starts = other_pool.task_history.get(tid, [])
        assert backup_starts, "backup never started on the peer host"
        assert all(host_of(i) == other_pool.host for i in backup_starts)
        # exactly-once completion despite the race
        assert queues.get_result("t", timeout=1.0) is None
        assert queues.active_count == 0
    finally:
        for pool in pools:
            pool.stop()
        queues.shutdown()


# ---------------------------------------------------------------------------
# launcher: 2 simulated hosts end to end
# ---------------------------------------------------------------------------

def _times_ten(x):
    time.sleep(0.05)
    return x * 10


@pytest.mark.slow
def test_two_host_campaign_exactly_once():
    spec = ClusterSpec([
        HostSpec("h0", pools={"t": 2}, thinker=True),
        HostSpec("h1", pools={"t": 2}),
    ], lease_timeout=5.0)
    with ClusterLauncher(spec,
                         methods=[(_times_ten, {"topic": "t",
                                                "name": "t"})]) as lc:
        queues = lc.connect()
        try:
            values = {}
            submitted = [queues.send_task(i, method="t", topic="t")
                         for i in range(24)]
            for i, tid in enumerate(submitted):
                values[tid] = i * 10
            results = {}
            workers = set()
            for _ in submitted:
                r = queues.get_result("t", timeout=60)
                assert r is not None and r.success, r and r.error
                assert r.task_id not in results, "duplicate completion"
                results[r.task_id] = r.value
                workers.add(host_of(r.worker))
            # keep the campaign going until BOTH hosts have won work (a
            # scheduler can let one host's intake start first; a healthy
            # peer pool must still win leases well before the deadline)
            deadline = time.time() + 60
            extra = 24
            while workers != {"h0", "h1"} and time.time() < deadline:
                tid = queues.send_task(extra, method="t", topic="t")
                submitted.append(tid)
                values[tid] = extra * 10
                extra += 1
                r = queues.get_result("t", timeout=60)
                assert r is not None and r.success
                assert r.task_id not in results, "duplicate completion"
                results[r.task_id] = r.value
                workers.add(host_of(r.worker))
            assert workers == {"h0", "h1"}, f"a host never won work: {workers}"
            assert set(results) == set(submitted)   # exactly-once, zero lost
            for tid, want in values.items():
                assert results[tid] == want
            # nothing else ever arrives; the campaign is quiescent
            assert queues.get_result("t", timeout=1.0) is None
            assert queues.active_count == 0
        finally:
            queues.shutdown()


def _slow_sim(x):
    time.sleep(0.5)
    return x + 1000


@pytest.mark.slow
def test_kill_one_host_redelivers_to_survivor():
    """Node-loss chaos: SIGKILL one host's whole pool process group
    mid-campaign.  Its queued dispatch envelopes are rescued back to the
    global topic, its in-flight leases expire into the same drain, and
    the surviving host finishes the campaign -- zero lost, zero
    duplicated.  The kill lands while every task is still executing or
    queued (tasks take 0.5 s; we kill at 0.2 s), so *every* completion
    must come from the survivor."""
    spec = ClusterSpec([
        HostSpec("h0", pools={"t": 2}, thinker=True),
        HostSpec("h1", pools={"t": 2}),
    ], lease_timeout=1.0)
    with ClusterLauncher(spec,
                         methods=[(_slow_sim, {"topic": "t",
                                               "name": "t"})]) as lc:
        queues = lc.connect()
        try:
            submitted = [queues.send_task(i, method="t", topic="t")
                         for i in range(14)]
            # let both hosts lease work, but kill before any 0.5s task
            # can possibly have completed
            time.sleep(0.2)
            lc.kill_host("h1")
            results = {}
            for _ in submitted:
                r = queues.get_result("t", timeout=60)
                assert r is not None and r.success, r and r.error
                assert r.task_id not in results, "duplicate completion"
                # the victim died pre-completion: only the survivor wins
                assert host_of(r.worker) == "h0"
                results[r.task_id] = r.value
            assert set(results) == set(submitted)   # zero lost
            assert queues.get_result("t", timeout=1.5) is None  # zero dup
            assert queues.active_count == 0
        finally:
            queues.shutdown()


# ---------------------------------------------------------------------------
# cluster Value Server shards + ssh hook + auto-snapshot
# ---------------------------------------------------------------------------

def test_cluster_vs_shards_shared_ring():
    from repro.core.transport.shards import ShardedValueServer
    spec = ClusterSpec([HostSpec("h0", vs_shards=1),
                        HostSpec("h1", vs_shards=1)])
    with ClusterLauncher(spec) as lc:
        assert len(lc.vs_addresses) == 2
        a = ShardedValueServer.connect(lc.vs_addresses)
        b = ShardedValueServer.connect(lc.vs_addresses)
        key = a.put({"x": list(range(100))})
        # a second client with the same ordered ring resolves the key
        assert b.get(key) == {"x": list(range(100))}
        assert a.shard_of(key) == b.shard_of(key)
        # connected clients do not own the shards
        a.shutdown()
        assert key in b


def test_ssh_command_hook(tmp_path):
    spec = ClusterSpec([
        HostSpec("h0", pools={"t": 2}, thinker=True),
        HostSpec("h1", pools={"t": 4}, ssh="user@node17"),
    ])
    lc = ClusterLauncher(spec, methods=[("repro.apps.synapp:syntask",
                                         {"topic": "t"})])
    lc._addresses = {"h0": ("tcp", "10.0.0.1", 5000),
                     "h1": ("tcp", "10.0.0.2", 5000)}
    cmds = lc.ssh_commands(str(tmp_path))
    assert list(cmds) == ["h1"]
    cmd = cmds["h1"]
    assert cmd[:2] == ["ssh", "user@node17"]
    assert "repro.core.cluster.agent" in cmd
    cfg_path = cmd[-1]
    assert os.path.exists(cfg_path)
    import pickle
    with open(cfg_path, "rb") as f:
        cfg = pickle.load(f)
    assert cfg.host == "h1" and cfg.pools == {"t": 4}
    assert cfg.broker_address == ("tcp", "10.0.0.2", 5000)
    # callables cannot travel over ssh
    lc2 = ClusterLauncher(spec, methods=[(_times_ten, {"topic": "t"})])
    lc2._addresses = lc._addresses
    with pytest.raises(ValueError, match="module:qualname"):
        lc2.write_agent_configs(str(tmp_path))


def test_broker_auto_snapshot_resumable(tmp_path):
    path = str(tmp_path / "auto.snap")
    queues = ColmenaQueues(["t"], backend="proc", lease_timeout=2.0,
                           snapshot_every=0.15, snapshot_path=path)
    try:
        for i in range(3):
            queues.send_task(i, method="t", topic="t")
        deadline = time.time() + 10
        while not os.path.exists(path) and time.time() < deadline:
            time.sleep(0.05)
        assert os.path.exists(path), "auto-snapshot never written"
        time.sleep(0.3)                 # at least one post-put snapshot
        payload = ColmenaQueues.load_checkpoint(path)
        # no application recorded the count: derived from envelope metas
        assert payload["active"] == 3
        assert payload["extra"] is None
        fresh = ColmenaQueues(["t"], backend="proc")
        try:
            assert fresh.resume(path, payload=payload) is None
            assert fresh.active_count == 3
            tasks = fresh.get_tasks("t", max_n=10, timeout=2)
            assert sorted(t.args[0] for t in tasks) == [0, 1, 2]
        finally:
            fresh.shutdown()
    finally:
        queues.shutdown()


def test_local_backend_rejects_auto_snapshot(tmp_path):
    with pytest.raises(ValueError, match="proc"):
        ColmenaQueues(["t"], backend="local", snapshot_every=1.0,
                      snapshot_path=str(tmp_path / "x"))
    from repro.core.transport import make_transport
    t = make_transport("local")
    with pytest.raises(ValueError, match="snapshot_every"):
        ColmenaQueues(["t"], transport=t, snapshot_every=1.0,
                      snapshot_path=str(tmp_path / "x"))


def test_derived_active_excludes_consumed_but_leased(tmp_path):
    """The piggyback-ack window: a snapshot can image a worker's
    dispatch lease for a task whose result was already published,
    consumed, and acked.  Counting it active would hang a resumed
    wait_until_done (the re-execution loses the restored claim and
    never delivers) -- claimed ids with no queued result envelope are
    excluded from the derived count."""
    from repro.core.transport import Envelope, make_transport
    t = make_transport("proc", lease_timeout=30.0)
    try:
        dispatch = t.channel(dispatch_topic("h0", "t"), "tasks")
        results = t.channel("t", "results")
        # stale: executed, result published+claimed, result consumed and
        # acked -- but the dispatch lease was never acked (worker died
        # with the ack still piggyback-pending)
        dispatch.put(Envelope(now(), b"stale", {"task_id": "done-task"}))
        got = []
        th = threading.Thread(
            target=lambda: got.extend(dispatch.get_batch(1, timeout=2)))
        th.start()
        th.join()
        assert len(got) == 1                # leased, never acked
        assert results.put(Envelope(now(), b"r", {"task_id": "done-task"}),
                           claim="done-task") is True
        assert results.get(timeout=2) is not None
        results.ack(flush=True)             # consumed: result is gone
        # live: a second task still genuinely in flight
        dispatch.put(Envelope(now(), b"live", {"task_id": "live-task"}))
        snap = t.snapshot()
        path = str(tmp_path / "auto.snap")
        with open(path, "wb") as f:
            f.write(snap)
        payload = ColmenaQueues.load_checkpoint(path)
        assert payload["active"] == 1       # live-task only
    finally:
        t.close()


# ---------------------------------------------------------------------------
# durable Value Server at cluster scale: replica survival, shard restart,
# and the kill -9'd campaign that resumes WITH the Value Server enabled
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cluster_vs_replicas_survive_host_kill_and_restore():
    """kill_host takes the host's shard processes with it (node loss).
    With vs_replicas=2 every key stays readable -- byte-identical --
    via its ring successor; restore_host_shards then rebuilds the
    replica factor and stale clients converge by redirect."""
    spec = ClusterSpec([
        HostSpec("h0", vs_shards=1, pools={"t": 1}, thinker=True),
        HostSpec("h1", vs_shards=1, pools={"t": 1}),
    ], vs_replicas=2, lease_timeout=3.0)
    with ClusterLauncher(spec) as lc:
        vs = lc.value_server()
        assert vs.replicas == 2             # adopted from the pushed ring
        vals = {vs.put(os.urandom(400), sync=True): None for _ in range(20)}
        vals = {k: vs.get(k) for k in vals}
        lc.kill_host("h1")
        for k, v in vals.items():
            assert vs.get(k) == v           # replicas cover the dead shard
        assert vs.client_stats["replica_reads"] > 0
        replaced = lc.restore_host_shards("h1")
        assert len(replaced) == 1 and replaced[0]["host"] == "h1"
        fresh = lc.value_server()
        assert fresh._epoch > 1
        for k, v in vals.items():
            assert fresh.get(k) == v
        # replica factor is fully restored: every key has 2 copies again
        assert sum(s["len"] for s in fresh.per_shard_stats()) == 2 * len(vals)
        # the stale pre-kill client is redirected onto the new ring
        for k, v in vals.items():
            assert vs.get(k) == v
        assert vs._epoch == fresh._epoch
        assert vs.client_stats["redirects"] >= 1


def _echo_payload(payload: bytes):
    time.sleep(0.2)
    return payload[:16]


@pytest.mark.slow
def test_cluster_campaign_kill9_resume_with_value_server(tmp_path):
    """The acceptance scenario: a 2-host cluster campaign with the Value
    Server ENABLED (inputs proxied through the shard ring) is checkpointed
    mid-flight, the whole incarnation is SIGKILLed -- agents, brokers,
    shards -- and a fresh cluster resumes from the file: zero lost ids,
    zero duplicated ids, and every restored proxy resolves (results echo
    their input payload's prefix, which only resolves through the VS)."""
    path = str(tmp_path / "cluster.ckpt")
    spec = ClusterSpec([
        HostSpec("h0", pools={"t": 1}, vs_shards=1, thinker=True),
        HostSpec("h1", pools={"t": 1}, vs_shards=1),
    ], vs_replicas=2, lease_timeout=2.0)
    payloads = {}
    with ClusterLauncher(spec, methods=[(_echo_payload,
                                         {"topic": "t", "name": "t"})],
                         proxy_threshold=1 << 10) as lc:
        vs = lc.value_server()
        queues = lc.connect(["t"], value_server=vs,
                            proxy_threshold=1 << 10)
        submitted = []
        for i in range(10):
            data = bytes([i]) * 2048        # above threshold: proxied
            tid = queues.send_task(data, method="t", topic="t")
            submitted.append(tid)
            payloads[tid] = data
        consumed = {}
        for _ in range(3):
            r = queues.get_result("t", timeout=60)
            assert r is not None and r.success, r and r.error
            consumed[r.task_id] = r.value
        queues.checkpoint(path)
        # kill -9 the whole incarnation: agents (process groups), every
        # broker, every shard -- nothing survives but the file
        for host, p in list(lc._agents.items()):
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for e in lc._shards:
            e["proc"].kill()
        for name, p in lc._brokers.items():
            p.kill()
        try:
            queues.transport.client.close()
        except Exception:
            pass
    # fresh incarnation, same spec shape
    spec2 = ClusterSpec([
        HostSpec("h0", pools={"t": 1}, vs_shards=1, thinker=True),
        HostSpec("h1", pools={"t": 1}, vs_shards=1),
    ], vs_replicas=2, lease_timeout=2.0)
    with ClusterLauncher(spec2, methods=[(_echo_payload,
                                          {"topic": "t", "name": "t"})],
                         proxy_threshold=1 << 10) as lc2:
        vs2 = lc2.value_server()
        q2 = lc2.connect(["t"], value_server=vs2, proxy_threshold=1 << 10)
        try:
            assert q2.resume(path) is None
            assert q2.active_count == len(submitted) - len(consumed)
            recovered = {}
            for _ in range(q2.active_count):
                r = q2.get_result("t", timeout=90)
                assert r is not None and r.success, r and r.error
                assert r.task_id not in consumed    # never redone
                assert r.task_id not in recovered   # never duplicated
                recovered[r.task_id] = r.value
            # zero lost: every submitted id completed exactly once, and
            # every completion echoes its ORIGINAL proxied payload
            done = {**consumed, **recovered}
            assert set(done) == set(submitted)
            for tid, value in done.items():
                assert value == payloads[tid][:16]
            assert q2.get_result("t", timeout=1.5) is None  # quiescent
            assert q2.active_count == 0
        finally:
            q2.shutdown()


class _NoProcess:
    """Stands in for a forked shard that has already exited."""

    def is_alive(self):
        return False

    def join(self, timeout=None):
        pass


def test_launcher_gives_each_inference_shard_of_a_host_its_own_chip(
        monkeypatch):
    from repro.serving import shard
    from repro.serving.shard import ServeSpec
    envs = []

    def record(address, spec, **kw):
        envs.append(kw["env"] or {})
        return _NoProcess()

    monkeypatch.setattr(shard, "start_inference_shard", record)
    spec = ClusterSpec([
        HostSpec("h0", inference_shards=2, thinker=True,
                 env={"JAX_PLATFORMS": "cpu"}),
        HostSpec("h1", inference_shards=1),
    ])
    with ClusterLauncher(spec, serve_spec=ServeSpec()):
        pass
    assert [e.get("TPU_VISIBLE_CHIPS") for e in envs] == ["0", "1", None]
    assert envs[0]["TPU_PROCESS_PORT"] != envs[1]["TPU_PROCESS_PORT"]
    assert all(e.get("JAX_PLATFORMS") == "cpu" for e in envs[:2])
    # a host's only shard keeps every chip of the host
    assert envs[2] == {}
