"""Inference shard tests: bucketed micro-batching, the serve loop over
stub engines (no jax -- these pin the fabric semantics, not the model),
the detached-lease channel API it is built on, and the SIGKILL chaos
story (lease expiry redelivers every in-flight request exactly once).
"""
import os
import signal
import threading
import time

import numpy as np
import pytest

from repro.core.queues import ColmenaQueues
from repro.core.transport import Envelope, make_transport
from repro.serving.batcher import (DecodeGroup, InferenceRequest,
                                   MicroBatch, MicroBatcher, batch_bucket,
                                   prompt_bucket)
from repro.serving.shard import (InferenceClient, ServeLoop, ServeSpec,
                                 send_shard_stop, start_inference_shard)
from repro.utils.timing import now


def _req(tid, tokens, max_new=4, t=0.0):
    return InferenceRequest(task_id=tid, tokens=list(tokens),
                            max_new=max_new, enqueue_t=t)


# ---------------------------------------------------------------------------
# batcher: pure bookkeeping
# ---------------------------------------------------------------------------

def test_prompt_and_batch_buckets():
    assert prompt_bucket(1, (16, 32)) == 16
    assert prompt_bucket(16, (16, 32)) == 16
    assert prompt_bucket(17, (16, 32)) == 32
    with pytest.raises(ValueError):
        prompt_bucket(33, (16, 32))
    assert [batch_bucket(n, 8) for n in (1, 2, 3, 5, 8, 30)] \
        == [1, 2, 4, 8, 8, 8]


def test_microbatcher_ragged_arrival_splits_full_then_partial():
    """N not a multiple of max_batch: full batches flush immediately,
    the ragged remainder waits for its deadline."""
    mb = MicroBatcher(max_batch=4, prompt_buckets=(16,),
                      max_batch_delay=10.0)
    for i in range(9):
        mb.add(_req(f"t{i}", [1] * 5, t=0.0))
    ready = mb.pop_ready(tnow=0.001)
    assert [len(b.requests) for b in ready] == [4, 4]
    # FIFO within the bucket
    assert [r.task_id for r in ready[0].requests] == ["t0", "t1", "t2", "t3"]
    assert mb.pending_count() == 1
    # the remainder is deadline-gated ...
    assert mb.pop_ready(tnow=0.002) == []
    assert mb.next_deadline() == pytest.approx(10.0)
    # ... and flushes as a partial batch once the oldest waited out
    late = mb.pop_ready(tnow=10.5)
    assert [len(b.requests) for b in late] == [1]
    assert late[0].requests[0].task_id == "t8"
    assert mb.pending_count() == 0


def test_microbatcher_force_flush_and_bucket_separation():
    mb = MicroBatcher(max_batch=8, prompt_buckets=(8, 16),
                      max_batch_delay=10.0)
    mb.add(_req("a", [1] * 3, t=0.0))     # bucket 8
    mb.add(_req("b", [1] * 12, t=0.0))    # bucket 16
    assert mb.pop_ready(tnow=0.0) == []
    ready = mb.pop_ready(tnow=0.0, force=True)
    assert sorted(b.bucket for b in ready) == [8, 16]
    assert mb.pending_count() == 0


def test_padded_tokens_left_pads_and_repeats_row0():
    m = MicroBatch(8, [_req("a", [5, 6, 7]), _req("b", [9])])
    out = m.padded_tokens(padded_b=4)
    assert out.shape == (4, 8)
    assert list(out[0]) == [0] * 5 + [5, 6, 7]
    assert list(out[1]) == [0] * 7 + [9]
    # padding rows repeat row 0: no novel content, outputs dropped
    assert (out[2] == out[0]).all() and (out[3] == out[0]).all()


def test_decode_group_early_retire_and_compaction():
    m = MicroBatch(8, [_req("a", [1], max_new=1), _req("b", [2], max_new=1),
                       _req("c", [3], max_new=1), _req("d", [4], max_new=3)])
    g = DecodeGroup(m, first_tokens=[10, 20, 30, 40], max_batch=8)
    # max_new=1 rows are finished right after the prefill token
    done = {r.task_id: toks for r, toks in g.finished()}
    assert done == {"a": [10], "b": [20], "c": [30]}
    g.retire_finished()
    assert [r.task_id for r in g.rows] == ["d"] and g.slots == [3]
    # survivor fits batch bucket 1 < padded_b 4 -> compaction
    assert g.compaction(padded_b=4) == 1
    g.reset_slots()
    assert g.slots == [0]
    # post-compaction decode steps index the gathered state
    g.record_step([41])
    g.record_step([42])
    ((r, toks),) = g.finished()
    assert r.task_id == "d" and toks == [40, 41, 42]
    g.retire_finished()
    assert g.done


# ---------------------------------------------------------------------------
# the channel API the shard's lease discipline rides on
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["local", "proc"])
def test_detach_lease_survives_next_get(backend):
    """detach_lease takes over the lease lifetime: the next get_batch no
    longer implicitly commits it, an unacked detached lease expires and
    redelivers, and ack_lease commits it for good."""
    t = make_transport(backend, lease_timeout=0.5)
    try:
        ch = t.channel("t", "requests")
        ch.put(Envelope(now(), b"one", {}))
        ch.put(Envelope(now(), b"two", {}))
        (e1,) = ch.get_batch(1, timeout=2.0)
        lid1 = ch.detach_lease()
        assert lid1 is not None
        # poll-is-commit must NOT touch the detached lease
        (e2,) = ch.get_batch(1, timeout=2.0)
        ch.ack(flush=True)                  # commits e2's lease only
        deadline = now() + 5.0
        redelivered = []
        while not redelivered and now() < deadline:
            redelivered = ch.get_batch(1, timeout=0.5)
        assert [e.data for e in redelivered] == [b"one"]
        assert redelivered[0].meta.get("redelivered", 0) >= 1
        # now commit the redelivery explicitly, as the shard does
        lid = ch.detach_lease()
        ch.ack_lease(lid, flush=True)
        time.sleep(0.7)                     # past expiry: stays committed
        assert ch.get_batch(1, timeout=0.05) == []
    finally:
        t.close()


# ---------------------------------------------------------------------------
# serve loop over a stub engine (local transport, in-thread shard)
# ---------------------------------------------------------------------------

class _StubState:
    def __init__(self, cur, padded_b):
        self.cur = cur
        self.padded_b = padded_b


class _StubEngine:
    """Echo chain: first = last prompt token + 1, each step +1.  Records
    the shapes it sees so tests can assert bucketing and compaction."""

    def __init__(self, step_sleep=0.0):
        self.step_sleep = step_sleep
        self.prefill_shapes = []
        self.gather_sizes = []

    def prefill_batch(self, tokens, *, reserve=None, frames=None):
        self.prefill_shapes.append(tokens.shape)
        first = tokens[:, -1].astype(np.int64) + 1
        return first, _StubState(first, tokens.shape[0])

    def decode_batch(self, state):
        if self.step_sleep:
            time.sleep(self.step_sleep)
        state.cur = state.cur + 1
        return state.cur

    def gather_rows(self, state, rows):
        idx = np.asarray(list(rows))
        self.gather_sizes.append(len(idx))
        return _StubState(state.cur[idx], len(idx))


def _stub_factory():
    return _StubEngine()


def _slow_stub_factory():
    return _StubEngine(step_sleep=0.05)


def _local_shard(spec, engine=None):
    q = ColmenaQueues([], backend="local", serve_spec=spec)
    loop = ServeLoop(q.transport, spec, engine=engine,
                     identity="infer@test:0")
    th = threading.Thread(target=loop.run, daemon=True, name="test-shard")
    th.start()
    return q, loop, th


def _stop_local(q, spec, th):
    send_shard_stop(q.transport, spec.topic)
    th.join(timeout=5)
    assert not th.is_alive()


def test_serve_loop_end_to_end_ragged():
    """Ragged arrival across buckets: every request answered with the
    right echo chain, reassembled in submission order."""
    spec = ServeSpec(engine_factory=_stub_factory, max_batch=4,
                     prompt_buckets=(8, 16), max_batch_delay_ms=5.0)
    eng = _StubEngine()
    q, loop, th = _local_shard(spec, engine=eng)
    try:
        client = InferenceClient(q)
        prompts = [[3, 4], [10], [7] * 12, [1, 2, 3], [20] * 5]
        res = client.infer(prompts, max_new=3, timeout=20.0)
        for p, r in zip(prompts, res):
            assert r.success, r.error
            assert r.value == [p[-1] + 1, p[-1] + 2, p[-1] + 3]
        assert q.active_count == 0
        # prompts landed in their length buckets, batch dims are pow2
        for (b, s) in eng.prefill_shapes:
            assert s in (8, 16) and b in (1, 2, 4)
    finally:
        _stop_local(q, spec, th)
    assert loop.stats["published"] == 5
    assert loop.stats["claim_lost"] == 0


def test_serve_loop_max_new_1_and_deadline_partial_flush():
    """max_new=1 rows stream straight from the prefill (zero decode
    steps), and a lone request flushes as a deadline-expired partial
    batch rather than waiting for company."""
    spec = ServeSpec(engine_factory=_stub_factory, max_batch=8,
                     prompt_buckets=(8,), max_batch_delay_ms=30.0)
    eng = _StubEngine()
    q, loop, th = _local_shard(spec, engine=eng)
    try:
        client = InferenceClient(q)
        t0 = now()
        (r,) = client.infer([[5, 6]], max_new=1, timeout=20.0)
        waited = now() - t0
        assert r.success and r.value == [7]
        # it waited out the deadline knob (partial flush), not a full
        # batch that would never come
        assert waited >= 0.8 * (spec.max_batch_delay_ms / 1000.0)
        assert loop.stats["decode_steps"] == 0
        assert eng.prefill_shapes == [(1, 8)]
    finally:
        _stop_local(q, spec, th)


def test_serve_loop_compaction_on_early_retire():
    """Mixed max_new in one bucket: short rows retire early and the
    engine state is gathered down to the survivor's batch bucket."""
    spec = ServeSpec(engine_factory=_stub_factory, max_batch=4,
                     prompt_buckets=(8,), max_batch_delay_ms=5.0)
    eng = _StubEngine()
    q, loop, th = _local_shard(spec, engine=eng)
    try:
        client = InferenceClient(q)
        tids = [q.send_inference([10], max_new=1),
                q.send_inference([20], max_new=1),
                q.send_inference([30], max_new=1),
                q.send_inference([40], max_new=6)]
        res = client.gather(tids, timeout=20.0)
        assert [r.value for r in res] == [[11], [21], [31],
                                          [41, 42, 43, 44, 45, 46]]
    finally:
        _stop_local(q, spec, th)
    # 4-row prefill, then a gather down to 1 survivor
    assert eng.prefill_shapes[0] == (4, 8)
    assert 1 in eng.gather_sizes
    assert loop.stats["compactions"] >= 1


def test_serve_loop_rejects_oversized_and_empty_prompts():
    spec = ServeSpec(engine_factory=_stub_factory, max_batch=4,
                     prompt_buckets=(8,), max_batch_delay_ms=5.0)
    q, loop, th = _local_shard(spec, engine=_StubEngine())
    try:
        client = InferenceClient(q)
        res = client.infer([[1] * 9, [2, 3]], max_new=2, timeout=20.0)
        assert not res[0].success and "outside buckets" in res[0].error
        assert res[1].success and res[1].value == [4, 5]
        assert q.active_count == 0
    finally:
        _stop_local(q, spec, th)
    assert loop.stats["errors"] == 1


def test_serve_loop_continuous_admission():
    """A second wave submitted while the first is mid-decode is admitted
    between decode steps, not after the first wave completes: total wall
    time is far below sequential group execution."""
    spec = ServeSpec(engine_factory=_stub_factory, max_batch=2,
                     prompt_buckets=(8,), max_batch_delay_ms=2.0)
    eng = _StubEngine(step_sleep=0.02)
    q, loop, th = _local_shard(spec, engine=eng)
    try:
        client = InferenceClient(q)
        first = client.submit([[1, 2], [3, 4]], max_new=20)
        time.sleep(0.1)                     # first group is mid-decode
        second = client.submit([[5, 6], [7, 8]], max_new=20)
        res = client.gather(first + second, timeout=30.0)
        assert all(r.success for r in res)
    finally:
        _stop_local(q, spec, th)
    # both groups were in flight concurrently: the loop interleaved
    # their steps (2 groups x 19 steps each, but admitted overlapping)
    assert loop.stats["prefills"] == 2
    assert loop.stats["decode_steps"] >= 38


class _DeviceStubEngine(_StubEngine):
    def device_info(self):
        return {"platform": "stub", "kind": "stub", "id": 3, "coords": None}


def test_results_name_their_device_and_gather_keeps_partial_results():
    """Every result names the device that computed it, and a gather that
    times out keeps what it already drained for the next gather."""
    spec = ServeSpec(engine_factory=_stub_factory, max_batch=4,
                     prompt_buckets=(8,), max_batch_delay_ms=5.0)
    q, loop, th = _local_shard(spec, engine=_DeviceStubEngine())
    try:
        client = InferenceClient(q)
        (tid,) = client.submit([[1, 2]], max_new=2)
        with pytest.raises(TimeoutError):
            client.gather([tid, "never-submitted"], timeout=1.0)
        (r,) = client.gather([tid], timeout=5.0)
        assert r.success and r.value == [3, 4]
        assert r.device["id"] == 3 and r.device["chip_files"] == []
    finally:
        _stop_local(q, spec, th)


def test_start_inference_shard_refuses_a_process_holding_an_accelerator(
        monkeypatch):
    import jax
    jax.devices()                           # this process's backend is up
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the chip"):
        start_inference_shard(("tcp", "127.0.0.1", 9),
                              ServeSpec(engine_factory=_stub_factory))


# ---------------------------------------------------------------------------
# synapp steering: the proxy-model scorer routed through a shard
# ---------------------------------------------------------------------------

def test_synapp_scored_steering_local():
    """ML-in-the-loop synapp: every submission ranks candidates through
    the scorer shard (an in-thread serve loop on the local backend) and
    the campaign still completes exactly."""
    from repro.apps.synapp import SynConfig, run_synapp
    cfg = SynConfig(T=8, D=0.0, I=1 << 10, N=2, use_value_server=False,
                    score_candidates=3)
    res = run_synapp(cfg)
    assert res["completed_total"] == 8
    assert res["scored"] == 8 * 3


@pytest.mark.slow
def test_synapp_scored_steering_proc():
    """Same steering loop with the scorer as a forked shard process."""
    from repro.apps.synapp import SynConfig, run_synapp
    cfg = SynConfig(T=8, D=0.0, I=1 << 10, N=2, use_value_server=False,
                    backend="proc", score_candidates=3)
    res = run_synapp(cfg)
    assert res["completed_total"] == 8
    assert res["scored"] == 8 * 3


# ---------------------------------------------------------------------------
# chaos: SIGKILL a shard mid-batch (proc backend, forked shard)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_shard_sigkill_redelivers_exactly_once():
    """Kill -9 a shard while batches are in flight: its detached leases
    expire and every undelivered request redelivers to the replacement
    shard; rows the dead shard already streamed out are deduped by the
    result claim.  Zero lost, zero duplicated."""
    spec = ServeSpec(engine_factory=_slow_stub_factory, max_batch=4,
                     prompt_buckets=(8,), max_batch_delay_ms=5.0)
    q = ColmenaQueues([], backend="proc", lease_timeout=1.0,
                      serve_spec=spec)
    procs = []
    try:
        procs.append(start_inference_shard(
            q.transport.address, spec, lease_timeout=1.0,
            identity="infer@chaos:0"))
        client = InferenceClient(q)
        tids = client.submit([[i + 1, i + 2] for i in range(12)],
                             max_new=6)
        # wait for proof the shard is mid-campaign (some results out,
        # some requests still leased), then kill it without warning
        got: dict = {}
        deadline = time.time() + 30
        while not got and time.time() < deadline:
            for r in q.get_results(spec.topic, max_n=64, timeout=0.5):
                got.setdefault(r.task_id, []).append(r)
        assert got, "shard produced nothing before the kill"
        assert len(got) < 12, "campaign finished before the kill"
        os.kill(procs[0].pid, signal.SIGKILL)
        procs[0].join(timeout=5)
        # replacement shard: the expired leases' requests land on it
        procs.append(start_inference_shard(
            q.transport.address, spec, lease_timeout=1.0,
            identity="infer@chaos:1"))
        deadline = time.time() + 60
        while len(got) < 12 and time.time() < deadline:
            for r in q.get_results(spec.topic, max_n=64, timeout=0.5):
                got.setdefault(r.task_id, []).append(r)
        # zero lost ...
        assert sorted(got) == sorted(tids)
        # ... zero duplicated (the claim admits one publish per id) ...
        dupes = {t: len(rs) for t, rs in got.items() if len(rs) > 1}
        assert not dupes, dupes
        # ... and every value is the right echo chain regardless of
        # which incarnation served it
        for i, t in enumerate(tids):
            (r,) = got[t]
            assert r.success, r.error
            assert r.value == [i + 3 + k for k in range(6)]
        assert q.active_count == 0
        # the queue stays quiet: nothing redelivers after completion
        assert q.get_results(spec.topic, max_n=64, timeout=1.5) == []
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=3)
        q.shutdown()
