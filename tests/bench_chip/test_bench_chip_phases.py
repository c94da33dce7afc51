"""The reduction of the program's own regions (``phases``) and the four
readers on it: known answers on synthetic events, silence on a trace
without the regions, and the clock anchor that maps the jsonl spans
onto the trace's clock, checked on a CPU profile of a serve loop."""
import json
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import phases, spec
from benchmarks.chip.layer_metrics import (decode_host_ms,
                                           idle_parked_share,
                                           idle_poll_share,
                                           idle_publish_share)

ms = 1_000_000
RECORDED = spec.HERE / "testdata" / "internlm2-decode.xplane.pb"
READERS = (decode_host_ms, idle_poll_share, idle_publish_share,
           idle_parked_share)


def synthetic():
    """A 100 ms window: the loop parks, admits a prefill, publishes, then
    polls and steps twice with a compaction between; 5 ms under no
    region; then parks again.  ``t`` puts perf_counter 1 ms behind the
    trace's clock, but for one anchor far off."""
    def anchor(name, s, e, t=None):
        return (s, e, name, {"t": s + 1 * ms if t is None else t})
    regions = [
        anchor("serve.park", 0, 10 * ms),
        (10 * ms, 12 * ms, "serve.intake", {}),
        (12 * ms, 30 * ms, "serve.admit", {"B": 4, "N": 3}),
        (13 * ms, 29 * ms, "engine.prefill", {}),
        (30 * ms, 34 * ms, "serve.publish", {"n": 2}),
        anchor("serve.poll", 34 * ms, 36 * ms),
        (36 * ms, 50 * ms, "serve.step", {"B": 4, "N": 3, "P": 8}),
        (37 * ms, 49 * ms, "engine.decode", {}),
        (37 * ms, 39 * ms, "engine.dispatch", {}),
        (40 * ms, 49 * ms, "engine.readback", {}),
        (50 * ms, 52 * ms, "serve.compact", {}),
        (50 * ms, 52 * ms, "engine.gather", {}),
        anchor("serve.poll", 52 * ms, 54 * ms, t=0),
        (54 * ms, 70 * ms, "serve.step", {"B": 1, "N": 1, "P": 9}),
        (55 * ms, 69 * ms, "engine.decode", {}),
        anchor("serve.park", 75 * ms, 120 * ms),
    ]
    ops = [(14 * ms, 28 * ms), (39 * ms, 47 * ms), (51 * ms, 52 * ms),
           (56 * ms, 68 * ms)]
    mods = [(14 * ms, 28 * ms, "jit_prefill(7)"),
            (39 * ms, 47 * ms, "jit_decode_step(9)"),
            (56 * ms, 68 * ms, "jit_decode_step(9)")]
    return [(0, 100 * ms)], regions, ops, mods


def test_reduce_events_known_answer():
    p = phases.reduce_events(*synthetic())
    assert p.window_s == pytest.approx(0.1)
    # busy 14 + 8 + 1 + 12 ms
    assert p.idle_s == pytest.approx(0.065)
    assert p.idle_in == pytest.approx({
        "serve.park": 0.035, "serve.intake": 0.002, "serve.admit": 0.004,
        "serve.publish": 0.004, "serve.poll": 0.004, "serve.step": 0.010,
        "serve.compact": 0.001})
    # 70-75 ms lies under no region
    assert p.unattributed_s == pytest.approx(0.005)
    assert p.unattributed_s + sum(p.idle_in.values()) == \
        pytest.approx(p.idle_s)
    # 12 - 8 and 14 - 12 ms idle inside the two engine.decode regions
    assert p.decode_idle_s == pytest.approx([0.004, 0.002])
    assert p.offset_ns == -1 * ms           # the median ignores t=0
    assert p.serve_seen
    assert p.modules == pytest.approx({"jit_prefill": 0.014,
                                       "jit_decode_step": 0.020})


def test_readers_known_answer():
    run = SimpleNamespace(phases=[phases.reduce_events(*synthetic())])
    assert decode_host_ms.read(run) == pytest.approx(3.0)
    assert idle_poll_share.read(run) == pytest.approx(4.0)
    assert idle_publish_share.read(run) == pytest.approx(4.0)
    assert idle_parked_share.read(run) == pytest.approx(35.0)


def test_readers_average_the_chips():
    traced, regions, ops, _ = synthetic()
    quiet = phases.reduce_events(traced, regions, ops + [(0, 10 * ms)])
    run = SimpleNamespace(phases=[phases.reduce_events(traced, regions, ops),
                                  quiet])
    assert idle_parked_share.read(run) == pytest.approx((35 + 25) / 2)
    assert decode_host_ms.read(run) == pytest.approx(3.0)


def test_a_program_without_regions_reads_nothing():
    traced, regions, ops, _ = synthetic()
    without = phases.reduce_events(traced, [], ops)
    for run in (SimpleNamespace(phases=[]),
                SimpleNamespace(phases=[without])):
        assert [m.read(run) for m in READERS] == [None] * 4
    assert phases.reduce_events(traced, regions, []) is None
    assert phases.reduce_events([], regions, ops) is None


def test_recorded_chip_trace_of_a_program_without_regions():
    """The committed v5e trace predates the regions: its window and
    busy time reduce as ``tracefile``'s do, and no reader reads it."""
    from benchmarks.chip import tracefile
    p = phases.reduce(RECORDED)
    r = tracefile.reduce(RECORDED)
    assert p.window_s == pytest.approx(r.window_s)
    assert p.idle_s == pytest.approx(r.window_s - r.busy_s)
    assert not p.serve_seen and p.offset_ns is None
    run = SimpleNamespace(phases=[p])
    assert [m.read(run) for m in READERS] == [None] * 4


def test_control_dir_is_found_by_the_window_start(tmp_path, monkeypatch):
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    for i, t0 in enumerate((12.5, 99.25)):
        d = tmp_path / f"chipbench-{i}"
        d.mkdir()
        (d / "window.json").write_text(json.dumps({"t0": t0}))
    (tmp_path / "chipbench-torn").mkdir()
    assert phases.control_dir(SimpleNamespace(t0=99.25)) == \
        tmp_path / "chipbench-1"
    assert phases.control_dir(SimpleNamespace(t0=1.0)) is None


class _Echo:
    """A stub engine: each step adds one to the last token."""

    def prefill_batch(self, tokens, *, reserve=None, frames=None):
        first = tokens[:, -1].astype(np.int64) + 1
        return first, SimpleNamespace(cur=first, padded_b=tokens.shape[0])

    def decode_batch(self, state):
        time.sleep(0.002)
        state.cur = state.cur + 1
        return state.cur


def test_clock_anchor_maps_a_prefill_span_onto_its_admit_region(
        tmp_path, monkeypatch):
    import jax
    from jax.profiler import ProfileData
    from repro import observability as obs
    from repro.core.queues import ColmenaQueues
    from repro.observability import metrics as obs_metrics
    from repro.observability import trace as obs_trace
    from repro.serving.shard import (InferenceClient, ServeLoop, ServeSpec,
                                     send_shard_stop)
    monkeypatch.setenv(obs.ENV_DIR, str(tmp_path / "obs"))
    monkeypatch.setenv(obs.ENV_SAMPLE, "1.0")
    obs_trace._T._pid = -1
    obs_metrics.reset()
    spec = ServeSpec(max_batch=4, prompt_buckets=(8,),
                     max_batch_delay_ms=5.0, max_new_cap=8)
    q = ColmenaQueues([], backend="local", serve_spec=spec)
    loop = ServeLoop(q.transport, spec, engine=_Echo(),
                     identity="infer@test:0")
    th = threading.Thread(target=loop.run, daemon=True, name="test-shard")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    th.start()
    jax.profiler.start_trace(str(tmp_path / "profile"),
                             profiler_options=opts)
    try:
        client = InferenceClient(q)
        for i in range(3):
            res = client.infer([[i + 1, 2, 3]], max_new=4, timeout=30.0)
            assert res[0].value == [4, 5, 6, 7]
    finally:
        jax.profiler.stop_trace()
        send_shard_stop(q.transport, spec.topic)
        th.join(timeout=10)
        q.shutdown()
        obs.flush()
        obs_trace._T._pid = -1
        obs_metrics.reset()
    assert not th.is_alive()
    (path,) = (tmp_path / "profile").glob("**/*.xplane.pb")
    _, regions, _, _ = phases._events(ProfileData.from_file(str(path)))
    offset = phases.anchor_offset_ns(regions)
    assert offset is not None
    admits = sorted(s for s, _, name, _ in regions if name == "serve.admit")
    spans = [json.loads(line) for f in (tmp_path / "obs").glob("*.jsonl")
             for line in f.read_text().splitlines()]
    prefills = sorted(s["t0"] for s in spans
                      if s.get("kind") == "span" and s["name"] == "prefill")
    assert len(admits) == len(prefills) == 3
    for admit, t0 in zip(admits, prefills):
        assert abs(phases.on_trace_clock(t0, offset) - admit) < 1 * ms
