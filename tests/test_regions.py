"""Named regions on the profiler's clock (``obs.region``).  Without a
profiler session a region is one shared no-op that imports no jax and
formats no args; under a CPU ``jax.profiler`` session around a reduced
serve loop, the serve loop's and the engine's regions appear, nested,
with their args, and the engine's programs carry their names."""
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.observability.names import REGION_NAMES

SRC = str(Path(__file__).resolve().parents[1] / "src")

NO_SESSION = """
import sys
from repro import observability as obs
import repro.serving.shard

class Loud:
    def __format__(self, spec):
        raise AssertionError("an arg was formatted")
    __str__ = __repr__ = lambda self: self.__format__("")

off = obs.region("serve.park", t=Loud())
assert off is obs.region("serve.step", B=Loud(), N=1, P=2)
with off:
    pass
assert "jax" not in sys.modules, "a region imported jax"
import jax
assert obs.region("serve.poll", t=Loud()) is off
print("ok")
"""


def test_region_is_a_shared_noop_without_a_profiler_session():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", NO_SESSION], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "ok"


def _host_regions(path):
    """(start, end, name, args) of every declared region in a trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                            dict(e.stats)) for e in line.events
                           if e.name in REGION_NAMES)
    return sorted(out)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A reduced internlm2 behind a serve loop in a thread, traced by a
    CPU profiler session: one micro-batch of three rows (max_new 2, 2
    and 6, so two retire after a step and the group compacts to one
    row) and one max_new=1 request in the other bucket."""
    import jax
    from repro.core.queues import ColmenaQueues
    from repro.serving.shard import (InferenceClient, ServeLoop, ServeSpec,
                                     default_engine_factory,
                                     send_shard_stop)
    spec = ServeSpec(max_batch=4, prompt_buckets=(8, 16),
                     max_batch_delay_ms=50.0, max_new_cap=8)
    engine = default_engine_factory(reduced=True, max_new=8)()
    q = ColmenaQueues([], backend="local", serve_spec=spec)
    loop = ServeLoop(q.transport, spec, engine=engine,
                     identity="infer@test:0")
    th = threading.Thread(target=loop.run, daemon=True, name="test-shard")
    out = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    th.start()
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        ids = [q.send_inference(p, max_new=m) for p, m in
               (([3, 4, 5], 2), ([1, 2], 2), ([7] * 6, 6))]
        ids.append(q.send_inference([9] * 12, max_new=1))
        results = InferenceClient(q).gather(ids, timeout=120.0)
    finally:
        jax.profiler.stop_trace()
        send_shard_stop(q.transport, spec.topic)
        th.join(timeout=10)
        q.shutdown()
    assert not th.is_alive()
    assert all(r.success for r in results), [r.error for r in results]
    (path,) = out.glob("**/*.xplane.pb")
    return engine, _host_regions(path)


def _within(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_serve_and_engine_regions_appear_nested_with_args(served):
    _, regions = served
    by = {}
    for r in regions:
        by.setdefault(r[2], []).append(r)
    assert set(by) == set(REGION_NAMES)
    # the serve loop's phases tile its turns: none overlaps another
    serve = [r for r in regions if r[2].startswith("serve.")]
    for a, b in zip(serve, serve[1:]):
        assert a[1] <= b[0], (a, b)
    # each engine region sits inside the phase or call that makes it
    parents = {"engine.prefill": "serve.admit", "engine.decode": "serve.step",
               "engine.gather": "serve.compact",
               "engine.grow_cache": "engine.prefill",
               "engine.dispatch": "engine.decode"}
    for child, parent in parents.items():
        for r in by[child]:
            assert any(_within(r, p) for p in by[parent]), (child, r)
    for r in by["engine.readback"]:
        assert any(_within(r, p) for p in by["engine.prefill"]
                   + by["engine.decode"])
    # args: the three-row micro-batch, its first step, the publishes
    admits = sorted((a["B"], a["N"], a["S"], a["R"])
                    for *_, a in by["serve.admit"])
    assert admits == [(1, 1, 16, 17), (4, 3, 8, 16)]
    steps = [(a["B"], a["N"], a["P"]) for *_, a in by["serve.step"]]
    assert steps[0] == (4, 3, 8)
    assert (1, 1, 9) in steps and len(steps) == 5
    assert sorted(a["n"] for *_, a in by["serve.publish"]) == [1, 1, 2]
    assert all(isinstance(a["t"], int) for *_, a in
               by["serve.park"] + by["serve.poll"])


def test_engine_programs_are_named(served):
    engine, _ = served
    tokens = {"tokens": np.zeros((1, 8), np.int32)}
    assert "@jit_prefill" in engine._prefill.lower(engine.params,
                                                   tokens).as_text()
    _, state = engine.prefill_batch(np.ones((1, 8), np.int32), reserve=10)
    import jax.numpy as jnp
    lowered = engine._decode.lower(engine.params, state.cache, state.cur,
                                   jnp.asarray(state.pos, jnp.int32))
    assert "@jit_decode_step" in lowered.as_text()
