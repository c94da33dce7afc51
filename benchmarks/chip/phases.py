"""The program's own regions in a shard's profiler trace, reduced to
what the phase metrics read.

The serve loop names each phase of a turn with ``obs.region``
(``serve.park``, ``serve.poll``, ``serve.intake``, ``serve.admit``,
``serve.step``, ``serve.publish``, ``serve.compact``; they tile a turn
and never nest), and the engine its calls (``engine.prefill``,
``engine.decode``, ``engine.gather``, with ``engine.grow_cache``,
``engine.dispatch`` and ``engine.readback`` nested inside).  From the
files ``tracefile.find`` returns:

- the traced window: the shard's ``bench.traced`` host annotation;
- busy time: the union of the device's ``XLA Ops`` intervals inside it;
- the device's idle time inside each ``serve.*`` region, inside each
  ``engine.decode`` region, and under no ``serve.*`` or ``engine.*``
  region at all;
- device time by program (``XLA Modules``), printed with the rest;
- the clock anchor: ``serve.park`` and ``serve.poll`` carry ``t``, the
  ``perf_counter_ns()`` at their entry, so the median of (region start
  - ``t``) maps the jsonl spans of every process on the host (one
  CLOCK_MONOTONIC) onto the trace's clock.

A program without these regions reduces to phases that name none, and
every reader of them returns None.
"""
from __future__ import annotations

import json
import sys
import tempfile
from bisect import bisect_right
from dataclasses import dataclass, field
from pathlib import Path
from statistics import median
from typing import Optional

from benchmarks.chip import tracefile

PREFIXES = ("serve.", "engine.")
ANCHORS = ("serve.park", "serve.poll")
TRACED = f"{tracefile.ANNOTATION}traced"


@dataclass
class Phases:
    window_s: float
    idle_s: float                  # device idle inside the window
    unattributed_s: float          # ... under no serve.*/engine.* region
    idle_in: dict = field(default_factory=dict)     # serve.* name -> s
    decode_idle_s: list = field(default_factory=list)  # per engine.decode
    offset_ns: Optional[float] = None   # trace ns - perf_counter ns
    serve_seen: bool = False       # any serve.* region in the window
    modules: dict = field(default_factory=dict)     # XLA module -> s


def _events(pd):
    """(``bench.traced`` intervals, program regions as (start, end,
    name, args), device ops as (start, end), device modules as (start,
    end, name)) in ns."""
    traced, regions, ops, mods = [], [], [], []
    for plane in pd.planes:
        if tracefile.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == tracefile.OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == tracefile.MODULES_LINE:
                    mods.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == TRACED:
                        traced.append(iv)
                    elif e.name.startswith(PREFIXES):
                        regions.append((*iv, e.name, dict(e.stats)))
    return traced, regions, ops, mods


class _Idle:
    """Device-idle time inside any interval of the window, from the
    busy intervals' complement and its running sum."""

    def __init__(self, w0: float, w1: float, busy: list):
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        self.gaps = list(zip(edges[::2], edges[1::2]))
        self.starts = [g0 for g0, _ in self.gaps]
        self.before = [0.0]
        for g0, g1 in self.gaps:
            self.before.append(self.before[-1] + g1 - g0)

    def upto(self, t: float) -> float:
        i = bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        g0, g1 = self.gaps[i]
        return self.before[i] + min(t, g1) - g0

    def between(self, s: float, e: float) -> float:
        return max(self.upto(e) - self.upto(s), 0.0)


def reduce_events(traced: list, regions: list, ops: list,
                  mods: tuple = ()) -> Optional[Phases]:
    if not traced or not ops:
        return None
    w0, w1 = traced[0]
    busy = tracefile.union([(max(s, w0), min(e, w1)) for s, e in ops
                            if e > w0 and s < w1])
    idle = _Idle(w0, w1, busy)
    idle_ns = idle.between(w0, w1)
    inside = [(max(s, w0), min(e, w1), name) for s, e, name, _ in regions
              if e > w0 and s < w1]
    idle_in: dict = {}
    for s, e, name in inside:
        if name.startswith("serve."):
            idle_in[name] = idle_in.get(name, 0.0) + idle.between(s, e)
    covered = sum(idle.between(s, e) for s, e in
                  tracefile.union([(s, e) for s, e, _ in inside]))
    decode = [idle.between(s, e) * 1e-9 for s, e, name, _ in regions
              if name == "engine.decode" and w0 <= s and e <= w1]
    modules: dict = {}
    for s, e, name in mods:
        if e > w0 and s < w1:
            name = name.split("(", 1)[0]
            modules[name] = (modules.get(name, 0.0)
                             + (min(e, w1) - max(s, w0)) * 1e-9)
    return Phases(window_s=(w1 - w0) * 1e-9, idle_s=idle_ns * 1e-9,
                  unattributed_s=(idle_ns - covered) * 1e-9,
                  idle_in={k: v * 1e-9 for k, v in idle_in.items()},
                  decode_idle_s=decode,
                  offset_ns=anchor_offset_ns(regions),
                  serve_seen=any(n.startswith("serve.") for *_, n in inside),
                  modules=modules)


def anchor_offset_ns(regions: list) -> Optional[float]:
    """The trace's clock less ``perf_counter_ns``: the median over the
    anchored regions of their start less their ``t``."""
    anchors = [s - a["t"] for s, _, name, a in regions
               if name in ANCHORS and "t" in a]
    return median(anchors) if anchors else None


def reduce(path: Path) -> Optional[Phases]:
    from jax.profiler import ProfileData
    return reduce_events(*_events(ProfileData.from_file(str(path))))


def on_trace_clock(t: float, offset_ns: float) -> float:
    """A jsonl span time (``perf_counter`` seconds, any process of the
    host) on the trace's clock, in ns."""
    return t * 1e9 + offset_ns


def control_dir(window) -> Optional[Path]:
    """The run's control directory (``harness.Session.ctl``, made by
    ``tempfile.mkdtemp(prefix="chipbench-")``): the one whose
    ``window.json`` holds this window's start."""
    for d in Path(tempfile.gettempdir()).glob("chipbench-*"):
        try:
            if json.loads((d / "window.json").read_text())["t0"] == window.t0:
                return d
        except (OSError, ValueError, KeyError):
            continue
    return None


def of(run) -> list:
    """The phases of each chip's trace, held on ``run`` as
    ``run.phases`` from the first call on.  Prints on stderr, per chip,
    the idle time under no program region and the idle time by region."""
    if not hasattr(run, "phases"):
        ctl = control_dir(run.window)
        found = [reduce(p) for p in tracefile.find(ctl)] if ctl else []
        run.phases = [p for p in found if p is not None]
        for i, p in enumerate(run.phases):
            share = 100.0 * p.unattributed_s / p.idle_s if p.idle_s else 0.0
            print(f"phases, chip {i}: idle under no serve.*/engine.* region"
                  f" {p.unattributed_s} s of {p.idle_s} s idle"
                  f" ({share}% of the window's idle time) in a"
                  f" {p.window_s} s window; idle by region"
                  f" {json.dumps(p.idle_in)}; engine.decode regions"
                  f" {len(p.decode_idle_s)}; device s by XLA module"
                  f" {json.dumps(p.modules)}", file=sys.stderr, flush=True)
    return run.phases


def idle_share(run, name: str) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device while the serve loop was in region ``name``, in %, averaged
    over the chips; None where the program names no ``serve.*``
    region."""
    ps = [p for p in of(run) if p.serve_seen and p.window_s > 0]
    if not ps:
        return None
    return 100.0 * sum(p.idle_in.get(name, 0.0) / p.window_s
                       for p in ps) / len(ps)
