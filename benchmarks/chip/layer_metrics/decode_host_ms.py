"""The host's part of a decode step: device-idle time inside each of
the program's ``engine.decode`` regions (the jitted step's dispatch, the
argmax, the position's transfer and the tokens' read-back), mean per
region, in ms."""
from benchmarks.chip import phases


def read(run):
    idle = [s for p in phases.of(run) for s in p.decode_idle_s]
    return 1e3 * sum(idle) / len(idle) if idle else None
