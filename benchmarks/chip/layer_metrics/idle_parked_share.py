"""Share of the traced window in which no operation ran on the device
while the serve loop was in ``serve.park`` (no group decoding, nothing
pending: idle for want of traffic), in %, averaged over the chips.  At
a fixed offered load a shard that serves faster waits longer."""
from benchmarks.chip import phases


def read(run):
    return phases.idle_share(run, "serve.park")
