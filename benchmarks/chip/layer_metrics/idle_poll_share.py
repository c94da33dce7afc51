"""Share of the traced window in which no operation ran on the device
while the serve loop was in ``serve.poll`` (waiting on the request
channel with work held: the admission poll between decode steps, or the
batcher's deadline), in %, averaged over the chips."""
from benchmarks.chip import phases


def read(run):
    return phases.idle_share(run, "serve.poll")
