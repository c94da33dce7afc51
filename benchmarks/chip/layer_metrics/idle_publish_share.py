"""Share of the traced window in which no operation ran on the device
while the serve loop was in ``serve.publish`` (one broker round trip per
result, under the fused put-claim), in %, averaged over the chips."""
from benchmarks.chip import phases


def read(run):
    return phases.idle_share(run, "serve.publish")
