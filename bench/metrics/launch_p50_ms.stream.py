"""Engine, in the stream cells: median over the bucket calls in the traced
window of their ``dispatch.upload`` and ``dispatch.call`` spans together,
paired to their ``dispatch b<bucket>`` by its span id: the host's time
from entering a call until the device holds the work."""
from bench.phases import launch_seconds
from bench.traffic import percentile


def read(run):
    d = launch_seconds(run.trace.spans)
    return percentile(d, 50) * 1e3 if d else None
