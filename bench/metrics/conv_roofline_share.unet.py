"""Kernels, U-Net cells: least time over device time of the step's XLA
part, the encoder's convolutions (``conv2d_l<i>``) with the skip joins
(``skip_concat_l<i>``), over the complete steps of the traced window.
The device time is that of every op of a step other than the
deconvolution kernels: XLA names its fusions itself, so the layers' scopes
are not in the trace's op names (`bench.graph_trace`), and relayouts XLA
adds count too.  The least time is the convolutions' (operations or
bytes, whichever bounds) and the joins' (bytes) from `bench.shapes_graph`."""
from bench import graph_trace, shapes_graph


def read(run):
    peak = run.shapes.peaks(run.device_kind)
    least = measured = 0.0
    for st in graph_trace.steps(run):
        by_kind = shapes_graph.least_seconds_by_kind(run.cfg, st["batch"],
                                                     peak)
        least += by_kind["conv"] + by_kind.get("join", 0.0)
        measured += st["xla_s"]
    return 100.0 * least / measured if measured > 0.0 else None
