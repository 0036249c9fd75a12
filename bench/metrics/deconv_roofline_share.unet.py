"""Kernels, U-Net cells: least time over device time of the decoder's
Pallas deconvolution kernels (the kernel every cell shares, here at
2x2 to 256x256 outputs), over the complete steps of the traced window
(`bench.graph_trace`).  A layer call's least time is the larger of its
operations over the matmul peak and its bytes over the memory bandwidth
(`bench.shapes_graph`)."""
from bench import graph_trace, shapes_graph


def read(run):
    peak = run.shapes.peaks(run.device_kind)
    least = measured = 0.0
    for st in graph_trace.steps(run):
        least += shapes_graph.least_seconds_by_kind(
            run.cfg, st["batch"], peak)["deconv"]
        measured += st["kernel_s"]
    return 100.0 * least / measured if measured > 0.0 else None
