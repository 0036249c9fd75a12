"""Kernels: least time over measured time of the deconvolution kernels.

Every bucket call the engine made inside the traced window (its
``dispatch b<bucket>`` span) runs each layer once, on every chip, at the
bucket's per-chip batch.  The least time of a layer call is the larger of
its operations over the matmul peak and its bytes over the memory
bandwidth (`bench.shapes`).  The measured time is the device time of the
``deconv2d*`` operations that ran inside the call's span.  A call counts
only if the trace holds all of its kernels."""
import bisect
import re

KERNEL = "deconv2d"
CALL = re.compile(r"dispatch b(\d+)")


def read(run):
    t = run.trace
    peak = run.shapes.peaks(run.device_kind)
    calls = sorted((s, e, int(m.group(1))) for n, _, s, e, _ in t.spans
                   if (m := CALL.fullmatch(n)) and e <= t.t_b)
    starts = [c[0] for c in calls]
    seconds = [0.0] * len(calls)
    kernels = [0] * len(calls)
    for ops in t.ops.values():
        for name, s, e in ops:
            if not name.startswith(KERNEL):
                continue
            mid = 0.5 * (s + e)
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid <= calls[i][1]:
                seconds[i] += e - s
                kernels[i] += 1
    whole = len(run.cfg["layers"]) * run.chips
    least = measured = 0.0
    for (_, _, bucket), sec, n in zip(calls, seconds, kernels):
        if n != whole:
            continue
        costs = run.shapes.layer_costs(run.cfg, bucket // run.chips)
        least += run.chips * sum(run.shapes.least_seconds(c, peak)[0]
                                 for c in costs)
        measured += sec
    return 100.0 * least / measured if measured > 0.0 else None
