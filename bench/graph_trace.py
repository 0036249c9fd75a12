"""The device ops of a traced U-Net-like tower, step by step.

Each step (one jitted bucket call on one chip) runs every deconvolution
layer's Pallas kernel once, in layer order, named
``deconv2d_l<i>_halo_reverse_loop``.  Everything else the step runs is
XLA's: the encoder's convolutions with their fused bias, BatchNorm and
LeakyReLU, the skip joins (concatenation, ReLU and the kernels' halo
padding, fused), and the relayouts XLA adds.  The program names the
convolutions ``conv2d_l<i>`` and the joins ``skip_concat_l<i>`` with
`jax.named_scope`, but XLA keeps a scope only in an op's ``op_name``
metadata: the fusions are named by XLA (``fusion.11``), one fusion may
hold two layers' convolutions, and `bench.trace_reduce` keeps each op's
instruction name and result type alone.  So this module matches ops by
that name: a ``deconv2d_l<i>`` prefix is a kernel, and every other op of
the step is the XLA part.

A chip's ops are cut into steps after each run of the last deconvolution
layer's kernel: a step is the ops after one such kernel up to and
including the next.  The first cut, which begins with the trace, is
dropped, and a step counts only if it ran each kernel exactly once.  Its
batch is the bucket of the ``dispatch b<bucket>`` call whose span covers
its last kernel (a step covered by calls of several buckets is dropped),
over the chips.
"""
from __future__ import annotations

import re
from typing import Dict, List

KERNEL = re.compile(r"deconv2d_l(\d+)_")
CALL = re.compile(r"dispatch b(\d+)")


def deconv_layers(cfg: Dict) -> List[int]:
    return [i for i, l in enumerate(cfg["layers"]) if l["kind"] == "deconv"]


def steps(run) -> List[Dict]:
    """Per complete step on any chip: ``batch`` (rows on that chip),
    ``kernel_s`` (device seconds of its deconvolution kernels) and
    ``xla_s`` (of its other ops)."""
    t = run.trace
    want = deconv_layers(run.cfg)
    last = want[-1]
    calls = [(s, e, int(m.group(1))) for n, _, s, e, _ in t.spans
             if (m := CALL.fullmatch(n))]
    out = []
    for ops in t.ops.values():
        cur: List[tuple] = []
        first = True
        for name, s, e in sorted(ops, key=lambda o: o[1]):
            cur.append((name, s, e))
            m = KERNEL.match(name)
            if not m or int(m.group(1)) != last:
                continue
            step, cur = cur, []
            if first:
                first = False
                continue
            kernels = sorted(int(k.group(1)) for n, _, _ in step
                             if (k := KERNEL.match(n)))
            mid = 0.5 * (s + e)
            buckets = {b for cs, ce, b in calls if cs <= mid <= ce}
            if kernels != want or len(buckets) != 1:
                continue
            kernel_s = sum(oe - os for n, os, oe in step if KERNEL.match(n))
            xla_s = sum(oe - os for n, os, oe in step
                        if not KERNEL.match(n))
            out.append({"batch": buckets.pop() // run.chips,
                        "kernel_s": kernel_s, "xla_s": xla_s})
    return out
