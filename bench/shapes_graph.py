"""Operations and bytes of a U-Net-like tower, from its sizes alone.

The configuration lists its layers in order, each a strided forward
convolution (``"kind": "conv"``) or a transposed convolution
(``"kind": "deconv"``) reading the previous layer's output, joined with an
earlier layer's output where it names a ``skip``.  Per layer:

* a forward convolution multiplies every *output* pixel by every tap:
  ``N * H_out * W_out * K * K * C_in * C_out`` multiply-adds;
* a transposed convolution multiplies every *input* pixel by every tap:
  ``N * H_in * W_in * K * K * C_in * C_out`` multiply-adds (`bench.shapes`);
* a skip join (``skip_concat_l<i>``) reads both of its inputs and writes
  their concatenation, and computes nothing on the matrix unit.

Two operations per multiply-add.  The least bytes of a layer are its
input, weights and bias read once and its output written once.  Both are
what the algorithm needs, whatever a kernel does.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def out_extent(layer: Dict, h: int) -> int:
    k, s, p = layer["kernel"], layer["stride"], layer["padding"]
    if layer["kind"] == "conv":
        return (h + 2 * p - k) // s + 1
    return (h - 1) * s + k - 2 * p


def layer_costs(cfg: Dict, batch: int) -> List[Dict]:
    """Per layer and skip join, in the order they run, at ``batch`` rows:
    ``name`` (``conv2d_l<i>``, ``deconv2d_l<i>`` or ``skip_concat_l<i>``),
    ``kind`` ("conv", "deconv" or "join"), ``flops`` and least ``bytes``."""
    item = np.dtype(cfg["dtype"]).itemsize
    out: List[Dict] = []
    sizes: List[tuple] = []     # each layer's output (extent, channels)
    h, c = cfg["in_hw"], cfg["in_c"]
    for i, l in enumerate(cfg["layers"]):
        if l.get("skip") is not None:
            hs, cs = sizes[l["skip"]]
            joined = batch * h * h * (cs + c)
            out.append({"name": f"skip_concat_l{i}", "kind": "join",
                        "flops": 0.0, "bytes": float(item * 2 * joined)})
            c += cs
        k, ho = l["kernel"], out_extent(l, h)
        pixels = ho if l["kind"] == "conv" else h
        macs = batch * pixels * pixels * k * k * l["c_in"] * l["c_out"]
        elems = (batch * h * h * l["c_in"] + k * k * l["c_in"] * l["c_out"]
                 + l["c_out"] + batch * ho * ho * l["c_out"])
        name = "conv2d" if l["kind"] == "conv" else "deconv2d"
        out.append({"name": f"{name}_l{i}", "kind": l["kind"],
                    "flops": 2.0 * macs, "bytes": float(item * elems)})
        h, c = ho, l["c_out"]
        sizes.append((h, c))
    return out


def flops_per_row(cfg: Dict) -> float:
    return sum(c["flops"] for c in layer_costs(cfg, 1))


def weight_count(cfg: Dict) -> int:
    """Parameters: each layer's weights and bias, and a BatchNorm's
    gamma, beta and two running statistics."""
    return sum(l["kernel"] ** 2 * l["c_in"] * l["c_out"] + l["c_out"]
               + (4 * l["c_out"] if l.get("norm") == "batch" else 0)
               for l in cfg["layers"])


def least_seconds_by_kind(cfg: Dict, batch: int, peak: Dict) -> Dict:
    """Least seconds of one step at ``batch`` rows, summed per ``kind``:
    each layer's larger of operations over the matmul peak and bytes
    over the memory bandwidth."""
    out: Dict[str, float] = {}
    for c in layer_costs(cfg, batch):
        t = max(c["flops"] / peak["matmul_flops_per_s"],
                c["bytes"] / peak["hbm_bytes_per_s"])
        out[c["kind"]] = out.get(c["kind"], 0.0) + t
    return out
