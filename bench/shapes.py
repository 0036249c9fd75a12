"""Operations and bytes of a deconvolution tower, from its sizes alone.

A transposed-convolution layer multiplies every input pixel by every tap:
``N * H_in * W_in * K * K * C_in * C_out`` multiply-adds, two operations
each.  The least bytes a layer must move are its input, weights and bias
read once and its output written once.  Both are what the algorithm
needs, whatever a kernel does.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List

import numpy as np

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    raises."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json knows {sorted(table)}")
    return table[device_kind]


def layer_costs(cfg: Dict, batch: int) -> List[Dict[str, float]]:
    """Per layer, at ``batch`` rows: ``flops`` and least ``bytes``."""
    item = np.dtype(cfg["dtype"]).itemsize
    out, h = [], 1
    for l in cfg["layers"]:
        k, s, p = l["kernel"], l["stride"], l["padding"]
        ho = (h - 1) * s + k - 2 * p
        macs = batch * h * h * k * k * l["c_in"] * l["c_out"]
        elems = (batch * h * h * l["c_in"] + k * k * l["c_in"] * l["c_out"]
                 + l["c_out"] + batch * ho * ho * l["c_out"])
        out.append({"flops": 2.0 * macs, "bytes": float(item * elems)})
        h = ho
    return out


def flops_per_row(cfg: Dict) -> float:
    return sum(c["flops"] for c in layer_costs(cfg, 1))


def least_seconds(cost: Dict[str, float], peak: Dict[str, float]):
    """(least seconds, "compute" or "memory") of one layer call."""
    t_c = cost["flops"] / peak["matmul_flops_per_s"]
    t_m = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
