"""The system under test for towers that are not chains: the same serving
stack as `bench/systems/dcnn_frontend.py`, for an image-rooted tower
whose layers may be strided forward convolutions, carry an eval-mode
BatchNorm and join an earlier layer's output (the pix2pix U-Net).

The configuration's layers become one `models.dcnn.DcnnConfig` (each
layer's ``kind``, ``norm``, ``skip``, ``in_activation`` and
``activation``; ``bias`` only says which layers the reference draws a
bias for).  Requests enter `AsyncServeFrontend` for one tenant with no
deadline and no degradation, over `DcnnServeEngine.from_config`, its
per-bucket `NetworkPlan`s, XLA's convolutions for the encoder and the
Pallas kernels for the decoder, each BatchNorm applied after its
convolution.  ``dot_operands`` "float32" serves every product in float32
(`DcnnConfig.dot_precision` "highest"); "bfloat16" at JAX's default.
"""
from __future__ import annotations

from typing import Dict

from bench.systems.dcnn_frontend import TENANT
from bench.systems.dcnn_frontend import System as ChainSystem

LAYER_KEYS = ("c_in", "c_out", "kernel", "stride", "padding", "activation",
              "kind", "norm", "skip", "in_activation")
DOT_PRECISION = {"float32": "highest", "bfloat16": None}


def model_config(cfg: Dict):
    """The program's `DcnnConfig` for the configuration file ``cfg``."""
    from repro.models.dcnn import DcnnConfig, DeconvLayerCfg

    return DcnnConfig(
        name=cfg["name"], z_dim=0, img_hw=cfg["img_hw"], img_c=cfg["img_c"],
        in_hw=cfg["in_hw"], dtype=cfg["dtype"],
        dot_precision=DOT_PRECISION[cfg["dot_operands"]],
        layers=tuple(DeconvLayerCfg(**{k: l.get(k) for k in LAYER_KEYS})
                     for l in cfg["layers"]))


class System(ChainSystem):
    """Engine and frontend built from a configuration file's ``engine``
    and ``frontend`` sections, as `dcnn_frontend.System` builds them."""

    def __init__(self, cfg: Dict, params, precision: str = None):
        from repro.launch.mesh import make_serving_mesh
        from repro.obs import trace as obstrace
        from repro.serve import (AsyncServeFrontend, DcnnServeEngine,
                                 EngineConfig, TenantClass)

        model = model_config(cfg)
        e = cfg["engine"]
        mesh = (make_serving_mesh(e["mesh_devices"]) if e["mesh_devices"]
                else None)
        self.engine = DcnnServeEngine.from_config(EngineConfig(
            model=model, backend=e["backend"],
            precision=precision or e["precision"], max_batch=e["max_batch"],
            mesh=mesh), params)
        self.frontend = AsyncServeFrontend(
            {"fp32": self.engine},
            [TenantClass(TENANT, slo_ms=None, allow_degrade=False)],
            max_queue_rows=cfg["frontend"]["max_queue_rows"], start=False)
        self.tracer = obstrace.get_tracer()
        self.metrics = self.engine.metrics
        self.row_shape = model.input_shape
        self.max_bucket = self.engine.max_bucket
