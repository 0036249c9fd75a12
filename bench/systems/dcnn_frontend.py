"""The system under test: the DCNN serving stack as a user runs it.

Requests enter `AsyncServeFrontend.submit` / `.result` for one tenant with
no deadline and no degradation, so every request is served in the
configuration's precision and the whole tail is measured.  The frontend
sits over `DcnnServeEngine.from_config`, its per-bucket `NetworkPlan`s and
the Pallas kernels, all as the program has them.
"""
from __future__ import annotations

from typing import Dict, Iterable, List

import numpy as np

TENANT = "t"


def wave_sizes(request_sizes: Iterable[int], max_rows: int) -> List[int]:
    """Every row count one frontend wave can carry: the frontend packs
    queued requests into a wave up to ``max_rows``, and a larger request
    rides alone."""
    sizes = sorted(set(int(s) for s in request_sizes))
    reach = [False] * (max_rows + 1)
    reach[0] = True
    for total in range(1, max_rows + 1):
        reach[total] = any(s <= total and reach[total - s] for s in sizes)
    return [n for n in range(1, max_rows + 1) if reach[n]] + [
        s for s in sizes if s > max_rows]


class System:
    """Engine and frontend built from a configuration file's ``engine``
    and ``frontend`` sections.  ``precision`` overrides the configured one
    (the lower-precision control runs the int8 path in the same place)."""

    def __init__(self, cfg: Dict, params, precision: str = None):
        from repro.launch.mesh import make_serving_mesh
        from repro.models.dcnn import DcnnConfig, DeconvLayerCfg
        from repro.obs import trace as obstrace
        from repro.serve import (AsyncServeFrontend, DcnnServeEngine,
                                 EngineConfig, TenantClass)

        model = DcnnConfig(
            name=cfg["name"], z_dim=cfg["z_dim"], img_hw=cfg["img_hw"],
            img_c=cfg["img_c"], dtype=cfg["dtype"],
            layers=tuple(DeconvLayerCfg(**l) for l in cfg["layers"]))
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

    def warm(self, request_sizes: Iterable[int]) -> List[int]:
        """Compile and run every bucket that the given request sizes can
        reach through the frontend's waves, and no other; returns them."""
        buckets = sorted({b for n in wave_sizes(request_sizes,
                                                 self.max_bucket)
                          for _, b in self.engine.plan_chunks(n)})
        for b in buckets:
            z = np.zeros((b,) + self.row_shape, np.float32)
            for _ in range(2):
                self.engine.generate(z)
        self.frontend.start()
        return buckets

    def compiles(self) -> int:
        return self.engine.total_compiles

    def dispatch_mean_ms(self) -> Dict[int, float]:
        """Mean wall clock of the engine's steady bucket calls so far."""
        return {b: round(t["mean_s"] * 1e3, 4)
                for b, t in sorted(self.engine.throughput().items())}

    def submit(self, z: np.ndarray):
        return self.frontend.submit(z, TENANT)

    def result(self, handle, timeout_s: float) -> np.ndarray:
        return self.frontend.result(handle, timeout_s=timeout_s)

    def devices(self):
        if self.engine.mesh is not None:
            return list(self.engine.mesh.devices.flat)
        import jax
        return jax.devices()[:1]

    def close(self) -> None:
        self.frontend.close()
