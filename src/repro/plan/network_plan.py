"""Whole-generator execution plan: the repo's analogue of a pinned bitstream.

`NetworkPlan` composes one `DeconvPlan` per generator layer and owns
everything the serving stack used to re-decide per call: the autotune
cache interaction (each layer's plan hash is its cache key), precision
selection (fp32 vs the calibrated int8 chain), the zero-skip schedules,
and the roofline/traffic estimates.  A deployment serializes the plan
(`to_json`) next to its checkpoint and reloads it (`from_json`) to serve
exactly the configuration that was validated — the way the paper's FPGA
deployment pins a bitstream.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..core.tiling import tap_fold_group
from .deconv_plan import (PLAN_SCHEMA_VERSION, DeconvPlan, PlanSchemaError,
                          build_layer_plan)

PRECISIONS = ("fp32", "int8")


@dataclasses.dataclass(frozen=True)
class NetworkPlan:
    """Per-layer `DeconvPlan`s plus the network-level choices that bind
    them: backend, precision, the (per-device) batch every layer's tiles
    were fitted to, and — for int8 — the calibration strategy the layer
    scales came from."""

    name: str
    backend: str
    precision: str
    batch: int
    layers: Tuple[DeconvPlan, ...]
    quant_strategy: Optional[str] = None
    # canonical `repro.workloads` registry name (None on legacy plans
    # pinned before the workload zoo existed — their hashes are stable)
    workload: Optional[str] = None
    schema_version: int = PLAN_SCHEMA_VERSION

    def __post_init__(self):
        if self.precision not in PRECISIONS:
            raise ValueError(f"unknown precision {self.precision!r}; "
                             f"expected one of {PRECISIONS}")

    # -- executor-facing views -----------------------------------------
    def tile_overrides(self) -> Optional[Dict[int, Any]]:
        """Per-layer TileChoice map (what generator_apply consumes) of the
        deconvolution layers, or None for backends without tile factors
        (forward-convolution layers never have any)."""
        deconvs = [(i, l) for i, l in enumerate(self.layers)
                   if l.kind == "deconv"]
        if any(l.tiles is None for _, l in deconvs):
            return None
        return {i: l.tiles for i, l in deconvs}

    def tap_folded_layers(self) -> int:
        """Deconvolution layers the dense float kernel runs tap-folded:
        those whose output-channel tile `core.tiling.tap_fold_group`
        folds.  The int8 and zero-skip kernels fold none."""
        if self.backend != "pallas" or self.precision != "fp32":
            return 0
        return sum(1 for l in self.layers
                   if l.kind == "deconv" and l.tiles is not None
                   and tap_fold_group(l.geometry.kernel, l.tiles.t_co) > 1)

    def sparse_plans(self) -> Optional[Dict[int, tuple]]:
        """Per-layer zero-skip schedules for backend="pallas_sparse"."""
        if self.backend != "pallas_sparse":
            return None
        if any(l.sparse_tables is None for l in self.layers):
            return None
        return {i: l.sparse_tables for i, l in enumerate(self.layers)}

    def quant_config(self):
        """Reconstruct the `quant.calibrate.QuantConfig` pinned in the
        per-layer plans (None for fp32 plans)."""
        if self.precision != "int8":
            return None
        from ..quant.calibrate import QuantConfig

        if any(l.quant is None for l in self.layers):
            raise ValueError("int8 plan is missing per-layer quant scales")
        return QuantConfig(
            name=self.name,
            strategy=self.quant_strategy or "mean_ksigma",
            layers=tuple(l.quant for l in self.layers),
        )

    def validate_for(self, cfg) -> None:
        """Reject a plan built for a different network geometry (the
        plan/params mismatch a pinned deployment must fail loudly on)."""
        geoms = list(cfg.geometries())
        if len(geoms) != len(self.layers):
            raise ValueError(
                f"plan '{self.name}' has {len(self.layers)} layers; "
                f"{cfg.name} has {len(geoms)}")
        for i, (g, l, c) in enumerate(zip(geoms, self.layers, cfg.layers)):
            if g != l.geometry:
                raise ValueError(
                    f"plan layer {i} geometry {l.geometry} does not match "
                    f"{cfg.name} layer {i} geometry {g}")
            want = (c.norm, c.skip, c.in_activation, cfg.dot_precision)
            if (l.norm, l.skip, l.in_activation, l.dot_precision) != want:
                raise ValueError(
                    f"plan layer {i} topology {l.topology()} does not "
                    f"match {cfg.name} layer {i} (norm, skip, "
                    f"in_activation, dot_precision) {want}")

    def verify_sparse_tables(self, params) -> None:
        """Fail loudly when a pinned pallas_sparse plan's zero-skip
        schedules no longer match the weights about to be served (e.g.
        the checkpoint was re-pruned after the plan was pinned) — a stale
        schedule would silently skip now-nonzero blocks.  One O(weights)
        host pass; call it where plan and concrete params meet (the
        serving engine does at construction)."""
        if self.backend != "pallas_sparse":
            return
        from ..kernels.deconv2d_sparse import make_sparse_plan
        from .deconv_plan import _sparse_digest

        for i, l in enumerate(self.layers):
            if l.sparse_digest is None:
                continue
            g = l.geometry
            want = _sparse_digest(make_sparse_plan(
                np.asarray(params[f"l{i}"]["w"]), g.stride, g.padding,
                l.tiles.t_ci, l.tiles.t_co))
            if want != l.sparse_digest:
                raise ValueError(
                    f"layer {i}: the pinned zero-skip schedule "
                    f"({l.sparse_digest}) does not match the schedule of "
                    f"the weights being served ({want}); the plan is "
                    "stale — re-plan against these params")

    # -- hashing / serialization ---------------------------------------
    def stable_hash(self) -> str:
        import hashlib

        d = {"schema": self.schema_version, "name": self.name,
             "backend": self.backend, "precision": self.precision,
             "batch": self.batch, "quant_strategy": self.quant_strategy,
             "layers": [l.request_dict("full") for l in self.layers]}
        # keyed in only when set, so legacy (pre-zoo) plan hashes hold
        if self.workload is not None:
            d["workload"] = self.workload
        blob = json.dumps(d, sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()[:24]

    def to_json(self, path: Optional[str] = None) -> str:
        s = json.dumps({
            "schema": self.schema_version,
            "kind": "repro.NetworkPlan",
            "name": self.name,
            "backend": self.backend,
            "precision": self.precision,
            "batch": self.batch,
            "quant_strategy": self.quant_strategy,
            "workload": self.workload,
            "stable_hash": self.stable_hash(),
            "layers": [l.to_json_dict() for l in self.layers],
        }, indent=1, sort_keys=True)
        if path is not None:
            with open(path, "w") as f:
                f.write(s)
        return s

    @classmethod
    def from_json(cls, s: str) -> "NetworkPlan":
        try:
            d = json.loads(s)
        except ValueError as e:
            raise PlanSchemaError(f"not a NetworkPlan JSON document: {e}")
        if not isinstance(d, dict) or d.get("kind") != "repro.NetworkPlan":
            raise PlanSchemaError(
                "not a NetworkPlan JSON document (missing kind tag)")
        if d.get("schema") != PLAN_SCHEMA_VERSION:
            raise PlanSchemaError(
                f"NetworkPlan schema {d.get('schema')!r} is not the "
                f"supported v{PLAN_SCHEMA_VERSION}; re-plan with this "
                "version instead of executing a stale configuration")
        plan = cls(
            name=d["name"], backend=d["backend"], precision=d["precision"],
            batch=int(d["batch"]), quant_strategy=d.get("quant_strategy"),
            workload=d.get("workload"),
            layers=tuple(DeconvPlan.from_json_dict(l) for l in d["layers"]),
        )
        want = d.get("stable_hash")
        if want is not None and plan.stable_hash() != want:
            raise PlanSchemaError(
                "NetworkPlan content hash mismatch: the document was "
                "edited after it was pinned")
        return plan

    @classmethod
    def load(cls, path: str) -> "NetworkPlan":
        with open(path) as f:
            return cls.from_json(f.read())

    # -- roofline / traffic estimates ----------------------------------
    def traffic_report(self) -> Dict[int, Any]:
        """Per-layer modeled HBM traffic (`core.tiling.DeconvTraffic`) at
        this plan's batch and tiles; empty for non-tiled backends."""
        from ..core.tiling import deconv_traffic_batched

        out: Dict[int, Any] = {}
        for i, l in enumerate(self.layers):
            if l.tiles is None:
                continue
            t = l.tiles
            out[i] = deconv_traffic_batched(
                l.geometry, self.batch, t.t_n, t.t_oh, t.t_ow, t.t_ci,
                t.t_co, l.dtype_bytes, out_dtype_bytes=l.out_dtype_bytes)
        return out

    def modeled_attainable(self, device=None) -> Dict[int, Any]:
        """Per-layer roofline `DsePoint` at this plan's tiles."""
        from ..core.dse import TPU_V5E, tile_attainable

        device = TPU_V5E if device is None else device
        out: Dict[int, Any] = {}
        for i, l in enumerate(self.layers):
            if l.tiles is None:
                continue
            t = l.tiles
            out[i] = tile_attainable(
                l.geometry, t.t_oh, t.t_ow, t.t_ci, t.t_co, device,
                t_n=t.t_n, batch=self.batch, dtype_bytes=l.dtype_bytes,
                out_dtype_bytes=l.out_dtype_bytes)
        return out

    def modeled_network_ops(self, device=None) -> Optional[float]:
        """Whole-network modeled throughput (total ops / sum of per-layer
        roofline times) — the paper's network metric; None if untiled."""
        pts = self.modeled_attainable(device)
        if len(pts) != len(self.layers):
            return None
        total_ops = sum(l.geometry.ops * self.batch for l in self.layers)
        total_t = sum(l.geometry.ops * self.batch / pts[i].attainable_ops
                      for i, l in enumerate(self.layers))
        return total_ops / total_t


def build_network_plan(
    cfg,
    *,
    batch: int = 1,
    backend: str = "pallas",
    precision: str = "fp32",
    params=None,
    quant_cfg=None,
    calib_batch: int = 64,
    calib_seed: int = 0,
    calib_strategy: str = "mean_ksigma",
    autotune: bool = True,
    refine: bool = False,
    device=None,
    sparse_table_cache: Optional[Dict] = None,
) -> NetworkPlan:
    """Plan a whole generator (``cfg`` is a `models.dcnn.DcnnConfig`).

    ``batch`` is the batch every layer's kernel will actually see — a
    serving bucket on one device, or the per-device sub-batch on a mesh.
    For precision="int8" a ``quant_cfg`` pins pre-calibrated scales;
    without one, ``params`` are calibrated here (statistical observers on
    the z ~ N(0,1) serving distribution).  For backend="pallas_sparse",
    ``params`` supply the static pruned weights the zero-skip schedules
    are compiled from.  Timing cost: plan building is the ONLY place tile
    resolution happens — executors run the pinned plan with zero per-call
    re-planning."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"expected one of {PRECISIONS}")
    if precision == "int8" and backend != "pallas":
        raise ValueError(
            "precision='int8' runs the dense int8 Pallas kernel; "
            f"backend={backend!r} has no quantized variant")
    if backend == "pallas_sparse" and params is None:
        # a weightless sparse plan would re-derive the O(weights) schedule
        # on every call (and crash under an outer jit) — the exact
        # per-call re-planning this API exists to eliminate
        raise ValueError(
            "backend='pallas_sparse' planning needs params: the zero-skip "
            "schedule is compiled from the static pruned weights")
    geoms = list(cfg.geometries())
    if cfg.is_graph and (precision != "fp32" or backend == "pallas_sparse"):
        raise ValueError(
            f"{cfg.name} is not a chain of deconvolutions: it plans at "
            "precision='fp32' on the pallas, xla or reverse_loop backend")
    if precision == "int8" and quant_cfg is None:
        if params is None:
            raise ValueError(
                "int8 planning needs either a pre-computed quant_cfg or "
                "params to calibrate")
        from ..quant.calibrate import calibrate
        from ..workloads import calibration_input

        # N(0,1) latents for generative towers, workload-synthesized
        # inputs for image-rooted ones; deterministic in calib_seed so
        # the engine's independent self-calibration lands the same scales
        z_cal = calibration_input(cfg, seed=calib_seed, batch=calib_batch)
        quant_cfg = calibrate(params, cfg, z_cal, strategy=calib_strategy)

    dtype = np.dtype(np.int8) if precision == "int8" else np.dtype(cfg.dtype)
    int8_chain = precision == "int8"
    layers = []
    for i, (g, l) in enumerate(zip(geoms, cfg.layers)):
        last = i == len(geoms) - 1
        layers.append(build_layer_plan(
            g,
            batch=batch,
            dtype=dtype,
            backend=backend,
            # a BatchNorm comes between the convolution and its
            # activation: the kernel's epilogue then stops at the bias
            activation=None if l.norm else l.activation,
            out_scale=(quant_cfg.out_scale(i) if int8_chain else None),
            # the int8 chain's final epilogue emits f32 images while every
            # intermediate layer re-quantizes to int8 (matches the
            # dtype-aware autotuner's pricing)
            out_dtype_bytes=(4 if int8_chain and last else None),
            quant=(quant_cfg.layers[i] if int8_chain else None),
            # only the zero-skip schedule needs the raw weights (an int8
            # engine holds a quantized tree without "w" leaves by now)
            weights=(params[f"l{i}"]["w"]
                     if backend == "pallas_sparse" and params is not None
                     else None),
            autotune=autotune,
            refine=refine,
            device=device,
            sparse_table_cache=sparse_table_cache,
            sparse_cache_key=i,
            norm=l.norm,
            skip=l.skip,
            in_activation=l.in_activation,
            dot_precision=cfg.dot_precision,
        ))
    from ..workloads import workload_name_for

    return NetworkPlan(
        name=cfg.name, backend=backend, precision=precision, batch=batch,
        layers=tuple(layers),
        quant_strategy=(quant_cfg.strategy if int8_chain else None),
        workload=workload_name_for(cfg),
    )


def executable_fingerprints(plans) -> Dict[int, str]:
    """{per-device batch -> stable hash} over a collection of
    `NetworkPlan`s — the "same executable everywhere" check.

    Two plans that agree on the per-device batch must agree on the hash:
    one mesh's bucket-16 at 8 devices is another's bucket-8 at 4, and a
    deployment that cannot prove that identity is running an executable
    nobody validated.  The elastic serving engine records these before
    and after a device-loss remesh and asserts the overlap matches;
    multi-host deployments can compare the fingerprints of the plan
    JSONs each host pinned.  Raises on an internal conflict (two plans
    for the same per-device batch that disagree)."""
    out: Dict[int, str] = {}
    for p in plans:
        h = p.stable_hash()
        prev = out.setdefault(p.batch, h)
        if prev != h:
            raise ValueError(
                f"two plans for per-device batch {p.batch} disagree: "
                f"{prev} vs {h}")
    return out


def variant_fingerprints(plans) -> Dict[str, str]:
    """{"b{per-device batch}/{precision}" -> stable hash} over plans that
    span *precision variants* (the async frontend pins one plan per
    bucket x precision: the fp32 chain and its int8 degradation both
    serve the same bucket, so `executable_fingerprints`' batch-only key
    would see a false conflict).  Same contract otherwise: two plans for
    the same (batch, precision) must agree on the hash, and a deployment
    compares these dicts across hosts / across a remesh to prove "same
    executables everywhere"."""
    out: Dict[str, str] = {}
    for p in plans:
        key = f"b{p.batch}/{p.precision}"
        h = p.stable_hash()
        prev = out.setdefault(key, h)
        if prev != h:
            raise ValueError(
                f"two plans for {key} disagree: {prev} vs {h}")
    return out


def timed_build(fn, *args, **kwargs):
    """(result, seconds) helper for plan-build cost accounting."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0
