"""Frozen per-layer execution plan for the deconv kernels.

The paper's accelerator decides geometry, tiling, precision and sparsity
handling once at design time and then executes the fixed datapath at
inference (Sec. III; Zhang et al. formalize the plan-then-execute split).
`DeconvPlan` is that design point for one deconv layer on the TPU stack:
it pins the layer geometry, the resolved tile assignment (including the
batch tile ``t_n``), the dtype / calibrated quantization scales, the
zero-skip schedule, and the fused epilogue — everything a kernel wrapper
needs to dispatch without re-deciding anything per call.

Plans are frozen dataclasses: hashable, comparable, and serializable
(`to_json_dict`/`from_json_dict`).  `stable_hash` is a content digest of
the *planning inputs* — the autotune cache is keyed on it (schema v4), so
two requests differing in dtype, batch, backend or epilogue can never
silently alias one cache entry.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..core.tiling import ConvGeometry, DeconvGeometry
from ..kernels.autotune import TileChoice

# Bump when the serialized plan layout changes incompatibly.  Loaders
# refuse a stale schema outright (PlanSchemaError) — a silently mis-read
# plan would execute a different configuration than the one that was
# pinned, the exact failure the plan exists to prevent.
PLAN_SCHEMA_VERSION = 1


class PlanSchemaError(ValueError):
    """A serialized plan carries a schema this code cannot execute."""


def _sparse_digest(tables: Tuple[np.ndarray, np.ndarray, np.ndarray]) -> str:
    """Content hash of a zero-skip schedule (make_sparse_plan output)."""
    h = hashlib.sha256()
    for a in tables:
        a = np.ascontiguousarray(a)
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class DeconvPlan:
    """One layer's pinned execution configuration.

    Planning inputs (hashed by `stable_hash`):
      * ``geometry``  — the static layer geometry;
      * ``batch``     — the batch the tiles are fitted to (a serving
                        bucket's per-device sub-batch);
      * ``dtype``     — streamed element dtype name ("float32"/"int8");
      * ``backend``   — "pallas" | "pallas_sparse" (or a non-tiled
                        backend, in which case ``tiles`` stays None);
      * ``activation``/``out_scale``/``out_dtype_bytes`` — the fused
                        epilogue: bias+activation, optional int8 requant
                        into the next layer's scale, optional widened
                        output block (the last int8 layer emits f32);
      * ``quant``     — the calibrated `quant.calibrate.LayerQuant`
                        scales for int8 layers;
      * ``sparse_digest`` — content hash of the zero-skip schedule.

    Resolved execution state:
      * ``tiles``         — the `TileChoice` the kernel grid runs at;
      * ``sparse_tables`` — the host-built (ci_idx, valid, tap_mask)
                            schedule (excluded from equality/hash; its
                            ``sparse_digest`` stands in for it).

    The layer's place in a U-Net-like tower (hashed only when set, so a
    chain's plans keep their hashes):
      * ``kind``          — "deconv" (the Pallas kernels) or "conv" (a
                            strided forward convolution run by XLA, with
                            a `core.tiling.ConvGeometry` and no tiles);
      * ``norm``          — "batch": the layer's eval-mode BatchNorm,
                            applied after the kernel from its running
                            statistics;
      * ``skip``          — the earlier layer whose output is
                            concatenated before the previous one's;
      * ``in_activation`` — applied to the (joined) input;
      * ``dot_precision`` — the precision of the layer's products
                            (`models.dcnn.DcnnConfig.dot_precision`).
    """

    geometry: DeconvGeometry
    batch: int = 1
    dtype: str = "float32"
    backend: str = "pallas"
    activation: Optional[str] = None
    out_scale: Optional[float] = None
    out_dtype_bytes: Optional[int] = None
    quant: Optional[Any] = None            # quant.calibrate.LayerQuant
    sparse_digest: Optional[str] = None
    tiles: Optional[TileChoice] = None
    sparse_tables: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = \
        dataclasses.field(default=None, compare=False, repr=False)
    kind: str = "deconv"
    norm: Optional[str] = None
    skip: Optional[int] = None
    in_activation: Optional[str] = None
    dot_precision: Optional[str] = None

    # -- hashing --------------------------------------------------------
    def request_dict(self, scope: str = "full") -> Dict[str, Any]:
        """Canonical planning-input dict.

        ``scope="tiles"`` keeps only the fields the tile autotuner's
        choice depends on — the v4 cache key hashes exactly this subset,
        so e.g. two weight sets with different sparsity patterns share
        one tile entry (the zero-skip schedule is DMA-level, not a tile
        legality/ranking input) while dtype/batch/backend never alias.
        """
        d: Dict[str, Any] = {
            "schema": PLAN_SCHEMA_VERSION,
            "geometry": dataclasses.asdict(self.geometry),
            "batch": self.batch,
            "dtype": self.dtype,
            "backend": self.backend,
            "out_dtype_bytes": self.out_dtype_bytes,
        }
        if scope == "tiles":
            return d
        d.update({
            "activation": self.activation,
            "out_scale": self.out_scale,
            "quant": (dataclasses.asdict(self.quant)
                      if self.quant is not None else None),
            "sparse_digest": self.sparse_digest,
            "tiles": (self.tiles.as_kwargs()
                      if self.tiles is not None else None),
        })
        d.update(self.topology())
        return d

    def topology(self) -> Dict[str, Any]:
        """The fields that place the layer in a non-chain tower, those
        that are set (empty for a chain layer)."""
        d: Dict[str, Any] = {} if self.kind == "deconv" else {
            "kind": self.kind}
        for name in ("norm", "skip", "in_activation", "dot_precision"):
            if getattr(self, name) is not None:
                d[name] = getattr(self, name)
        return d

    def stable_hash(self, scope: str = "full") -> str:
        """Deterministic content digest of the plan.

        ``scope="full"`` pins the complete executable configuration
        (including the resolved tiles); ``scope="tiles"`` hashes only the
        tile-planning inputs and is what `kernels.autotune.cache_key`
        keys the v4 cache on."""
        blob = json.dumps(self.request_dict(scope), sort_keys=True,
                          separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()[:24]

    # -- convenience ----------------------------------------------------
    @property
    def dtype_bytes(self) -> int:
        return int(np.dtype(self.dtype).itemsize)

    def tile_kwargs(self) -> Dict[str, int]:
        if self.tiles is None:
            raise ValueError("plan has no resolved tiles "
                             f"(backend={self.backend!r})")
        return self.tiles.as_kwargs()

    def padded_geometry(self) -> Tuple[int, ...]:
        """The resolved `halo_pad_geometry` output for this plan's batch
        and tiles: ``(oh, ow, ohp, owp, pad_l, pad_rh, pad_rw, cip, cop,
        t_n, np_)`` — every address-arithmetic quantity the kernel's
        padding/grid depends on, pinned at plan time (the kernels
        recompute the same numbers from the same static inputs, so this
        is the documented/inspectable form, not a second source of
        truth)."""
        from ..core.offsets import make_phase_plan
        from ..kernels.deconv2d.ops import halo_pad_geometry

        g = self.geometry
        t = self.tiles
        if t is None:
            raise ValueError("plan has no resolved tiles "
                             f"(backend={self.backend!r})")
        pp = make_phase_plan(g.kernel, g.stride, g.padding)
        return halo_pad_geometry(self.batch, g.in_h, g.in_w, g.c_in,
                                 g.c_out, pp, t.t_oh, t.t_ow, t.t_ci,
                                 t.t_co, t.t_n)

    # -- (de)serialization ---------------------------------------------
    def to_json_dict(self) -> Dict[str, Any]:
        d = self.request_dict("full")
        if self.sparse_tables is not None:
            d["sparse_tables"] = [np.asarray(a).tolist()
                                  for a in self.sparse_tables]
        if self.tiles is not None:
            # keep the provenance/model fields the cache also stores
            d["tiles"] = dataclasses.asdict(self.tiles)
        return d

    @classmethod
    def from_json_dict(cls, d: Dict[str, Any]) -> "DeconvPlan":
        if d.get("schema") != PLAN_SCHEMA_VERSION:
            raise PlanSchemaError(
                f"DeconvPlan schema {d.get('schema')!r} is not the "
                f"supported v{PLAN_SCHEMA_VERSION}; re-build the plan with "
                "this version of the code instead of executing a stale one")
        from ..quant.calibrate import LayerQuant

        quant = d.get("quant")
        tiles = d.get("tiles")
        tables = d.get("sparse_tables")
        if tables is not None:
            tables = tuple(np.asarray(a, np.int32) for a in tables)
        kind = d.get("kind", "deconv")
        geom = ConvGeometry if kind == "conv" else DeconvGeometry
        plan = cls(
            geometry=geom(**d["geometry"]),
            batch=int(d["batch"]),
            dtype=str(d["dtype"]),
            backend=str(d["backend"]),
            activation=d.get("activation"),
            out_scale=d.get("out_scale"),
            out_dtype_bytes=d.get("out_dtype_bytes"),
            quant=(LayerQuant(x_scale=float(quant["x_scale"]),
                              w_scale=tuple(float(v)
                                            for v in quant["w_scale"]))
                   if quant is not None else None),
            sparse_digest=d.get("sparse_digest"),
            tiles=(TileChoice(**{k: v for k, v in tiles.items()
                                 if k in TileChoice.__dataclass_fields__})
                   if tiles is not None else None),
            sparse_tables=tables,
            kind=kind,
            norm=d.get("norm"),
            skip=d.get("skip"),
            in_activation=d.get("in_activation"),
            dot_precision=d.get("dot_precision"),
        )
        if tables is not None and plan.sparse_digest is not None:
            got = _sparse_digest(tables)
            if got != plan.sparse_digest:
                raise PlanSchemaError(
                    "sparse schedule content hash mismatch "
                    f"({got} != {plan.sparse_digest}): the serialized "
                    "zero-skip tables do not match the plan that was pinned")
        return plan


def build_layer_plan(
    geom: DeconvGeometry,
    *,
    batch: int = 1,
    dtype="float32",
    backend: str = "pallas",
    activation: Optional[str] = None,
    out_scale: Optional[float] = None,
    out_dtype_bytes: Optional[int] = None,
    quant=None,
    weights: Optional[np.ndarray] = None,
    tiles: Optional[TileChoice] = None,
    autotune: bool = True,
    refine: bool = False,
    device=None,
    sparse_table_cache: Optional[Dict] = None,
    sparse_cache_key=None,
    norm: Optional[str] = None,
    skip: Optional[int] = None,
    in_activation: Optional[str] = None,
    dot_precision: Optional[str] = None,
) -> DeconvPlan:
    """Resolve one layer's `DeconvPlan` (tiles via the DSE autotuner).

    ``weights`` (the pruned static weight array) is required to build the
    zero-skip schedule for backend="pallas_sparse"; ``sparse_table_cache``
    memoizes host-built tables across plans that share
    (``sparse_cache_key``, t_ci, t_co) — e.g. a serving engine's buckets,
    which key by layer index.  The memo is only consulted when the caller
    names a ``sparse_cache_key`` (an object identity would be reused by
    the allocator and could serve another weight set's schedule).
    Non-tiled backends ("reverse_loop", "xla") and forward-convolution
    layers (a `ConvGeometry`: XLA's convolution on every backend) get a
    plan with ``tiles=None``.  ``norm``, ``skip``, ``in_activation`` and
    ``dot_precision`` place the layer in its tower (`DeconvPlan`)."""
    from ..core.dse import planning_device

    device = planning_device() if device is None else device
    dtype_name = np.dtype(dtype).name
    topology = dict(kind="conv" if isinstance(geom, ConvGeometry)
                    else "deconv", norm=norm, skip=skip,
                    in_activation=in_activation, dot_precision=dot_precision)
    if (backend not in ("pallas", "pallas_sparse")
            or topology["kind"] == "conv"):
        return DeconvPlan(geometry=geom, batch=batch, dtype=dtype_name,
                          backend=backend, activation=activation, **topology)
    if tiles is None:
        from ..kernels.autotune import choose_tiles, fallback_tiles

        if autotune:
            tiles = choose_tiles(geom, np.dtype(dtype), backend=backend,
                                 refine=refine, device=device, batch=batch,
                                 out_dtype_bytes=out_dtype_bytes)
        else:
            tiles = fallback_tiles(geom, np.dtype(dtype).itemsize,
                                   device.onchip_bytes, batch=batch,
                                   out_dtype_bytes=out_dtype_bytes)
    sparse_tables = None
    digest = None
    if backend == "pallas_sparse" and weights is not None:
        from ..kernels.deconv2d_sparse import make_sparse_plan

        use_memo = (sparse_table_cache is not None
                    and sparse_cache_key is not None)
        memo_key = (sparse_cache_key, tiles.t_ci, tiles.t_co)
        if use_memo and memo_key in sparse_table_cache:
            sparse_tables = sparse_table_cache[memo_key]
        else:
            sparse_tables = make_sparse_plan(
                np.asarray(weights), geom.stride, geom.padding,
                tiles.t_ci, tiles.t_co)
            if use_memo:
                sparse_table_cache[memo_key] = sparse_tables
        digest = _sparse_digest(sparse_tables)
    return DeconvPlan(
        geometry=geom, batch=batch, dtype=dtype_name, backend=backend,
        activation=activation, out_scale=out_scale,
        out_dtype_bytes=out_dtype_bytes, quant=quant,
        sparse_digest=digest, tiles=tiles, sparse_tables=sparse_tables,
        **topology,
    )
