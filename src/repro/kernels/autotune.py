"""DSE-driven tile autotuner for the deconv Pallas kernels.

Tile selection runs in three stages, cheapest first:

1. **Cache** — a JSON store keyed by (backend, dtype, layer geometry);
   serving engines and repeated benchmark runs never re-tune.
2. **Roofline model** — enumerate legal candidates (stride-aligned square
   spatial tiles x channel-tile options), drop everything whose
   `kernel_vmem_bytes` exceeds the device's on-chip budget, and rank the
   rest by `dse.tile_attainable` (the paper's §V-A attainable-throughput
   construction, Fig. 5).
3. **On-device timing** (optional, ``refine=True``) — time the few
   top-ranked candidates with the real kernel and keep the fastest.  Only
   available outside a jit trace; inside a trace the model choice stands.

The cache file lives at ``$REPRO_AUTOTUNE_CACHE`` (default
``~/.cache/repro/autotune.json``); ``clear_cache()`` wipes it.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dse import TPU_V5E, Device, planning_device, tile_attainable
from ..core.tiling import (LANE, SUBLANE, ConvGeometry, DeconvGeometry,
                           kernel_vmem_bytes)

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
# v2: the batch tile t_n joined the schema — both the key format and the
# stored entry gained a field, so v1 entries (4-tuple tiles, no batch in
# the key) must never be served.  The version is embedded in every key and
# `_valid_entry` drops anything that does not carry the full 5-tuple.
# v3: the ranking model became dtype-aware (the requested dtype's byte
# width drives the traffic/VMEM models and selects the int8 MXU peak), so
# a v2 entry — ranked with the device's native width regardless of the
# request — is stale even though its key already named the dtype.
# `_load_cache` drops every key from a different schema version.
# v4: keys are no longer hand-assembled tuples — the planning inputs are
# canonicalized by `plan.DeconvPlan.stable_hash(scope="tiles")`, so a new
# field (dtype, t_n-relevant batch, out_dtype_bytes, backend, ...) can
# never be forgotten from the key and silently alias two requests again.
# v3 keys, which did hand-assemble, are dropped on load like every other
# stale schema.
# v5: candidates are only tiles Mosaic can compile (sublane-aligned W
# windows, full-dim or lane-multiple channel tiles, t_co <= 128), so a v4
# entry may name a tile the chip's compiler refuses.
_CACHE_VERSION = 5
_lock = threading.Lock()
_cache: Optional[Dict[str, dict]] = None

_TILE_FIELDS = ("t_oh", "t_ow", "t_ci", "t_co", "t_n")


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """One resolved tile assignment for the deconv kernel grid."""

    t_oh: int
    t_ow: int
    t_ci: int
    t_co: int
    t_n: int = 1              # batch tile (images per grid program)
    # provenance, not semantics: two choices with the same factors are the
    # same executable wherever they came from (plan equality relies on it)
    source: str = dataclasses.field(default="model", compare=False)
    attainable_ops: float = 0.0
    vmem_bytes: int = 0

    def as_kwargs(self) -> Dict[str, int]:
        return {"t_oh": self.t_oh, "t_ow": self.t_ow,
                "t_ci": self.t_ci, "t_co": self.t_co, "t_n": self.t_n}


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------
def cache_path() -> pathlib.Path:
    default = pathlib.Path.home() / ".cache" / "repro" / "autotune.json"
    return pathlib.Path(os.environ.get(_CACHE_ENV, str(default)))


def cache_key(geom: DeconvGeometry, dtype, backend: str,
              device: Device = TPU_V5E, batch: int = 1,
              out_dtype_bytes: Optional[int] = None) -> str:
    """Cache key: a `DeconvPlan` content hash over the tile-planning
    inputs (geometry, dtype, batch, backend, epilogue output width).

    The platform and the modeled device stay in the readable prefix:
    refine=True timings taken in CPU interpret mode must never be served
    as authoritative on TPU, and a choice fitted to one device's VMEM
    budget/roofline must not leak to another's.  Everything else is
    hashed through one canonical dict — the schema-v3 failure mode
    (a new ranking input hand-appended to the key string, or forgotten
    from it) cannot alias entries anymore."""
    from ..plan import DeconvPlan

    plan = DeconvPlan(geometry=geom, batch=batch,
                      dtype=np.dtype(dtype).name, backend=backend,
                      out_dtype_bytes=out_dtype_bytes)
    return plan_cache_key(plan, device)


def plan_cache_key(plan, device: Device = TPU_V5E) -> str:
    """Cache key for a (possibly unresolved) `plan.DeconvPlan`: a resolved
    plan and the bare planning request hash identically, so the tiles a
    plan was built with are exactly the tiles its key serves back."""
    plat = jax.default_backend()
    return (f"v{_CACHE_VERSION}|{plat}|{device.name}|"
            f"{plan.stable_hash(scope='tiles')}")


def _valid_entry(v) -> bool:
    """A cache entry must carry the full current tile schema.  Entries from
    an older schema (e.g. v1's 4-tuple, before t_n existed) or corrupted
    by hand-editing are dropped instead of being served as stale tiles."""
    return (isinstance(v, dict)
            and all(isinstance(v.get(f), int) and v[f] > 0
                    for f in _TILE_FIELDS))


def _load_cache() -> Dict[str, dict]:
    global _cache
    if _cache is None:
        path = cache_path()
        try:
            raw = json.loads(path.read_text())
        except (OSError, ValueError):
            raw = {}
        if not isinstance(raw, dict):  # corrupt top-level: recover empty
            raw = {}
        prefix = f"v{_CACHE_VERSION}|"
        _cache = {k: v for k, v in raw.items()
                  if k.startswith(prefix) and _valid_entry(v)}
    return _cache


def _store(key: str, choice: TileChoice) -> None:
    with _lock:
        cache = _load_cache()
        cache[key] = dataclasses.asdict(choice)
        path = cache_path()
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(".tmp")
            tmp.write_text(json.dumps(cache, indent=1, sort_keys=True))
            tmp.replace(path)
        except OSError:
            pass  # cache is an optimization; never fail the call


def clear_cache() -> None:
    """Drop the in-memory cache and delete the cache file."""
    global _cache
    with _lock:
        _cache = {}
        try:
            cache_path().unlink()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# candidate enumeration + model ranking
# ---------------------------------------------------------------------------
def _channel_tile_options(c: int, dtype_bytes: int = 4) -> List[int]:
    """Input-channel tile candidates Mosaic can block.  A channel count
    within one lane width streams whole (the block spans the padded dim);
    a wider one takes lane-width multiples up to its lane-padded count
    (the kernel pads channels up to the tile).  The int8 kernel always
    takes lane multiples: its window's (rows, channels) reshape is a plain
    layout change only over whole 128-lane int8 rows."""
    if c <= LANE and dtype_bytes != 1:
        return [_round_up(c, SUBLANE)]
    cp = _round_up(c, LANE)
    return [v for v in (LANE, 2 * LANE, 4 * LANE) if v <= cp]


def _out_channel_tile(c: int) -> int:
    """The output-channel tile: the whole (sublane-padded) channel count
    within one lane width, else one lane width — the accumulator's strided
    phase store takes no wider a last dim."""
    return min(_round_up(c, SUBLANE), LANE)


def _spatial_tile_options(out: int, stride: int,
                          max_spatial: int) -> List[int]:
    """Square spatial-tile candidates Mosaic can window, ascending: the
    single tile over the whole (stride-padded) output, plus every smaller
    multiple of ``SUBLANE * stride`` up to ``max_spatial`` — there each W
    window advances ``t / S`` input rows, a whole number of sublanes, so
    every window starts on a sublane boundary."""
    full = _round_up(out, stride)
    step = SUBLANE * stride
    return sorted({full} | set(range(step, min(full, max_spatial + 1),
                                     step)))


def _batch_tile_options(batch: int, cap: int = 64) -> List[int]:
    """Batch-tile candidates: powers of two up to (never beyond) the
    batch, plus the batch itself so non-power-of-two batches can run as a
    single grid step.  t_n > batch is never enumerated — it would be
    scored with an MXU-row fill the real (clamped) kernel can't reach."""
    hi = min(batch, cap)
    opts = {1, hi}
    t = 1
    while t * 2 <= hi:
        t *= 2
        opts.add(t)
    return sorted(opts)


def legal_tile_candidates(
    geom: DeconvGeometry,
    dtype_bytes: int = 4,
    vmem_budget: int = TPU_V5E.onchip_bytes,
    max_spatial: int = 64,
    batch: int = 1,
    out_dtype_bytes: Optional[int] = None,
) -> List[Tuple[int, int, int, int, int]]:
    """All (t_oh, t_ow, t_ci, t_co, t_n) with square spatial tiles Mosaic
    can window (`_spatial_tile_options`) and channel tiles it can block
    (`_channel_tile_options`, `_out_channel_tile`) that fit the on-chip
    budget (paper Fig. 5 'legal solutions'), jointly enumerated with the
    batch tile.  The full-output tile is a candidate even beyond
    ``max_spatial``.  ``out_dtype_bytes`` prices a wider output block
    than the streamed dtype (the last int8 layer's f32 epilogue) so
    near-budget candidates don't pass the filter at a quarter of their
    real output footprint."""
    t_co = _out_channel_tile(geom.c_out)
    out: List[Tuple[int, int, int, int, int]] = []
    for t in _spatial_tile_options(geom.out_h, geom.stride, max_spatial):
        for t_ci in _channel_tile_options(geom.c_in, dtype_bytes):
            for t_n in _batch_tile_options(batch):
                fp = kernel_vmem_bytes(geom, t, t, t_ci, t_co, dtype_bytes,
                                       t_n=t_n,
                                       out_dtype_bytes=out_dtype_bytes)
                if fp <= vmem_budget:
                    out.append((t, t, t_ci, t_co, t_n))
    return out


def rank_candidates(
    geom: DeconvGeometry,
    candidates: List[Tuple[int, int, int, int, int]],
    device: Device = TPU_V5E,
    batch: int = 1,
    dtype_bytes: Optional[int] = None,
    out_dtype_bytes: Optional[int] = None,
) -> List[TileChoice]:
    """Sort by modeled attainable throughput (desc), tie-breaking toward
    higher CTC then larger tiles (fewer grid programs).  ``dtype_bytes``
    makes the ranking precision-aware: int8 candidates are scored with
    quarter-width traffic and the device's doubled int8 MXU peak
    (``out_dtype_bytes`` widening the output block where the epilogue
    emits f32)."""
    scored = []
    for (t_oh, t_ow, t_ci, t_co, t_n) in candidates:
        pt = tile_attainable(geom, t_oh, t_ow, t_ci, t_co, device,
                             t_n=t_n, batch=batch, dtype_bytes=dtype_bytes,
                             out_dtype_bytes=out_dtype_bytes)
        scored.append(TileChoice(
            t_oh=t_oh, t_ow=t_ow, t_ci=t_ci, t_co=t_co, t_n=t_n,
            source="model",
            attainable_ops=pt.attainable_ops,
            vmem_bytes=pt.vmem_bytes,
        ))
    return sorted(
        scored,
        key=lambda c: (-c.attainable_ops, -c.t_n * c.t_oh * c.t_ow,
                       -c.t_ci * c.t_co),
    )


def fallback_tiles(
    geom: DeconvGeometry,
    dtype_bytes: int = 4,
    vmem_budget: int = TPU_V5E.onchip_bytes,
    batch: int = 1,
    out_dtype_bytes: Optional[int] = None,
) -> TileChoice:
    """The fixed heuristic (~32x32 spatial, the narrowest legal channel
    tiles), clamped through `kernel_vmem_bytes` so large CI x CO layers
    can no longer blow the VMEM budget: the spatial tile shrinks through
    the Mosaic-legal sizes until the footprint fits.  The batch tile then
    grows (powers of two, within the batch and the budget) until the tap
    matmuls reach ~128 contraction rows — a full MXU column load."""
    s = geom.stride
    spatial = _spatial_tile_options(geom.out_h, s, max_spatial=32)
    # largest legal tile <= 32 (the smallest when even that is larger)
    spatial = [t for t in spatial if t <= 32] or spatial[:1]
    t_ci = _channel_tile_options(geom.c_in, dtype_bytes)[0]
    t_co = _out_channel_tile(geom.c_out)

    def vmem(t: int, tn: int) -> int:
        return kernel_vmem_bytes(geom, t, t, t_ci, t_co, dtype_bytes,
                                 t_n=tn, out_dtype_bytes=out_dtype_bytes)

    while len(spatial) > 1 and vmem(spatial[-1], 1) > vmem_budget:
        spatial.pop()
    t = spatial[-1]
    t_n = 1
    rows_per_img = (t // s) ** 2
    while (t_n * 2 <= batch and t_n * rows_per_img < 128
           and vmem(t, t_n * 2) <= vmem_budget):
        t_n *= 2
    return TileChoice(
        t_oh=t, t_ow=t, t_ci=t_ci, t_co=t_co, t_n=t_n,
        source="fallback", vmem_bytes=vmem(t, t_n),
    )


def network_tiles(
    cfg,
    dtype=None,
    backend: str = "pallas",
    batch: int = 1,
    refine: bool = False,
    autotune: bool = True,
    device: Optional[Device] = None,
) -> Optional[Dict[int, TileChoice]]:
    """Per-layer tile choices for a whole generator network.

    ``cfg`` is any config exposing ``geometries()`` (and ``jdtype`` when
    ``dtype`` is omitted) — in practice a ``models.dcnn.DcnnConfig``.
    ``batch`` is the batch size each layer's kernel will actually see: a
    serving bucket on one device, or the *per-device sub-batch* when the
    caller shards the bucket across a mesh (the DSE then picks ``t_n``
    against the shard, not the global batch).  Returns None for backends
    without tile factors.  For integer dtypes the *last* layer is tuned
    with a 4-byte output block: the int8 chain's final epilogue emits f32
    images while every intermediate layer re-quantizes to int8."""
    if backend not in ("pallas", "pallas_sparse"):
        return None
    device = planning_device() if device is None else device
    if dtype is None:
        dtype = cfg.jdtype
    geoms = list(cfg.geometries())
    int8_chain = np.dtype(dtype).kind in ("i", "u")

    def out_bytes(i: int) -> Optional[int]:
        return 4 if int8_chain and i == len(geoms) - 1 else None

    # forward-convolution layers run XLA's convolution: no tiles
    deconvs = [(i, g) for i, g in enumerate(geoms)
               if not isinstance(g, ConvGeometry)]
    if autotune:
        return {i: choose_tiles(g, dtype, backend=backend, refine=refine,
                                device=device, batch=batch,
                                out_dtype_bytes=out_bytes(i))
                for i, g in deconvs}
    itemsize = np.dtype(dtype).itemsize
    return {i: fallback_tiles(g, itemsize, device.onchip_bytes, batch=batch,
                              out_dtype_bytes=out_bytes(i))
            for i, g in deconvs}


# ---------------------------------------------------------------------------
# on-device timing refinement
# ---------------------------------------------------------------------------
def _time_candidate(
    geom: DeconvGeometry,
    choice: TileChoice,
    dtype,
    backend: str,
    reps: int = 3,
    batch: int = 1,
) -> float:
    """Median wall-clock of the real kernel at this tile choice (seconds).

    Proxy caveats: inputs/weights are dense random samples, so for
    backend="pallas_sparse" the measured schedule keeps every CI slab —
    the ranking reflects the dense workload, not a pruned network's; and
    on non-TPU hosts the kernel runs in interpret mode, where relative
    timings only loosely track TPU behavior."""
    from .deconv2d import deconv2d

    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (batch, geom.in_h, geom.in_w, geom.c_in),
                          dtype)
    w = (jax.random.normal(
        kw, (geom.kernel, geom.kernel, geom.c_in, geom.c_out), dtype) * 0.1
    ).astype(dtype)
    if backend == "pallas_sparse":
        from .deconv2d_sparse import deconv2d_sparse as fn
    else:
        fn = deconv2d
    from .deconv2d.ops import suppress_tile_warnings

    from ..obs import clock as obsclock

    kwargs = choice.as_kwargs()
    with suppress_tile_warnings():  # internal harness, not a user call
        jax.block_until_ready(
            fn(x, w, None, geom.stride, geom.padding, **kwargs))  # compile
        ts = []
        for _ in range(reps):
            t0 = obsclock.now()
            jax.block_until_ready(
                fn(x, w, None, geom.stride, geom.padding, **kwargs))
            ts.append(obsclock.now() - t0)
    return float(np.median(ts))


def _refine(geom: DeconvGeometry, ranked: List[TileChoice], dtype,
            backend: str, batch: int) -> TileChoice:
    """Time each candidate and return the fastest, ``source="timed"``."""
    from ..obs import metrics as obsmetrics

    failures = obsmetrics.default_registry().counter(
        "autotune.refine_failures",
        "refine candidates the compiler refused (label: candidate)")
    timed = []
    errors = []
    for c in ranked:
        try:
            timed.append((_time_candidate(geom, c, dtype, backend,
                                          batch=max(batch, c.t_n)), c))
        except jax.errors.JaxRuntimeError as e:
            failures.inc(backend=backend, candidate=str(c.as_kwargs()))
            errors.append((c.as_kwargs(), e))
    if not timed:
        raise RuntimeError(
            f"every refine candidate for {geom} failed to compile: "
            f"{[kw for kw, _ in errors]}") from errors[-1][1]
    return dataclasses.replace(min(timed, key=lambda tc: tc[0])[1],
                               source="timed")


def choose_tiles(
    geom: DeconvGeometry,
    dtype=jnp.float32,
    backend: str = "pallas",
    refine: bool = False,
    refine_top_k: int = 3,
    device: Optional[Device] = None,
    use_cache: bool = True,
    batch: int = 1,
    out_dtype_bytes: Optional[int] = None,
) -> TileChoice:
    """Resolve the tile assignment for one deconv layer.

    ``batch`` is the (bucketed) serving batch the choice is fitted to: the
    DSE enumerates the batch tile t_n jointly with the spatial/channel
    tiles, trading MXU row fill + weight amortization against VMEM.
    ``refine=True`` times the top-`refine_top_k` model-ranked candidates on
    the current backend and keeps the fastest (then persists it, so the
    timing cost is paid once per (geometry, dtype, backend, batch)).
    ``out_dtype_bytes`` widens the modeled output block when the kernel's
    epilogue emits a wider dtype than it streams (the last int8 layer
    writes f32 images).  ``device`` defaults to `dse.planning_device()`:
    the attached TPU's constants, or the v5e off the chip.

    A refine candidate that the compiler refuses is counted
    (``autotune.refine_failures``, labelled with the candidate) and
    skipped; when every candidate is refused the call raises."""
    device = planning_device() if device is None else device
    dtype_bytes = np.dtype(dtype).itemsize
    if refine and np.dtype(dtype).kind != "f":
        # the timing harness drives the float kernels with random normal
        # inputs; integer (int8) requests keep the model ranking — the
        # dtype-aware roofline is what differentiates them anyway
        refine = False
    key = cache_key(geom, dtype, backend, device, batch, out_dtype_bytes)
    if use_cache:
        hit = _load_cache().get(key)
        # a refine=True request is only satisfied by a *timed* entry; a
        # stored model/fallback choice must not suppress the requested
        # on-device refinement (the re-tune overwrites it below)
        if hit is not None and (not refine or hit.get("source") == "timed"):
            return dataclasses.replace(
                TileChoice(**{k: v for k, v in hit.items()
                              if k in TileChoice.__dataclass_fields__}),
                source="cache")

    cands = legal_tile_candidates(geom, dtype_bytes, device.onchip_bytes,
                                  batch=batch,
                                  out_dtype_bytes=out_dtype_bytes)
    if not cands:
        choice = fallback_tiles(geom, dtype_bytes, device.onchip_bytes,
                                batch=batch,
                                out_dtype_bytes=out_dtype_bytes)
    else:
        ranked = rank_candidates(geom, cands, device, batch=batch,
                                 dtype_bytes=dtype_bytes,
                                 out_dtype_bytes=out_dtype_bytes)
        choice = ranked[0]
        if refine:
            choice = _refine(geom, ranked[:refine_top_k], dtype, backend,
                             batch)
    if use_cache:
        _store(key, choice)
    return choice
