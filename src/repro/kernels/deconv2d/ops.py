"""Public wrapper for the deconv2d Pallas kernel.

`deconv2d` is a thin plan dispatcher: the preferred fast path takes a
pre-built `plan.DeconvPlan` (geometry, tiles, fused epilogue all pinned
at plan time) and goes straight into the jit'd `_deconv2d_jit`, which
performs the halo / channel padding and invokes the kernel.  The legacy
surface — explicit tile kwargs, or none at all — resolves tiles
(explicit overrides > autotuner > clamped fallback heuristic) into an
ad-hoc plan and routes through the same path; passing tile kwargs
directly is deprecated in favor of building the plan once.

On non-TPU backends the kernel runs in interpret mode."""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.offsets import make_phase_plan
from ...core.tiling import SUBLANE, DeconvGeometry, halo_tile, out_size
from .kernel import deconv2d_pallas_call

_warned_tile_kwargs = set()
_suppress_tile_warnings = 0


class suppress_tile_warnings:
    """Context manager for the library's own supported legacy surfaces
    (``generator_apply(tile_overrides=...)`` and friends): they forward
    tile kwargs into the wrappers on the user's behalf, and must not nag
    the user about an expansion the user never wrote."""

    def __enter__(self):
        global _suppress_tile_warnings
        _suppress_tile_warnings += 1

    def __exit__(self, *exc):
        global _suppress_tile_warnings
        _suppress_tile_warnings -= 1


def warn_legacy_tiles(fn_name: str) -> None:
    """One DeprecationWarning per wrapper per process for direct tile
    kwargs — the call still works (routed through the plan path), but the
    plan API is where new capability (int4, mixed precision) lands."""
    if _suppress_tile_warnings or fn_name in _warned_tile_kwargs:
        return
    _warned_tile_kwargs.add(fn_name)
    warnings.warn(
        f"passing tile kwargs (t_oh/t_ow/t_ci/t_co/t_n) directly to "
        f"{fn_name} is deprecated: build a repro.plan.DeconvPlan once "
        f"(plan.build_layer_plan) and pass it via plan=",
        DeprecationWarning, stacklevel=3)


def check_layer_plan(plan, x: jax.Array, w: jax.Array, backend: str,
                     fn_name: str) -> None:
    """Fail loudly when a plan is executed against data it was not built
    for — the pinned-configuration contract."""
    n, ih, iw, ci = x.shape
    k, _, wci, co = w.shape
    g = plan.geometry
    if (ih, iw, ci, co, k) != (g.in_h, g.in_w, g.c_in, g.c_out, g.kernel) \
            or wci != g.c_in:
        raise ValueError(
            f"{fn_name}: plan geometry {g} does not match x{x.shape} / "
            f"w{w.shape}")
    if plan.backend != backend:
        raise ValueError(
            f"{fn_name}: plan was built for backend={plan.backend!r}")
    if plan.tiles is None:
        raise ValueError(f"{fn_name}: plan has no resolved tiles")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def halo_pad_geometry(n: int, ih: int, iw: int, ci: int, co: int,
                      plan, t_oh: int, t_ow: int, t_ci: int, t_co: int,
                      t_n: int):
    """Host-side padded geometry shared by the dense, int8 and sparse jit
    wrappers.

    Returns ``(oh, ow, ohp, owp, pad_l, pad_rh, pad_rw, cip, cop, t_n,
    np_)``: the true output extents, the tile-multiple output grid, the
    halo padding that keeps every per-tile window in bounds (enhancement
    3: all address arithmetic resolved ahead of the kernel), the channel
    tiles' padded extents, the batch tile clamped to the batch, and the
    t_n-multiple padded batch.  The right padding covers the last window
    of each dim, the W one sublane-aligned as the kernels stream it.  One
    implementation, three kernels — the padded geometry (and the final
    un-padding slice) can never drift between them."""
    k, s, p = plan.kernel_size, plan.stride, plan.padding
    oh = out_size(ih, k, s, p)
    ow = out_size(iw, k, s, p)
    ohp = _round_up(oh, t_oh)
    owp = _round_up(ow, t_ow)
    pad_l = plan.left_halo
    need_h = halo_tile(t_oh, k, s, p).min_padded_extent(ohp // t_oh)
    need_w = halo_tile(t_ow, k, s, p, align=SUBLANE).min_padded_extent(
        owp // t_ow)
    pad_rh = max(0, need_h - pad_l - ih)
    pad_rw = max(0, need_w - pad_l - iw)
    cip = _round_up(ci, t_ci)
    cop = _round_up(co, t_co)
    t_n = min(t_n, n) if n > 0 else 1
    np_ = _round_up(n, t_n)
    return oh, ow, ohp, owp, pad_l, pad_rh, pad_rw, cip, cop, t_n, np_


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "padding", "t_oh", "t_ow", "t_ci", "t_co", "t_n",
        "activation", "interpret", "layer",
    ),
)
def _deconv2d_jit(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array],
    stride: int,
    padding: int,
    t_oh: int,
    t_ow: int,
    t_ci: int,
    t_co: int,
    t_n: int,
    activation: Optional[str],
    interpret: bool,
    layer: Optional[int] = None,
) -> jax.Array:
    n, ih, iw, ci = x.shape
    k, _, _, co = w.shape
    plan = make_phase_plan(k, stride, padding)

    # padded output grid + halo padding (enhancement 3: all address
    # arithmetic resolved up front; the per-tile windows the kernel
    # streams stay in bounds by construction)
    (oh, ow, ohp, owp, pad_l, pad_rh, pad_rw, cip, cop, t_n,
     np_) = halo_pad_geometry(n, ih, iw, ci, co, plan, t_oh, t_ow, t_ci,
                              t_co, t_n)
    xp = jnp.pad(
        x, ((0, np_ - n), (pad_l, pad_rh), (pad_l, pad_rw), (0, cip - ci))
    )
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, cip - ci), (0, cop - co)))
    bb = b if b is not None else jnp.zeros((co,), dtype=x.dtype)
    bp = jnp.pad(bb, (0, cop - co)).reshape(1, cop).astype(x.dtype)

    y = deconv2d_pallas_call(
        xp, wp, bp,
        plan=plan,
        ohp=ohp, owp=owp,
        t_oh=t_oh, t_ow=t_ow, t_ci=t_ci, t_co=t_co, t_n=t_n,
        activation=activation,
        interpret=interpret,
        layer=layer,
    )
    return y[:n, :oh, :ow, :co]


def resolve_tiles(
    x: jax.Array,
    w: jax.Array,
    stride: int,
    padding: int,
    t_oh: Optional[int],
    t_ow: Optional[int],
    t_ci: Optional[int],
    t_co: Optional[int],
    t_n: Optional[int] = None,
    backend: str = "pallas",
    autotune: bool = True,
    out_dtype_bytes: Optional[int] = None,
):
    """Fill unspecified tile factors (shared by dense and sparse wrappers).

    The batch tile ``t_n`` is resolved jointly with the spatial/channel
    tiles against the caller's batch size (``x.shape[0]``): the autotuner
    DSE scores candidates by MXU row fill + amortized weight traffic.
    Explicitly passing all four legacy factors but not ``t_n`` keeps the
    per-image grid (t_n=1) — the pre-batch-fusion behavior."""
    n, ih, iw, ci = x.shape
    k, _, _, co = w.shape
    if None not in (t_oh, t_ow, t_ci, t_co):
        return t_oh, t_ow, t_ci, t_co, (t_n or 1)
    geom = DeconvGeometry(ih, iw, ci, co, k, stride, padding)
    if autotune:
        from ..autotune import choose_tiles

        c = choose_tiles(geom, x.dtype, backend=backend, batch=n,
                         out_dtype_bytes=out_dtype_bytes)
    else:
        from ..autotune import fallback_tiles

        c = fallback_tiles(geom, jnp.dtype(x.dtype).itemsize, batch=n,
                           out_dtype_bytes=out_dtype_bytes)
    return (t_oh or c.t_oh, t_ow or c.t_ow, t_ci or c.t_ci, t_co or c.t_co,
            t_n or c.t_n)


def deconv2d(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array],
    stride: Optional[int] = None,
    padding: Optional[int] = None,
    t_oh: Optional[int] = None,
    t_ow: Optional[int] = None,
    t_ci: Optional[int] = None,
    t_co: Optional[int] = None,
    t_n: Optional[int] = None,
    activation: Optional[str] = None,
    interpret: Optional[bool] = None,
    autotune: bool = True,
    plan=None,
    layer: Optional[int] = None,
) -> jax.Array:
    """Transposed conv y = act(deconv(x, w) + b) via the reverse-loop kernel.

    x: (N, IH, IW, CI); w: (K, K, CI, CO); b: (CO,) or None.
    Output: (N, OH, OW, CO), OH = (IH-1)*S + K - 2P.
    `activation` ("relu"/"tanh"/"leaky_relu"/None) runs fused in the
    kernel's flush phase.

    **Plan fast path** — ``plan`` is a `repro.plan.DeconvPlan`: stride,
    padding, the full tile assignment and the fused activation all come
    pre-resolved from the plan; nothing is re-decided here.  An explicit
    ``activation`` argument overrides the plan's.

    **Legacy path** — without a plan, ``stride``/``padding`` are required;
    unspecified tile factors come from the DSE autotuner cache/model
    (`autotune=False` selects the clamped fixed heuristic), explicit tile
    kwargs are deprecated, and the resolved choice routes through the same
    jit as the plan path (bit-identical executables).  ``t_n`` is the
    batch tile: each grid program owns ``t_n`` images and the tap matmuls
    contract over ``t_n * T_OH/S * T_OW/S`` rows (the batch is zero-padded
    to a ``t_n`` multiple and sliced back).

    ``layer``, the layer's index in its tower, names the kernel
    (`kernel.kernel_name`); it changes no plan and no result.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if plan is not None:
        check_layer_plan(plan, x, w, "pallas", "deconv2d")
        t = plan.tiles
        if activation is None:
            activation = plan.activation
        return _deconv2d_jit(
            x, w, b, plan.geometry.stride, plan.geometry.padding,
            t.t_oh, t.t_ow, t.t_ci, t.t_co, t.t_n, activation, interpret,
            layer,
        )
    if stride is None or padding is None:
        raise TypeError("deconv2d needs stride and padding (or a plan=)")
    if any(v is not None for v in (t_oh, t_ow, t_ci, t_co, t_n)):
        warn_legacy_tiles("deconv2d")
    t_oh, t_ow, t_ci, t_co, t_n = resolve_tiles(
        x, w, stride, padding, t_oh, t_ow, t_ci, t_co, t_n,
        backend="pallas", autotune=autotune,
    )
    return _deconv2d_jit(
        x, w, b, stride, padding, t_oh, t_ow, t_ci, t_co, t_n, activation,
        interpret, layer,
    )
