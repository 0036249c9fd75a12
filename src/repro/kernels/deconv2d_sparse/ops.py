"""Jit'd wrapper for the block-sparse zero-skipping deconv kernel.

The sparsity schedule is computed on the host from the (static) pruned
weights — the paper's zero-skipping, hoisted to compile/load time.  Tile
resolution shares `deconv2d.ops.resolve_tiles` (autotuner-backed, keyed
under backend="pallas_sparse")."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.offsets import make_phase_plan
from ...core.sparsity import block_mask
from ..deconv2d.ops import (_round_up, check_layer_plan, halo_pad_geometry,
                            resolve_tiles, warn_legacy_tiles)
from .kernel import build_schedule, deconv2d_sparse_pallas_call


def make_sparse_plan(
    w: np.ndarray, stride: int, padding: int,
    t_ci: int, t_co: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side schedule from pruned weights (static per network)."""
    k = w.shape[0]
    cip = _round_up(w.shape[2], t_ci)
    cop = _round_up(w.shape[3], t_co)
    wp = np.pad(np.asarray(w), ((0, 0), (0, 0), (0, cip - w.shape[2]),
                                (0, cop - w.shape[3])))
    mask = block_mask(wp, t_ci, t_co)  # (K, K, n_ci, n_co)
    ci_idx, valid, tap_mask, _ = build_schedule(mask)
    return ci_idx, valid, tap_mask


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "t_oh", "t_ow", "t_ci", "t_co",
                     "t_n", "activation", "interpret", "layer"),
)
def _deconv2d_sparse_jit(
    x, w, b, ci_idx, valid, tap_mask,
    stride, padding, t_oh, t_ow, t_ci, t_co, t_n, activation, interpret,
    layer=None,
):
    n, ih, iw, ci = x.shape
    k, _, _, co = w.shape
    plan = make_phase_plan(k, stride, padding)
    (oh, ow, ohp, owp, pad_l, pad_rh, pad_rw, cip, cop, t_n,
     np_) = halo_pad_geometry(n, ih, iw, ci, co, plan, t_oh, t_ow, t_ci,
                              t_co, t_n)
    xp = jnp.pad(x, ((0, np_ - n), (pad_l, pad_rh), (pad_l, pad_rw),
                     (0, cip - ci)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, cip - ci), (0, cop - co)))
    bb = b if b is not None else jnp.zeros((co,), dtype=x.dtype)
    bp = jnp.pad(bb, (0, cop - co)).reshape(1, cop).astype(x.dtype)
    y = deconv2d_sparse_pallas_call(
        xp, wp, bp, ci_idx, valid, tap_mask,
        plan=plan, ohp=ohp, owp=owp,
        t_oh=t_oh, t_ow=t_ow, t_ci=t_ci, t_co=t_co, t_n=t_n,
        activation=activation, interpret=interpret, layer=layer,
    )
    return y[:n, :oh, :ow, :co]


def deconv2d_sparse(
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
    """Sparse transposed conv; weights are expected pre-pruned (zeros).

    ``plan`` is either a `repro.plan.DeconvPlan` (the fast path: tiles,
    fused activation AND the zero-skip schedule all pinned at plan time)
    or — legacy — a bare `make_sparse_plan` tables tuple built with the
    same t_ci/t_co; both avoid re-deriving the static schedule, an
    O(weights) host computation, on every call.  ``t_n`` batch-tiles the
    grid exactly as in the dense kernel (the schedule is batch-
    independent, so one plan serves every bucket).  ``layer`` names the
    kernel, as in `deconv2d.ops.deconv2d`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if plan is not None and hasattr(plan, "geometry"):
        check_layer_plan(plan, x, w, "pallas_sparse", "deconv2d_sparse")
        t = plan.tiles
        if activation is None:
            activation = plan.activation
        tables = plan.sparse_tables
        if tables is None:
            tables = make_sparse_plan(np.asarray(w), plan.geometry.stride,
                                      plan.geometry.padding, t.t_ci, t.t_co)
        stride, padding = plan.geometry.stride, plan.geometry.padding
        t_oh, t_ow, t_ci, t_co, t_n = t.t_oh, t.t_ow, t.t_ci, t.t_co, t.t_n
        plan = tables
    else:
        if stride is None or padding is None:
            raise TypeError(
                "deconv2d_sparse needs stride and padding (or a "
                "repro.plan.DeconvPlan via plan=)")
        if any(v is not None for v in (t_oh, t_ow, t_ci, t_co, t_n)):
            warn_legacy_tiles("deconv2d_sparse")
        t_oh, t_ow, t_ci, t_co, t_n = resolve_tiles(
            x, w, stride, padding, t_oh, t_ow, t_ci, t_co, t_n,
            backend="pallas_sparse", autotune=autotune,
        )
    if plan is None:
        plan = make_sparse_plan(np.asarray(w), stride, padding, t_ci, t_co)
    ci_idx, valid, tap_mask = plan
    n_co = _round_up(w.shape[3], t_co) // t_co
    if ci_idx.shape[0] != n_co:
        raise ValueError(
            f"sparse plan was built for {ci_idx.shape[0]} C_out tiles but the "
            f"resolved t_co={t_co} yields {n_co}; rebuild the plan with the "
            f"same channel tiles (or pass matching t_ci/t_co overrides)")
    return _deconv2d_sparse_jit(
        x, w, b, jnp.asarray(ci_idx), jnp.asarray(valid),
        jnp.asarray(tap_mask), stride, padding,
        t_oh, t_ow, t_ci, t_co, t_n, activation, interpret, layer,
    )
