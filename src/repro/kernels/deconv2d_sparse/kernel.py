"""Block-sparse reverse-loop deconvolution with static zero-skipping.

TPU adaptation of the paper's zero-skipping (§V-C): the FPGA skips individual
zero-weight MACs via conditional execution; the MXU executes in lockstep, so
per-element skips have no TPU analogue (documented in DESIGN.md).  Instead we
exploit that *inference weights are static*: after magnitude pruning, the
host computes which ``(C_in-tile, C_out-tile)`` weight slabs are entirely zero
and builds a compressed schedule that

* skips the **HBM→VMEM DMA** of skipped input/weight slabs entirely, via a
  scalar-prefetched indirection on the CI grid dimension (only slabs with any
  nonzero are streamed), and
* skips the **compute** of zero taps inside surviving slabs, via a
  scalar-prefetched per-tap bitmask and `pl.when` predication.

The schedule is fixed per network — the execution time is data-independent,
preserving the run-to-run determinism the paper argues for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.offsets import PhasePlan
from ...core.tiling import SUBLANE, HaloTile, halo_tile
from ..deconv2d.kernel import (COMPILER_PARAMS, apply_activation,
                               check_mosaic_tiles, kernel_name,
                               window_start)


def build_schedule(block_tap_mask: np.ndarray):
    """Compress the CI-tile dimension per CO tile.

    block_tap_mask: (K, K, n_ci, n_co) bool — slab has any nonzero.
    Returns (ci_idx (n_co, L) int32, valid (n_co, L) int32,
             tap_mask (n_co, L, K*K) int32) where L = max surviving CI tiles.
    Padding entries repeat index 0 with valid=0 (DMA'd but not computed).
    """
    k1, k2, n_ci, n_co = block_tap_mask.shape
    any_tap = block_tap_mask.any(axis=(0, 1))  # (n_ci, n_co)
    lists = [np.nonzero(any_tap[:, co])[0] for co in range(n_co)]
    max_len = max(1, max(len(l) for l in lists))
    ci_idx = np.zeros((n_co, max_len), dtype=np.int32)
    valid = np.zeros((n_co, max_len), dtype=np.int32)
    tap_mask = np.zeros((n_co, max_len, k1 * k2), dtype=np.int32)
    for co, l in enumerate(lists):
        for j, ci in enumerate(l):
            ci_idx[co, j] = ci
            valid[co, j] = 1
            tap_mask[co, j] = block_tap_mask[:, :, ci, co].reshape(-1)
    return ci_idx, valid, tap_mask, max_len


def _sparse_kernel(
    # scalar prefetch (SMEM)
    ci_idx_ref,    # (n_co, L)
    valid_ref,     # (n_co, L)
    tap_ref,       # (n_co, L, K*K)
    # VMEM blocks
    x_ref,         # (T_N, T_IH, T_IW, T_CI)  halo windows
    w_ref,         # (K, K, T_CI, T_CO)
    b_ref,         # (1, T_CO)
    o_ref,         # (T_N, T_OH, T_OW, T_CO)
    acc_ref,       # (T_N, T_OH/S, S, T_OW, T_CO) f32
    *,
    plan: PhasePlan,
    ht_h: HaloTile,
    ht_w: HaloTile,
    t_oh: int,
    t_ow: int,
    n_sched: int,
    kernel_size: int,
    activation,
    out_dtype,
):
    s = plan.stride
    th, tw = t_oh // s, t_ow // s
    t_n = x_ref.shape[0]
    l_idx = pl.program_id(4)
    co_t = pl.program_id(3)

    @pl.when(l_idx == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(
            b_ref[0].astype(jnp.float32), acc_ref.shape
        )

    t_ci = x_ref.shape[3]
    t_co = w_ref.shape[3]
    is_valid = valid_ref[co_t, l_idx] > 0

    @pl.when(is_valid)
    def _compute():
        for ph in range(s):
            for pw in range(s):
                acc = jnp.zeros((t_n * th * tw, t_co), dtype=jnp.float32)
                for kh, dh in plan.taps[ph]:
                    for kw, dw in plan.taps[pw]:
                        # static-schedule zero-skipping: the tap bit is a
                        # scalar in SMEM, so Mosaic predicates the matmul.
                        tap_live = tap_ref[co_t, l_idx, kh * kernel_size + kw] > 0
                        # static halo-local rows (window follows the grid);
                        # batch folded into the contraction rows, weight
                        # slab stationary across the T_N images.
                        r0 = ht_h.local_offset(dh)
                        c0 = ht_w.local_offset(dw)
                        xs = x_ref[:, r0:r0 + th, c0:c0 + tw, :]
                        contrib = jnp.dot(
                            xs.reshape(t_n * th * tw, t_ci),
                            w_ref[kh, kw],
                            preferred_element_type=jnp.float32,
                        )
                        acc = acc + jnp.where(tap_live, contrib, 0.0)
                acc_ref[:, :, ph, pl.ds(pw, tw, stride=s), :] += (
                    acc.reshape(t_n, th, tw, t_co))

    @pl.when(l_idx == n_sched - 1)
    def _flush():
        y = acc_ref[...].reshape(t_n, t_oh, t_ow, t_co)
        o_ref[...] = apply_activation(y, activation).astype(out_dtype)


def deconv2d_sparse_pallas_call(
    x_padded: jax.Array,
    w: jax.Array,
    b: jax.Array,
    ci_idx: jax.Array,     # (n_co, L) int32
    valid: jax.Array,      # (n_co, L) int32
    tap_mask: jax.Array,   # (n_co, L, K*K) int32
    *,
    plan: PhasePlan,
    ohp: int,
    owp: int,
    t_oh: int,
    t_ow: int,
    t_ci: int,
    t_co: int,
    t_n: int = 1,
    activation=None,
    interpret: bool = False,
    layer=None,
) -> jax.Array:
    n, ihp, iwp, cip = x_padded.shape
    k = w.shape[0]
    cop = w.shape[3]
    s = plan.stride
    assert n % t_n == 0, "batch must be padded to a t_n multiple"
    ht_h = halo_tile(t_oh, k, s, plan.padding)
    ht_w = halo_tile(t_ow, k, s, plan.padding, align=SUBLANE)
    n_tiles_h = ohp // t_oh
    n_tiles_w = owp // t_ow
    assert ihp >= ht_h.min_padded_extent(n_tiles_h), "input under-padded (h)"
    assert iwp >= ht_w.min_padded_extent(n_tiles_w), "input under-padded (w)"
    n_ci = cip // t_ci
    if not interpret:
        check_mosaic_tiles(ht_w, n_tiles_w, t_ci, cip, t_co, cop)
    n_sched = ci_idx.shape[1]
    grid = (n // t_n, n_tiles_h, n_tiles_w, cop // t_co, n_sched)

    kernel = functools.partial(
        _sparse_kernel,
        plan=plan,
        ht_h=ht_h,
        ht_w=ht_w,
        t_oh=t_oh,
        t_ow=t_ow,
        n_sched=n_sched,
        kernel_size=k,
        activation=activation,
        out_dtype=x_padded.dtype,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (pl.Element(t_n), pl.Element(ht_h.extent),
                 pl.Element(ht_w.extent), pl.Element(t_ci)),
                # Eq. 5 halo windows (t_n images) following the output grid,
                # with DMA indirection on channels: only surviving CI slabs
                # stream.
                lambda nb, oh, ow, co, l, ci_idx, valid, taps: (
                    nb * t_n, oh * ht_h.step + ht_h.base,
                    window_start(ow, ht_w.step, ht_w.base, n_tiles_w),
                    window_start(ci_idx[co, l], t_ci, 0, n_ci),
                ),
            ),
            pl.BlockSpec(
                (k, k, t_ci, t_co),
                lambda nb, oh, ow, co, l, ci_idx, valid, taps: (
                    0, 0, ci_idx[co, l], co,
                ),
            ),
            pl.BlockSpec(
                (1, t_co),
                lambda nb, oh, ow, co, l, ci_idx, valid, taps: (0, co),
            ),
        ],
        out_specs=pl.BlockSpec(
            (t_n, t_oh, t_ow, t_co),
            lambda nb, oh, ow, co, l, ci_idx, valid, taps: (nb, oh, ow, co),
        ),
        scratch_shapes=[
            pltpu.VMEM((t_n, t_oh // s, s, t_ow, t_co), jnp.float32)
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, ohp, owp, cop), x_padded.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name=kernel_name("sparse_reverse_loop", layer),
    )(ci_idx, valid, tap_mask, x_padded, w, b)
