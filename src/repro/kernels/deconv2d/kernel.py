"""Output-tiled, phase-decomposed transposed-convolution Pallas TPU kernel.

This is the paper's FPGA accelerator re-derived for the TPU memory hierarchy:

* **Grid = disjoint output tiles** (reverse loop over the *output* space):
  each grid program owns one ``(T_N, T_OH, T_OW, T_CO)`` output block —
  one-shot writes, no overlapping-sum, exactly the paper's CU array.  The
  leading ``T_N`` is the *batch tile*: the batch is folded into the MXU row
  dimension so each tap matmul contracts over ``T_N * T_OH/S * T_OW/S``
  rows with the weight slab stationary — on the fat-channel early layers
  (16–49 spatial rows vs a 128x128 MXU) this is what fills the systolic
  array, and it amortizes the weight-slab HBM stream over T_N images.
* **Eq. 5 input streaming**: the x BlockSpec is a per-output-tile *halo
  window* of constant extent ``T_IH x T_IW`` (core.tiling.halo_tile) whose
  element-offset index map follows the output grid — each program streams
  only the input rows its tile touches (overlapping halo reads), never the
  whole image.  HBM traffic per tile is O(T_IH*T_IW), independent of image
  size.  The W window is sublane-aligned (`x_halo_blockspec`).
* **Eq. 3 offsets → trace-time phase plan**: the stride-hole-skipping offsets
  are folded into a static (phase → taps, input displacement) table computed
  on the host; inside the halo window every tap slice is *static* (local row
  ``HaloTile.local_offset(delta)``) — the kernel body contains zero modulo/division ops
  and zero grid-dependent address arithmetic.
* **Enhancement (2) — loop interchange**: the K×K tap loops are the outermost
  static loops; each (tap, phase) contribution is a channel-contraction
  matmul on the MXU with the weight slab held stationary.
* **Fused epilogue**: bias is the accumulator's initial value (Algorithm 1's
  initializeToBias) and the activation (relu/tanh/leaky_relu) runs in the
  ``_flush`` phase on the f32 accumulator — the generator never
  materializes a pre-activation layer in HBM.

* **Tap fold** for thin outputs (``T_CO`` at most half a lane): a dot's
  output columns are the MXU's columns, so a per-tap
  ``(rows, T_CI) @ (T_CI, T_CO)`` dot with ``T_CO`` = 8 uses 8 of its 128.  Every tap of a tile reads the same
  halo window, only at another offset, so the kernel multiplies first and
  shifts after: `tap_fold_group` taps' weights sit side by side in one
  lane-wide slab (`fold_tap_weights`), one dot of the whole window
  yields their products at once, and each phase adds each tap's
  ``T_CO``-lane block at the tap's static offset.  The products and
  addends are the per-tap form's; only the order of the f32 sums differs.

The accumulator scratch is laid out ``(T_N, T_OH/S, S, T_OW, T_CO)``: phase
``(ph, pw)`` accumulates into H-phase slot ``ph`` and every S-th W row from
``pw`` (a strided 32-bit store), so the final phase reassembly is a
leading-dim reshape and the block keeps the output's (T_OW, T_CO) tiling.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.deconv import LEAKY_SLOPE
from ...core.dse import TPU_V5E
from ...core.offsets import PhasePlan
from ...core.tiling import (LANE, SUBLANE, HaloTile, halo_tile,
                            tap_fold_group)

ACTIVATIONS = (None, "none", "relu", "tanh", "leaky_relu")

# Shared by the dense, int8 and sparse kernels: grid axes (batch, oh, ow,
# co, ci) with the CI reduction last, and a scoped-VMEM limit twice the
# 16 MiB `kernel_vmem_bytes` fits blocks into.  The model counts blocks,
# scratch and one tap's values; the headroom covers the relayout copies
# Mosaic makes of sublane-unaligned tap slices, which it does not count
# (an int8 K=7 layer at t_n=64 overran a 16 MiB limit by 292 KiB).  A
# v5e core has 128 MiB of VMEM.
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=(
        "parallel", "parallel", "parallel", "parallel", "arbitrary",
    ),
    vmem_limit_bytes=2 * TPU_V5E.onchip_bytes,
)


def apply_activation(y: jax.Array, activation: Optional[str]) -> jax.Array:
    """Epilogue nonlinearity on the f32 accumulator (shared with refs)."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"unsupported fused activation {activation!r}; "
                         f"expected one of {ACTIVATIONS}")
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "tanh":
        return jnp.tanh(y)
    if activation == "leaky_relu":
        return jnp.where(y >= 0, y, LEAKY_SLOPE * y)
    return y


def window_start(idx, step: int, base: int, n_tiles: int):
    """Element offset of grid index ``idx``'s window: ``idx*step + base``.
    A dim with a single window returns the constant ``base`` — Mosaic must
    prove a sublane/lane window offset tile-aligned, and it cannot do so
    from ``idx * step`` when ``step`` is not a tile multiple."""
    return base if n_tiles == 1 else idx * step + base


def x_halo_blockspec(
    ht_h: HaloTile, ht_w: HaloTile, t_ci: int, t_n: int, n_tiles_w: int,
    n_ci: int,
) -> pl.BlockSpec:
    """Per-output-tile input window BlockSpec (the Eq. 5 streaming read).

    Every block dim is a `pl.Element`, so the index map returns *element*
    offsets, which is what lets consecutive output tiles read overlapping
    halo windows — impossible with block-granular indexing.  The leading
    dimension is the batch tile: one program streams the windows of
    ``t_n`` images (batch folded into the MXU row dimension).  ``ht_w``
    must be sublane-aligned (``halo_tile(..., align=SUBLANE)``) and
    ``t_ci`` a lane multiple, unless the W / channel dim has a single
    window (``n_tiles_w`` / ``n_ci`` == 1).  Exposed as a function so the
    tests can assert the block shape / index map directly.
    """
    def index_map(nb, oh, ow, co, ci):
        return (nb * t_n, oh * ht_h.step + ht_h.base,
                window_start(ow, ht_w.step, ht_w.base, n_tiles_w),
                window_start(ci, t_ci, 0, n_ci))

    return pl.BlockSpec(
        (pl.Element(t_n), pl.Element(ht_h.extent), pl.Element(ht_w.extent),
         pl.Element(t_ci)),
        index_map,
    )


def check_mosaic_tiles(ht_w: HaloTile, n_tiles_w: int, t_ci: int, cip: int,
                       t_co: int, cop: int, int8: bool = False) -> None:
    """The block-shape rules Mosaic enforces and interpret mode does not:
    a W window offset on a sublane boundary; an input-channel block that
    is a lane multiple or spans the padded dim (always a lane multiple for
    ``int8``, whose window reshape needs whole 128-lane rows); and an
    output-channel block of at most one lane width, which the
    accumulator's strided phase store needs, that is a lane or spans the
    padded dim.  Called for compiled (non-interpret) kernels, so an
    illegal tile fails with the rule it broke before Mosaic's message."""
    bad = []
    if n_tiles_w > 1 and ht_w.step % SUBLANE:
        bad.append(f"t_ow/S={ht_w.step} is not a multiple of {SUBLANE} "
                   "with more than one W tile")
    if t_ci % LANE and (int8 or t_ci != cip):
        bad.append(f"t_ci={t_ci} is not a multiple of {LANE}"
                   + ("" if int8 else f" nor the padded channel count {cip}"))
    if t_co > LANE or (t_co != LANE and t_co != cop):
        bad.append(f"t_co={t_co} is neither {LANE} nor the padded channel "
                   f"count {cop} <= {LANE}")
    if bad:
        raise ValueError("tiles Mosaic cannot block: " + "; ".join(bad))


def tap_order(plan: PhasePlan) -> List[Tuple[int, int, int, int, int, int]]:
    """Every ``(ph, pw, kh, dh, kw, dw)`` tap, phase by phase: the order of
    the folded weight slab's lane blocks and of the folded kernel's sums."""
    return [(ph, pw, kh, dh, kw, dw)
            for ph in range(plan.stride) for pw in range(plan.stride)
            for kh, dh in plan.taps[ph] for kw, dw in plan.taps[pw]]


def fold_tap_weights(w: jax.Array, plan: PhasePlan, t_co: int) -> jax.Array:
    """Lay padded ``(K, K, CIp, COp)`` weights out for the folded kernel:
    ``(CIp, n_co * n_groups * LANE)``.  Output-channel tile ``c`` holds
    ``n_groups`` lane-wide slabs; slab ``j`` holds taps ``j*g .. j*g+g-1``
    of `tap_order` (``g`` = `tap_fold_group`) side by side, ``t_co``
    lanes each, zero-padded to the lane."""
    k, _, cip, cop = w.shape
    g = tap_fold_group(k, t_co)
    taps = tap_order(plan)
    n_groups = -(-len(taps) // g)
    wt = jnp.stack([w[kh, kw] for _, _, kh, _, kw, _ in taps])
    wt = jnp.pad(wt, ((0, n_groups * g - len(taps)), (0, 0), (0, 0)))
    # (group, tap, ci, co tile, t_co) -> (ci, co tile, group, tap * t_co)
    wt = wt.reshape(n_groups, g, cip, cop // t_co, t_co)
    wt = wt.transpose(2, 3, 0, 1, 4).reshape(
        cip, cop // t_co, n_groups, g * t_co)
    wt = jnp.pad(wt, ((0, 0), (0, 0), (0, 0), (0, LANE - g * t_co)))
    return wt.reshape(cip, cop // t_co * n_groups * LANE)


def _deconv2d_kernel(
    x_ref,      # (T_N, T_IH, T_IW, T_CI)  VMEM halo windows
    w_ref,      # (K, K, T_CI, T_CO), folded (T_CI, n_groups * LANE)
    b_ref,      # (1, T_CO)                VMEM
    o_ref,      # (T_N, T_OH, T_OW, T_CO)  VMEM
    acc_ref,    # (T_N, T_OH/S, S, T_OW, T_CO) f32 scratch
    *fold_ref,  # folded: (T_N, T_IH, T_IW, LANE) f32 scratch, a slab's dot
    plan: PhasePlan,
    ht_h: HaloTile,
    ht_w: HaloTile,
    t_oh: int,
    t_ow: int,
    group: int,
    n_ci_tiles: int,
    activation: Optional[str],
    out_dtype,
):
    s = plan.stride
    th, tw = t_oh // s, t_ow // s
    t_n, ihw, iww, t_ci = x_ref.shape
    t_co = acc_ref.shape[4]
    ci_idx = pl.program_id(4)

    @pl.when(ci_idx == 0)
    def _init():
        # initializeToBias() — broadcast bias into every phase slot.
        acc_ref[...] = jnp.broadcast_to(
            b_ref[0].astype(jnp.float32), acc_ref.shape
        )

    def add_phase(ph, pw, acc):
        # phase (ph, pw): H-phase slot ph, every S-th W row from pw
        acc_ref[:, :, ph, pl.ds(pw, tw, stride=s), :] += acc.reshape(
            t_n, th, tw, t_co)

    if group == 1:
        # Loop interchange (enhancement 2): taps outermost, weight slab
        # stationary across both the phase loops AND the T_N batch images —
        # each tap matmul contracts over T_N*th*tw rows (the batch-fused
        # MXU fill).
        for ph in range(s):
            for pw in range(s):
                acc = jnp.zeros((t_n * th * tw, t_co), dtype=jnp.float32)
                for kh, dh in plan.taps[ph]:
                    for kw, dw in plan.taps[pw]:
                        # static halo-local rows: the window already starts
                        # at this tile's minimum displacement.
                        r0 = ht_h.local_offset(dh)
                        c0 = ht_w.local_offset(dw)
                        xs = x_ref[:, r0:r0 + th, c0:c0 + tw, :]
                        acc = acc + jnp.dot(
                            xs.reshape(t_n * th * tw, t_ci),
                            w_ref[kh, kw],
                            preferred_element_type=jnp.float32,
                        )
                add_phase(ph, pw, acc)
    else:
        # Tap fold: one dot of the whole window per slab of `group` taps,
        # then each tap's t_co-lane block of it at the tap's offset.
        (y_ref,) = fold_ref
        x = x_ref[...].reshape(t_n * ihw * iww, t_ci)
        taps = tap_order(plan)
        phase, acc = None, None
        for j in range(0, len(taps), group):
            y_ref[...] = jnp.dot(
                x, w_ref[:, j // group * LANE:(j // group + 1) * LANE],
                preferred_element_type=jnp.float32,
            ).reshape(t_n, ihw, iww, LANE)
            for m, (ph, pw, _, dh, _, dw) in enumerate(taps[j:j + group]):
                if (ph, pw) != phase:
                    if phase is not None:
                        add_phase(*phase, acc)
                    phase, acc = (ph, pw), None
                r0 = ht_h.local_offset(dh)
                c0 = ht_w.local_offset(dw)
                ys = y_ref[:, r0:r0 + th, c0:c0 + tw,
                           m * t_co:(m + 1) * t_co]
                acc = ys if acc is None else acc + ys
        add_phase(*phase, acc)

    @pl.when(ci_idx == n_ci_tiles - 1)
    def _flush():
        # One-shot disjoint write: merge the H phases, fused epilogue, cast.
        y = acc_ref[...].reshape(t_n, t_oh, t_ow, t_co)
        o_ref[...] = apply_activation(y, activation).astype(out_dtype)


def kernel_name(kind: str, layer: Optional[int]) -> str:
    """A deconvolution kernel's Pallas call name, ``deconv2d_<kind>``, or
    ``deconv2d_l<layer>_<kind>`` for layer ``layer`` of a tower: each
    layer's kernel is then its own op in a profile."""
    return "deconv2d_" + ("" if layer is None else f"l{layer}_") + kind


def deconv2d_pallas_call(
    x_padded: jax.Array,     # (N, IHp, IWp, CIp)  host-padded
    w: jax.Array,            # (K, K, CIp, COp)
    b: jax.Array,            # (1, COp)
    *,
    plan: PhasePlan,
    ohp: int,
    owp: int,
    t_oh: int,
    t_ow: int,
    t_ci: int,
    t_co: int,
    t_n: int = 1,
    activation: Optional[str] = None,
    interpret: bool = False,
    layer: Optional[int] = None,
) -> jax.Array:
    """The dense kernel over host-padded operands: per tap, named
    ``halo_reverse_loop``, or, where `tap_fold_group` folds ``t_co``, with
    the weights laid out by `fold_tap_weights`, named ``halo_tapfold``."""
    n, ihp, iwp, cip = x_padded.shape
    k = w.shape[0]
    cop = w.shape[3]
    group = tap_fold_group(k, t_co)
    s = plan.stride
    assert t_oh % s == 0 and t_ow % s == 0, "tiles must be stride-aligned"
    assert cip % t_ci == 0 and cop % t_co == 0
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
    grid = (n // t_n, n_tiles_h, n_tiles_w, cop // t_co, n_ci)

    kernel = functools.partial(
        _deconv2d_kernel,
        plan=plan,
        ht_h=ht_h,
        ht_w=ht_w,
        t_oh=t_oh,
        t_ow=t_ow,
        group=group,
        n_ci_tiles=n_ci,
        activation=activation,
        out_dtype=x_padded.dtype,
    )
    scratch = [pltpu.VMEM((t_n, t_oh // s, s, t_ow, t_co), jnp.float32)]
    if group == 1:
        w_spec = pl.BlockSpec((k, k, t_ci, t_co),
                              lambda nb, oh, ow, co, ci: (0, 0, ci, co))
    else:
        w = fold_tap_weights(w, plan, t_co)
        w_spec = pl.BlockSpec((t_ci, w.shape[1] // (cop // t_co)),
                              lambda nb, oh, ow, co, ci: (ci, co))
        scratch.append(pltpu.VMEM((t_n, ht_h.extent, ht_w.extent, LANE),
                                  jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            x_halo_blockspec(ht_h, ht_w, t_ci, t_n, n_tiles_w, n_ci),
            w_spec,
            pl.BlockSpec((1, t_co), lambda nb, oh, ow, co, ci: (0, co)),
        ],
        out_specs=pl.BlockSpec(
            (t_n, t_oh, t_ow, t_co),
            lambda nb, oh, ow, co, ci: (nb, oh, ow, co),
        ),
        out_shape=jax.ShapeDtypeStruct((n, ohp, owp, cop), x_padded.dtype),
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name=kernel_name("halo_reverse_loop" if group == 1
                         else "halo_tapfold", layer),
    )(x_padded, w, b)
