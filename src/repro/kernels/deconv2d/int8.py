"""int8 batch-fused reverse-loop deconvolution Pallas kernel.

The quantized twin of `kernel.py` — same grid (disjoint output tiles with
the batch folded into the MXU row dimension), same Eq. 5 halo-window
BlockSpecs, same trace-time phase plan — with the paper's low-precision
datapath mapped onto the TPU MXU:

* **int8 inputs and weights, int32 accumulation.**  Every tap matmul
  contracts int8 x int8 into an int32 accumulator — integer-exact, so
  the kernel is bit-comparable against an integer reference (no float
  reassociation in the reduction), and the MXU runs at its doubled int8
  rate while the HBM stream drops to a quarter of f32.
* **Fused requant epilogue.**  The flush phase applies the one multiply
  post-training quantization needs — ``y = acc * (s_x * s_w[c]) + b`` with
  the per-output-channel combined scale streamed like the bias — then the
  activation, then either casts to f32 (last layer) or *re-quantizes* to
  int8 with the next layer's calibrated input scale (``out_scale``), so a
  chained generator never materializes an f32 activation in HBM between
  quantized layers.  This sits in exactly the epilogue slot the f32
  kernel uses for bias + ReLU/tanh.

Scales come from `quant.calibrate` (statistical observers); tiles come
from the dtype-aware autotuner (int8 byte width in the VMEM/traffic
models, int8 MXU peak in the roofline).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.offsets import PhasePlan, make_phase_plan
from ...core.tiling import SUBLANE, HaloTile, halo_tile
from ...quant.qmath import QMAX, quantize_symmetric
from .kernel import (COMPILER_PARAMS, apply_activation, check_mosaic_tiles,
                     kernel_name, x_halo_blockspec)


def requant_epilogue(acc_i32: jax.Array, scale: jax.Array, bias: jax.Array,
                     activation: Optional[str],
                     out_scale: Optional[float]) -> jax.Array:
    """The fused epilogue math, shared verbatim with the parity reference:
    dequantize the int32 accumulator through the combined per-channel
    scale, add bias, apply the activation, then (optionally) re-quantize
    to int8 at the next layer's input scale — through the same
    `quant.qmath` round/clip every other quantization call site uses."""
    y = acc_i32.astype(jnp.float32) * scale + bias
    y = apply_activation(y, activation)
    if out_scale is None:
        return y
    return quantize_symmetric(y, out_scale)


def _deconv2d_int8_kernel(
    x_ref,      # (T_N, T_IH, T_IW, T_CI)  VMEM int8 halo windows
    w_ref,      # (K, K, T_CI, T_CO)       VMEM int8 (batch-stationary)
    s_ref,      # (1, T_CO)                VMEM f32 combined s_x * s_w
    b_ref,      # (1, T_CO)                VMEM f32 bias
    o_ref,      # (T_N, T_OH, T_OW, T_CO)  VMEM int8 or f32
    acc_ref,    # (T_N, T_OH/S, S, T_OW, T_CO) int32 scratch
    *,
    plan: PhasePlan,
    ht_h: HaloTile,
    ht_w: HaloTile,
    t_oh: int,
    t_ow: int,
    n_ci_tiles: int,
    activation: Optional[str],
    out_scale: Optional[float],
):
    s = plan.stride
    th, tw = t_oh // s, t_ow // s
    t_n = x_ref.shape[0]
    ci_idx = pl.program_id(4)

    @pl.when(ci_idx == 0)
    def _init():
        # bias lives in the f32 requant epilogue, not the integer
        # accumulator: the accumulator stays exactly sum(q_x * q_w)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.int32)

    t_ci = x_ref.shape[3]
    t_co = w_ref.shape[3]
    for ph in range(s):
        for pw in range(s):
            acc = jnp.zeros((t_n * th * tw, t_co), dtype=jnp.int32)
            for kh, dh in plan.taps[ph]:
                for kw, dw in plan.taps[pw]:
                    r0 = ht_h.local_offset(dh)
                    c0 = ht_w.local_offset(dw)
                    xs = x_ref[:, r0:r0 + th, c0:c0 + tw, :]
                    acc = acc + jnp.dot(
                        xs.reshape(t_n * th * tw, t_ci),
                        w_ref[kh, kw],
                        preferred_element_type=jnp.int32,
                    )
            acc_ref[:, :, ph, pl.ds(pw, tw, stride=s), :] += acc.reshape(
                t_n, th, tw, t_co)

    @pl.when(ci_idx == n_ci_tiles - 1)
    def _flush():
        acc = acc_ref[...].reshape(t_n, t_oh, t_ow, t_co)
        o_ref[...] = requant_epilogue(
            acc, s_ref[0], b_ref[0], activation, out_scale)


def deconv2d_int8_pallas_call(
    x_padded: jax.Array,     # (N, IHp, IWp, CIp)  int8, host-padded
    w: jax.Array,            # (K, K, CIp, COp)    int8
    scale: jax.Array,        # (1, COp)            f32 combined s_x * s_w
    b: jax.Array,            # (1, COp)            f32
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
    out_scale: Optional[float] = None,
    interpret: bool = False,
    layer: Optional[int] = None,
) -> jax.Array:
    n, ihp, iwp, cip = x_padded.shape
    k = w.shape[0]
    cop = w.shape[3]
    s = plan.stride
    assert x_padded.dtype == jnp.int8 and w.dtype == jnp.int8
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
        check_mosaic_tiles(ht_w, n_tiles_w, t_ci, cip, t_co, cop, int8=True)
    grid = (n // t_n, n_tiles_h, n_tiles_w, cop // t_co, n_ci)
    out_dtype = jnp.int8 if out_scale is not None else jnp.float32

    kernel = functools.partial(
        _deconv2d_int8_kernel,
        plan=plan,
        ht_h=ht_h,
        ht_w=ht_w,
        t_oh=t_oh,
        t_ow=t_ow,
        n_ci_tiles=n_ci,
        activation=activation,
        out_scale=out_scale,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            x_halo_blockspec(ht_h, ht_w, t_ci, t_n, n_tiles_w, n_ci),
            pl.BlockSpec(
                (k, k, t_ci, t_co),
                lambda nb, oh, ow, co, ci: (0, 0, ci, co),
            ),
            pl.BlockSpec((1, t_co), lambda nb, oh, ow, co, ci: (0, co)),
            pl.BlockSpec((1, t_co), lambda nb, oh, ow, co, ci: (0, co)),
        ],
        out_specs=pl.BlockSpec(
            (t_n, t_oh, t_ow, t_co),
            lambda nb, oh, ow, co, ci: (nb, oh, ow, co),
        ),
        out_shape=jax.ShapeDtypeStruct((n, ohp, owp, cop), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((t_n, t_oh // s, s, t_ow, t_co), jnp.int32)
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name=kernel_name("int8_halo_reverse_loop", layer),
    )(x_padded, w, scale, b)


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "padding", "t_oh", "t_ow", "t_ci", "t_co", "t_n",
        "activation", "out_scale", "interpret", "layer",
    ),
)
def _deconv2d_int8_jit(
    x: jax.Array,
    w: jax.Array,
    scale: jax.Array,
    b: Optional[jax.Array],
    stride: int,
    padding: int,
    t_oh: int,
    t_ow: int,
    t_ci: int,
    t_co: int,
    t_n: int,
    activation: Optional[str],
    out_scale: Optional[float],
    interpret: bool,
    layer: Optional[int] = None,
) -> jax.Array:
    n, ih, iw, ci = x.shape
    k, _, _, co = w.shape
    plan = make_phase_plan(k, stride, padding)
    from .ops import halo_pad_geometry

    (oh, ow, ohp, owp, pad_l, pad_rh, pad_rw, cip, cop, t_n,
     np_) = halo_pad_geometry(n, ih, iw, ci, co, plan, t_oh, t_ow, t_ci,
                              t_co, t_n)
    # symmetric (zero-point-free) quantization: int8 zero IS real zero, so
    # halo/channel/batch padding needs no offset handling
    xp = jnp.pad(
        x, ((0, np_ - n), (pad_l, pad_rh), (pad_l, pad_rw), (0, cip - ci))
    )
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, cip - ci), (0, cop - co)))
    sp = jnp.pad(scale.astype(jnp.float32),
                 (0, cop - co)).reshape(1, cop)
    bb = b if b is not None else jnp.zeros((co,), jnp.float32)
    bp = jnp.pad(bb.astype(jnp.float32), (0, cop - co)).reshape(1, cop)

    y = deconv2d_int8_pallas_call(
        xp, wp, sp, bp,
        plan=plan,
        ohp=ohp, owp=owp,
        t_oh=t_oh, t_ow=t_ow, t_ci=t_ci, t_co=t_co, t_n=t_n,
        activation=activation,
        out_scale=out_scale,
        interpret=interpret,
        layer=layer,
    )
    return y[:n, :oh, :ow, :co]


def deconv2d_int8(
    x: jax.Array,
    w: jax.Array,
    scale: jax.Array,
    b: Optional[jax.Array],
    stride: Optional[int] = None,
    padding: Optional[int] = None,
    t_oh: Optional[int] = None,
    t_ow: Optional[int] = None,
    t_ci: Optional[int] = None,
    t_co: Optional[int] = None,
    t_n: Optional[int] = None,
    activation: Optional[str] = None,
    out_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    autotune: bool = True,
    plan=None,
    layer: Optional[int] = None,
) -> jax.Array:
    """Quantized transposed conv through the int8 reverse-loop kernel.

    x: (N, IH, IW, CI) int8; w: (K, K, CI, CO) int8; scale: (CO,) f32 —
    the combined ``x_scale * w_scale`` requant factor per output channel
    (see `quant.calibrate.quantize_params`); b: (CO,) f32 or None.
    ``out_scale`` (a static float) re-quantizes the activated output to
    int8 for the next quantized layer; ``None`` emits f32.

    ``plan`` (a `repro.plan.DeconvPlan` built at precision int8) pins the
    whole epilogue — tiles, activation AND requant out_scale — and skips
    tile resolution entirely.  Without a plan, unspecified tile factors
    resolve through the dtype-aware autotuner — the int8 byte width flows
    into the VMEM/traffic models and the int8 MXU peak into the roofline
    ranking — and explicit tile kwargs are deprecated.  ``layer`` names
    the kernel, as in `ops.deconv2d`.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from .ops import check_layer_plan, resolve_tiles, warn_legacy_tiles

    if plan is not None:
        check_layer_plan(plan, x, w, "pallas", "deconv2d_int8")
        t = plan.tiles
        if activation is None:
            activation = plan.activation
        if out_scale is None:
            out_scale = plan.out_scale
        return _deconv2d_int8_jit(
            x, w, jnp.asarray(scale), b, plan.geometry.stride,
            plan.geometry.padding, t.t_oh, t.t_ow, t.t_ci, t.t_co, t.t_n,
            activation, out_scale, interpret, layer,
        )
    if stride is None or padding is None:
        raise TypeError(
            "deconv2d_int8 needs stride and padding (or a plan=)")
    if any(v is not None for v in (t_oh, t_ow, t_ci, t_co, t_n)):
        warn_legacy_tiles("deconv2d_int8")
    t_oh, t_ow, t_ci, t_co, t_n = resolve_tiles(
        x, w, stride, padding, t_oh, t_ow, t_ci, t_co, t_n,
        backend="pallas", autotune=autotune,
        # no out_scale -> the epilogue emits f32: the autotuner must
        # price the output block at 4 bytes, not the streamed int8 width
        out_dtype_bytes=(4 if out_scale is None else None),
    )
    return _deconv2d_int8_jit(
        x, w, jnp.asarray(scale), b, stride, padding, t_oh, t_ow, t_ci,
        t_co, t_n, activation, out_scale, interpret, layer,
    )
